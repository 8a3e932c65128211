"""Transformer stack assembly.

The layer stack is organized by the config's repeating *pattern* of P block
kinds (P=1 for homogeneous models, 8 for jamba, 2 for xlstm). Parameters for
pattern position j are stacked over the R = num_layers / P repetitions, and
the forward pass is a ``lax.scan`` over R with the P positions unrolled inside
— the same scan unit the Select-N memory manager later re-groups into
offloading intervals.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import BlockSpec, ModelConfig
from repro.models import layers as L
from repro.models.spec import TensorSpec, tree_map_spec
from repro.sharding.rules import shard

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Spec construction
# ---------------------------------------------------------------------------


def block_spec(cfg: ModelConfig, blk: BlockSpec, cross: bool = False) -> Params:
    spec: Params = {"norm1": L.norm_spec(cfg)}
    if blk.mixer == "attention":
        spec["attn"] = L.attn_spec(cfg)
    elif blk.mixer == "mamba":
        spec["attn"] = L.mamba_spec(cfg)
    elif blk.mixer == "mlstm":
        spec["attn"] = L.mlstm_spec(cfg)
    elif blk.mixer == "slstm":
        spec["attn"] = L.slstm_spec(cfg)
    if cross:
        spec["norm_cross"] = L.norm_spec(cfg)
        spec["cross"] = L.attn_spec(cfg)
    if cfg.d_ff > 0:
        spec["norm2"] = L.norm_spec(cfg)
        spec["mlp"] = L.moe_spec(cfg) if blk.mlp == "moe" else L.mlp_spec(cfg)
    return spec


def pattern_info(cfg: ModelConfig) -> tuple[int, int]:
    """(P, R): pattern length and repetitions. num_layers must be P*R."""
    p = len(cfg.pattern)
    assert cfg.num_layers % p == 0, (cfg.name, cfg.num_layers, p)
    return p, cfg.num_layers // p


def decoder_stack_spec(cfg: ModelConfig, cross: bool = False) -> list[Params]:
    p, r = pattern_info(cfg)
    out = []
    for j in range(p):
        bs = block_spec(cfg, cfg.pattern[j], cross=cross)
        out.append(tree_map_spec(lambda s: s.stacked(r), bs))
    return out


def encoder_stack_spec(cfg: ModelConfig) -> list[Params]:
    bs = block_spec(cfg, BlockSpec(mixer="attention", mlp="dense"))
    return [tree_map_spec(lambda s: s.stacked(cfg.encoder_layers), bs)]


def model_spec(cfg: ModelConfig) -> Params:
    d = cfg.d_model
    vp = cfg.padded_vocab()
    spec: Params = {
        "embed": TensorSpec((vp, d), ("vocab", "fsdp"), fan_in_axes=(1,)),
        "blocks": decoder_stack_spec(cfg, cross=cfg.encoder_layers > 0),
        "final_norm": L.norm_spec(cfg),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = TensorSpec((d, vp), ("fsdp", "vocab"))
    if cfg.encoder_layers > 0:
        spec["encoder"] = {
            "blocks": encoder_stack_spec(cfg),
            "final_norm": L.norm_spec(cfg),
        }
    return spec


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------


def attn_cache_spec(cfg: ModelConfig, batch: int, cache_len: int,
                    virtual_kv: int) -> Params:
    hd = cfg.resolved_head_dim
    return {
        "k": TensorSpec((batch, cache_len, virtual_kv, hd),
                        ("batch", "cache_seq", "kv", None),
                        dtype=jnp.bfloat16, init="zeros"),
        "v": TensorSpec((batch, cache_len, virtual_kv, hd),
                        ("batch", "cache_seq", "kv", None),
                        dtype=jnp.bfloat16, init="zeros"),
        "pos": TensorSpec((batch, cache_len), ("batch", "cache_seq"),
                          dtype=jnp.int32, init="zeros"),
    }


def mixer_cache_spec(cfg: ModelConfig, blk: BlockSpec, batch: int,
                     cache_len: int, virtual_kv: int) -> Params:
    if blk.mixer == "attention":
        clen = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
        return attn_cache_spec(cfg, batch, clen, virtual_kv)
    if blk.mixer == "mamba":
        return L.mamba_cache_spec(cfg, batch)
    if blk.mixer == "mlstm":
        return L.mlstm_cache_spec(cfg, batch)
    if blk.mixer == "slstm":
        return L.slstm_cache_spec(cfg, batch)
    raise ValueError(blk.mixer)


def cache_spec(cfg: ModelConfig, batch: int, cache_len: int, virtual_kv: int,
               enc_len: int = 0) -> list[Params]:
    """Per pattern position, stacked over R. Cross caches included for encdec."""
    p, r = pattern_info(cfg)
    out = []
    for j in range(p):
        cs: Params = {"self": mixer_cache_spec(
            cfg, cfg.pattern[j], batch, cache_len, virtual_kv)}
        if cfg.encoder_layers > 0 and enc_len > 0:
            cs["cross"] = attn_cache_spec(cfg, batch, enc_len, virtual_kv)
            del cs["cross"]["pos"]  # cross positions are static iota
        out.append(tree_map_spec(lambda s: s.stacked(r), cs))
    return out


# ---------------------------------------------------------------------------
# Cache fill helpers
# ---------------------------------------------------------------------------


def fill_cache(full: jax.Array, positions: jax.Array, cache_len: int):
    """Store the last cache_len entries of [B,S,...] at slots p % cache_len.

    Returns (cache, pos_array [B, cache_len]).
    """
    b, s = full.shape[0], full.shape[1]
    if s <= cache_len:
        pad = [(0, 0)] * full.ndim
        pad[1] = (0, cache_len - s)
        cache = jnp.pad(full, pad)
        pos = jnp.pad(positions, ((0, 0), (0, cache_len - s)),
                      constant_values=-1)
        return cache, pos
    tail = full[:, s - cache_len:]
    tpos = positions[:, s - cache_len:]
    shift = s % cache_len
    return (jnp.roll(tail, shift, axis=1), jnp.roll(tpos, shift, axis=1))


def cache_write_decode(cache_k, cache_v, cache_pos, k1, v1, pos):
    """Write one token at slot pos % cache_len (per batch row). pos: [B]."""
    clen = cache_k.shape[1]
    slot = pos % clen

    def wr(c, x1, s):
        return jax.lax.dynamic_update_slice(c, x1, (s,) + (0,) * (c.ndim - 1))

    ck = jax.vmap(wr)(cache_k, k1, slot)
    cv = jax.vmap(wr)(cache_v, v1, slot)
    cp = jax.vmap(lambda c, p, s: jax.lax.dynamic_update_slice(c, p[None], (s,))
                  )(cache_pos, pos, slot)
    return ck, cv, cp


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SeqCtx:
    """Context for a full-sequence pass (train/prefill)."""
    positions: jax.Array            # [B, S]
    want_cache: bool = False
    cache_len: int = 0
    virtual_kv: int = 0
    enc_out: jax.Array | None = None
    enc_pos: jax.Array | None = None
    attn_impl: str = "chunked"      # chunked | reference


def _self_attn_seq(cfg, p, x, ctx: SeqCtx):
    q, k, v = L.qkv_project(cfg, p, x, ctx.positions, ctx.virtual_kv)
    impl = L.attn_chunked if ctx.attn_impl == "chunked" else L.attn_reference
    o = impl(cfg, q, k, v, ctx.positions, ctx.positions,
             window=cfg.sliding_window)
    y = L.attn_out(cfg, p, o)
    cache = None
    if ctx.want_cache:
        clen = (min(ctx.cache_len, cfg.sliding_window)
                if cfg.sliding_window else ctx.cache_len)
        ck, cpos = fill_cache(k, ctx.positions, clen)
        cv, _ = fill_cache(v, ctx.positions, clen)
        cache = {"k": ck, "v": cv, "pos": cpos}
    return y, cache


def _cross_attn_seq(cfg, p, x, ctx: SeqCtx):
    """Cross attention for enc-dec; enc_out already normed."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    k = jnp.einsum("bsd,dhk->bshk", ctx.enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", ctx.enc_out, p["wv"])
    k = L._expand_kv(k, ctx.virtual_kv)
    v = L._expand_kv(v, ctx.virtual_kv)
    o = L.attn_chunked(cfg, q, k, v, ctx.positions, ctx.enc_pos, cross=True)
    y = L.attn_out(cfg, p, o)
    cache = {"k": k, "v": v}
    return y, cache


def apply_block_seq(cfg: ModelConfig, blk: BlockSpec, p: Params, x: jax.Array,
                    ctx: SeqCtx):
    """Returns (x, cache_dict_or_None, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(cfg, p["norm1"], x)
    cache: Params = {}
    if blk.mixer == "attention":
        y, self_cache = _self_attn_seq(cfg, p["attn"], h, ctx)
        state = None
    elif blk.mixer == "mamba":
        y, state = L.apply_mamba_seq(cfg, p["attn"], h)
        self_cache = None
    elif blk.mixer == "mlstm":
        y, state = L.apply_mlstm_seq(cfg, p["attn"], h)
        self_cache = None
    else:  # slstm
        y, state = L.apply_slstm_seq(cfg, p["attn"], h)
        self_cache = None
    x = x + y
    if ctx.want_cache:
        cache["self"] = self_cache if self_cache is not None else state

    if "cross" in p:
        h = L.apply_norm(cfg, p["norm_cross"], x)
        y, xcache = _cross_attn_seq(cfg, p["cross"], h, ctx)
        x = x + y
        if ctx.want_cache:
            cache["cross"] = xcache

    if cfg.d_ff > 0:
        h = L.apply_norm(cfg, p["norm2"], x)
        if blk.mlp == "moe":
            y, a = L.apply_moe(cfg, p["mlp"], h)
            aux = aux + a
        else:
            y = L.apply_mlp(cfg, p["mlp"], h)
        x = x + y
    return x, (cache if ctx.want_cache else None), aux


def _self_attn_decode(cfg, p, x, pos, cache, virtual_kv):
    q, k1, v1 = L.qkv_project(cfg, p, x, pos[:, None], virtual_kv)
    ck, cv, cpos = cache_write_decode(
        cache["k"], cache["v"], cache["pos"], k1, v1, pos)
    o = L.attn_reference(cfg, q, ck, cv, pos[:, None], cpos,
                         window=cfg.sliding_window)
    y = L.attn_out(cfg, p, o)
    return y, {"k": ck, "v": cv, "pos": cpos}


def _cross_attn_decode(cfg, p, x, pos, cache, enc_pos):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    o = L.attn_reference(cfg, q, cache["k"], cache["v"], pos[:, None],
                         enc_pos, cross=True)
    return L.attn_out(cfg, p, o), cache


def apply_block_decode(cfg: ModelConfig, blk: BlockSpec, p: Params,
                       x: jax.Array, pos: jax.Array, cache: Params,
                       virtual_kv: int, enc_pos: jax.Array | None = None):
    """x: [B,1,D]; pos: [B]. Returns (x, new_cache)."""
    h = L.apply_norm(cfg, p["norm1"], x)
    new_cache: Params = {}
    if blk.mixer == "attention":
        y, new_cache["self"] = _self_attn_decode(
            cfg, p["attn"], h, pos, cache["self"], virtual_kv)
    elif blk.mixer == "mamba":
        y, new_cache["self"] = L.apply_mamba_decode(cfg, p["attn"], h,
                                                    cache["self"])
    elif blk.mixer == "mlstm":
        y, new_cache["self"] = L.apply_mlstm_decode(cfg, p["attn"], h,
                                                    cache["self"])
    else:
        y, new_cache["self"] = L.apply_slstm_decode(cfg, p["attn"], h,
                                                    cache["self"])
    x = x + y

    if "cross" in p:
        h = L.apply_norm(cfg, p["norm_cross"], x)
        y, new_cache["cross"] = _cross_attn_decode(
            cfg, p["cross"], h, pos, cache["cross"], enc_pos)
        x = x + y

    if cfg.d_ff > 0:
        h = L.apply_norm(cfg, p["norm2"], x)
        if blk.mlp == "moe":
            y, _ = L.apply_moe(cfg, p["mlp"], h)
        else:
            y = L.apply_mlp(cfg, p["mlp"], h)
        x = x + y
    return x, new_cache


def apply_block_decode_paged(cfg: ModelConfig, blk: BlockSpec, p: Params,
                             x: jax.Array, pos: jax.Array, pool: jax.Array,
                             layer: jax.Array, block_tables: jax.Array,
                             context_lens: jax.Array,
                             write_frames: jax.Array,
                             write_offsets: jax.Array, virtual_kv: int):
    """One decode block through the paged KV pool (no slot-dense cache).

    ``pool``: [frames, page, L, 2, vh, hd] — the single physical page buffer
    shared by every layer; ``layer`` (traced) selects the L-axis slice. The
    new token's K/V land at (write_frames[b], write_offsets[b]) and attention
    reads through ``block_tables``/``context_lens`` with the Pallas paged
    decode kernel. Returns (x, pool).
    """
    if blk.mixer != "attention":
        raise NotImplementedError(
            "paged decode supports attention mixers only; recurrent-state "
            f"mixer {blk.mixer!r} needs a per-slot state slab (ROADMAP)")
    from repro.kernels.decode_attention import paged_decode_attention_pallas

    h = L.apply_norm(cfg, p["norm1"], x)
    q, k1, v1 = L.qkv_project(cfg, p["attn"], h, pos[:, None], virtual_kv)
    pool = pool.at[write_frames, write_offsets, layer, 0].set(
        k1[:, 0].astype(pool.dtype))
    pool = pool.at[write_frames, write_offsets, layer, 1].set(
        v1[:, 0].astype(pool.dtype))
    kv_l = jax.lax.dynamic_index_in_dim(pool, layer, axis=2, keepdims=False)
    o = paged_decode_attention_pallas(
        q[:, 0], kv_l[:, :, 0], kv_l[:, :, 1], block_tables, context_lens,
        window=cfg.sliding_window)
    x = x + L.attn_out(cfg, p["attn"], o[:, None])

    if cfg.d_ff > 0:
        h = L.apply_norm(cfg, p["norm2"], x)
        if blk.mlp == "moe":
            y, _ = L.apply_moe(cfg, p["mlp"], h)
        else:
            y = L.apply_mlp(cfg, p["mlp"], h)
        x = x + y
    return x, pool


def apply_block_prefill_paged(cfg: ModelConfig, blk: BlockSpec, p: Params,
                              x: jax.Array, positions: jax.Array,
                              pool: jax.Array, layer: jax.Array,
                              block_table: jax.Array,
                              context_len: jax.Array,
                              write_frames: jax.Array,
                              write_offsets: jax.Array, virtual_kv: int):
    """One prefill *chunk* through the paged KV pool (incremental prefill).

    ``x``: [1, C, D] — the chunk's tokens at absolute ``positions`` [1, C];
    the chunk's K/V land at (write_frames[t], write_offsets[t]) per token,
    then the chunk's queries attend over the request's whole resident
    context (``block_table``/``context_len``) with the Pallas chunk kernel —
    no prefix recompute. Returns (x, pool).
    """
    if blk.mixer != "attention":
        raise NotImplementedError(
            "paged chunk prefill supports attention mixers only; "
            f"recurrent-state mixer {blk.mixer!r} needs a state slab")
    if cfg.sliding_window > 0:
        raise NotImplementedError(
            "paged chunk prefill has no sliding-window mask")
    from repro.kernels.prefill_attention import paged_chunk_attention_pallas

    h = L.apply_norm(cfg, p["norm1"], x)
    q, k1, v1 = L.qkv_project(cfg, p["attn"], h, positions, virtual_kv)
    pool = pool.at[write_frames, write_offsets, layer, 0].set(
        k1[0].astype(pool.dtype))
    pool = pool.at[write_frames, write_offsets, layer, 1].set(
        v1[0].astype(pool.dtype))
    kv_l = jax.lax.dynamic_index_in_dim(pool, layer, axis=2, keepdims=False)
    o = paged_chunk_attention_pallas(
        q[0], kv_l[:, :, 0], kv_l[:, :, 1], block_table, positions[0, 0],
        context_len)
    x = x + L.attn_out(cfg, p["attn"], o[None])

    if cfg.d_ff > 0:
        h = L.apply_norm(cfg, p["norm2"], x)
        if blk.mlp == "moe":
            y, _ = L.apply_moe(cfg, p["mlp"], h)
        else:
            y = L.apply_mlp(cfg, p["mlp"], h)
        x = x + y
    return x, pool


# ---------------------------------------------------------------------------
# Stack application (scan over R periods)
# ---------------------------------------------------------------------------


def apply_stack_seq(cfg: ModelConfig, blocks: list[Params], x: jax.Array,
                    ctx: SeqCtx, pattern: tuple[BlockSpec, ...] | None = None,
                    remat: bool = False):
    """Returns (x, caches_or_None, total_aux)."""
    pattern = pattern if pattern is not None else cfg.pattern

    def period(x, pslices):
        caches = []
        aux = jnp.zeros((), jnp.float32)
        for j, blk in enumerate(pattern):
            x, c, a = apply_block_seq(cfg, blk, pslices[j], x, ctx)
            caches.append(c)
            aux = aux + a
        return x, caches, aux

    if remat:
        period = jax.checkpoint(period)

    def body(carry, pslices):
        x = carry
        x, caches, aux = period(x, pslices)
        return x, (caches, aux)

    x, (caches, aux) = jax.lax.scan(body, x, blocks)
    return x, (caches if ctx.want_cache else None), jnp.sum(aux)


def apply_stack_decode(cfg: ModelConfig, blocks: list[Params], x: jax.Array,
                       pos: jax.Array, caches: list[Params], virtual_kv: int,
                       enc_pos: jax.Array | None = None,
                       pattern: tuple[BlockSpec, ...] | None = None):
    pattern = pattern if pattern is not None else cfg.pattern

    def body(x, xs):
        pslices, cslices = xs
        new = []
        for j, blk in enumerate(pattern):
            x, nc = apply_block_decode(cfg, blk, pslices[j], x, pos,
                                       cslices[j], virtual_kv, enc_pos)
            new.append(nc)
        return x, new

    x, new_caches = jax.lax.scan(body, x, (blocks, caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# Embedding / logits / loss
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array):
    x = jnp.take(params["embed"], tokens, axis=0)
    return shard(x, "batch", None, None)


def lm_logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    return shard(logits, "batch", None, "vocab")


def xent_loss(cfg: ModelConfig, logits: jax.Array, labels: jax.Array,
              mask: jax.Array | None = None) -> jax.Array:
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def xent_loss_chunked(cfg: ModelConfig, params: Params, hidden: jax.Array,
                      labels: jax.Array, mask: jax.Array | None = None,
                      chunk: int = 512) -> jax.Array:
    """Fused big-vocab cross entropy (§Perf hillclimb B4): computes the loss
    from the final *hidden* states, materializing logits only one sequence
    chunk at a time. The [B, S, V] f32 logits of a 256k-vocab model are the
    single largest training tensor (fwd write, lse read, gather read, bwd
    softmax re-materialization); chunking bounds that to [B, chunk, V] and
    jax.checkpoint recomputes it in the backward pass. Numerically identical
    to xent_loss(lm_logits(...)) — see tests/test_system.py."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    nc = (s + chunk - 1) // chunk
    pad = nc * chunk - s
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    mc = mask.reshape(b, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_nll(h, y, m):
        lf = jnp.einsum("bsd,dv->bsv", h, head,
                        preferred_element_type=jnp.float32)
        lf = shard(lf, "batch", None, "vocab")
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, y[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll) * m)

    def body(carry, xs):
        h, y, m = xs
        return carry + chunk_nll(h, y, m), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, yc, mc))
    return total / jnp.maximum(jnp.sum(mc), 1.0)
