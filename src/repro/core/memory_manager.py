"""Runtime memory manager (§4.3): applies an OffloadPlan to a model.

The layer stack (pattern-period scan units, leaves stacked [R, ...]) is
re-grouped into

    resident  [G, i-1, ...]   -- device HBM
    offloaded [G, ...]        -- pinned_host
    tail      [r, ...]        -- device HBM (units after the last full group)

and the step functions run a scan over G groups. Inside one group, an
explicit in-jit ``device_put`` moves the offloaded unit's weights to device
memory *before* the resident-unit scan (paper Fig. 7: the prefetch is issued
when the first layer of the interval starts computing), so (i-1) units of
compute hide one host transfer — XLA's latency-hiding scheduler has the
structural slack to overlap the copy. Verified to lower on both the TPU
target semantics and the XLA CPU backend (dry-run).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.interval import OffloadPlan
from repro.models import layers as L
from repro.models import spec as S
from repro.models import transformer as T
from repro.models.model import Model
from repro.models.spec import TensorSpec

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Splitting stacked trees by plan
# ---------------------------------------------------------------------------


def _is_leaf(x) -> bool:
    return isinstance(x, TensorSpec)


def _split_array(a: jax.Array, plan: OffloadPlan):
    g, i = plan.num_groups, plan.interval
    used = g * i if plan.enabled else 0
    head = a[:used]
    if plan.enabled:
        head = head.reshape(g, i, *a.shape[1:])
        resident = head[:, : i - 1]
        offloaded = head[:, i - 1]
    else:
        resident = a[:0].reshape(0, 1, *a.shape[1:])
        offloaded = a[:0]
    tail = a[used:]
    return resident, offloaded, tail


def _split_spec(s: TensorSpec, plan: OffloadPlan):
    g, i = plan.num_groups, plan.interval
    r = s.shape[0]
    rest, logical = s.shape[1:], s.logical[1:]
    mk = lambda lead: dataclasses.replace(
        s, shape=(*lead, *rest), logical=("stack",) * len(lead) + logical,
        fan_in_axes=tuple(a + len(lead) - 1 for a in s.fan_in_axes))
    if plan.enabled:
        resident = mk((g, i - 1))
        offloaded = mk((g,))
        tail = mk((r - g * i,))
    else:
        resident = mk((0, 1))
        offloaded = mk((0,))
        tail = mk((r,))
    return resident, offloaded, tail


def split_stacked(tree: Any, plan: OffloadPlan) -> dict[str, Any]:
    """Split every leaf (leading dim R) into the three placement groups."""
    def split(leaf):
        if isinstance(leaf, TensorSpec):
            return _split_spec(leaf, plan)
        return _split_array(leaf, plan)

    parts = jax.tree.map(split, tree, is_leaf=_is_leaf)
    pick = lambda k: jax.tree.map(
        lambda p: p[k], parts,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and not isinstance(x, TensorSpec))
    return {"resident": pick(0), "offloaded": pick(1), "tail": pick(2)}


def split_model_params(params: Params, plan: OffloadPlan) -> Params:
    """Model params/spec tree -> offload layout (blocks replaced)."""
    out = dict(params)
    out["blocks"] = split_stacked(params["blocks"], plan)
    return out


def merge_stacked(split: Any, plan: OffloadPlan) -> Any:
    """Inverse of split_stacked for array trees: placement sections back to
    one [R, ...] stack in unit order. Sections may be None (the prefill path
    returns None for empty placements) or zero-length arrays."""
    g, i = plan.num_groups, plan.interval
    res, off, tail = split["resident"], split["offloaded"], split["tail"]
    if not plan.enabled or (res is None and off is None):
        assert tail is not None
        return tail
    if res is None:          # interval == 1: every unit in a group offloaded
        head = off
    else:
        head = jax.tree.map(
            lambda r, o: jnp.concatenate([r, o[:, None]], axis=1)
            .reshape(g * i, *r.shape[2:]), res, off)
    if tail is None:
        return head
    return jax.tree.map(lambda h, t: jnp.concatenate([h, t], axis=0),
                        head, tail)


def merge_model_params(split: Params, plan: OffloadPlan) -> Params:
    """Inverse of split_model_params (arrays only) — checkpoint round-trips."""
    out = dict(split)
    out["blocks"] = merge_stacked(split["blocks"], plan)
    return out


@functools.partial(jax.jit, static_argnums=(3, 4))
def _resplit_leaf(res, off, tail, old: OffloadPlan, new: OffloadPlan):
    merged = merge_stacked({"resident": res, "offloaded": off, "tail": tail},
                           old)
    return _split_array(merged, new)


def resplit_model_params(split: Params, old: OffloadPlan,
                         new: OffloadPlan) -> Params:
    """Re-group params split for ``old`` into the layout of ``new``.

    Consumes ``split``: one stacked leaf at a time is re-grouped (one jitted
    copy) and its old sections released, so the transient cost of an
    interval change is one leaf, not a second copy of the model. The caller
    must hold no other reference to the old sections."""
    blocks = split.pop("blocks")
    out = dict(split)
    split.clear()
    res, treedef = jax.tree.flatten(blocks["resident"])
    off = jax.tree.leaves(blocks["offloaded"])
    tail = jax.tree.leaves(blocks["tail"])
    del blocks
    # empty sections go in as None (merge_stacked's "no placement"): XLA's
    # CPU compiler rejects a concatenate with a zero-length operand
    some = lambda a: a if a.size else None
    parts = []
    for k in range(len(tail)):
        parts.append(_resplit_leaf(some(res[k]), off[k], some(tail[k]), old,
                                   new))
        res[k] = off[k] = tail[k] = None
    out["blocks"] = {
        name: jax.tree.unflatten(treedef, [p[i] for p in parts])
        for i, name in enumerate(("resident", "offloaded", "tail"))}
    return out


def offload_memory_kind_fn(path: tuple) -> str | None:
    """memory_kind for spec.shardings(): pinned_host under blocks/offloaded."""
    keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    if "blocks" in keys:
        i = keys.index("blocks")
        if i + 1 < len(keys) and keys[i + 1] == "offloaded":
            return "pinned_host"
    return None


# ---------------------------------------------------------------------------
# Grouped step functions
# ---------------------------------------------------------------------------


def _prefetch(tree: Any, shardings=None):
    """Explicit prefetch: device_put to device-memory shardings at the group
    start (paper Fig. 7 — the copy is issued before the resident-unit
    compute). Without shardings (plain device-resident params, e.g. the CPU
    demo engine), identity: nothing to move."""
    if shardings is None:
        return tree
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)


def _scan_units(cfg: ModelConfig, apply_unit, x, units_params, units_caches,
                unroll: bool = False):
    """lax.scan over a stacked group of units; handles the 0-length case.

    unroll=True emits a straight-line program: the per-unit cache slices
    become *static*, so XLA updates them in place instead of a
    dynamic-slice/dynamic-update-slice round trip over the whole stacked
    cache every layer (§Perf A3 — halves decode HBM traffic)."""
    n = jax.tree.leaves(units_params)[0].shape[0]
    if n == 0:
        return x, units_caches

    def body(carry, xs):
        p, c = xs
        x2, nc = apply_unit(carry, p, c)
        return x2, nc

    return jax.lax.scan(body, x, (units_params, units_caches),
                        unroll=n if unroll else 1)


@dataclasses.dataclass(frozen=True)
class OffloadRuntime:
    """Bundles a model + plan into offload-aware step callables."""
    model: Model
    plan: OffloadPlan
    # Device-memory shardings for one offloaded unit (leading stack dim
    # dropped); when set, the group body issues an explicit device_put
    # prefetch. None => params already device-resident.
    device_shardings: Any = None
    # Unroll the per-unit scans in decode (static cache slices update in
    # place; see _scan_units). Off for training/prefill where the scan keeps
    # the program compact and remat-friendly.
    unroll_decode: bool = False

    # ----- decode --------------------------------------------------------------
    def decode_step(self, params_split: Params, tokens: jax.Array,
                    pos: jax.Array, caches_split: Any,
                    enc_pos: jax.Array | None = None):
        cfg, model = self.model.cfg, self.model
        vkv = model.virtual_kv

        def apply_unit(x, pslices, cslices):
            new = []
            for j, blk in enumerate(cfg.pattern):
                x, nc = T.apply_block_decode(cfg, blk, pslices[j], x, pos,
                                             cslices[j], vkv, enc_pos)
                new.append(nc)
            return x, new

        x = T.embed_tokens(cfg, params_split, tokens[:, None])
        blk = params_split["blocks"]
        cch = caches_split

        # Single two-index dynamic slice [g] / [g, j] straight out of the
        # carried cache stacks — one ds/dus per layer instead of slicing the
        # whole group block and re-slicing inside (§Perf hillclimb C: the
        # nested-scan double slice was ~40% extra decode HBM traffic).
        def _cache_at(tree, g, j=None):
            nlead = 1 if j is None else 2
            def one(t):
                starts = ((g,) if j is None else (g, j)) \
                    + (0,) * (t.ndim - nlead)
                sizes = (1,) * nlead + t.shape[nlead:]
                return jax.lax.dynamic_slice(t, starts, sizes).reshape(
                    t.shape[nlead:])
            return jax.tree.map(one, tree)

        def _cache_set(tree, new, g, j=None):
            nlead = 1 if j is None else 2
            def one(t, n):
                starts = ((g,) if j is None else (g, j)) \
                    + (0,) * (t.ndim - nlead)
                return jax.lax.dynamic_update_slice(
                    t, n.reshape((1,) * nlead + n.shape), starts)
            return jax.tree.map(one, tree, new)

        new_caches = {}
        g = self.plan.num_groups
        if g > 0:
            def group_body(carry, xs):
                x, res_c, off_c = carry
                gi, res_p, off_p = xs
                off_dev = _prefetch(off_p, self.device_shardings)
                for j in range(self.plan.interval - 1):
                    pj = jax.tree.map(lambda t: t[j], res_p)
                    x, nc = apply_unit(x, pj, _cache_at(res_c, gi, j))
                    res_c = _cache_set(res_c, nc, gi, j)
                x, noc = apply_unit(x, off_dev, _cache_at(off_c, gi))
                off_c = _cache_set(off_c, noc, gi)
                return (x, res_c, off_c), None

            (x, nrc, noc), _ = jax.lax.scan(
                group_body, (x, cch["resident"], cch["offloaded"]),
                (jnp.arange(g), blk["resident"], blk["offloaded"]))
            new_caches["resident"], new_caches["offloaded"] = nrc, noc
        else:
            new_caches["resident"] = cch["resident"]
            new_caches["offloaded"] = cch["offloaded"]
        x, new_caches["tail"] = _scan_units(cfg, apply_unit, x, blk["tail"],
                                            cch["tail"], self.unroll_decode)
        x = L.apply_norm(cfg, params_split["final_norm"], x)
        logits = T.lm_logits(cfg, params_split, x)[:, 0]
        return logits, new_caches

    # ----- paged decode ---------------------------------------------------------
    def paged_decode_step(self, params_split: Params, tokens: jax.Array,
                          pos: jax.Array, pool: jax.Array,
                          block_tables: jax.Array, context_lens: jax.Array,
                          write_frames: jax.Array, write_offsets: jax.Array):
        """One decode iteration through the physical KV page pool.

        Same weight-placement scan as ``decode_step`` (the offloaded unit's
        prefetch still overlaps the resident-unit compute), but instead of
        carrying slot-dense caches the scan carries ``pool`` — the single
        [frames, page, L, 2, vh, hd] buffer the paged Pallas kernel indexes
        through ``block_tables``. Each unit writes the new token's K/V at
        (write_frames, write_offsets) for its global layer index and attends
        over ``context_lens`` tokens. Returns (logits, pool).
        """
        cfg, model = self.model.cfg, self.model
        vkv = model.virtual_kv
        pat = len(cfg.pattern)

        def apply_unit(x, pslices, unit_idx, pool):
            for j, blk in enumerate(cfg.pattern):
                x, pool = T.apply_block_decode_paged(
                    cfg, blk, pslices[j], x, pos, pool,
                    unit_idx * pat + j, block_tables, context_lens,
                    write_frames, write_offsets, vkv)
            return x, pool

        x = T.embed_tokens(cfg, params_split, tokens[:, None])
        blk = params_split["blocks"]
        g, iv = self.plan.num_groups, self.plan.interval
        if g > 0:
            def group_body(carry, xs):
                x, pool = carry
                gi, res_p, off_p = xs
                off_dev = _prefetch(off_p, self.device_shardings)
                for j in range(iv - 1):
                    pj = jax.tree.map(lambda t: t[j], res_p)
                    x, pool = apply_unit(x, pj, gi * iv + j, pool)
                x, pool = apply_unit(x, off_dev, gi * iv + (iv - 1), pool)
                return (x, pool), None

            (x, pool), _ = jax.lax.scan(
                group_body, (x, pool),
                (jnp.arange(g), blk["resident"], blk["offloaded"]))
        n_tail = jax.tree.leaves(blk["tail"])[0].shape[0]
        for t in range(n_tail):   # unrolled: static layer index per unit
            pt = jax.tree.map(lambda a: a[t], blk["tail"])
            x, pool = apply_unit(x, pt, g * iv + t, pool)
        x = L.apply_norm(cfg, params_split["final_norm"], x)
        logits = T.lm_logits(cfg, params_split, x)[:, 0]
        return logits, pool

    # ----- paged chunk prefill --------------------------------------------------
    def paged_prefill_chunk(self, params_split: Params, tokens: jax.Array,
                            start: jax.Array, pool: jax.Array,
                            block_table: jax.Array, context_len: jax.Array,
                            write_frames: jax.Array,
                            write_offsets: jax.Array, last: jax.Array):
        """One incremental prefill chunk through the physical KV page pool.

        ``tokens``: [C] — the chunk at absolute positions ``start..start+C-1``
        of one request, real up to index ``last`` (the rows after it are
        padding whose writes the caller sends to a frame nobody reads).
        Same weight-placement scan as ``paged_decode_step``, but each unit
        writes the chunk's K/V at (write_frames, write_offsets)
        [C] and attends the chunk's queries over the request's resident
        context through ``block_table`` [nb] / ``context_len`` — no prefix
        recompute. Returns (logits at row ``last`` [1, V], pool).
        """
        cfg, model = self.model.cfg, self.model
        vkv = model.virtual_kv
        pat = len(cfg.pattern)
        c = tokens.shape[0]
        posm = (start + jnp.arange(c, dtype=jnp.int32))[None]   # [1, C]

        def apply_unit(x, pslices, unit_idx, pool):
            for j, blk in enumerate(cfg.pattern):
                x, pool = T.apply_block_prefill_paged(
                    cfg, blk, pslices[j], x, posm, pool,
                    unit_idx * pat + j, block_table, context_len,
                    write_frames, write_offsets, vkv)
            return x, pool

        x = T.embed_tokens(cfg, params_split, tokens[None])     # [1, C, D]
        blk = params_split["blocks"]
        g, iv = self.plan.num_groups, self.plan.interval
        if g > 0:
            def group_body(carry, xs):
                x, pool = carry
                gi, res_p, off_p = xs
                off_dev = _prefetch(off_p, self.device_shardings)
                for j in range(iv - 1):
                    pj = jax.tree.map(lambda t: t[j], res_p)
                    x, pool = apply_unit(x, pj, gi * iv + j, pool)
                x, pool = apply_unit(x, off_dev, gi * iv + (iv - 1), pool)
                return (x, pool), None

            (x, pool), _ = jax.lax.scan(
                group_body, (x, pool),
                (jnp.arange(g), blk["resident"], blk["offloaded"]))
        n_tail = jax.tree.leaves(blk["tail"])[0].shape[0]
        for t in range(n_tail):   # unrolled: static layer index per unit
            pt = jax.tree.map(lambda a: a[t], blk["tail"])
            x, pool = apply_unit(x, pt, g * iv + t, pool)
        x = L.apply_norm(cfg, params_split["final_norm"], x)
        h = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
        logits = T.lm_logits(cfg, params_split, h)[:, 0]
        return logits, pool

    # ----- prefill --------------------------------------------------------------
    def prefill(self, params_split: Params, inputs: dict, cache_len: int,
                attn_impl: str = "chunked", last_pos=None):
        cfg, model = self.model.cfg, self.model
        enc_out = enc_pos = None
        if cfg.encoder_layers > 0:
            enc_out, enc_pos = model.encode(params_split, inputs["enc_embeds"],
                                            attn_impl)
        x = T.embed_tokens(cfg, params_split, inputs["tokens"])
        if cfg.frontend is not None and cfg.family != "audio":
            x = jnp.concatenate(
                [inputs["frontend_embeds"].astype(x.dtype), x], axis=1)
        b, s, _ = x.shape
        posm = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        ctx = T.SeqCtx(positions=posm, want_cache=True, cache_len=cache_len,
                       virtual_kv=model.virtual_kv, enc_out=enc_out,
                       enc_pos=enc_pos, attn_impl=attn_impl)

        def apply_unit(x, pslices, _c):
            caches = []
            for j, blk in enumerate(cfg.pattern):
                x, c, _a = T.apply_block_seq(cfg, blk, pslices[j], x, ctx)
                caches.append(c)
            return x, caches

        blk = params_split["blocks"]

        def group_body(x, xs):
            res_p, off_p = xs
            off_dev = _prefetch(off_p, self.device_shardings)
            n = jax.tree.leaves(res_p)[0].shape[0]
            if n:
                def body(carry, p):
                    x2, c = apply_unit(carry, p, None)
                    return x2, c
                x, res_caches = jax.lax.scan(body, x, res_p)
            else:
                res_caches = None
            x, off_caches = apply_unit(x, off_dev, None)
            return x, (res_caches, off_caches)

        caches: dict[str, Any] = {}
        if self.plan.num_groups > 0:
            x, (rc, oc) = jax.lax.scan(group_body, x,
                                       (blk["resident"], blk["offloaded"]))
            caches["resident"], caches["offloaded"] = rc, oc
        else:
            # No offload groups: nothing cached under these sections (None is
            # a consistent empty pytree for the decode-side scans).
            caches["resident"] = None
            caches["offloaded"] = None
        n_tail = jax.tree.leaves(blk["tail"])[0].shape[0]
        if n_tail:
            def body(carry, p):
                x2, c = apply_unit(carry, p, None)
                return x2, c
            x, caches["tail"] = jax.lax.scan(body, x, blk["tail"])
        else:
            caches["tail"] = None
        x = L.apply_norm(cfg, params_split["final_norm"], x)
        # last_pos: logits position for shape-bucketed prefills whose tokens
        # carry suffix padding — causal attention keeps every position < S
        # bitwise-independent of the padding, but the last ROW is padding,
        # so the caller passes the true last position (traced: one compile
        # serves every prompt length in the bucket)
        h = x[:, -1:] if last_pos is None else \
            jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        logits = T.lm_logits(cfg, params_split, h)[:, 0]
        return logits, caches, enc_pos

    # ----- cache helpers ---------------------------------------------------------
    def split_caches(self, caches: Any):
        return split_stacked(caches, self.plan)

    def cache_spec_split(self, batch: int, cache_len: int, enc_len: int = 0):
        return split_stacked(
            self.model.cache_spec(batch, cache_len, enc_len), self.plan)

    def spec_split(self) -> Params:
        return split_model_params(self.model.spec, self.plan)

    # ----- accounting ---------------------------------------------------------------
    def memory_report(self) -> dict:
        from repro.core import costs
        ub = costs.unit_weight_bytes(self.model.cfg)
        p, r = T.pattern_info(self.model.cfg)
        other = S.tree_bytes(self.model.spec) - ub * r
        return {
            "unit_bytes": ub,
            "host_bytes": self.plan.host_bytes(ub),
            "device_stack_bytes": self.plan.device_bytes(ub),
            "device_other_bytes": other,
            "link_bytes_per_iter": self.plan.link_bytes_per_iter(ub),
        }


