"""Plain reference for dense decoder-only transformers (Llama / Qwen2 /
DeepSeek-LLM family), written from the published description and imports
nothing of the program under test.

Architecture: token embedding; per layer RMSNorm (eps ``rms_norm_eps``) ->
Q/K/V projections (+ bias when ``attention_bias``) -> rotary embedding on
Q and K (rotate-half form, inverse frequencies ``rope_theta ** (-2i/d)``) ->
causal softmax attention with ``num_key_value_heads`` shared by
``num_attention_heads / num_key_value_heads`` query heads each, scaled by
``1/sqrt(head_dim)`` -> output projection -> residual; RMSNorm -> SwiGLU MLP
``(silu(x W1) * x W3) W2`` -> residual; final RMSNorm; logits against the
embedding (``tie_word_embeddings``) or a separate head.

Weights are drawn from a seed by the initialisation law the served system
uses (one key per parameter leaf, in sorted-path order; a normal leaf is
``N(0, 1) / sqrt(fan_in)`` drawn in float32 and rounded to bfloat16; norm
scales are ones, biases zeros). Departures from the published models, each
shared with the served system: random weights, and an embedding / head whose
rows are padded to a multiple of 256 (the padded rows are random, so they
take part in the argmax like any other).

Everything is computed in float32 at ``highest`` matmul precision, one layer
at a time, so the reference fits beside nothing but its own weights. The
control is the same computation with every weight matmul one step below
bfloat16: ``precision="int8"`` (symmetric int8) or ``"fp8"`` (float8 e4m3),
weights scaled per output channel, activations per token, dequantised into
a float32 accumulate.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# matmul names of the layer weights, with the axes each contracts over
_CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
             "w1": (0,), "w2": (0,), "w3": (0,), "head": (0,)}


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // 256) * 256


def leaf_specs(c: dict) -> list[tuple[str, tuple, str, int]]:
    """(name, shape, init, fan_in) of every parameter leaf, in the order
    the initialisation law assigns keys: sorted by path."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd, f, vp = d // h, c["intermediate_size"], padded_vocab(c)
    attn = {"wq": ((L, d, h, hd), d), "wk": ((L, d, kv, hd), d),
            "wv": ((L, d, kv, hd), d), "wo": ((L, h, hd, d), h * hd)}
    if c["attention_bias"]:
        attn.update(bq=((L, h, hd), 0), bk=((L, kv, hd), 0),
                    bv=((L, kv, hd), 0))
    out = []
    for name in sorted(attn):
        shape, fan = attn[name]
        out.append((name, shape, "zeros" if name.startswith("b") else
                    "normal", fan))
    out += [("w1", (L, d, f), "normal", d), ("w2", (L, f, d), "normal", f),
            ("w3", (L, d, f), "normal", d),
            ("norm1", (L, d), "ones", 0), ("norm2", (L, d), "ones", 0),
            ("embed", (vp, d), "normal", d), ("final_norm", (d,), "ones", 0)]
    if not c["tie_word_embeddings"]:
        out.append(("lm_head", (d, vp), "normal", d))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _normal(key, shape, scale):
    z = jax.lax.optimization_barrier(jax.random.normal(key, shape,
                                                       jnp.float32))
    return (z * scale).astype(jnp.bfloat16)


def weights(c: dict, seed: int = 0) -> dict:
    """Every leaf, bfloat16, on the default device."""
    specs = leaf_specs(c)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    w = {}
    for (name, shape, init, fan), k in zip(specs, keys):
        if init == "normal":
            w[name] = _normal(k, shape, np.float32(1.0 / np.sqrt(fan)))
        else:
            fill = jnp.zeros if init == "zeros" else jnp.ones
            w[name] = fill(shape, jnp.bfloat16)
    return w


def _quant(x, axes, mode):
    """Round trip through int8 or float8 e4m3, one scale per slice over
    ``axes``."""
    top = 127.0 if mode == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    if mode == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, x, w, name, low):
    if low:
        x = _quant(x, (-1,), low)
        w = _quant(w, _CONTRACT[name], low)
    return jnp.einsum(eq, x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    s, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "low"))
def _layer(x, w, layer, *, cfg, low):
    """One decoder layer over a whole sequence. x: [S, d] float32."""
    eps, theta, bias = cfg
    p = {k: jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
         .astype(jnp.float32) for k, v in w.items()}
    s = x.shape[0]
    h = _rms(x, p["norm1"], eps)
    q = _mm("sd,dhk->shk", h, p["wq"], "wq", low)
    k = _mm("sd,dhk->shk", h, p["wk"], "wk", low)
    v = _mm("sd,dhk->shk", h, p["wv"], "wv", low)
    if bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    nh, nkv, hd = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(s, nkv, nh // nkv, hd)
    sc = jnp.einsum("qvgk,svk->vgqs", qg, k,
                    precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("vgqs,svk->qvgk", pr, v, precision=HIGHEST)
    x = x + _mm("shk,hkd->sd", o.reshape(s, nh, hd), p["wo"], "wo", low)
    h = _rms(x, p["norm2"], eps)
    a = jax.nn.silu(_mm("sd,df->sf", h, p["w1"], "w1", low))
    a = a * _mm("sd,df->sf", h, p["w3"], "w3", low)
    return x + _mm("sf,fd->sd", a, p["w2"], "w2", low)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "low"))
def _head(x, rows, final_norm, table, *, eps, tied, low):
    h = _rms(x[rows], final_norm.astype(jnp.float32), eps)
    t = table.astype(jnp.float32)
    return _mm("sd,dv->sv", h, t.T if tied else t, "head", low)


def _bucket(n: int, max_seq: int) -> int:
    return min(max_seq, -(-n // 512) * 512)


def logits_at(c: dict, w: dict, tokens: np.ndarray, rows: np.ndarray,
              max_seq: int, precision: str = "float32") -> jax.Array:
    """Logits at positions ``rows`` of one sequence (float32, [len(rows),
    padded vocab]). The sequence is padded at the end to a bucket: causal
    attention keeps every real position independent of the padding."""
    low = None if precision == "float32" else precision
    s = _bucket(len(tokens), max_seq)
    toks = np.zeros(s, np.int32)
    toks[:len(tokens)] = tokens
    x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    layer_w = {k: w[k] for k in ("wq", "wk", "wv", "wo", "w1", "w2", "w3",
                                 "norm1", "norm2")}
    if c["attention_bias"]:
        layer_w.update({k: w[k] for k in ("bq", "bk", "bv")})
    cfg = (float(c["rms_norm_eps"]), float(c["rope_theta"]),
           bool(c["attention_bias"]))
    for layer in range(c["num_hidden_layers"]):
        x = _layer(x, layer_w, jnp.int32(layer), cfg=cfg, low=low)
    tied = bool(c["tie_word_embeddings"])
    table = w["embed"] if tied else w["lm_head"]
    return _head(x, jnp.asarray(rows, jnp.int32), w["final_norm"], table,
                 eps=float(c["rms_norm_eps"]), tied=tied, low=low)
