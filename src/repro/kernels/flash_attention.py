"""Pallas TPU flash attention (prefill hot spot).

Online-softmax tiling: grid (B, H, Sq/bq, Sk/bk), KV innermost; running
(m, l, acc) live in VMEM scratch across KV steps. Causal and sliding-window
masks prune fully-masked KV blocks with @pl.when, GQA via head-group index
mapping. fp32 accumulation; MXU-aligned default tiles (128 x head_dim).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  nk: int, sq: int, kv_len: int, pos_offset: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level pruning: skip KV blocks that are entirely masked.
    q_lo = i * bq + pos_offset                 # absolute pos of first query
    q_hi = q_lo + bq - 1
    k_lo = j * bk
    k_hi = k_lo + bk - 1
    live = k_lo < kv_len
    if causal:
        live &= k_lo <= q_hi
        if window > 0:
            live &= k_hi > q_lo - window

    @pl.when(live)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)               # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < kv_len
        if causal:
            valid &= kpos <= qpos
            if window > 0:
                valid &= kpos > qpos - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        vv = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, vv, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           kv_len: int | None = None, pos_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q: [B,H,Sq,D]; k/v: [B,V,Sk,D], V | H. Pads Sq/Sk to block multiples."""
    b, h, sq, d = q.shape
    vh, sk = k.shape[1], k.shape[2]
    g = h // vh
    kv_len = kv_len if kv_len is not None else sk
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(sk, 8))

    pq = (bq - sq % bq) % bq
    pk = (bk - sk % bk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = q.shape[2] // bq
    nk = k.shape[2] // bk

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, sq=sq, kv_len=kv_len,
        pos_offset=pos_offset + (kv_len - sq if causal else 0))

    if interpret is None:
        interpret = interpret_mode()
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if pq:
        out = out[:, :, :sq]
    return out
