"""Pallas TPU paged GQA chunk-prefill attention (incremental prefill).

One prompt chunk of C query tokens attends against the request's resident
paged KV — the prefix pages written by earlier chunks plus the chunk's own
freshly written pages — through a block table, so chunked prefill computes
O(C * prefix) work per chunk instead of recomputing the whole prefix
(quadratic across the schedule). Same template as the decode kernel: block
table and (context_len, start, layer) metadata ride in scalar-prefetch SMEM,
the grid walks pages of one layer of the engine's pool
(``repro.kernels.paged_pool``), and online softmax runs in VMEM scratch
sized for the whole chunk's query rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.paged_pool import (layer_index, page_heads,
                                      pool_geometry, pool_operand)

NEG_INF = -1e30


def _chunk_kernel(bt_ref, meta_ref, q_ref, kv_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page: int,
                  vh: int, g: int, d: int, c: int, nb: int):
    j = pl.program_id(0)
    cl = meta_ref[0]
    start = meta_ref[1]
    cg = c * g

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * page < cl)
    def _update():
        # q rows laid out [V, C*G]: row r = token (r // g), group lane r % g
        q = q_ref[...].astype(jnp.float32) * scale         # [C, H, D]
        qr = q.reshape(c, vh, g, d).transpose(1, 0, 2, 3).reshape(vh, cg, d)
        k = page_heads(kv_ref, 0)                          # [V, page, D]
        # [V, C*G, D] x [V, page, D] -> [V, C*G, page]
        s = jax.lax.dot_general(
            qr, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, (vh, cg, page), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (vh, cg, page), 2)
        qpos = start + row // g
        kpos = j * page + col
        # causal within the full context: a chunk query at absolute position
        # qpos sees every key at kpos <= qpos (qpos < cl always holds, so no
        # separate length mask is needed)
        valid = kpos <= qpos

        m_prev = m_scr[...]                                # [V, C*G]
        m_new = jnp.maximum(m_prev,
                            jnp.max(jnp.where(valid, s, NEG_INF), axis=-1))
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        vv = page_heads(kv_ref, 1)                        # [V, page, D]
        pv = jax.lax.dot_general(p, vv, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
        m_scr[...] = m_new

    @pl.when(j == nb - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o = acc_scr[...] / l[..., None]                    # [V, C*G, D]
        o_ref[...] = o.reshape(vh, c, g, d).transpose(1, 0, 2, 3).reshape(
            c, vh * g, d).astype(o_ref.dtype)


def paged_chunk_attention_pallas(q: jax.Array, pool: jax.Array, layer,
                                 block_table: jax.Array, start: jax.Array,
                                 context_len: jax.Array, *,
                                 interpret: bool | None = None) -> jax.Array:
    """q: [C,H,D] — the chunk's C query tokens at absolute positions
    ``start .. start+C-1``; pool: [frames, page, L, 2, V, D], read at layer
    ``layer`` (int32 scalar, static or traced); block_table: [nb] int32
    covering the request's pages 0..ceil(context_len/page)-1;
    ``context_len`` = start + C (the chunk's own KV is already in the pool).
    Returns [C,H,D].

    Table entries past the last live page may hold any value (clamped into
    pool range, masked by the causal bound); ``start``/``context_len`` are
    clamped to the table capacity. ``interpret=None`` asks
    ``repro.kernels.interpret_mode``.
    """
    c, h, d = q.shape
    npages, page, layers, vh, _ = pool_geometry(pool)
    nb = block_table.shape[0]
    g = h // vh
    block_table = jnp.clip(block_table.astype(jnp.int32), 0, npages - 1)
    context_len = jnp.clip(context_len.astype(jnp.int32), 0, nb * page)
    start = jnp.clip(start.astype(jnp.int32), 0, context_len)
    meta = jnp.stack([context_len, start, layer_index(layer, layers)])
    pool, page_spec = pool_operand(pool, lambda j, bt, meta: (bt[j], meta[2]))

    kernel = functools.partial(
        _chunk_kernel, scale=1.0 / math.sqrt(d), page=page, vh=vh, g=g, d=d,
        c=c, nb=nb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((c, h, d), lambda j, bt, meta: (0, 0, 0)),
            page_spec,
        ],
        out_specs=pl.BlockSpec((c, h, d), lambda j, bt, meta: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((vh, c * g), jnp.float32),
            pltpu.VMEM((vh, c * g), jnp.float32),
            pltpu.VMEM((vh, c * g, d), jnp.float32),
        ],
    )

    if interpret is None:
        interpret = interpret_mode()
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_table, meta, q, pool)
