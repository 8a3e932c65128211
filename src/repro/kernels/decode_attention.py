"""Pallas TPU paged GQA decode attention (TPOT hot spot).

One query token per request reads its KV pages through a block table. The
block table, context lengths and layer index ride in scalar-prefetch memory
(SMEM) so the page index map can chase them straight into the engine's
``[frames, page, L, 2, vh, hd]`` pool (``repro.kernels.paged_pool``); online
softmax runs over pages with VMEM scratch. Grid (B, n_pages), pages
innermost.
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


def _decode_kernel(bt_ref, cl_ref, layer_ref, q_ref, kv_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, page: int,
                   vh: int, g: int, d: int, nb: int, window: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cl = cl_ref[b]
    live = j * page < cl
    if window > 0:
        live &= (j + 1) * page > cl - window

    @pl.when(live)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale          # [H, D]
        qr = q.reshape(vh, g, d)
        k = page_heads(kv_ref, 0)                         # [V, page, D]
        # [V, G, D] x [V, page, D] -> [V, G, page]
        s = jax.lax.dot_general(
            qr, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (vh, g, page), 2)
        valid = kpos < cl
        if window > 0:
            valid &= kpos >= cl - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]                               # [V, G]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
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
        o = acc_scr[...] / l[..., None]                   # [V, G, D]
        o_ref[0] = o.reshape(vh * g, d).astype(o_ref.dtype)


def paged_decode_attention_pallas(q: jax.Array, pool: jax.Array, layer,
                                  block_tables: jax.Array,
                                  context_lens: jax.Array, *,
                                  window: int = 0,
                                  interpret: bool | None = None) -> jax.Array:
    """q: [B,H,D]; pool: [frames, page, L, 2, V, D]; layer: int32 scalar,
    static or traced; block_tables: [B, nb] int32; context_lens: [B] int32.
    Attends over layer ``layer`` of the pool. Returns [B,H,D].

    Padded batches are first-class: table entries past a request's last live
    page may hold any value (they are clamped into the pool range before the
    index map chases them and masked by ``context_lens``), and a row with
    ``context_lens[b] <= 0`` — an idle batch slot — produces a zero output
    instead of reading anything. ``context_lens`` is likewise clamped to the
    table's capacity ``nb * page`` so an oversized length cannot index past
    the last table column. ``interpret=None`` asks
    ``repro.kernels.interpret_mode`` (compiled on a TPU backend, the Pallas
    interpreter elsewhere).
    """
    b, h, d = q.shape
    npages, page, layers, vh, _ = pool_geometry(pool)
    nb = block_tables.shape[1]
    g = h // vh
    # harden padded inputs: every table entry must be a valid frame id for
    # the prefetch index map, every length must fit the table
    block_tables = jnp.clip(block_tables.astype(jnp.int32), 0, npages - 1)
    context_lens = jnp.clip(context_lens.astype(jnp.int32), 0, nb * page)
    layer = layer_index(layer, layers).reshape(1)
    pool, page_spec = pool_operand(
        pool, lambda b_, j, bt, cl, ly: (bt[b_, j], ly[0]))

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(d), page=page, vh=vh, g=g, d=d,
        nb=nb, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, j, bt, cl, ly: (b_, 0, 0)),
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, h, d),
                               lambda b_, j, bt, cl, ly: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((vh, g), jnp.float32),
            pltpu.VMEM((vh, g), jnp.float32),
            pltpu.VMEM((vh, g, d), jnp.float32),
        ],
    )

    if interpret is None:
        interpret = interpret_mode()
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, layer, q, pool)
