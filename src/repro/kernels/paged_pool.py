"""The paged KV pool as the attention kernels read it.

The engine keeps every layer's K and V in one physical buffer,
``pool[frames, page, L, 2, vh, hd]``. The paged decode and chunk kernels
take that buffer whole, with a layer index: the page BlockSpec's index map
picks the frame and the layer, so one DMA brings one page's K and V of one
layer and no caller writes a layer's slice of the pool out first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def pool_geometry(pool: jax.Array) -> tuple[int, int, int, int, int]:
    """(frames, page, layers, vh, hd) of a ``[frames, page, L, 2, vh, hd]``
    pool."""
    frames, page, layers, kv, vh, hd = pool.shape
    if kv != 2:
        raise ValueError(f"pool axis 3 holds K and V, got {pool.shape}")
    return frames, page, layers, vh, hd


def layer_index(layer, layers: int) -> jax.Array:
    """``layer`` (a Python int, or a traced scalar inside a layer scan) as an
    int32 scalar clamped into the pool's ``layers``, for the kernels'
    scalar-prefetch operands."""
    return jnp.clip(jnp.asarray(layer, jnp.int32), 0, layers - 1)


def pool_operand(pool: jax.Array, frame_and_layer):
    """The pool as a kernel operand, and the BlockSpec of one page of one
    layer, K and V together. ``frame_and_layer`` maps the kernel's grid
    indices and scalar-prefetch refs to (frame, layer).

    With a single KV head the unit head axis is dropped (a free reshape):
    TPU lays that pool out with K/V, not the unit axis, beside ``hd``, and
    a Pallas operand must arrive in the default layout, so XLA would
    otherwise re-lay the whole pool out before every kernel call."""
    frames, page, layers, vh, hd = pool_geometry(pool)
    if vh == 1:
        pool = pool.reshape(frames, page, layers, 2, hd)
    rest = pool.shape[3:]

    def index_map(*idx):
        frame, layer = frame_and_layer(*idx)
        return (frame, 0, layer) + (0,) * len(rest)

    return pool, pl.BlockSpec((None, page, None) + rest, index_map)


def page_heads(kv_ref, kv: int) -> jax.Array:
    """K (``kv`` 0) or V (1) of the fetched page as float32
    ``[vh, page, hd]``."""
    x = kv_ref[:, kv].astype(jnp.float32)
    return x[None] if x.ndim == 2 else x.transpose(1, 0, 2)


def one_layer_pool(k_pages: jax.Array, v_pages: jax.Array) -> jax.Array:
    """Separate K and V pages, ``[frames, page, vh, hd]`` each, as a
    one-layer pool ``[frames, page, 1, 2, vh, hd]``."""
    return jnp.stack([k_pages, v_pages], axis=2)[:, :, None]
