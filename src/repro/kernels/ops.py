"""Jitted public wrappers for the Pallas kernels + KV page copy paths.

``interpret=None`` leaves the choice to ``repro.kernels.interpret_mode``:
the compiled kernels on a TPU backend, the Pallas interpreter elsewhere.

The page copy helpers move whole KV pages between the device page pool
(``[npages, page, ...]``, the buffer the paged decode kernel indexes through
block tables) and a host pool (numpy — host memory on every backend; on a
TPU host this is the pinned staging buffer). They are the data plane of
serving.kv_offload's two-tier allocator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention import paged_decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "kv_len",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    kv_len: int | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, kv_len=kv_len,
        block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                           window: int = 0, interpret: bool | None = None):
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, block_tables, context_lens, window=window,
        interpret=interpret)


# ---------------------------------------------------------------------------
# KV page migration (two-tier host offloading data plane)
# ---------------------------------------------------------------------------


@jax.jit
def gather_kv_pages(pages: jax.Array, page_ids: jax.Array) -> jax.Array:
    """Read pages ``page_ids`` out of a ``[npages, page, ...]`` pool."""
    return jnp.take(pages, page_ids, axis=0)


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_kv_pages(pages: jax.Array, page_ids: jax.Array,
                     values: jax.Array) -> jax.Array:
    """Write ``values`` (``[n, page, ...]``) into pool frames ``page_ids``.
    The pool buffer is donated: XLA updates the frames in place instead of
    rematerializing a multi-GB pool per migration batch. Batch one
    iteration's migrations into a single call."""
    return pages.at[page_ids].set(values)


def pack_token_pages(k_all: np.ndarray, v_all: np.ndarray, page_size: int,
                     dtype=None) -> np.ndarray:
    """Pack per-layer prefill KV into combined page values.

    ``k_all``/``v_all``: [L, S, vh, hd] (global layer order). Returns
    [n_pages, page, L, 2, vh, hd] — the trailing page is zero-padded past S
    (decode fills those slots later). This is the value layout of the
    engine's single physical page pool: one page holds every layer's K and V
    for ``page_size`` consecutive token positions, so one ``scatter_kv_pages``
    call lands a whole prefill.
    """
    L, S, vh, hd = k_all.shape
    n = -(-S // page_size)
    dt = dtype or k_all.dtype
    out = np.zeros((n, page_size, L, 2, vh, hd), dt)
    kt = np.zeros((n * page_size, L, vh, hd), dt)
    vt = np.zeros((n * page_size, L, vh, hd), dt)
    kt[:S] = np.transpose(k_all, (1, 0, 2, 3))
    vt[:S] = np.transpose(v_all, (1, 0, 2, 3))
    out[:, :, :, 0] = kt.reshape(n, page_size, L, vh, hd)
    out[:, :, :, 1] = vt.reshape(n, page_size, L, vh, hd)
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_pages_on_device(pages: jax.Array, src_ids: jax.Array,
                         dst_ids: jax.Array) -> jax.Array:
    """Copy-on-write data plane: duplicate frames ``src_ids`` into frames
    ``dst_ids`` within the same pool (one batched gather+scatter; the pool
    buffer is donated so XLA updates in place). The source frames are read
    before the scatter, so src/dst lists may interleave freely as long as
    they are disjoint."""
    return pages.at[dst_ids].set(jnp.take(pages, src_ids, axis=0))


def copy_pages_to_host(device_pages: jax.Array, device_ids,
                       host_pool: np.ndarray, host_ids) -> None:
    """Swap-out: device frames -> host pool slots (in place on the host
    side; the device pool is unchanged — its frames get recycled by the
    allocator)."""
    if len(device_ids) == 0:
        return
    got = gather_kv_pages(device_pages, jnp.asarray(device_ids, jnp.int32))
    host_pool[np.asarray(host_ids)] = np.asarray(got)


def copy_pages_from_host(host_pool: np.ndarray, host_ids,
                         device_pages: jax.Array, device_ids) -> jax.Array:
    """Swap-in: host pool slots -> device frames. Returns the updated device
    pool (functional, jit-compatible scatter)."""
    if len(device_ids) == 0:
        return device_pages
    vals = jnp.asarray(host_pool[np.asarray(host_ids)],
                       dtype=device_pages.dtype)
    return scatter_kv_pages(device_pages, jnp.asarray(device_ids, jnp.int32),
                            vals)
