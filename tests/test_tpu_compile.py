"""AOT compiles of the served path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (unaligned
slices, too much VMEM) — what interpret-mode tests cannot see. Shapes are
qwen2.5-3b's: page 16, 2 KV heads of 128, 16 query heads, batch 8, 256
block-table columns, 256-token prefill chunks.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import paged_chunk_attention_pallas

PAGE, KV_HEADS, HEAD_DIM, HEADS = 16, 2, 128, 16
BATCH, COLUMNS, CHUNK = 8, 256, 256
FRAMES = 2 * BATCH * COLUMNS + 1          # device tier + slab + null frame


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_paged_decode_kernel_compiles_for_v5e(one_chip):
    pages = _sds(one_chip, (FRAMES, PAGE, KV_HEADS, HEAD_DIM))
    text = _compiled_text(
        lambda q, k, v, bt, cl: paged_decode_attention_pallas(
            q, k, v, bt, cl, interpret=False),
        _sds(one_chip, (BATCH, HEADS, HEAD_DIM)), pages, pages,
        _sds(one_chip, (BATCH, COLUMNS), jnp.int32),
        _sds(one_chip, (BATCH,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_chunk_kernel_compiles_for_v5e(one_chip):
    pages = _sds(one_chip, (FRAMES, PAGE, KV_HEADS, HEAD_DIM))
    scalar = _sds(one_chip, (), jnp.int32)
    text = _compiled_text(
        lambda q, k, v, bt, start, cl: paged_chunk_attention_pallas(
            q, k, v, bt, start, cl, interpret=False),
        _sds(one_chip, (CHUNK, HEADS, HEAD_DIM)), pages, pages,
        _sds(one_chip, (COLUMNS,), jnp.int32), scalar, scalar)
    assert "tpu_custom_call" in text
