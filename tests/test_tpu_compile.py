"""AOT compiles of the served path's Pallas kernels and jitted steps for a
described TPU v5e.

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
import importlib.util
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention, prefill_attention
from repro.kernels.decode_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import paged_chunk_attention_pallas

from _engine_builders import mk_reduced_engine

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


def _bench_kernel_keys() -> dict:
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "metrics_common.py")
    spec = importlib.util.spec_from_file_location("bench_metrics_common",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNELS


def test_served_step_names_match_the_benchmark_keys(one_chip, monkeypatch):
    """The benchmark's trace readers find the decode and chunk programs by
    their jit names and the decode kernel by the name Pallas gives its
    custom call (``KERNELS`` in bench/metrics_common.py). Compile the
    engine's own jitted steps (one layer, head size 128) for a v5e and pin
    both: a rename here silences ``decode_attn_roofline``."""
    monkeypatch.setattr(decode_attention, "interpret_mode", lambda: False)
    monkeypatch.setattr(prefill_attention, "interpret_mode", lambda: False)
    eng, _ = mk_reduced_engine(d_model=256, heads=2, layers=1, d_ff=512,
                               vocab=256, max_batch=8, max_seq=64,
                               hbm_gb=0.05, prefill_chunk_tokens=32,
                               incremental_prefill=True)
    iv = eng.interval
    eng._rt(iv)
    w = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), eng._w)
    pool = _sds(one_chip, eng.pool.shape)

    def i32(*shape):
        return _sds(one_chip, shape, jnp.int32)

    b, nb, c = eng.ecfg.max_batch, eng.nb, eng.scheduler.chunk_tokens
    decode = eng._jit_decode[iv].lower(
        w, i32(b), i32(b), pool, i32(b, nb), i32(b), i32(b),
        i32(b)).compile().as_text()
    chunk = eng._jit_chunk[iv].lower(
        w, i32(c), i32(), pool, i32(nb), i32(), i32(c), i32(c),
        i32()).compile().as_text()
    assert decode.startswith("HloModule jit_paged_decode_step,")
    assert chunk.startswith("HloModule jit_paged_prefill_chunk,")
    key = _bench_kernel_keys()["decode_attention"]

    def matches(text):
        return [ln for ln in text.splitlines()
                if all(k in ln for k in key)]
    hit = matches(decode)
    assert len(hit) == 1 and 'custom_call_target="tpu_custom_call"' in hit[0]
    assert matches(chunk) == []
