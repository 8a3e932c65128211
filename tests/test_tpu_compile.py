"""AOT compiles of the served path's Pallas kernels and jitted steps for a
described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (unaligned
slices, too much VMEM) — what interpret-mode tests cannot see. Shapes are
qwen2.5-3b's: page 16, 2 KV heads of 128, 16 query heads, batch 8, 256
block-table columns, 256-token prefill chunks, over a pool of a few layers.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""
import importlib.util
import math
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention, prefill_attention
from repro.kernels.decode_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import paged_chunk_attention_pallas

from _engine_builders import mk_reduced_engine

PAGE, KV_HEADS, HEAD_DIM, HEADS = 16, 2, 128, 16
BATCH, COLUMNS, CHUNK, LAYERS = 8, 256, 256, 3
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


def _pool(sharding):
    return _sds(sharding, (FRAMES, PAGE, LAYERS, 2, KV_HEADS, HEAD_DIM))


def test_paged_decode_kernel_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda q, pool, layer, bt, cl: paged_decode_attention_pallas(
            q, pool, layer, bt, cl, interpret=False),
        _sds(one_chip, (BATCH, HEADS, HEAD_DIM)), _pool(one_chip),
        _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (BATCH, COLUMNS), jnp.int32),
        _sds(one_chip, (BATCH,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_chunk_kernel_compiles_for_v5e(one_chip):
    scalar = _sds(one_chip, (), jnp.int32)
    text = _compiled_text(
        lambda q, pool, layer, bt, start, cl: paged_chunk_attention_pallas(
            q, pool, layer, bt, start, cl, interpret=False),
        _sds(one_chip, (CHUNK, HEADS, HEAD_DIM)), _pool(one_chip), scalar,
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


def _compiled_steps(sharding, layers: int, heads: int = 2):
    """The engine's decode and chunk programs (reduced qwen2.5-3b, head
    size 128, ``heads`` query heads, ``layers`` layers, resident weights)
    compiled for a v5e, and the pool's shape."""
    eng, _ = mk_reduced_engine(d_model=128 * heads, heads=heads,
                               layers=layers, d_ff=512, vocab=256,
                               max_batch=8, max_seq=64, hbm_gb=1.0,
                               prefill_chunk_tokens=32,
                               incremental_prefill=True)
    iv = eng.interval
    eng._rt(iv)
    w = jax.tree.map(lambda a: _sds(sharding, a.shape, a.dtype), eng._w)
    pool = _sds(sharding, eng.pool.shape)

    def i32(*shape):
        return _sds(sharding, shape, jnp.int32)

    b, nb, c = eng.ecfg.max_batch, eng.nb, eng.scheduler.chunk_tokens
    decode = eng._jit_decode[iv].lower(
        w, i32(b), i32(b), pool, i32(b, nb), i32(b), i32(b),
        i32(b)).compile().as_text()
    chunk = eng._jit_chunk[iv].lower(
        w, i32(c), i32(), pool, i32(nb), i32(), i32(c), i32(c),
        i32()).compile().as_text()
    return decode, chunk, eng.pool.shape


def _compiled_for_v5e(monkeypatch):
    monkeypatch.setattr(decode_attention, "interpret_mode", lambda: False)
    monkeypatch.setattr(prefill_attention, "interpret_mode", lambda: False)


def test_served_step_names_match_the_benchmark_keys(one_chip, monkeypatch):
    """The benchmark's trace readers find the decode and chunk programs by
    their jit names and the decode kernel by the name Pallas gives its
    custom call (``KERNELS`` in bench/metrics_common.py). Compile the
    engine's own jitted steps (one layer, head size 128) for a v5e and pin
    both: a rename here silences ``decode_attn_roofline``."""
    _compiled_for_v5e(monkeypatch)
    decode, chunk, _ = _compiled_steps(one_chip, layers=1)
    assert decode.startswith("HloModule jit_paged_decode_step,")
    assert chunk.startswith("HloModule jit_paged_prefill_chunk,")
    key = _bench_kernel_keys()["decode_attention"]

    def matches(text):
        return [ln for ln in text.splitlines()
                if all(k in ln for k in key)]
    hit = matches(decode)
    assert len(hit) == 1 and 'custom_call_target="tpu_custom_call"' in hit[0]
    assert matches(chunk) == []


_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = [a-z0-9]+\[([0-9,]*)\]\{[^}]*\} ([a-z-]+)\(")


@pytest.mark.parametrize("heads", [2, 16], ids=["1kv-head", "2kv-heads"])
def test_served_steps_read_the_pool_without_a_per_layer_slice(
        one_chip, monkeypatch, heads):
    """The paged kernels take the whole pool and a layer index. No
    instruction of the compiled decode or chunk program holds one layer's
    slice of the pool (``frames x page x 2 x vh x hd`` elements) or its K or
    V half, which XLA would write out before each kernel read a page, and
    none copies the whole pool into another layout, which it would do
    before each kernel call were the operand not in its default layout (a
    single KV head's pool is laid out otherwise)."""
    _compiled_for_v5e(monkeypatch)
    layers = 2
    decode, chunk, pool_shape = _compiled_steps(one_chip, layers=layers,
                                                heads=heads)
    pool_elems = math.prod(pool_shape)
    per_layer = {pool_elems // layers, pool_elems // (2 * layers)}
    for text in (decode, chunk):
        assert text.count('custom_call_target="tpu_custom_call"') >= layers
        found = []
        for ln in text.splitlines():
            m = _INSTR.match(ln)
            if not m or not m.group(2):
                continue
            name, dims, op = m.groups()
            elems = math.prod(int(x) for x in dims.split(","))
            relayout = op == "copy" or (op == "fusion"
                                        and name.startswith("copy"))
            if elems in per_layer or (elems == pool_elems and relayout):
                found.append(ln.strip()[:160])
        assert found == [], found
