"""bench/trace_reduce.py: its interval arithmetic on hand-made events, its
reading of a real profile recorded on the CPU around two named jitted
functions, and of one recorded around a tiny engine's serving."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace_reduce as tr


def ev(name, a, b, **stats):
    return tr.Event(name, a, b, stats)


def test_union_clip_and_covered():
    u = tr.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)]
    assert tr.covered(tr.clip(u, 2, 5.5)) == pytest.approx(1.5)
    starts = [s for s, _ in u]
    assert tr.covered_between(u, starts, 2.5, 5.5) == pytest.approx(1.0)


def test_reduce_by_hand():
    # window [0, 10]; ops busy [1,3] and [2,4] and [6,7]: busy 4 s;
    # one step span [0.5, 4.5] holding a program of 3 s
    p = tr.Profile(
        ops=[[ev("a", 1, 3), ev("b", 2, 4), ev("a", 6, 7)]],
        modules=[[ev("jit_step(3)", 1, 4)]],
        spans=[ev("bench.window", 0, 10), ev("bench.step", 0.5, 4.5,
                                             index=7),
               ev("bench.wait_arrival", 4.5, 6)])
    r = tr.reduce(p)
    assert r.busy_s == pytest.approx(4.0)
    assert r.window_s == pytest.approx(10.0)
    assert r.op_s == {"a": pytest.approx(3.0), "b": pytest.approx(2.0)}
    assert r.program_s == {"step": pytest.approx(3.0)} and \
        r.program_n == {"step": 1}
    assert r.steps == [{"index": 7, "host_s": pytest.approx(4.0),
                        "device_busy_s": pytest.approx(3.0),
                        "programs": {"step": pytest.approx(3.0)}}]
    # idle gaps: [0,1] 1 s (step span), [4,6] 2 s (mid 5: wait_arrival),
    # [7,10] 3 s (window only)
    assert r.gaps == [("bench.window", pytest.approx(3.0)),
                      ("bench.wait_arrival", pytest.approx(2.0)),
                      ("bench.step", pytest.approx(1.0))]


def test_program_name():
    assert tr.program_name("jit_paged_decode_step(42)") == \
        "paged_decode_step"
    assert tr.program_name("PjitFunction(prefill)") == "prefill"


# op labels of one decode step as a v5e trace shows them (shapes cut short):
# the decode kernel is the one custom call inside the decode program
CHIP_LABELS = {
    "%paged_decode_step.45 = bf16[16,16,128]{2,1,0} custom-call(s32[16,256]"
    " %broadcast_clamp_fusion, bf16[8193,16,2,128] %get-tuple-element)": 3e-4,
    "%copy_bitcast_fusion.20 = bf16[8193,16,2,2,128] fusion(bf16[8193,16,36,"
    "2,2,128] %fusion.32), kind=kLoop": 4e-4,
    "%fusion.1373 = bf16[16,152064] fusion(bf16[152064,2048] "
    "%params_split__embed__.1), kind=kOutput": 8e-4,
}


def test_decode_kernel_key_on_chip_labels():
    from bench.metrics_common import kernel_seconds
    run = type("Run", (), {"profile": type("P", (), {
        "label_s": CHIP_LABELS})()})()
    assert kernel_seconds(run, "decode_attention") == pytest.approx(3e-4)


@jax.jit
def alpha(x):
    return jnp.tanh(x @ x)


@jax.jit
def beta(x):
    return jnp.sin(x).sum()


def test_recorded_cpu_profile(tmp_path):
    x = jnp.ones((256, 256))
    alpha(x).block_until_ready()
    beta(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.step", index=i):
                alpha(x).block_until_ready()
                beta(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    r = tr.reduce(tr.load(str(path)))
    assert [s["index"] for s in r.steps] == [0, 1, 2]
    assert 0 < r.busy_s < r.window_s
    assert r.program_n.get("alpha", 0) >= 3 and r.program_n.get("beta", 0) >= 3
    assert any("dot" in name for name in r.op_s)
    # the sleeps leave the longest idle gaps, each inside a wait span
    assert r.gaps[0][0] == "bench.wait_arrival"
    assert r.gaps[0][1] >= 0.015
    for s in r.steps:
        assert s["device_busy_s"] <= s["host_s"]


# the readers BENCHMARK.json listed before the engine's spans were loaded
BENCH_READERS = ("gen_lag_p99_ms", "host_ms_per_step", "window_compiles",
                 "decode_device_ms", "decode_mfu", "chunk_prefill_device_ms",
                 "hbm_peak_gb", "decode_attn_roofline", "device_idle_share")


class SlowLogits:
    """A decode program's logits whose crossing to the host takes 20 ms
    longer, with the device idle: a wait inside ``engine.decode.wait``."""

    def __init__(self, a):
        self.a, self.nbytes = a, a.nbytes

    def __array__(self, dtype=None, copy=None):
        self.a.block_until_ready()
        time.sleep(0.02)
        return np.asarray(self.a, dtype)


def test_engine_spans_on_a_recorded_serving_trace(tmp_path):
    """A tiny engine served on the CPU under the profiler: loading the
    ``engine.*`` spans beside the ``bench.*`` ones changes no reading of the
    trace, names the idle gaps by the engine's phases, and puts the
    window's idle time down to spans in full."""
    from bench import traffic
    from bench.harness import Server
    from bench.run import Run, metric_reader
    from bench.tests import tiny
    cell = tiny.cell()
    server = Server(cell)
    server.warm_up(3)
    eng = server.eng
    for k, fn in list(eng._jit_decode.items()):
        def slow(*args, fn=fn):
            logits, pool = fn(*args)
            return SlowLogits(logits), pool
        eng._jit_decode[k] = slow
    items = [dataclasses.replace(it, due_s=0.0) for it in traffic.schedule(
        cell.mix, 4.0, 2.0, 5, 512, 256)]
    t_gen0 = time.perf_counter()
    state = {}

    def on_time(now):
        if not state:
            jax.profiler.start_trace(str(tmp_path))
            state["ann"] = jax.profiler.TraceAnnotation("bench.window")
            state["ann"].__enter__()
            state["w0"] = now
        elif "w1" not in state and now >= state["w0"] + 1.5:
            state["ann"].__exit__(None, None, None)
            state["w1"] = time.perf_counter()
            jax.profiler.stop_trace()

    requests = server.serve(items, t_gen0, t_gen0 + 60.0, on_time)
    assert "w1" in state
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    both = tr.load(path)
    bench_only = dataclasses.replace(both, spans=[
        s for s in both.spans if s.name.startswith("bench.")])
    assert len(bench_only.spans) < len(both.spans)
    r, r0 = tr.reduce(both), tr.reduce(bench_only)
    for field in ("window", "busy_s", "op_s", "label_s", "program_s",
                  "program_n", "steps"):
        assert getattr(r, field) == getattr(r0, field), field
    assert len(r.steps) > 5 and r.program_n.get("paged_decode_step")
    readings = []
    for profile in (r, r0):
        run = Run(cell=cell, requests=requests, steps=server.steps,
                  w0=state["w0"], w1=state["w1"], setup_s=1.0,
                  window_compiles=0, memory_peak_bytes=None,
                  profile=profile, peaks=tiny.DEVICE["peaks"])
        readings.append({n: metric_reader(n)(run) for n in BENCH_READERS})
    assert readings[0] == readings[1]
    assert readings[0]["host_ms_per_step"] is not None
    idle = r.window_s - r.busy_s
    assert sum(r.idle_by_span.values()) == pytest.approx(idle, rel=0.01)
    assert r.idle_by_span.get("engine.decode.wait", 0.0) >= 0.015
    assert any(name == "engine.decode.wait" and s >= 0.015
               for name, s in r.gaps)
    assert all(name.startswith("bench.") or name == tr.OUTSIDE
               for name, _ in r0.gaps)
