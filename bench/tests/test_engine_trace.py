"""bench/engine_trace.py and the readers of the engine's own spans and
counters: idle time put down to the innermost span on hand-made events
(``bench/trace_reduce.py``), the
``engine.*`` spans of a real profile recorded on the CPU around a tiny
engine's steps, each reader on a hand-made run, and bench/phases.py's run
at CPU size."""
import json
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import trace_reduce as tr
from bench.run import CompileMeter, Run, metric_reader
from bench.tests import tiny


def ev(name, a, b, **stats):
    return tr.Event(name, a, b, stats)


def nested_profile():
    # window [0, 10]; device busy [1, 3] and [6, 7]; one bench.step [0.5,
    # 5] holding engine.step [0.6, 4.9] with engine.plan [0.6, 1.2] and
    # engine.decode.wait [3.5, 4.7]; then a wait span [5, 8]
    return tr.Profile(
        ops=[[ev("a", 1, 3), ev("a", 6, 7)]],
        modules=[[ev("jit_step(3)", 1, 3)]],
        spans=[ev("bench.window", 0, 10),
               ev("bench.step", 0.5, 5, index=0),
               ev("engine.step", 0.6, 4.9, index=0),
               ev("engine.plan", 0.6, 1.2),
               ev("engine.decode.wait", 3.5, 4.7),
               ev("bench.wait_arrival", 5, 8)])


def test_idle_goes_to_the_innermost_span():
    p = nested_profile()
    r = tr.reduce(p)
    # gaps: [3,6] 3 s (mid 4.5: decode.wait, the shortest span open),
    # [7,10] 3 s (mid 8.5: the window), [0,1] 1 s (mid 0.5: bench.step)
    assert r.gaps == [("engine.decode.wait", pytest.approx(3.0)),
                      ("bench.window", pytest.approx(3.0)),
                      ("bench.step", pytest.approx(1.0))]
    idle = tr.idle_by_span(p, *r.window)
    assert idle == {
        "bench.window": pytest.approx(0.5 + 2.0),     # [0,.5], [8,10]
        "bench.step": pytest.approx(0.1 + 0.1),       # [.5,.6], [4.9,5]
        "engine.plan": pytest.approx(0.4),            # [.6,1]
        "engine.step": pytest.approx(0.5 + 0.2),      # [3,3.5], [4.7,4.9]
        "engine.decode.wait": pytest.approx(1.2),
        "bench.wait_arrival": pytest.approx(1.0 + 1.0)}   # [5,6], [7,8]
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    # the step's own records are still read from bench.step alone
    assert [s["index"] for s in r.steps] == [0]


def test_idle_outside_every_span():
    p = tr.Profile(ops=[[ev("a", 2, 3)]], modules=[[]],
                   spans=[ev("engine.step", 1, 2)])
    assert tr.idle_by_span(p, 0, 4) == {
        "outside bench spans": pytest.approx(2.0),
        "engine.step": pytest.approx(1.0)}


def test_recorded_profile_holds_the_engine_spans(tmp_path):
    from bench.harness import Server
    from repro.serving.request import Request
    server = Server(tiny.cell())
    server.warm_up(7)
    eng = server.eng
    rng = np.random.default_rng(0)
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=rng.integers(
            0, 500, 100).astype(np.int32), max_new_tokens=3,
            ttft_slo_s=30.0, tpot_slo_s=30.0))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for k in range(6):
            with jax.profiler.TraceAnnotation("bench.step", index=k):
                eng.step()
    jax.profiler.stop_trace()
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    p = tr.load(path)
    spans = [s for s in p.spans if s.name.startswith("engine.")]
    names = {s.name for s in spans}
    assert {"engine.step", "engine.plan", "engine.apply", "engine.chunk",
            "engine.chunk.dispatch", "engine.chunk.wait", "engine.inputs",
            "engine.decode.dispatch", "engine.decode.wait", "engine.sample",
            "engine.record"} <= names
    steps = [s for s in spans if s.name == "engine.step"]
    assert [s.stats.get("index") for s in steps] == list(range(
        len(eng.trace.iterations) - 6, len(eng.trace.iterations)))
    chunks = [s for s in spans if s.name == "engine.chunk"]
    assert {s.stats.get("rid") for s in chunks} == {0, 1}
    assert all(s.stats.get("end") > s.stats.get("start") for s in chunks)
    # every phase nests inside its step
    for s in spans:
        assert any(st.start <= s.start and s.end <= st.end for st in steps)
    r = tr.reduce(p)
    assert len(r.steps) == 6
    assert r.idle_by_span == tr.idle_by_span(p, *r.window)
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s)


# ------------------------------------------------------------- the readers --
def rec(t0, host_s, decode_batch=1, h2d=0, d2h=0):
    return SimpleNamespace(wall_t0_s=t0, host_s=host_s,
                           decode_batch=decode_batch, h2d_bytes=h2d,
                           d2h_bytes=d2h)


def req(due, sub, adm, first, rejected=False):
    return SimpleNamespace(due=due, rejected=rejected, req=SimpleNamespace(
        submitted_wall_s=sub, admitted_wall_s=adm, first_token_wall_s=first))


def hand_run(**kw):
    base = dict(w0=10.0, w1=20.0, requests=[], iterations=[])
    base.update(kw)
    return Run(**base)


ITERS = [rec(9.0, {"plan": 1.0, "inputs": 1.0}, h2d=9e6, d2h=9e6),
         rec(10.0, {"plan": 0.002, "apply": 0.001, "inputs": 0.004,
                    "decode.dispatch": 0.0005, "sample": 0.0002},
             h2d=1e6, d2h=5e6),
         rec(11.0, {"plan": 0.004, "apply": 0.002, "inputs": 0.006,
                    "decode.dispatch": 0.0007, "sample": 0.0004},
             h2d=3e6, d2h=5e6),
         rec(12.0, {"plan": 0.010, "chunk": 0.05}, decode_batch=0,
             h2d=2e6, d2h=2e6),
         rec(20.0, {"plan": 1.0, "inputs": 1.0}, h2d=9e6, d2h=9e6)]


@pytest.mark.parametrize("name, value", [
    ("plan_ms_per_step", 6.0),           # median of 3, 6, 10 ms
    ("inputs_ms_per_step", 5.0),         # decode steps: 4, 6 ms
    ("dispatch_ms_per_step", 0.6),
    ("sample_ms_per_step", 0.3),
    ("d2h_mb_per_step", 4.0),            # (5 + 5 + 2) / 3 MB
    ("h2d_mb_per_step", 2.0),            # (1 + 3 + 2) / 3 MB
])
def test_step_readers(name, value):
    read = metric_reader(name)
    assert read(hand_run(iterations=ITERS)) == pytest.approx(value)
    # a program with no wall stamps, or a run that kept no records
    old = [SimpleNamespace(decode_batch=1)]
    assert read(hand_run(iterations=old)) is None
    assert read(Run(w0=0.0, w1=1.0, requests=[])) is None


REQS = [req(9.0, 9.0, 9.5, 9.9),             # due before the window
        req(11.0, 11.0, 11.2, 11.5),
        req(12.0, 12.1, 12.2, 12.6),
        req(13.0, 13.0, 13.1, None),        # no first token by w1 = 20
        req(14.0, 14.0, None, None),        # never admitted
        req(15.0, 15.0, None, None, rejected=True)]


def test_queue_wait_reader():
    # waits 0.2, 0.1, 0.1, 6.0 (still waiting at 20): p95 by np.percentile
    v = metric_reader("queue_wait_p95_ms")(hand_run(requests=REQS))
    assert v == pytest.approx(1e3 * np.percentile([0.2, 0.1, 0.1, 6.0], 95))


def test_prefill_wall_reader():
    # admitted ones: 0.3, 0.4, 6.9 (no first token by 20)
    v = metric_reader("prefill_wall_p95_ms")(hand_run(requests=REQS))
    assert v == pytest.approx(1e3 * np.percentile([0.3, 0.4, 6.9], 95))


# stamps after the window's end (w1 = 20), as in a traced run whose host
# stalls while the profiler stops
LATE = [req(11.0, 11.0, 11.5, 27.0),        # first token after the window
        req(12.0, 12.0, 25.0, 26.0),        # admitted after it
        req(19.9, 20.5, 21.0, 22.0)]        # submitted after it


@pytest.mark.parametrize("name, waits", [
    ("queue_wait_p95_ms", [0.5, 8.0]),
    ("prefill_wall_p95_ms", [8.5]),
])
def test_request_readers_stop_at_the_window_end(name, waits):
    v = metric_reader(name)(hand_run(requests=LATE))
    assert v == pytest.approx(1e3 * np.percentile(waits, 95))


# a saturated window: the requests it admits were due before it, and none
# of those due inside it gets a slot by its end
SATURATED = [req(5.0, 5.0, 10.5, 11.0),
             req(6.0, 6.0, 12.0, None),      # no first token by w1 = 20
             req(7.0, 7.0, None, None),      # due before, never admitted
             req(11.0, 11.0, None, None),
             req(15.0, 15.0, None, None),
             req(19.9, 20.5, None, None)]    # submitted after the window


@pytest.mark.parametrize("name, waits", [
    ("queue_wait_p95_ms", [9.0, 5.0]),
    ("prefill_wall_p95_ms", [0.5, 8.0]),
])
def test_request_readers_read_a_saturated_window(name, waits):
    v = metric_reader(name)(hand_run(requests=SATURATED))
    assert v == pytest.approx(1e3 * np.percentile(waits, 95))


@pytest.mark.parametrize("name", ["queue_wait_p95_ms",
                                  "prefill_wall_p95_ms"])
def test_request_readers_read_nothing_with_nothing_to_read(name):
    # nothing admitted inside the window; nothing due in it submitted by
    # its end
    reqs = [req(9.0, 9.0, 9.5, None), req(19.9, 20.5, None, None)]
    assert metric_reader(name)(hand_run(requests=reqs)) is None


@pytest.mark.parametrize("name", ["queue_wait_p95_ms",
                                  "prefill_wall_p95_ms"])
def test_request_readers_read_nothing_without_wall_stamps(name):
    old = [SimpleNamespace(due=11.0, rejected=False,
                           req=SimpleNamespace(admitted_s=0.0))]
    assert metric_reader(name)(hand_run(requests=old)) is None


def test_phases_run_at_cpu_size():
    """bench/phases.py's traced run, through execute, at CPU size: every
    engine reader reads, the idle seconds add up, and the gaps carry
    engine span names."""
    from bench import phases
    bench = {"end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"}],
             "per_layer": [{"name": "device_idle_share", "unit": "%"}]}
    line = phases.one_run(tiny.cell(), 2**33 + 5, 3.0, True, tiny.DEVICE,
                          bench, CompileMeter())
    json.dumps(line)
    assert line["correct"]
    assert all(v is not None for v in line["readers"].values()), \
        line["readers"]
    assert set(phases.ENGINE_METRICS) <= set(line["readers"])
    idle = sum(line["idle_by_span"].values())
    assert idle == pytest.approx(line["window_s"] - line["busy_s"])
    assert any(name.startswith("engine.") for name, _ in line["idle_gaps"])
    assert line["engine_spans_per_step"] > 5
    assert 0 < line["phase_ms"]["step"] <= line["phase_ms_max"]["step"]
    assert line["gc_pauses"]["n"] >= 0 and "end_to_end" not in line
