"""Where a cell's host time and device idle time go, by the engine's own
spans and counters (``bench/engine_trace.py``).

    python3 bench/phases.py --workload qwen2.5-3b.chat --seeds 7 \
        --seconds 51

For each seed, two runs of the cell as ``bench/run.py`` makes them (same
set-up, traffic, window and correctness check), in one process: one with
the profiler off, then one that traces the first ``TRACE_S`` seconds of
the window. Each reports the median and largest self time of every
``engine.*`` phase per step, the garbage collector's pauses in the
window, and the readings of the engine-span readers (``ENGINE_METRICS``,
under ``bench/metrics``); the untraced run its end-to-end metrics (the
traced run stalls while the profiler stops). The traced run also
reports the per-layer metrics ``BENCHMARK.json`` lists for the cell, its
longest device idle gaps named by the innermost span of either family
(``bench.*`` or ``engine.*``), the window's idle seconds by innermost span
(``idle_by_span``), and the ``engine.*`` spans per step. First, the cost
of one span enter and exit with the profiler off, on this host. One JSON
line per seed on standard output, also appended to
``chiprun_out/phases_<cell>.jsonl``. Needs an accelerator, like run.py.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.run import (TRACE_S, CompileMeter, Run, enable_cache,  # noqa: E402
                       execute, metric_reader, metrics_for, require_device)

# readers of the engine's spans and counters
ENGINE_METRICS = ("queue_wait_p95_ms", "prefill_wall_p95_ms",
                  "plan_ms_per_step", "inputs_ms_per_step",
                  "dispatch_ms_per_step", "sample_ms_per_step",
                  "d2h_mb_per_step", "h2d_mb_per_step")


def span_cost_us(n: int = 20_000) -> float:
    """Microseconds of one ``TraceRecorder.span`` enter and exit inside an
    open step, with no profiler recording."""
    from repro.serving.telemetry import TraceRecorder
    rec = TraceRecorder("cost", 1, 1)
    with rec.span("step"):
        t0 = time.perf_counter()
        for _ in range(n):
            with rec.span("phase"):
                pass
        return 1e6 * (time.perf_counter() - t0) / n


def server_class(trace: bool):
    """A Server that keeps the engine's records at release and, with
    ``trace``, records the profiler trace of the window's first
    ``TRACE_S`` seconds (as run.py's traced run does) and keeps the file."""
    import jax
    from bench.harness import Server

    class Kept(Server):
        def serve(self, items, t_gen0, t_end, on_time=None, wake=()):
            self.w0 = t_gen0 + self.cell.params["warm_up"]["traffic_s"]
            self.trace_dir = self.closed = None
            self.gc_pauses = []          # (start, seconds) of collections
            ann = None
            began = [0.0]

            def on_gc(phase, info):
                if phase == "start":
                    began[0] = time.perf_counter()
                else:
                    self.gc_pauses.append(
                        (began[0], time.perf_counter() - began[0]))

            def traced(now):
                nonlocal ann
                if trace and self.trace_dir is None and now >= self.w0 - 1:
                    self.trace_dir = tempfile.mkdtemp(prefix="bench-phases-")
                    jax.profiler.start_trace(self.trace_dir)
                if trace and self.closed is None and ann is None \
                        and now >= self.w0:
                    ann = jax.profiler.TraceAnnotation("bench.window")
                    ann.__enter__()
                if ann is not None and now >= self.w0 + TRACE_S:
                    ann.__exit__(None, None, None)
                    ann = None
                    self.closed = time.perf_counter()
                    jax.profiler.stop_trace()
                if on_time is not None:
                    on_time(now)

            gc.callbacks.append(on_gc)
            try:
                self.requests = super().serve(items, t_gen0, t_end, traced,
                                              wake)
            finally:
                gc.callbacks.remove(on_gc)
            if ann is not None:
                traced(float("inf"))
            return self.requests

        def release(self):
            self.iterations = self.eng.trace.iterations
            super().release()

    return Kept


def phase_ms(steps, q: float = 50) -> dict:
    """The ``q``-th percentile of each phase's self ms over the steps that
    ran it."""
    from bench.harness import percentile
    names = sorted({k for r in steps for k in r.host_s})
    return {k: 1e3 * percentile([r.host_s[k] for r in steps
                                 if k in r.host_s], q) for k in names}


def one_run(cell, seed, seconds, trace, device, bench, meter) -> dict:
    import jax
    from bench import engine_trace, trace_reduce
    t_start = time.perf_counter()
    made = []

    def factory(c):
        made.append(server_class(trace)(c))
        return made[-1]

    out = execute(cell, seed, seconds, False, device, bench, meter,
                  t_start, server_factory=factory)
    srv = made[0]
    w1 = srv.closed or srv.w0 + seconds
    profile = prof = None
    if srv.trace_dir is not None:
        path = str(next(pathlib.Path(srv.trace_dir).rglob("*.xplane.pb")))
        prof = trace_reduce.load(path)
        profile = trace_reduce.reduce(prof, top=20)
        shutil.rmtree(srv.trace_dir, ignore_errors=True)
    run = Run(cell=cell, requests=srv.requests, steps=srv.steps, w0=srv.w0,
              w1=w1, setup_s=srv.w0 - t_start,
              window_compiles=sum(r.compiles for r in srv.iterations
                                  if srv.w0 <= r.wall_t0_s < w1),
              memory_peak_bytes=(jax.devices()[0].memory_stats() or {})
              .get("peak_bytes_in_use"),
              profile=profile, peaks=device["peaks"],
              iterations=srv.iterations)
    names = list(ENGINE_METRICS)
    if trace:
        names = [m["name"] for m in metrics_for(bench, cell.name, True)] \
            + names
    steps = engine_trace.window_steps(run)
    pauses = [d for t, d in srv.gc_pauses if srv.w0 <= t < w1]
    line = {"correct": out["correct"], "compared": out["compared"],
            "steps": len(steps),
            "decode_steps": sum(1 for r in steps if r.decode_batch),
            "phase_ms": phase_ms(steps),
            "decode_phase_ms": phase_ms(
                [r for r in steps if r.decode_batch and not r.n_chunks]),
            "chunk_phase_ms": phase_ms([r for r in steps if r.n_chunks]),
            "phase_ms_max": phase_ms(steps, 100),
            "gc_pauses": {"n": len(pauses), "total_ms": 1e3 * sum(pauses),
                          "max_ms": 1e3 * max(pauses, default=0.0)},
            "readers": {n: metric_reader(n)(run) for n in names}}
    if not trace:
        # a traced run's window stalls while the profiler stops: its
        # end-to-end metrics are not the cell's
        line["end_to_end"] = {k: v["value"]
                              for k, v in out["metrics"].items()}
    if profile is not None:
        lo, hi = profile.window
        n_spans = sum(1 for s in prof.spans if s.name.startswith("engine.")
                      and lo <= s.start < hi)
        line.update(
            busy_s=profile.busy_s, window_s=profile.window_s,
            idle_gaps=profile.gaps,
            idle_by_span=dict(sorted(profile.idle_by_span.items(),
                                     key=lambda kv: -kv[1])),
            engine_spans_per_step=n_spans / max(len(profile.steps), 1))
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    from bench.harness import load_benchmark, load_cell
    bench = load_benchmark()
    cell = load_cell(args.workload, bench)
    device = require_device(cell.entry["chips"])
    enable_cache()
    meter = CompileMeter()
    out_path = ROOT / "chiprun_out" / f"phases_{cell.name}.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    cost = span_cost_us()
    for seed in (int(x) for x in args.seeds.split(",")):
        line = {"workload": cell.name, "seed": seed, "span_cost_us": cost,
                "device": device["kind"]}
        for trace in (False, True):
            line["traced" if trace else "untraced"] = one_run(
                cell, seed, args.seconds, trace, device, bench, meter)
        print(json.dumps(line), flush=True)
        with out_path.open("a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
