"""Run one cell of the benchmark once, on the accelerator this process sees.

    python3 bench/run.py --workload qwen2.5-3b.chat --seed 7 --seconds 51 \
        --trace 0

Set-up builds the engine of the cell (``BENCHMARK.json`` -> the cell's
config, traffic and ``bench/cells/<cell>.json``), compiles every program the
cell runs, and starts the open-loop generator; requests due in the first
``warm_up.traffic_s`` seconds are warm-up traffic. The window that follows
lasts ``--seconds``. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a profiler trace of the
first ``TRACE_S`` seconds of the window and from the run's own records and
the engine's iteration records of those seconds. The traced run's
``breakdown`` names the longest device idle gaps by the innermost host span
open over each (the benchmark's ``bench.*`` or the engine's ``engine.*``)
and sums the window's idle time by innermost span (``idle_by_span``).

After the window the engine is released and a sample of the requests it
finished is checked against the plain reference (``bench/check.py``). The
last line of standard output is one JSON object; the numbers compared are
its last key (``compared``) and the last lines of standard error. Without an
accelerator, or with fewer devices than the cell asks for, the run exits 2
and prints no result; a metric the cell lists that reads nothing exits 1.

``--control int8|fp8`` runs the correctness check's control: the same run,
judged by the same comparison, with the tokens the reference picks at that
precision (teacher forced on the served sequences) in the served tokens'
place. It has to come out not correct. Benchmark runs never pass it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# seconds of the window the traced run records
TRACE_S = 10.0


class CompileMeter:
    """Programs built (from ``jax.monitoring``; after the chip smoke's
    meter). ``programs`` counts lowerings: every jit cache miss lowers once,
    whether the executable then comes from the persistent cache or from the
    compiler."""

    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_secs(self, event: str, secs: float, **_) -> None:
        self.programs += event == self.LOWERED


class Run:
    """What the metric readers read: the cell, the run's records, and the
    reduced trace of a traced run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def in_window(self, t: float) -> bool:
        return self.w0 <= t < self.w1

    def due_in_window(self) -> list:
        return [r for r in self.requests if self.in_window(r.due)]


def idle_list(idle: dict[str, float], n: int = 10) -> list:
    """``[span, idle seconds]`` pairs, largest first, at most ``n``: the
    ``n - 1`` largest and the rest summed as ``other spans`` where there are
    more, so the list still sums to the window's idle time."""
    pairs = sorted(([k, v] for k, v in idle.items()), key=lambda kv: -kv[1])
    if len(pairs) > n:
        pairs[n - 1:] = [["other spans", sum(v for _, v in pairs[n - 1:])]]
    return pairs


def metric_reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def require_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform} device(s): nothing was run",
              file=sys.stderr)
        raise SystemExit(2)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if devs[0].device_kind not in peaks:
        print(f"bench: no peaks for device kind {devs[0].device_kind!r} in "
              "bench/peaks.json", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "peaks": peaks[devs[0].device_kind]}


def enable_cache() -> None:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or the checkout's fixed ``.jax_cache/``), holding every program
    however fast it compiled: the many small page-copy programs too."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def execute(cell, seed: int, seconds: float, trace: bool, device: dict,
            bench: dict, meter: CompileMeter, t_process: float,
            server_factory=None, control: str | None = None) -> dict:
    """One run of ``cell``: set-up, window, check. Returns the result line.
    ``server_factory`` replaces the Server class (the fault tests plant
    their faults through it); ``control`` judges that precision's picks in
    place of the served tokens."""
    import jax
    from bench import check
    from bench.harness import Server, items_for
    from bench import trace_reduce

    p = cell.params
    server = (server_factory or Server)(cell)
    server.warm_up(seed)
    items = items_for(cell, seed, seconds)
    t_gen0 = time.perf_counter()
    w0 = t_gen0 + p["warm_up"]["traffic_s"]
    w1 = w0 + seconds
    # a traced run records [w0, w0 + TRACE_S]: the profiler starts a second
    # before the window (its start-up stalls the host) and stops after it
    state = {"compiles0": None, "trace_dir": None, "ann": None,
             "closed": None}

    def on_time(now: float) -> None:
        if trace and state["trace_dir"] is None and now >= w0 - 1.0:
            state["trace_dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(state["trace_dir"])
        if state["compiles0"] is None and now >= w0:
            state["compiles0"] = meter.programs
            if trace:
                state["ann"] = jax.profiler.TraceAnnotation("bench.window")
                state["ann"].__enter__()
        if state["ann"] is not None and now >= w0 + TRACE_S:
            state["ann"].__exit__(None, None, None)
            state["ann"] = None
            state["closed"] = time.perf_counter()
            jax.profiler.stop_trace()

    requests = server.serve(items, t_gen0, w1, on_time,
                            wake=(w0 - 1.0, w0, w0 + TRACE_S))
    if state["ann"] is not None:
        on_time(float("inf"))
    window_compiles = meter.programs - (state["compiles0"]
                                       or meter.programs)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    finished = [r.req for r in requests
                if w0 <= r.due < w1 and r.req.state.name == "FINISHED"]
    if not finished:
        finished = [r.req for r in requests if r.req.state.name == "FINISHED"]
    samples = check.sample(finished, seed, p["check"]["tokens"],
                           p["check"]["max_requests"])
    steps, iterations = server.steps, server.eng.trace.iterations
    server.release()
    del server
    gc.collect()

    profile = None
    if state["trace_dir"] is not None:
        path = next(pathlib.Path(state["trace_dir"]).rglob("*.xplane.pb"))
        profile = trace_reduce.reduce(trace_reduce.load(str(path)))
        shutil.rmtree(state["trace_dir"], ignore_errors=True)

    # the traced run's readers read the traced part of the window
    run = Run(cell=cell, requests=requests, steps=steps,
              iterations=iterations, w0=w0, w1=state["closed"] or w1,
              setup_s=w0 - t_process, window_compiles=window_compiles,
              memory_peak_bytes=peak, profile=profile,
              peaks=device["peaks"])
    metrics, missing = {}, []
    for m in metrics_for(bench, cell.name, trace):
        value = metric_reader(m["name"])(run)
        if value is None:
            missing.append(m["name"])
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    limit = p["check"]["max_gap_limit"]
    g = check.gaps(cell.config, samples, cell.config["engine"]["max_seq"],
                   controls=(control,) if control else ())
    judged = g[control or "program"]
    gap = float(judged.max()) if len(judged) else None
    n_tokens = int(len(judged))
    correct = gap is not None and gap <= limit
    attempted = [r for r in requests if w0 <= r.due < w1]
    out = {"correct": correct, "missing": missing,
           "window_compiles": window_compiles,
           "attempted": len(attempted),
           "failed": sum(r.rejected for r in attempted),
           "metrics": metrics,
           "device": {"platform": device["platform"],
                      "kind": device["kind"], "count": device["count"],
                      "memory_peak_bytes": peak}}
    if profile is not None:
        out["device"].update(busy_s=profile.busy_s,
                             window_s=profile.window_s)
        top = sorted(profile.op_s.items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [[k, v] for k, v in top],
                            "idle_gaps": [[k, v] for k, v in profile.gaps],
                            "idle_by_span": idle_list(profile.idle_by_span)}
    out["compared"] = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "served_tokens_checked": {"value": n_tokens,
                                  "limit": p["check"]["min_tokens"]}}
    out["correct"] = correct and n_tokens >= p["check"]["min_tokens"]
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8", "fp8"), default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    from bench.harness import load_benchmark, load_cell
    bench = load_benchmark()
    cell = load_cell(args.workload, bench)
    device = require_device(cell.entry["chips"])
    enable_cache()
    meter = CompileMeter()
    out = execute(cell, args.seed, args.seconds, bool(args.trace), device,
                  bench, meter, T_PROCESS, control=args.control)
    if out.pop("missing"):
        raise SystemExit(f"bench: {cell.name} lists metrics that read "
                         f"nothing in this run: {out}")
    print(f"bench: programs lowered in the window: "
          f"{out.pop('window_compiles')}", file=sys.stderr)
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
