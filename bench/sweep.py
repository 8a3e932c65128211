"""Find a cell's capacity: serve it at a ladder of arrival rates, on several
seeds, in one process with one set-up, and report for each window its
end-to-end metrics, its attainment and how full the engine ran.

    python3 bench/sweep.py --workload qwen2.5-3b.chat --seeds 5,6,7 \
        --seconds 30 --rates 0.4,0.6,0.8,1.0,1.3

Each (rate, seed) gets the cell's warm-up traffic, then a window of
``--seconds``; the engine is drained between them. A rate is sustained
where the backlog (requests waiting or in prefill, its mean over the
window's first and last thirds) does not grow across the window; the ISSUE-style knee is the highest rate at which also at
least 90% of the window's requests meet both limits. Each line gives the
mean decode batch and the mean and largest number of device KV pages in
use over the window's steps. One JSON line per window on standard output,
also appended to ``chiprun_out/sweep_<cell>.jsonl``. Needs an
accelerator, like run.py.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

E2E = ("output_tok_s", "itl_p95_s", "ttft_p95_s", "slo_attainment")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds, each served per rate")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates (the mix's unit)")
    args = ap.parse_args(argv)
    from bench.harness import Server, load_benchmark, load_cell
    from bench.run import enable_cache, require_device
    cell = load_cell(args.workload, load_benchmark())
    device = require_device(cell.entry["chips"])
    enable_cache()
    seeds = [int(x) for x in args.seeds.split(",")]
    server = Server(cell)
    server.warm_up(seeds[0])
    out_path = ROOT / "chiprun_out" / f"sweep_{cell.name}.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    print(f"set-up {time.perf_counter() - T_PROCESS:.1f} s", flush=True)
    warm = cell.params["warm_up"]["traffic_s"]
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            line = one_window(server, cell, device, rate, seed, warm,
                              args.seconds)
            print(json.dumps(line), flush=True)
            with out_path.open("a") as f:
                f.write(json.dumps({"cell": cell.name, **line}) + "\n")
            server.drain(120.0)


def one_window(server, cell, device, rate, seed, warm, seconds) -> dict:
    from bench.harness import items_for
    from bench.run import Run, metric_reader
    items = items_for(cell, seed, seconds, rate)
    t_gen0 = time.perf_counter()
    w0, w1 = t_gen0 + warm, t_gen0 + warm + seconds
    seen = []                           # (time, backlog) inside the window

    def on_time(now):
        if now >= w0:
            seen.append((now, server.backlog()))

    server.steps = []
    requests = server.serve(items, t_gen0, w1, on_time)
    third = seconds / 3
    backlog = {k: sum(b for t, b in part) / max(len(part), 1)
               for k, part in (
                   ("first_third", [x for x in seen if x[0] < w0 + third]),
                   ("last_third", [x for x in seen if x[0] >= w1 - third]))}
    backlog["end"] = server.backlog()
    run = Run(cell=cell, requests=requests, steps=server.steps, w0=w0,
              w1=w1, peaks=device["peaks"], profile=None)
    inside = [st for st in server.steps if w0 <= st.t1 < w1]
    n = max(len(inside), 1)
    line = {"rate_per_s": rate, "seed": seed, "backlog": backlog,
            "mean_decode_batch": sum(st.decode_batch for st in inside) / n,
            "mean_live_pages": sum(st.live_pages for st in inside) / n,
            "max_live_pages": max((st.live_pages for st in inside),
                                  default=0),
            "steps": len(inside),
            "due_in_window": len(run.due_in_window()),
            "rejected": sum(r.rejected for r in run.due_in_window())}
    for name in E2E:
        line[name] = metric_reader(name)(run)
    return line


if __name__ == "__main__":
    main()
