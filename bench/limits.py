"""Read the two ends a cell's correctness limit is set between: the widest
logit gap of the served tokens over many seeds (the program), and the same
reading of the controls, the reference computed in int8 and in fp8 in the
program's place, on the same prompts and tokens.

    python3 bench/limits.py --workload qwen2.5-3b.chat --seconds 20 \
        --seeds 101,102,...,112 --control-seeds 3

One process, one set-up: each seed's traffic is served for the cell's
warm-up plus ``--seconds`` at the cell's rate, its sample drawn as a run
draws it (bench/check.py), and the engine drained. Then the engine is
released and the reference reads every sample; the control reads the
samples of the first ``--control-seeds`` seeds. One JSON line per seed on
standard output, also appended to ``chiprun_out/limits_<cell>.jsonl``.
Needs an accelerator, like run.py.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    from bench import check
    from bench.harness import Server, items_for, load_benchmark, load_cell
    from bench.run import enable_cache, require_device
    cell = load_cell(args.workload, load_benchmark())
    require_device(cell.entry["chips"])
    enable_cache()
    p = cell.params
    seeds = [int(s) for s in args.seeds.split(",")]
    server = Server(cell)
    server.warm_up(seeds[0])
    samples = {}
    for seed in seeds:
        items = items_for(cell, seed, args.seconds)
        t_gen0 = time.perf_counter()
        w0 = t_gen0 + p["warm_up"]["traffic_s"]
        reqs = server.serve(items, t_gen0, w0 + args.seconds)
        done = [r.req for r in reqs
                if w0 <= r.due and r.req.state.name == "FINISHED"]
        samples[seed] = check.sample(done, seed, p["check"]["tokens"],
                                     p["check"]["max_requests"])
        server.drain(120.0)
    server.release()
    ref = check.reference(cell.config["reference"])
    w = ref.weights(cell.config)
    out_path = ROOT / "chiprun_out" / f"limits_{cell.name}.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    for i, seed in enumerate(seeds):
        ctrl = tuple(args.controls.split(",")) if i < args.control_seeds \
            else ()
        g = check.gaps(cell.config, samples[seed],
                       cell.config["engine"]["max_seq"], w=w, controls=ctrl)
        line = {"cell": cell.name, "seed": seed,
                "tokens": int(len(g["program"])),
                "requests": len(samples[seed]),
                "program_max_gap": float(g["program"].max())
                if len(g["program"]) else None}
        for c in ctrl:
            line[f"{c}_control_max_gap"] = float(g[c].max())
            line[f"{c}_control_share_off_best"] = float((g[c] > 0).mean())
        line["program_share_off_best"] = float((g["program"] > 0).mean()) \
            if len(g["program"]) else None
        print(json.dumps(line), flush=True)
        with out_path.open("a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
