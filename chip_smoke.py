"""Chip smoke: serve full-width qwen2.5-3b through the paged engine on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips, fleet phase only

One chip runs two phases through ``launch.serve`` (random weights from a
fixed seed, published widths, all 36 layers):

  resident   weights and KV on the device (NO_OFFLOAD); afterwards the same
             engine is stepped in lock-step with the dense reference
             (``Model.prefill`` / ``Model.decode_step``, the differential
             harness's ``DualEngine``) on two fresh prompts;
  offloaded  an HBM budget below the weights, so the engine serves at a
             finite offloading interval, with a host KV tier whose pages
             stream through the device slab and incremental chunked prefill
             through the compiled chunk kernel; the trace must audit clean.

``--four-chips`` runs only the fleet comparison: the fig20 chat trace on one
pooled instance (device 0, released before the fleet starts), then on a
4-instance KV-affinity fleet with one instance per device. Greedy tokens
must be identical, the fleet audit clean, and every instance's weights and
page pool on its own device.

Everything runs in this one process: a chip belongs to one process. Times
are host wall clock (``host_wall``, around ``block_until_ready``) and
compile seconds (``compile_s``); neither is a device metric. The last line
of the output is ``{"ok": true, "device": {...}}``; any failed check exits
non-zero before it. Without a TPU the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "tests" / "differential")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH, WIDTH = "qwen2.5-3b", "full"
OUT_DIR = ROOT / "chiprun_out"
# HBM budget of the resident phase: a v5e's 16 GB less room for the runtime
RESIDENT_HBM_GB = 14.0
# offloaded phase: the budget holds interval OFFLOAD_INTERVAL's resident
# units plus DEVICE_KV_PAGES pages, so the other pages spill to the host
OFFLOAD_INTERVAL, DEVICE_KV_PAGES = 6, 2
MAX_BATCH, MAX_SEQ, REQUESTS, CHUNK = 4, 1024, 8, 256
# what device 0 may still hold after a phase is released: less than the
# largest weight leaf (1.6 GB), so no model survives into the next phase
RELEASED_MAX_BYTES = 1_000_000_000
# generous SLOs: this run checks serving, not latency headroom
SLO_ARGS = ["--ttft-slo-ms", "5000", "--tpot-slo-ms", "1000",
            "--arrival-rate", "200"]


def require_tpu() -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform "
                         f"{dev['platform']!r}); nothing was run")
    return dev


def model_config():
    from repro.configs import get_config
    return get_config(ARCH)


def hardware_name() -> str:
    from repro.core.hardware import preset_for_device_kind
    return preset_for_device_kind(jax.devices()[0].device_kind).name


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling (persistent-cache
    reads included), and the persistent-cache hits, since ``reset``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def reset(self) -> None:
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

    def _on_secs(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += secs
            self.compiles += event == self.EVENTS[-1]

    def _on_event(self, event: str, **_) -> None:
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


def device_bytes(device=None, stat: str = "peak_bytes_in_use") -> int | None:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get(stat)


def released() -> None:
    """After a phase's last references are dropped: collect them (an engine
    sits in reference cycles, so only the cyclic collector frees its device
    arrays) and check that device 0 no longer holds a model."""
    gc.collect()
    held = device_bytes(stat="bytes_in_use")
    if held is None:                      # a backend without memory stats
        print("released: bytes_in_use not reported", flush=True)
        return
    check(held < RELEASED_MAX_BYTES,
          f"released: device 0 holds {held} B < {RELEASED_MAX_BYTES} B")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def report(phase: str, meter: CompileMeter, t0: float, **extra) -> None:
    line = {"phase": phase, "compile_s": meter.seconds,
            "compiles": meter.compiles, "cache_hits": meter.cache_hits,
            "host_wall_phase_s": time.perf_counter() - t0,
            "peak_bytes_in_use": device_bytes(),
            "bytes_in_use": device_bytes(stat="bytes_in_use"), **extra}
    print("phase_report " + json.dumps(line), flush=True)


def time_decode_steps(eng, n: int = 10) -> list[float]:
    """Host wall seconds of ``n`` calls of the engine's compiled decode step
    (every row live at half of max_seq), each ended by block_until_ready."""
    b, nb, page = eng.ecfg.max_batch, eng.nb, eng.ecfg.page_size
    ctx = eng.ecfg.max_seq // 2
    bt = (np.arange(b * nb, dtype=np.int32) % eng.dev_cap).reshape(b, nb)
    cl = np.full(b, ctx, np.int32)
    wf, wo = bt[:, ctx // page], np.full(b, ctx % page, np.int32)
    tokens = np.zeros(b, np.int32)
    fn = eng._jit_decode[eng.interval]
    walls = []
    for _ in range(n):
        t0 = time.perf_counter()
        logits, eng.pool = fn(eng._weights(), jnp.asarray(tokens),
                              jnp.asarray(cl), eng.pool, jnp.asarray(bt),
                              jnp.asarray(cl), jnp.asarray(wf),
                              jnp.asarray(wo))
        jax.block_until_ready(logits)
        walls.append(time.perf_counter() - t0)
    return walls


def serve_argv(hw: str, hbm_gb: float, *extra: str) -> list[str]:
    return ["--arch", ARCH, "--width", WIDTH, "--hw", hw,
            "--hbm-gb", repr(hbm_gb), "--max-batch", str(MAX_BATCH),
            "--max-seq", str(MAX_SEQ), "--requests", str(REQUESTS),
            *SLO_ARGS, *extra]


def served_all(out: dict, n: int) -> None:
    check(out["finished"] == n and out["rejected"] == 0,
          f"{out['finished']}/{n} requests finished, "
          f"{out['rejected']} rejected")


def one_chip(meter: CompileMeter) -> None:
    from harness import DualEngine
    from repro.core import costs
    from repro.core.interval import NO_OFFLOAD, OffloadPlan
    from repro.launch import serve
    from repro.models.transformer import pattern_info
    from repro.serving.request import Request

    cfg = model_config()
    hw = hardware_name()
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}; preset {hw}", flush=True)

    # ---- resident ----------------------------------------------------------
    meter.reset()
    t0 = time.perf_counter()
    out = serve.main(serve_argv(hw, RESIDENT_HBM_GB))
    eng = out["engines"][0]
    served_all(out, REQUESTS)
    check(eng.interval >= NO_OFFLOAD, "resident phase at NO_OFFLOAD")
    dual = DualEngine(eng)
    rng = np.random.default_rng(0)
    for k in range(2):
        eng.submit(Request(rid=10_000 + k,
                           prompt=rng.integers(0, cfg.vocab_size, 40)
                           .astype(np.int32), max_new_tokens=4,
                           ttft_slo_s=5.0, tpot_slo_s=1.0))
    dual.run_until_drained(max_iters=50)
    check(dual.prefill_compares == 2 and dual.decode_compares >= 2,
          f"paged engine == dense reference within rtol={dual.rtol} "
          f"atol={dual.atol}: {dual.prefill_compares} prefill and "
          f"{dual.decode_compares} decode compares, max |dlogit| "
          f"{dual.max_abs_diff}, {dual.tied_tokens} tied tokens")
    compiled = meter.compiles
    walls = time_decode_steps(eng)
    check(meter.compiles == compiled, "no compile inside the timed decodes")
    report("resident", meter, t0,
           host_wall_decode_step_s=walls,
           host_wall_decode_step_median_s=statistics.median(walls))
    del dual, eng, out
    released()

    # ---- offloaded ----------------------------------------------------------
    meter.reset()
    t0 = time.perf_counter()
    _, units = pattern_info(cfg)
    unit = costs.unit_weight_bytes(cfg)
    page_bytes = 16 * costs.kv_cache_bytes(cfg, 1, 1, cfg.num_kv_heads)
    weights = OffloadPlan(units, NO_OFFLOAD).device_bytes(unit)
    hbm = (OffloadPlan(units, OFFLOAD_INTERVAL).device_bytes(unit)
           + DEVICE_KV_PAGES * page_bytes)
    check(hbm < weights, f"offload budget {hbm / 1e9} GB < stacked weights "
                         f"{weights / 1e9} GB")
    OUT_DIR.mkdir(exist_ok=True)
    out = serve.main(serve_argv(
        hw, hbm / 1e9, "--host-kv-gb", "1",
        "--prefill-chunk-tokens", str(CHUNK), "--incremental-prefill",
        "--metrics-out", str(OUT_DIR / "smoke_offloaded_trace.json")))
    eng = out["engines"][0]
    served_all(out, REQUESTS)
    check(eng.interval < NO_OFFLOAD, f"served at interval {eng.interval}")
    check(eng.streamed_pages_peak > 0,
          f"host pages streamed (peak {eng.streamed_pages_peak} per step)")
    check(eng.prefill_tokens_computed > 0 and bool(eng._jit_chunk),
          "incremental chunked prefill ran")
    audit = eng.trace.audit()
    check(audit.ok, f"trace audit clean ({audit.checks} checks)")
    compiled = meter.compiles
    walls = time_decode_steps(eng)
    check(meter.compiles == compiled, "no compile inside the timed decodes")
    report("offloaded", meter, t0, interval=eng.interval,
           streamed_pages_peak=eng.streamed_pages_peak,
           host_wall_decode_step_s=walls,
           host_wall_decode_step_median_s=statistics.median(walls))


def four_chips(meter: CompileMeter) -> None:
    from benchmarks import fig20_fleet as f20
    from repro.core import costs
    from repro.core.hardware import preset_for_device_kind
    from repro.core.interval import NO_OFFLOAD, OffloadPlan
    from repro.launch.serve import build_engine
    from repro.models.transformer import pattern_info
    from repro.serving.engine import EngineConfig
    from repro.serving.fleet import Fleet

    devices = jax.devices()
    check(len(devices) == 4, f"{len(devices)} devices")
    cfg = model_config()
    hw = preset_for_device_kind(devices[0].device_kind)
    _, units = pattern_info(cfg)
    unit = costs.unit_weight_bytes(cfg)
    page_bytes = f20.PAGE * costs.kv_cache_bytes(cfg, 1, 1, cfg.num_kv_heads)
    slos = [0.002 * k for k in range(1, 30)]

    def mk_engine(name: str, scale: int, device):
        """fig20's instance at full width: weights resident, a device KV
        tier smaller than the batch's working set, a host tier, prefix
        dedup with the keep-alive cache, preempt-to-host."""
        hbm = (OffloadPlan(units, NO_OFFLOAD).device_bytes(unit)
               + scale * f20.DEVICE_EXTRA_PAGES * page_bytes)
        ecfg = EngineConfig(
            max_batch=scale * f20.MAX_BATCH, max_seq=f20.MAX_SEQ,
            page_size=f20.PAGE, hbm_budget_bytes=hbm,
            host_kv_bytes=scale * f20.HOST_PAGES * page_bytes,
            prefix_dedup=True, preemption=True,
            host_prefix_cache_pages=scale * f20.CACHE_PAGES)
        return build_engine(name, cfg, hw, ecfg, slos, device=device)

    def on_own_device(eng) -> bool:
        held = {d for a in jax.tree.leaves(eng._w) for d in a.devices()}
        return held == {eng.device} and eng.pool.devices() == {eng.device}

    reqs = f20.workload()
    meter.reset()
    t0 = time.perf_counter()
    pooled = mk_engine("pooled", len(devices), devices[0])
    out = pooled.run(f20.clone_requests(reqs), max_iters=200_000)
    served_all(out, len(reqs))
    check(pooled.trace.audit().ok, "pooled instance audit clean")
    want = {r.rid: list(r.generated) for r in pooled.finished}
    report("pooled", meter, t0)
    del pooled, out
    released()

    meter.reset()
    t0 = time.perf_counter()
    engines = [mk_engine(f"e{i}", 1, d) for i, d in enumerate(devices)]
    fleet = Fleet(engines, policy="affinity")
    out = fleet.run(f20.clone_requests(reqs), max_iters=200_000)
    served_all(out, len(reqs))
    ok, violations = fleet.audit()
    check(ok, f"fleet audit clean {violations[:3]}")
    got = {r.rid: list(r.generated) for e in engines for r in e.finished}
    check(got == want, f"fleet greedy tokens == pooled instance's, "
                       f"{sum(map(len, got.values()))} tokens over "
                       f"{len(got)} requests")
    check(len({e.device for e in engines}) == 4
          and all(on_own_device(e) for e in engines),
          "one instance per device, params and pool on it")
    served = {e.name: len(e.finished) for e in engines}
    report("fleet", meter, t0, migrations=out["migrations"],
           served_per_instance=served,
           peak_bytes_in_use_per_device=[device_bytes(d) for d in devices])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-instance fleet vs pooled-instance "
                         "phase (needs four chips)")
    args = ap.parse_args()
    dev = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    (four_chips if args.four_chips else one_chip)(meter)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
