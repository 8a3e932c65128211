"""Drive the serving engine open loop on the wall clock, and keep the
records the metric readers read.

The system under test is ``repro.serving.engine.ServingEngine``, built the
way ``repro.launch.serve`` builds it (``build_engine``). The benchmark
drives ``submit`` and ``step`` itself: each request is handed over when it
is due, ``step`` runs while work is pending, and the tokens a step emitted
are stamped with ``time.perf_counter()`` when it returns (a step pulls its
logits to the host, so its device work has finished by then). Nothing here
reads the engine's modelled clock: every time is host wall clock.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import pathlib
import time

import jax
import numpy as np

from bench import traffic

ROOT = pathlib.Path(__file__).resolve().parents[1]
# rid of the warm-up request, outside the range the schedule hands out
WARM_RID = 10**9
# config-file keys (the published config's names) -> ModelConfig fields
CONFIG_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
               "num_attention_heads": "num_heads",
               "num_key_value_heads": "num_kv_heads",
               "intermediate_size": "d_ff", "vocab_size": "vocab_size",
               "rope_theta": "rope_theta",
               "tie_word_embeddings": "tie_embeddings",
               "attention_bias": "qkv_bias"}


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict          # the BENCHMARK.json workload entry
    config: dict         # bench/configs/<config>.json
    mix: dict            # bench/traffic/<traffic>.json
    params: dict         # bench/cells/<cell>.json: rate, limits, engine


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: dict, root: pathlib.Path = ROOT) -> Cell:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(name, entry, json.loads((root / conf["file"]).read_text()),
                traffic.load_mix(entry["traffic"],
                                 root / "bench" / "traffic"),
                json.loads((root / "bench" / "cells" / f"{name}.json")
                           .read_text()))


def model_config(config: dict):
    from repro.configs import get_config
    return dataclasses.replace(get_config(config["registry"]),
                               **{f: config[k] for k, f in
                                  CONFIG_KEYS.items()})


def footprint(cfg, page_size: int) -> tuple[int, int]:
    """Device bytes of the weights at NO_OFFLOAD and of one KV page."""
    from repro.core import costs
    from repro.core.interval import NO_OFFLOAD, OffloadPlan
    from repro.models.model import build_model
    from repro.models.transformer import pattern_info
    weights = OffloadPlan(pattern_info(cfg)[1], NO_OFFLOAD).device_bytes(
        costs.unit_weight_bytes(cfg))
    return weights, page_size * costs.kv_cache_bytes(
        cfg, 1, 1, build_model(cfg).virtual_kv)


def engine_config(cell: Cell, cfg):
    """The cell's ``EngineConfig``: the geometry of the config file's
    ``engine`` object (``max_batch``, ``max_seq``, ``page_size``), then
    every key of the cell file's ``engine`` object under its own name (a
    key that is no ``EngineConfig`` field, or that the config file already
    states, is refused). A cell that states no ``hbm_budget_bytes`` gets
    the weights at NO_OFFLOAD and every row's KV at ``max_seq``, with half a
    page of slack (the allocator floors bytes to whole pages)."""
    from repro.serving.engine import EngineConfig

    geo, opt = cell.config["engine"], cell.params["engine"]
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    unknown = sorted(set(opt) - fields)
    if unknown:
        raise ValueError(f"{cell.name}: engine keys that are not "
                         f"EngineConfig fields: {', '.join(unknown)}")
    twice = sorted(set(opt) & set(geo))
    if twice:
        raise ValueError(f"{cell.name}: engine keys the config file "
                         f"already states: {', '.join(twice)}")
    kw = dict(geo, **opt)
    if "hbm_budget_bytes" not in kw:
        weights, page_bytes = footprint(cfg, geo["page_size"])
        all_pages = geo["max_batch"] * -(-geo["max_seq"] // geo["page_size"])
        kw["hbm_budget_bytes"] = weights + (all_pages + 0.5) * page_bytes
    return EngineConfig(**kw)


@dataclasses.dataclass
class Req:
    req: object                 # repro.serving.request.Request
    due: float                  # perf_counter seconds
    submit: float
    stamps: list[float] = dataclasses.field(default_factory=list)
    rejected: bool = False


@dataclasses.dataclass
class Step:
    index: int
    t0: float
    t1: float
    decode_batch: int
    live_pages: int             # device KV pages in use after the step
    contexts: np.ndarray        # context length of each live decode row


class Server:
    """One engine of one cell, and the wall-clock loop that serves it."""

    def __init__(self, cell: Cell, device=None):
        from repro.core.hardware import preset_for_device_kind, PRESETS
        from repro.launch.serve import build_engine

        self.cell = cell
        self.device = device if device is not None else jax.devices()[0]
        cfg = model_config(cell.config)
        ecfg = engine_config(cell, cfg)
        hw = (preset_for_device_kind(self.device.device_kind)
              if self.device.platform == "tpu" else PRESETS["tpu_v5e"])
        slos = [0.002 * k for k in range(1, 120)]   # as launch.serve builds
        self.eng = build_engine("bench", cfg, hw, ecfg, slos,
                                device=self.device)
        self.ttft_limit = cell.params["ttft_limit_s"]
        self.itl_limit = cell.params["itl_limit_s"]
        self.steps: list[Step] = []
        self.n_submitted = 0            # rids are unique over the process

    # ---------------------------------------------------------------- set-up
    def _request(self, rid: int, prompt: np.ndarray, max_new: int):
        from repro.serving.request import Request
        return Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                       ttft_slo_s=self.ttft_limit,
                       tpot_slo_s=self.itl_limit, arrival_s=0.0)

    def warm_up(self, seed: int) -> None:
        """Compile every program the cell's traffic runs: one request
        through the served path (prefill chunks, decode)."""
        from repro.serving.request import State
        eng, w = self.eng, self.cell.params["warm_up"]
        rng = np.random.Generator(np.random.Philox(key=int(seed) + 1))
        req = self._request(WARM_RID, rng.integers(
            0, self.cell.config["vocab_size"], w["prompt_len"],
            dtype=np.int32), 4)
        eng.submit(req)
        for _ in range(10_000):
            if req.state in (State.FINISHED, State.REJECTED):
                break
            eng.step()
        if req.state is not State.FINISHED:
            raise RuntimeError(f"warm-up request ended {req.state}: "
                               f"{req.reject_reason}")

    # ---------------------------------------------------------------- serving
    def busy(self) -> bool:
        return self.eng.scheduler.has_work() or bool(self.eng.active.any())

    def serve(self, items: list, t_gen0: float, t_end: float,
              on_time=None, wake=()) -> list[Req]:
        """Hand ``items`` to the engine as they fall due (``t_gen0 +
        item.due_s``) and step it until ``t_end``. ``on_time(now)`` is
        called between steps, after the requests due by ``now`` were handed
        over (the traced window opens and closes there, so the profiler's
        stop does not hold back a request due inside it), and at each
        instant of ``wake`` that falls in a wait for the next arrival.
        Returns one record per submitted request."""
        from jax.profiler import TraceAnnotation
        from repro.serving.request import State
        eng = self.eng
        done_states = (State.FINISHED, State.REJECTED)
        out: list[Req] = []
        inflight: list[Req] = []
        nxt = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if nxt < len(items) and t_gen0 + items[nxt].due_s <= now:
                with TraceAnnotation("bench.submit"):
                    while (nxt < len(items)
                           and t_gen0 + items[nxt].due_s <= now):
                        it = items[nxt]
                        r = Req(self._request(self.n_submitted, it.prompt,
                                              it.max_new_tokens),
                                t_gen0 + it.due_s, time.perf_counter())
                        self.n_submitted += 1
                        eng.submit(r.req)
                        out.append(r)
                        inflight.append(r)
                        nxt += 1
            if on_time is not None:
                on_time(now)
            if self.busy():
                k = len(self.steps)
                with TraceAnnotation("bench.step", index=k):
                    t0 = time.perf_counter()
                    eng.step()
                    t1 = time.perf_counter()
                rec = eng.trace.iterations[-1]
                dec = eng.last_decode
                ctx = (dec["pos"][dec["active"]] + 1 if dec is not None
                       else np.zeros(0, np.int32))
                self.steps.append(Step(k, t0, t1, rec.decode_batch,
                                       eng.kv.device.used_pages, ctx))
                keep = []
                for r in inflight:
                    n = len(r.req.generated)
                    if n > len(r.stamps):
                        r.stamps.extend([t1] * (n - len(r.stamps)))
                    r.rejected = r.req.state is State.REJECTED
                    if r.req.state not in done_states:
                        keep.append(r)
                inflight = keep
            else:
                due = (t_gen0 + items[nxt].due_s if nxt < len(items)
                       else t_end)
                until = min([due, t_end] + [w for w in wake if w > now])
                with TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(until - time.perf_counter(), 0.0))
        return out

    def backlog(self) -> int:
        """Requests waiting or still in prefill."""
        sch = self.eng.scheduler
        return len(sch.queue) + len(sch._prefilling)

    def drain(self, limit_s: float) -> None:
        """Step without new arrivals until idle or ``limit_s`` passed."""
        t_end = time.perf_counter() + limit_s
        while self.busy() and time.perf_counter() < t_end:
            self.eng.step()

    def release(self) -> None:
        """Drop the engine and collect its reference cycles, so its device
        arrays are freed before anything else needs the memory."""
        self.eng = None
        gc.collect()


def percentile(values, q: float) -> float | None:
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


def horizon(cell: Cell, seconds: float) -> float:
    """How far the schedule must reach: warm-up traffic plus the window."""
    return cell.params["warm_up"]["traffic_s"] + seconds + 1.0


def items_for(cell: Cell, seed: int, seconds: float,
              rate: float | None = None) -> list:
    return traffic.schedule(
        cell.mix, rate if rate is not None else cell.params["rate_per_s"],
        horizon(cell, seconds), seed, cell.config["vocab_size"],
        cell.config["engine"]["max_seq"])
