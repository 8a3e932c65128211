"""bench/harness.py's ``engine_config``: the chat cell's engine as it was
built before cells could state their own, every key of a cell's ``engine``
object passed through under its own name, a cell on the host KV tier made
of new files alone, served a single-round mix, and the serving
loop's wait for an arrival ending at the traced window's edges."""
import copy
import dataclasses
import json
import shutil
import time

import pytest

from bench.harness import (Server, engine_config, footprint,
                           load_benchmark, load_cell, model_config)
from bench.phases import ENGINE_METRICS
from bench.run import CompileMeter, execute, metrics_for
from bench.tests import tiny


def test_chat_cell_engine_is_unchanged():
    from repro.serving.engine import EngineConfig
    cell = load_cell("qwen2.5-3b.chat", load_benchmark())
    got = engine_config(cell, model_config(cell.config))
    # the budget: 5,549,543,424 B of weights at NO_OFFLOAD and 4,096.5
    # pages of 589,824 B
    want = EngineConfig(max_batch=16, max_seq=4096, page_size=16,
                        hbm_budget_bytes=7965757440.0,
                        prefill_chunk_tokens=256, incremental_prefill=True)
    assert dataclasses.asdict(got) == dataclasses.asdict(want)


def test_cell_keys_pass_through():
    cell = tiny.cell()
    cell.params["engine"].update(hbm_budget_bytes=1e6, host_kv_bytes=2e6,
                                 preemption=True)
    got = engine_config(cell, model_config(cell.config))
    assert (got.hbm_budget_bytes, got.host_kv_bytes, got.preemption,
            got.prefill_chunk_tokens, got.max_batch) == (1e6, 2e6, True,
                                                         64, 4)


@pytest.mark.parametrize("key", ["host_kv_gb", "max_batch"])
def test_engine_key_refused_by_name(key):
    cell = tiny.cell()
    cell.params["engine"][key] = 1
    with pytest.raises(ValueError, match=key):
        engine_config(cell, model_config(cell.config))


# ------------------------------------------- a host-tier cell as new files --
DOCS = {"kind": "sessions", "shape_seed": 5, "shuffle_block": 2,
        "lead_s": 0.0, "system_prompt_len": 0, "mean_rounds": 1.0,
        "mean_think_s": 1.0, "median_turn_len": 96, "turn_len_sigma": 0.5,
        "max_prompt_frac": 0.75, "mean_output_len": 8.0,
        "max_output_len": 16}
DEVICE_PAGES, HOST_PAGES = 4, 128


@pytest.fixture(scope="module")
def host_tier_tree(tmp_path_factory):
    """A checkout with one more cell: ``tiny.docs``, a tiny qwen2.5-3b on a
    device tier of four pages (less than one prompt) above a host tier,
    served a single-round mix of distinct prompts. Every file of the repo's benchmark is copied
    as it is; the cell adds new files and appends entries to
    BENCHMARK.json's lists."""
    from bench.harness import ROOT
    root = tmp_path_factory.mktemp("tree")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    for d in ("configs", "cells", "traffic"):
        shutil.copytree(ROOT / "bench" / d, root / "bench" / d)
    weights, page = footprint(model_config(tiny.CONFIG),
                              tiny.CONFIG["engine"]["page_size"])
    params = copy.deepcopy(tiny.PARAMS)
    params["rate_per_s"] = 4.0
    params["engine"].update(
        hbm_budget_bytes=weights + (DEVICE_PAGES + 0.5) * page,
        host_kv_bytes=HOST_PAGES * page)
    new = {"bench/configs/tiny.json": tiny.CONFIG,
           "bench/cells/tiny.docs.json": params,
           "bench/traffic/tiny_docs.json": DOCS}
    for rel, obj in new.items():
        assert not (root / rel).exists()
        (root / rel).write_text(json.dumps(obj))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "bench/tests/tiny.py",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "CPU size"})
    bench["workloads"].append({"name": "tiny.docs", "config": "tiny",
                               "traffic": "tiny_docs", "chips": 1,
                               "why": "host KV tier"})
    for m in bench["per_layer"]:
        if m["name"] in ENGINE_METRICS:
            m["workloads"].append("tiny.docs")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, page


def test_host_tier_cell_builds_from_new_files(host_tier_tree):
    root, page = host_tier_tree
    cell = load_cell("tiny.docs", load_benchmark(root), root)
    assert cell.mix == DOCS
    eng = Server(cell).eng
    assert eng.kv.device.total_pages == DEVICE_PAGES
    assert eng.kv.host.total_pages == HOST_PAGES
    assert eng.host_pool is not None


def test_host_tier_run_reads_every_engine_metric(host_tier_tree):
    root, page = host_tier_tree
    bench = load_benchmark(root)
    cell = load_cell("tiny.docs", bench, root)
    assert {m["name"] for m in metrics_for(bench, cell.name, True)} == \
        set(ENGINE_METRICS)
    streamed = []

    class Kept(Server):
        def release(self):
            streamed.append(self.eng.streamed_pages_peak)
            super().release()

    out = execute(cell, 2**33 + 11, 3.0, True, tiny.DEVICE, bench,
                  CompileMeter(), time.perf_counter(), server_factory=Kept)
    assert out["correct"] and not out["missing"], out
    assert set(out["metrics"]) == set(ENGINE_METRICS)
    # pages streamed from the host tier, many more bytes a step than the
    # step's own inputs (under 1 KB here)
    assert streamed[0] > 0
    assert out["metrics"]["h2d_mb_per_step"]["value"] * 1e6 > 2 * page
    idle = out["breakdown"]["idle_by_span"]
    assert len(idle) <= 10
    assert sum(v for _, v in idle) == pytest.approx(
        out["device"]["window_s"] - out["device"]["busy_s"], rel=0.01)


def test_wait_for_an_arrival_wakes_at_the_window_edges():
    """A wait for the next arrival ends at each instant of ``wake``, so a
    traced window opens on time however far apart requests arrive."""
    server = Server(tiny.cell())
    t0 = time.perf_counter()
    wake = (t0 + 0.3, t0 + 0.6)
    calls = []
    server.serve([], t0, t0 + 1.0, calls.append, wake=wake)
    for w in wake:
        assert any(w <= t < w + 0.1 for t in calls), (w, calls)
