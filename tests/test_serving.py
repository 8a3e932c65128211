"""Serving engine integration: continuous batching, SLO admission, offload
interval switching, paged accounting."""
import jax
import numpy as np
import pytest

from repro.core.interval import NO_OFFLOAD, OffloadPlan
from repro.serving.kv_cache import PageConfig, PagedKVAllocator
from repro.serving.request import Request

from _engine_builders import mk_reduced_engine

# compile-heavy (full JAX jit of models/kernels): excluded from the fast CI
# tier, run in the nightly full suite
pytestmark = pytest.mark.slow


def _mk_engine(name="e0", hbm_gb=0.05, max_batch=4, max_seq=48,
               extra_device_pages: float | None = None, host_pages: int = 0):
    """Standard engine, or (with ``extra_device_pages``) one whose HBM holds
    the resident weights plus only that many KV pages, with ``host_pages``
    of pinned-host KV — the tiered-serving shape."""
    return mk_reduced_engine(
        name=name, max_batch=max_batch, max_seq=max_seq,
        hbm_gb=None if extra_device_pages is not None else hbm_gb,
        extra_device_pages=extra_device_pages, host_pages=host_pages)


def _reqs(n, prompt_len=8, new=6, ttft=1.0, tpot=1.0):
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(0, 100, prompt_len).astype(np.int32),
                    max_new_tokens=new, ttft_slo_s=ttft, tpot_slo_s=tpot)
            for i in range(n)]


def test_engine_serves_batched_requests():
    eng, _ = _mk_engine()
    eng.set_interval(NO_OFFLOAD)
    out = eng.run(_reqs(6), max_iters=500)
    assert out["finished"] == 6
    assert out["rejected"] == 0
    assert out["tokens"] == 6 * 6
    assert out["throughput_tok_s"] > 0
    # all KV pages returned
    assert eng.allocator.used_pages == 0


def test_engine_continuous_batching_overlaps():
    """More requests than slots: finishing requests free slots for queued."""
    eng, _ = _mk_engine(max_batch=2)
    out = eng.run(_reqs(5), max_iters=500)
    assert out["finished"] == 5


def test_engine_interval_switch_preserves_decoding():
    eng, _ = _mk_engine()
    reqs = _reqs(2, new=10)
    for r in reqs:
        eng.submit(r)
    eng.set_interval(NO_OFFLOAD)
    for _ in range(3):
        eng.step()
    eng.set_interval(2)            # offload half-way through decoding
    while eng.queue or eng._active_batch() > 0:
        eng.step()
    assert len(eng.finished) == 2
    for r in eng.finished:
        assert len(r.generated) == 10


def test_engine_starts_at_the_interval_its_budget_fits():
    """A budget below the resident weights: with no coordinator or tuner,
    the engine serves at the largest interval that fits, not NO_OFFLOAD."""
    probe, _ = _mk_engine()
    budget = (OffloadPlan(probe.num_units, 2).device_bytes(probe.unit_bytes)
              + 4 * probe.kv.page_bytes)
    assert budget < OffloadPlan(probe.num_units, NO_OFFLOAD).device_bytes(
        probe.unit_bytes)
    eng, _ = _mk_engine(hbm_gb=budget / 1e9, host_pages=64)
    assert eng.interval == 2
    out = eng.run(_reqs(4), max_iters=500)
    assert out["finished"] == 4 and eng.interval == 2


def test_engine_holds_one_grouping_of_the_weights():
    """An interval change re-groups the one device copy of the weights (and
    the merged view still equals the initial stacked weights)."""
    eng, _ = _mk_engine()
    stacked = [np.asarray(a) for a in jax.tree.leaves(eng.params)]
    nbytes = sum(a.nbytes for a in stacked)
    eng.run(_reqs(2, new=3), max_iters=100)
    for interval in (2, 4, NO_OFFLOAD):
        eng.set_interval(interval)
        eng.run(_reqs(2, new=3), max_iters=100)
        assert eng._w_interval == interval
        assert sum(a.nbytes for a in jax.tree.leaves(eng._w)) == nbytes
        for a, b in zip(jax.tree.leaves(eng.params), stacked):
            np.testing.assert_array_equal(np.asarray(a), b)


def test_incremental_chunks_compile_once_per_interval():
    """Chunks of every length run at the scheduler's chunk length: prompts
    of five lengths compile the chunk program once."""
    eng, _ = mk_reduced_engine(max_seq=64, hbm_gb=0.05,
                               prefill_chunk_tokens=16,
                               incremental_prefill=True)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, 100, n).astype(np.int32),
                    max_new_tokens=3, ttft_slo_s=1.0, tpot_slo_s=1.0)
            for i, n in enumerate((5, 16, 21, 30, 40))]
    out = eng.run(reqs, max_iters=500)
    assert out["finished"] == 5
    assert eng._jit_chunk[eng.interval]._cache_size() == 1


def test_engine_rejects_infeasible_slo():
    eng, _ = _mk_engine(hbm_gb=0.00002)  # tiny HBM: model cannot stay resident
    reqs = _reqs(1, tpot=1e-6)           # impossible SLO
    out = eng.run(reqs, max_iters=50)
    assert out["rejected"] == 1
    assert "infeasible" in eng.rejected[0].reject_reason


def test_paged_allocator_roundtrip():
    alloc = PagedKVAllocator(16 * 64, PageConfig(page_size=4, bytes_per_token=4))
    assert alloc.total_pages == 64
    pages = alloc.alloc(1, 17)   # 5 pages
    assert len(pages) == 5
    assert alloc.extend(1, 25)   # 7 pages total
    assert alloc.used_pages == 7
    assert alloc.max_allocatable_tokens() == (64 - 7) * 4
    alloc.free(1)
    assert alloc.used_pages == 0
    assert alloc.alloc(2, 64 * 4 + 1) is None  # over capacity


def test_single_token_request_finishes_at_prefill():
    """Regression: max_new_tokens=1 is satisfied by the prefill token; the
    request must finish without a decode step (which would over-generate
    and, for a page-aligned prompt, write past the allocated pages)."""
    eng, _ = _mk_engine()
    out = eng.run(_reqs(2, prompt_len=8, new=1), max_iters=20)
    assert out["finished"] == 2
    for r in eng.finished:
        assert len(r.generated) == 1
    assert eng.kv.device.used_pages == 0


def test_block_table_overflow_raises_instead_of_truncating():
    """Regression: a request holding more pages than the table has columns
    must raise — silently truncating would make the paged kernel attend
    through the wrong frames."""
    alloc = PagedKVAllocator(16 * 64, PageConfig(page_size=4, bytes_per_token=4))
    alloc.alloc(1, 5 * 4)        # 5 pages
    with pytest.raises(ValueError, match="truncate"):
        alloc.block_table(1, 4)
    bt = alloc.block_table(1, 8)  # padded fit is fine
    assert bt.shape == (8,) and list(bt[:5]) == alloc.pages_of(1)

    from repro.serving.kv_offload import TieredKVAllocator
    kv = TieredKVAllocator(16 * 64, 0, PageConfig(page_size=4,
                                                  bytes_per_token=4))
    kv.alloc(7, 5 * 4)
    with pytest.raises(ValueError, match="truncate"):
        kv.device_block_table(7, 4)


def test_trace_replay_with_host_tier_meets_slos():
    """End-to-end trace replay through the paged engine with a host KV pool
    (--host-kv-gb > 0 equivalent): serve a mixed request stream, record
    TTFT/TPOT per request, and assert zero SLO violations under the modeled
    hardware — while the trace actually exercises the host tier."""
    from repro.data.pipeline import DataConfig, request_stream

    eng, _ = _mk_engine(extra_device_pages=3.5, host_pages=64)
    rng = np.random.default_rng(1)
    stream = request_stream(DataConfig(seed=1, mean_prompt_len=8,
                                       mean_output_len=6), 10,
                            ttft_slo_s=1.0, tpot_slo_s=1.0)
    reqs = [Request(rid=r.rid,
                    prompt=rng.integers(0, 100, min(r.prompt_len, 16)
                                        ).astype(np.int32),
                    max_new_tokens=min(r.max_new_tokens, 8),
                    ttft_slo_s=r.ttft_slo_s, tpot_slo_s=r.tpot_slo_s,
                    arrival_s=r.arrival_s) for r in stream]
    # burst replay (submit_all): the point is host-tier pressure, which the
    # honored Poisson arrivals at this rate are too spread out to create
    out = eng.run(reqs, max_iters=800, submit_all=True)

    assert out["finished"] == len(reqs)
    assert out["rejected"] == 0
    per = out["per_request"]
    assert len(per) == len(reqs)
    for m in per:                       # TTFT/TPOT recorded per request
        assert m["ttft_s"] is not None and m["ttft_s"] > 0
        assert m["tpot_mean_s"] > 0
        assert m["ttft_ok"] and m["tpot_ok"]
    assert out["slo_ok"]
    assert eng.host_kv_peak_pages > 0   # the host tier really was used
    assert eng.kv.device.used_pages == 0 and eng.kv.host.used_pages == 0
    eng.kv.check_invariants()


def test_engine_interval_lowers_kv_headroom_tradeoff():
    """Fig. 14 mechanics: smaller interval => more free pages."""
    eng, _ = _mk_engine(hbm_gb=0.01)
    eng.set_interval(NO_OFFLOAD)
    base = eng.allocator.total_pages
    eng.set_interval(2)
    assert eng.allocator.total_pages > base
    eng2, _ = _mk_engine(hbm_gb=0.01)
    eng2.set_interval(1)
    assert eng2.allocator.total_pages > base


def test_batch_capacity_is_a_packing_plan_not_an_average():
    """Regression (PR 8 open note): the old average-footprint estimate
    divided the WHOLE host pool — pages already claimed by a parked request
    included — by the mean footprint, and over-admitted under host
    pressure. The packing plan counts actual free frames."""
    eng, _ = _mk_engine(max_batch=4, extra_device_pages=4, host_pages=12)
    page = eng.ecfg.page_size
    # parked resident holding every frame: 16 pages (4 device + 12 host)
    parked = Request(rid=0,
                     prompt=np.zeros(16 * page - 6, np.int32),
                     max_new_tokens=6, ttft_slo_s=1.0, tpot_slo_s=1.0)
    assert eng.kv.alloc(parked.rid, 16 * page) is not None
    eng.scheduler.preempted.append(parked)
    waiters = [Request(rid=1 + i, prompt=np.zeros(4 * page - 6, np.int32),
                       max_new_tokens=6, ttft_slo_s=1.0, tpot_slo_s=1.0)
               for i in range(4)]
    eng.scheduler.queue.extend(waiters)

    cap = eng._batch_capacity(eng.interval)
    # true packing: the parked resident alone — zero free frames remain
    assert cap == 1

    # the retired estimate, recomputed inline: it still believed 2 fit
    pool_pages = eng.kv.device.total_pages + eng.kv.host.total_pages
    per_req = [-(-(r.prompt_len + r.max_new_tokens) // page)
               for r in [parked] + waiters]
    pages_each = max(sum(per_req) / len(per_req), 1.0)
    old_cap = int(max(1, min(eng.ecfg.max_batch, pool_pages // pages_each)))
    assert old_cap > cap, "the over-admission case no longer discriminates"

    # frames freed -> packing capacity recovers
    eng.kv.free(parked.rid)
    eng.scheduler.preempted.clear()
    assert eng._batch_capacity(eng.interval) == 4


def test_prefetch_depth_drains_parked_disk_pages_in_fewer_boundaries():
    """Satellite gate: ``EngineConfig.prefetch_pages_per_boundary`` sets how
    many of a parked request's disk pages stage host-ward per iteration
    boundary — depth 1 (default) takes one boundary per page, depth 4
    drains the same parked set in ceil(n/4) boundaries."""
    def boundaries(depth):
        eng, _ = mk_reduced_engine(
            name=f"pf{depth}", max_batch=2, max_seq=64,
            extra_device_pages=8, host_pages=8, disk_pages=16,
            preemption=True, async_data_plane=True,
            prefetch_pages_per_boundary=depth)
        rng = np.random.default_rng(3)
        req = Request(rid=0,
                      prompt=rng.integers(0, 100, 56).astype(np.int32),
                      max_new_tokens=8, ttft_slo_s=1.0, tpot_slo_s=1.0)
        eng.submit(req)
        eng.step()                     # admit + prefill + first decode
        moves = eng.kv.park(req.rid, [])
        assert moves is not None
        eng.kv.demote_to_disk(req.rid, 99)
        eng.data_plane.drain()
        n_disk = len(eng.kv.disk_pages_of(req.rid))
        eng.scheduler.preempted.append(req)
        n = 0
        while eng.kv.disk_pages_of(req.rid):
            eng._issue_prefetch()
            eng.data_plane.drain()
            n += 1
            assert n <= n_disk, "prefetch made no progress"
        eng.kv.check_invariants()
        return n, n_disk

    n1, d1 = boundaries(1)
    n4, d4 = boundaries(4)
    assert d1 == d4 and d1 >= 4
    assert n1 == d1                    # default: one page per boundary
    assert n4 == -(-d4 // 4)
    assert n4 < n1
