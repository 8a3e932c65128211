"""Two-tier KV offloading: allocator edge cases, page migration round trips,
combined weight+KV link algebra, coordinator arbitration, and the engine
serving beyond-HBM workloads without TPOT violations."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.coordinator import InstanceState, coordinate
from repro.core.interval import (LayerTimes, NO_OFFLOAD,
                                 iter_time_with_interval,
                                 iter_time_with_interval_kv, link_bandwidth)
from repro.core.simulator import schedule_for_interval, simulate_iteration
from repro.kernels import ops
from repro.serving.kv_cache import PageConfig, PagedKVAllocator
from repro.serving.kv_offload import (DEVICE, DISK, HOST, DiskKVPool,
                                      LinkSpec, PageRef, SwapScheduler,
                                      TieredKVAllocator)


def _pcfg(page_size=4, bpt=4):
    return PageConfig(page_size=page_size, bytes_per_token=bpt)


# ---------------------------------------------------------------------------
# PagedKVAllocator edge cases
# ---------------------------------------------------------------------------

def test_allocator_double_free_is_noop():
    a = PagedKVAllocator(16 * 16, _pcfg())
    a.alloc(1, 10)
    a.free(1)
    a.free(1)                       # second free must not corrupt the pool
    a.check_invariants()
    assert a.used_pages == 0


def test_allocator_extend_after_free():
    a = PagedKVAllocator(16 * 16, _pcfg())
    a.alloc(1, 10)
    a.free(1)
    assert a.extend(1, 8)           # rid was forgotten: extend re-allocates
    assert a.used_pages == a.pages_for(8)
    a.check_invariants()


def test_allocator_zero_page_alloc():
    a = PagedKVAllocator(16 * 16, _pcfg())
    pages = a.alloc(1, 0)
    assert pages == []
    assert a.used_pages == 0
    a.check_invariants()


def test_allocator_exhaustion_and_refill():
    a = PagedKVAllocator(8 * 16, _pcfg())   # 8 pages
    total = a.total_pages
    rids = []
    for rid in range(total):
        assert a.alloc(rid, a.pcfg.page_size) is not None
        rids.append(rid)
    assert a.free_pages == 0
    assert a.alloc(99, 1) is None
    a.check_invariants()
    for rid in rids:
        a.free(rid)
    a.check_invariants()
    assert a.free_pages == total
    assert len(set(a._free)) == total        # free list holds no duplicates
    assert a.alloc(100, total * a.pcfg.page_size) is not None
    a.check_invariants()


def test_allocator_release_foreign_page_raises():
    a = PagedKVAllocator(16 * 16, _pcfg())
    a.alloc(1, 4)
    with pytest.raises(ValueError):
        a.release_pages(1, [123])
    a.check_invariants()


# ---------------------------------------------------------------------------
# Tiered allocation + migration
# ---------------------------------------------------------------------------

def test_tiered_spill_layout_host_holds_cold_prefix():
    kv = TieredKVAllocator(4 * 16, 8 * 16, _pcfg())   # 4 device, 8 host pages
    refs = kv.alloc(1, 7 * 4)                          # 7 pages: 3 spill
    assert refs is not None and len(refs) == 7
    assert [r.tier for r in refs] == [HOST] * 3 + [DEVICE] * 4
    kv.check_invariants()
    assert kv.alloc(2, 5 * 4, allow_host=False) is None  # device exhausted


def test_tiered_migration_round_trip_accounting():
    kv = TieredKVAllocator(6 * 16, 6 * 16, _pcfg())
    kv.alloc(1, 6 * 4)                                 # fully device
    out = kv.swap_out(1, 2)
    assert len(out) == 2
    assert len(kv.host_pages_of(1)) == 2
    assert len(kv.device_pages_of(1)) == 4
    kv.check_invariants()
    back = kv.swap_in(1, 99)                           # promote everything
    assert len(back) == 2
    assert kv.host_pages_of(1) == []
    kv.check_invariants()
    kv.free(1)
    kv.check_invariants()
    assert kv.device.used_pages == 0 and kv.host.used_pages == 0


def test_tiered_extend_self_evicts_cold_page():
    kv = TieredKVAllocator(3 * 16, 8 * 16, _pcfg())    # 3 device pages
    kv.alloc(1, 3 * 4)                                 # device full
    moves = kv.extend(1, 4 * 4)                        # tail growth
    assert moves is not None and len(moves) == 1       # one demotion
    assert moves[0].src_tier == DEVICE
    # the tail (newest) page stays on device, the cold prefix went host-ward
    assert kv.refs(1)[0].tier == HOST
    assert kv.refs(1)[-1].tier == DEVICE
    kv.check_invariants()


def test_tiered_extend_on_demote_fires_before_frame_reuse():
    """The vacated device frame may be recycled as the new tail page within
    the same extend() call, so the data-plane copy hook must run while the
    frame is still free — this is the contract a real page buffer needs."""
    kv = TieredKVAllocator(2 * 16, 8 * 16, _pcfg())    # 2 device pages
    kv.alloc(1, 2 * 4)                                 # device full
    seen = []

    def on_demote(m):
        # at hook time the demoted frame is free, not yet reused
        assert m.src_page in kv.device._free
        seen.append(m.src_page)

    moves = kv.extend(1, 3 * 4, on_demote=on_demote)
    assert len(moves) == 1 and seen == [moves[0].src_page]
    # ...and afterwards that same frame IS the new tail (LIFO free list),
    # which is exactly why the hook has to be synchronous
    assert kv.refs(1)[-1].page == moves[0].src_page
    kv.check_invariants()


def test_tiered_extend_failure_rolls_back_tail_pages():
    """A mid-loop failure must not leave stray tail pages: the refs list has
    to keep matching the request's token count (demotions may remain — the
    data plane can already have copied them)."""
    kv = TieredKVAllocator(3 * 16, 1 * 16, _pcfg())    # 1 host page only
    kv.alloc(1, 3 * 4)
    out = kv.extend(1, 6 * 4)                          # needs 3, host fits 1
    assert out is None
    assert len(kv.refs(1)) == 3                        # token count preserved
    kv.check_invariants()


def test_tiered_resize_device_overflow_raises_before_mutation():
    kv = TieredKVAllocator(8 * 16, 2 * 16, _pcfg())
    kv.alloc(1, 5 * 4)
    kv.alloc(2, 3 * 4)
    with pytest.raises(RuntimeError):
        kv.resize_device(2 * 16)                       # overflow 6 > host 2
    kv.check_invariants()
    # nothing moved: the failure happened before any mutation
    assert len(kv.device_pages_of(1)) == 5
    assert kv.host.used_pages == 0
    kv.check_invariants()


def test_tiered_resize_device_demotes_then_reassigns():
    kv = TieredKVAllocator(8 * 16, 8 * 16, _pcfg())
    kv.alloc(1, 5 * 4)
    kv.alloc(2, 3 * 4)
    res = kv.resize_device(4 * 16)                     # shrink 8 -> 4 pages
    assert res.num_demoted == 4
    # demotions name real old device frames / host slots for the data plane
    assert all(m.src_tier == "device" for m in res.demotions)
    assert sorted(m.dst_page for m in res.demotions) == \
        sorted(p for rid in (1, 2) for p in kv.host_pages_of(rid))
    # surviving pages got a frame remap usable for a physical permute
    assert sorted(n for _, n in res.remap) == \
        sorted(p for rid in (1, 2) for p in kv.device_pages_of(rid))
    assert len(kv.device_pages_of(1)) + len(kv.device_pages_of(2)) == 4
    assert len(kv.host_pages_of(1)) + len(kv.host_pages_of(2)) == 4
    kv.check_invariants()
    grown = kv.resize_device(16 * 16)                  # grow back
    assert grown.num_demoted == 0
    sched = SwapScheduler(kv)
    plan = sched.plan_iteration([1, 2])                # promotions backfill
    assert len(plan.promotions) == 4
    assert kv.host_pages_of(1) == [] and kv.host_pages_of(2) == []
    kv.check_invariants()


def test_page_copy_round_trip_bitwise():
    """device -> host -> device through the real data plane, bitwise equal."""
    page, vh, d = 8, 2, 16
    pcfg = PageConfig(page_size=page, bytes_per_token=1)
    kv = TieredKVAllocator(6 * page, 8 * page, pcfg)
    kv.alloc(0, 3 * page)
    rng = np.random.default_rng(0)
    k_pool = jnp.asarray(rng.normal(size=(6, page, vh, d)).astype(np.float32))
    v_pool = jnp.asarray(rng.normal(size=(6, page, vh, d)).astype(np.float32))
    k_host = kv.host.make_pool_buffer((page, vh, d))
    v_host = kv.host.make_pool_buffer((page, vh, d))

    q = jnp.asarray(rng.normal(size=(1, 4, d)).astype(np.float32))
    cl = jnp.asarray([3 * page - 2], jnp.int32)
    bt0 = kv.device_block_table(0, 3)[None]
    out0 = ops.paged_decode_attention(q, k_pool, v_pool,
                                      jnp.asarray(bt0), cl, interpret=True)
    k_orig = np.asarray(k_pool)

    # migrations batch into one copy per direction per buffer (the intended
    # data-plane usage: one scatter/gather per iteration, not per page)
    moves = kv.swap_out(0, 2)
    src = [m.src_page for m in moves]
    dst = [m.dst_page for m in moves]
    ops.copy_pages_to_host(k_pool, src, k_host, dst)
    ops.copy_pages_to_host(v_pool, src, v_host, dst)
    # clobber the vacated device frames: the copy path must restore content
    k_pool = k_pool.at[jnp.asarray(src)].set(0.0)
    v_pool = v_pool.at[jnp.asarray(src)].set(0.0)

    back = kv.swap_in(0, 2)
    bsrc = [m.src_page for m in back]
    bdst = [m.dst_page for m in back]
    k_pool = ops.copy_pages_from_host(k_host, bsrc, k_pool, bdst)
    v_pool = ops.copy_pages_from_host(v_host, bsrc, v_pool, bdst)
    # bitwise round trip of the migrated page contents
    dev_now = kv.device_block_table(0, 3)
    for before, after in zip(bt0[0], dev_now):
        assert np.array_equal(k_orig[before], np.asarray(k_pool)[after])
    out1 = ops.paged_decode_attention(q, k_pool, v_pool,
                                      jnp.asarray(dev_now[None]), cl,
                                      interpret=True)
    assert np.array_equal(np.asarray(out0), np.asarray(out1))


def test_streamed_and_writeback_bytes_count_shared_pages_once():
    """Regression for a latent PR-1 double-count that sharing exposes: a
    host page referenced by several active requests streams over the link
    ONCE per iteration and a shared demotion writes back ONCE — but the
    per-request accounting (`sum(host_bytes_of(r))`) bills it per owner.
    The SLO math consumes these numbers directly, so the double-count would
    inflate the modeled iteration time and make admission refuse requests
    the link can actually serve."""
    pcfg = _pcfg()
    pb = pcfg.page_size * pcfg.bytes_per_token
    kv = TieredKVAllocator(0, 8 * pb, pcfg, scope="m", enable_dedup=True)
    prompt = np.arange(2 * pcfg.page_size, dtype=np.int64)
    kv.alloc(1, 2 * pcfg.page_size, prompt=prompt)   # 2 host pages
    kv.alloc(2, 2 * pcfg.page_size, prompt=prompt)   # same 2 frames shared
    assert kv.host.used_pages == 2
    sched = SwapScheduler(kv)
    # frame-wise: 2 unique pages, not 4 owner references
    assert sched.streamed_bytes([1, 2]) == 2 * pb
    assert sum(kv.host_bytes_of(r) for r in (1, 2)) == 4 * pb  # the trap
    # tie to the SLO math: the modeled iteration charges the deduped
    # stream; per-owner billing would claim a strictly slower iteration
    times = LayerTimes(2e-3, 5e-3, 8, 1 << 20, 0.0)
    bw = link_bandwidth(times)
    t = iter_time_with_interval_kv(times, NO_OFFLOAD,
                                   sched.streamed_bytes([1, 2]))
    assert t == pytest.approx(times.t_iter_no_offload_s + 2 * pb / bw)
    t_wrong = iter_time_with_interval_kv(
        times, NO_OFFLOAD, sum(kv.host_bytes_of(r) for r in (1, 2)))
    assert t_wrong > t
    # write-back side: demoting a shared frame is ONE migration -> one
    # pending-out page, charged once
    kv2 = TieredKVAllocator(4 * pb, 8 * pb, pcfg, scope="m",
                            enable_dedup=True)
    p2 = np.arange(2 * pcfg.page_size, dtype=np.int64) + 7
    kv2.alloc(1, 2 * pcfg.page_size, prompt=p2)
    kv2.alloc(2, 2 * pcfg.page_size, prompt=p2)
    res = kv2.resize_device(0)
    assert res.num_demoted == 2                      # unique frames moved
    sched2 = SwapScheduler(kv2)
    sched2.note_demotions(res.num_demoted)
    assert sched2.pending_out_bytes() == 2 * pb
    # and promotion back in bills each shared frame once as kv_in
    kv2.resize_device(4 * pb)
    plan = sched2.plan_iteration([1, 2])
    assert len(plan.promotions) == 2
    assert plan.kv_in_bytes == 2 * pb + plan.streamed_bytes
    assert plan.streamed_bytes == 0.0
    kv2.check_invariants()


def test_swap_out_spills_unshared_before_shared_hot_frames():
    """Regression (spill path of the park-target rule): ``swap_out`` used to
    demote the OLDEST device frames even when an active sibling still
    referenced them — moving a hot shared frame frees no lasting capacity
    (the sibling must stream it back every iteration). With ``active_rids``
    given, unshared frames spill first and the shared hot frame stays on
    device until nothing else remains."""
    pcfg = _pcfg()
    kv = TieredKVAllocator(8 * 16, 8 * 16, pcfg, scope="m", enable_dedup=True)
    shared_prompt = np.arange(2 * pcfg.page_size, dtype=np.int64)
    kv.alloc(1, 2 * pcfg.page_size, prompt=shared_prompt)   # origin
    long_prompt = np.concatenate(
        [shared_prompt, np.arange(100, 100 + 2 * pcfg.page_size)])
    kv.alloc(2, 4 * pcfg.page_size, prompt=long_prompt)     # shares pages 0-1
    shared = {r.page for r in kv.refs(1)}
    assert shared and shared == {r.page for r in kv.refs(2)[:2]}

    # oldest-first would take refs[0] (shared, hot); the fix takes the first
    # frame no active sibling references
    moves = kv.swap_out(2, 1, active_rids=[1])
    assert len(moves) == 1
    assert moves[0].src_page not in shared, "shared hot frame spilled"
    assert all(r.tier == DEVICE for r in kv.refs(1)), "sibling was disturbed"
    kv.check_invariants()

    # fall back only when nothing unshared remains: demanding 3 more frames
    # spills the last private one first, then the shared ones move too
    moves2 = kv.swap_out(2, 3, active_rids=[1])
    assert len(moves2) == 3
    assert moves2[0].src_page not in shared
    assert {m.src_page for m in moves2[1:]} == shared
    assert all(r.tier == HOST for r in kv.refs(1))          # moved once, both
    kv.check_invariants()


def test_park_preview_nets_out_reclaimable_cache():
    """Regression (preview/park parity): ``park`` reclaims keep-alive
    prefix-cache frames before giving up, but the preview used to report
    the raw target count — a precheck against ``host.free_pages`` refused
    parks the real call absorbs. The netted preview certifies a park that
    succeeds ONLY through cache reclaim."""
    kv = TieredKVAllocator(2 * 16, 2 * 16, _pcfg(), scope="pp",
                           enable_dedup=True, host_prefix_cache_pages=4)
    p = np.arange(16, dtype=np.int64)
    kv.alloc(0, 16, prompt=p)                  # 2 host (cold) + 2 device
    kv.free(0)                                 # host frames adopted as cache
    assert kv.host.free_pages == 0
    assert kv.reclaimable_host_pages() == 2
    kv.alloc(1, 8)                             # 2 device pages
    n_free, n_need = kv.park_preview(1)
    assert n_free == 2
    assert n_need == 0, "preview must credit reclaimable cache frames"
    moves = kv.park(1)                         # succeeds only via reclaim
    assert moves is not None and len(moves) == 2
    assert len(kv.host_pages_of(1)) == 2
    kv.check_invariants()


def test_plan_iteration_reselects_cheapest_after_shared_promotion():
    """Regression (stale promotion order): a shared-frame ``swap_in``
    rewrites SIBLING host-page counts mid-loop, so the one-shot up-front
    sort by "fewest host pages" goes stale. A(2 pages, one shared with C),
    B(3), C(3): promoting A drops C to 2, so the remaining free frames
    belong to C — the stale order would hand them to B."""
    pcfg = _pcfg()
    pb = pcfg.page_size * pcfg.bytes_per_token
    kv = TieredKVAllocator(4 * pb, 16 * pb, pcfg, scope="m",
                           enable_dedup=True)
    kv.alloc(9, 4 * pcfg.page_size)            # fill the device pool
    pa = np.arange(2 * pcfg.page_size, dtype=np.int64)
    kv.alloc(1, 2 * pcfg.page_size, prompt=pa)                   # A: 2 host
    kv.alloc(2, 3 * pcfg.page_size)                              # B: 3 host
    pc = np.concatenate([pa[:pcfg.page_size],
                         np.arange(900, 900 + 2 * pcfg.page_size)])
    kv.alloc(3, 3 * pcfg.page_size, prompt=pc)   # C: 3 host, page 0 shared w/ A
    assert kv.refs(3)[0] in kv.refs(1), "A and C must share page 0"
    kv.free(9)                                 # 4 device frames free up
    sched = SwapScheduler(kv)
    plan = sched.plan_iteration([1, 2, 3])
    # A promotes first (cheapest: 2). Its shared frame moves C to 2 host
    # pages — so the remaining 2 free frames go to C, not B.
    assert [m.rid for m in plan.promotions] == [1, 1, 3, 3]
    assert kv.host_pages_of(1) == [] and kv.host_pages_of(3) == []
    assert len(kv.host_pages_of(2)) == 3
    kv.check_invariants()


# ---------------------------------------------------------------------------
# Disk (NVMe) tier: three-tier migration, staging, cache retirement
# ---------------------------------------------------------------------------

def _mk_3tier(dev=4, host=4, disk=8, **kw):
    return TieredKVAllocator(dev * 16, host * 16, _pcfg(),
                             disk_bytes=disk * 16,
                             disk_link=LinkSpec(bw_bytes_s=1e9,
                                                latency_s=1e-6), **kw)


def test_disk_tier_park_demote_resume_round_trip_accounting():
    kv = _mk_3tier()
    kv.alloc(1, 16)                            # 4 device pages
    assert kv.park(1) is not None              # -> 4 host pages
    moves = kv.demote_to_disk(1, 99)
    assert len(moves) == 4
    assert all(m.src_tier == HOST and m.dst_tier == DISK for m in moves)
    assert len(kv.disk_pages_of(1)) == 4
    assert kv.host.used_pages == 0
    assert kv.pending_disk_out_pages == 4      # NVMe writes, not PCIe
    kv.check_invariants()
    back = kv.resume(1)
    assert back is not None
    # staged disk->host (4 NVMe reads), then promoted host->device
    assert kv.pending_disk_in_pages == 4
    assert len(back) == 4
    assert kv.disk_pages_of(1) == []
    assert all(r.tier == DEVICE for r in kv.refs(1))
    kv.check_invariants()
    kv.free(1)
    assert all(p.used_pages == 0 for p in kv.pools.values())


def test_demote_to_disk_skips_frames_active_sibling_streams():
    """An active request streams its host pages every iteration and the
    engine never reads the disk pool: frames shared with an active sibling
    must not retire to disk at all."""
    kv = TieredKVAllocator(0, 8 * 16, _pcfg(), scope="m", enable_dedup=True,
                           disk_bytes=8 * 16)
    p = np.arange(8, dtype=np.int64)
    kv.alloc(1, 8, prompt=p)                   # 2 host pages (parked)
    kv.alloc(2, 8, prompt=p)                   # active sibling shares both
    assert kv.demote_to_disk(1, 99, active_rids=[2]) == []
    kv.alloc(3, 8)                             # 2 private host pages
    moves = kv.demote_to_disk(3, 99, active_rids=[2])
    assert len(moves) == 2
    kv.check_invariants()


def test_unspill_from_disk_reverses_a_demotion_in_place():
    """The park-fell-through defensive path: every disk page returns to a
    host frame (NVMe reads charged), leaving no disk residency behind —
    the guarantee that an active request never keeps disk pages."""
    kv = _mk_3tier(dev=0, host=4, disk=8)
    kv.alloc(1, 16)                            # 4 host pages
    assert len(kv.demote_to_disk(1, 99)) == 4
    assert kv.host.used_pages == 0
    kv.pending_disk_out_pages = 0
    assert kv.unspill_from_disk(1) == 4
    assert kv.disk_pages_of(1) == []
    assert len(kv.host_pages_of(1)) == 4
    assert kv.pending_disk_in_pages == 4       # the reads are not free
    kv.check_invariants()


def test_resume_returns_none_when_host_cannot_stage():
    kv = _mk_3tier(dev=4, host=2, disk=8)
    kv.alloc(1, 8)                             # 2 device
    assert kv.park(1) is not None              # 2 host
    assert len(kv.demote_to_disk(1, 99)) == 2  # 2 disk
    kv.pending_disk_out_pages = 0
    kv.alloc(2, 24)                            # 4 device + 2 host: host full
    before = kv.refs(1)
    assert kv.resume(1) is None                # nothing staged, nothing moved
    assert kv.refs(1) == before
    assert kv.pending_disk_in_pages == 0
    kv.check_invariants()


def test_prefix_cache_demotes_to_disk_and_revives_on_hit():
    """Under host pressure, aged-out prefix-cache frames retire to the disk
    tier instead of being evicted — and a later dedup hit on a disk-resident
    entry revives it through a host frame (one NVMe read) and still counts
    as a cache hit."""
    kv = TieredKVAllocator(1 * 16, 4 * 16, _pcfg(), scope="dc",
                           enable_dedup=True, host_prefix_cache_pages=4,
                           disk_bytes=8 * 16,
                           disk_link=LinkSpec(bw_bytes_s=1e9))
    pa = (np.arange(12) * 7).astype(np.int64) % 97
    kv.alloc(0, 16, prompt=pa)                 # 3 host (indexed) + 1 device
    kv.free(0)
    assert len(kv.cached_pages()) == 3
    idx_before = len(kv.index)
    # a fresh 3-host-page allocation forces reclaim of 2 cache frames:
    # they must retire to disk, not die
    kv.alloc(1, 16, prompt=(np.arange(12) + 500).astype(np.int64))
    assert kv.reclaimable_disk_pages() == 2
    assert len(kv.index) == idx_before + 3     # nothing evicted, 3 added
    assert kv.pending_disk_out_pages == 2
    kv.check_invariants()
    kv.free(1)
    # resubmit pa: pages 0-1 hit on disk (revived), page 2 hits on host
    refs = kv.alloc(2, 16, prompt=pa)
    assert refs is not None
    assert kv.dedup_hit_pages(2) == [0, 1, 2]
    assert kv.cache_hits >= 3
    assert all(r.tier == HOST for r in refs[:3])
    assert kv.pending_disk_in_pages == 2       # two revival reads
    kv.check_invariants()


def test_disk_pool_backing_and_copy_hook_round_trip_bitwise(tmp_path):
    """Data-plane gate: page bytes survive host -> disk -> host bitwise,
    through both a RAM-backed buffer and a file-backed (np.memmap) pool,
    driven by the allocator's synchronous ``disk_copy`` hook exactly as the
    engine wires it."""
    for path in (None, str(tmp_path / "kv_disk.bin")):
        kv = TieredKVAllocator(2 * 16, 2 * 16, _pcfg(), disk_bytes=4 * 16,
                               disk_backing_path=path)
        page_shape = (4, 3)
        dev_buf = np.zeros((2, *page_shape), np.float32)
        host_buf = kv.host.make_pool_buffer(page_shape, np.float32)
        disk_buf = kv.disk.make_pool_buffer(page_shape, np.float32)
        if path is not None:
            assert isinstance(disk_buf, np.memmap)

        def copy(src_tier, src_page, dst_tier, dst_page,
                 host_buf=host_buf, disk_buf=disk_buf):
            if src_tier == HOST and dst_tier == DISK:
                disk_buf[dst_page] = host_buf[src_page]
            else:
                host_buf[dst_page] = disk_buf[src_page]

        kv.disk_copy = copy
        # resume's h2d legs run through promote_copy in planning order so
        # host transit frames can be reused by later stagings (the engine
        # differential test drives the actual frame-reuse chain)
        kv.promote_copy = (
            lambda src, dst, host_buf=host_buf, dev_buf=dev_buf:
            dev_buf.__setitem__(dst, host_buf[src]))
        kv.alloc(1, 8)                         # 2 device pages
        rng = np.random.default_rng(0)
        want = []
        for i, r in enumerate(kv.refs(1)):
            dev_buf[r.page] = rng.normal(size=page_shape).astype(np.float32)
            want.append(dev_buf[r.page].copy())
        moves = kv.park(1)
        assert moves is not None
        for m in moves:                        # park's d2h legs (engine job)
            host_buf[m.dst_page] = dev_buf[m.src_page]
        assert len(kv.demote_to_disk(1, 99)) == 2
        host_buf[:] = -1.0                     # clobber the host pool
        dev_buf[:] = -2.0                      # and the device pool
        back = kv.resume(1)                    # stages + promotes via hooks
        assert back is not None and len(back) == 2
        assert all(r.tier == DEVICE for r in kv.refs(1))
        for i, r in enumerate(kv.refs(1)):
            np.testing.assert_array_equal(dev_buf[r.page], want[i])
        kv.check_invariants()


def test_disk_traffic_has_own_latency_term():
    """NVMe traffic never rides the PCIe copy stream: small disk queues
    hide under the iteration, large ones bound it (max of the two
    channels), zero reduces exactly to the two-tier model, and unmodeled
    disk traffic (no bandwidth) is an error, not a free ride."""
    times = LayerTimes(2e-3, 5e-3, 8, 1 << 20, 0.0)
    base = iter_time_with_interval_kv(times, NO_OFFLOAD)
    assert iter_time_with_interval_kv(times, NO_OFFLOAD, disk_in_bytes=1e3,
                                      disk_bw=1e9) == base
    big = iter_time_with_interval_kv(times, NO_OFFLOAD, disk_in_bytes=5e8,
                                     disk_out_bytes=5e8, disk_bw=1e9,
                                     disk_latency_s=1e-3)
    assert big == pytest.approx(1e-3 + 1.0)
    with pytest.raises(ValueError):
        iter_time_with_interval_kv(times, NO_OFFLOAD, disk_out_bytes=1.0)
    for i in (1, 2, 7, NO_OFFLOAD):
        assert iter_time_with_interval_kv(times, i, 1e5, 2e5) == \
            iter_time_with_interval_kv(times, i, 1e5, 2e5, disk_bw=5e9)


def test_disk_pool_zero_is_two_tier():
    """Disk disabled: the three-tier allocator is the two-tier allocator —
    no disk pool pages, no NVMe counters, reclaim evicts like before."""
    kv = TieredKVAllocator(2 * 16, 2 * 16, _pcfg(), scope="z",
                           enable_dedup=True, host_prefix_cache_pages=4)
    assert kv.disk.total_pages == 0
    p = np.arange(16, dtype=np.int64)
    kv.alloc(0, 16, prompt=p)
    kv.free(0)
    kv.alloc(1, 16, prompt=np.arange(100, 116, dtype=np.int64))
    assert kv.reclaimable_disk_pages() == 0
    assert kv.pending_disk_out_pages == 0      # evicted, nothing retired
    assert kv.demote_to_disk(1, 99) == []
    kv.check_invariants()


# ---------------------------------------------------------------------------
# Combined weight+KV link algebra (acceptance: SLO-exact under swap traffic)
# ---------------------------------------------------------------------------

@given(tc=st.floats(1e-4, 1e-1), tt=st.floats(1e-4, 1e-1),
       n=st.integers(2, 64), i=st.integers(1, 64),
       kin=st.floats(0.0, 5e-2), kout=st.floats(0.0, 5e-2))
@settings(max_examples=200, deadline=None)
def test_analytic_matches_simulator_with_kv_traffic(tc, tt, n, i, kin, kout):
    """iter_time_with_interval_kv must equal the event simulation when KV
    swap traffic shares the copy stream with weight prefetch — every byte
    charged exactly once."""
    i = min(i, n)
    times = LayerTimes(tc, tt, n, 1 << 20, t_rest_s=0.0)
    bw = link_bandwidth(times)
    analytic = iter_time_with_interval_kv(times, i, kin * bw, kout * bw)
    sched = schedule_for_interval([tc] * n, i, tt, kv_in_s=kin, kv_out_s=kout)
    sim = simulate_iteration(sched)["latency_s"]
    assert sim == pytest.approx(analytic, rel=1e-9, abs=1e-12)


def test_kv_traffic_reduces_to_plain_interval_time():
    times = LayerTimes(2e-3, 5e-3, 32, 400 << 20, 1e-3)
    for i in (1, 2, 7, 32, NO_OFFLOAD):
        assert iter_time_with_interval_kv(times, i) == \
            iter_time_with_interval(times, i)


def test_kv_write_back_overlaps_when_no_offload():
    """With no weight transfers, write-back (d2h) rides a free copy stream:
    only swap-in, which gates layer 0, shows up in latency."""
    times = LayerTimes(2e-3, 5e-3, 8, 1 << 20, 0.0)
    bw = link_bandwidth(times)
    t = iter_time_with_interval_kv(times, NO_OFFLOAD, 0.0, 10 * (1 << 20))
    assert t == pytest.approx(times.t_iter_no_offload_s)
    t_in = iter_time_with_interval_kv(times, NO_OFFLOAD, 2 * (1 << 20), 0.0)
    assert t_in == pytest.approx(times.t_iter_no_offload_s
                                 + 2 * (1 << 20) / bw)


def test_coordinator_arbitrates_combined_weight_kv_rate():
    """KV swap traffic rides the same per-bus budget as weight prefetch: an
    instance streaming KV forces its neighbour to a larger interval on a
    link that weights-only traffic would have fit."""
    def inst(name, kv_bytes):
        return InstanceState(name=name, num_units=32, unit_bytes=400 << 20,
                             t_iter_s=0.050, min_interval=2,
                             max_interval=NO_OFFLOAD,
                             kv_bytes_per_iter=kv_bytes)

    a, b = inst("a", 0.0), inst("b", 0.0)
    base = coordinate([a, b], link_bw=1e14)
    link = base.total_link_rate * 1.05          # slack without KV traffic
    assert coordinate([a, b], link_bw=link).intervals == {"a": 2, "b": 2}

    kv_bytes = 0.050 * 0.2 * link               # b streams 20% of the link
    bk = inst("b", kv_bytes)
    res = coordinate([a, bk], link_bw=link)
    assert res.ok
    assert res.total_link_rate <= link * (1 + 1e-9)
    # combined rate is accounted: someone had to back off
    assert res.intervals["a"] > 2 or res.intervals["b"] > 2
    # and the KV rate is charged exactly once
    got_b = bk.link_rate(res.intervals["b"])
    from repro.core.interval import OffloadPlan
    want_b = OffloadPlan(32, res.intervals["b"]).link_rate(400 << 20, 0.050) \
        + kv_bytes / 0.050
    assert got_b == pytest.approx(want_b)


# ---------------------------------------------------------------------------
# Engine acceptance: serving beyond the HBM budget via host KV tiering
# ---------------------------------------------------------------------------

def _mk_tiered_engine(host_pages: int, extra_device_pages: float = 0.4,
                      max_batch: int = 4, max_seq: int = 48):
    """Engine whose HBM fits the resident weights but (essentially) no KV:
    every request's cache must spill to the host tier."""
    from _engine_builders import mk_reduced_engine

    eng, _ = mk_reduced_engine(name="tiered", max_batch=max_batch,
                               max_seq=max_seq,
                               extra_device_pages=extra_device_pages,
                               host_pages=host_pages)
    return eng


def _reqs(n, prompt_len=8, new=6, ttft=1.0, tpot=1.0):
    from repro.serving.request import Request
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(0, 100, prompt_len).astype(np.int32),
                    max_new_tokens=new, ttft_slo_s=ttft, tpot_slo_s=tpot)
            for i in range(n)]


def test_engine_serves_beyond_hbm_via_host_tier():
    """Acceptance: an HBM budget too small for the target (batch, context)
    under weights-only offloading is served through host KV tiering with no
    TTFT/TPOT violation in the modeled clock — and the engine's clock
    advance matches the combined-traffic prediction exactly."""
    eng = _mk_tiered_engine(host_pages=16)
    assert eng.kv.device.total_pages == 0       # weights-only: no KV fits
    reqs = _reqs(3)
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert eng._active_batch() > 0              # admitted via host spill
    assert eng.kv.host.used_pages > 0

    # predicted vs simulated clock under combined weight+KV traffic
    streamed = eng.swap.streamed_bytes(eng._active_rids())
    assert streamed > 0
    times = eng.times_fn(eng._active_batch(), eng.ecfg.max_seq, "decode")
    predicted = iter_time_with_interval_kv(times, eng.interval, streamed, 0.0)
    t0 = eng.clock_s
    was_queued = len(eng.queue)
    eng.step()
    if len(eng.queue) == was_queued:            # no admission: pure decode
        assert eng.clock_s - t0 == pytest.approx(predicted, rel=1e-9)

    it = 0
    while (eng.queue or eng._active_batch() > 0) and it < 300:
        eng.step()
        it += 1
    assert len(eng.finished) == 3
    for r in eng.finished:
        m = r.metrics()
        assert m["ttft_ok"] and m["tpot_ok"]
    assert eng.kv.host.used_pages == 0          # all pages returned
    eng.kv.check_invariants()


def test_engine_without_host_tier_cannot_serve_it():
    """Control: with host_kv_bytes=0 the same workload is unservable —
    the device pool never has a page, so requests wait forever."""
    eng = _mk_tiered_engine(host_pages=0)
    out = eng.run(_reqs(2), max_iters=50)
    assert out["finished"] == 0
    assert len(eng.queue) == 2                  # waiting, not rejected


def test_engine_spill_admission_respects_tpot():
    """If streaming the spilled KV would push the iteration past the TPOT
    SLO, the request is NOT admitted (it waits) — no modeled violation."""
    eng = _mk_tiered_engine(host_pages=16)
    times = eng.times_fn(1, eng.ecfg.max_seq, "decode")
    pages = eng.kv.device.pages_for(8 + 6)
    stream_bytes = pages * eng.kv.page_bytes
    dt0 = iter_time_with_interval_kv(times, eng.interval)
    dt_stream = iter_time_with_interval_kv(times, eng.interval, stream_bytes)
    assert dt_stream > dt0
    tight = (dt0 + dt_stream) / 2               # feasible w/o KV, not with
    reqs = _reqs(1, tpot=tight)
    out = eng.run(reqs, max_iters=20)
    assert out["finished"] == 0
    assert len(eng.queue) == 1                  # waiting on device pages
    assert eng.kv.host.used_pages == 0


# ---------------------------------------------------------------------------
# PrefixIndex keep-alive (host-tier prefix cache, LRU-bounded)
# ---------------------------------------------------------------------------

def _mk_cached_kv(device_pages=1, host_pages=8, cache_pages=4):
    return TieredKVAllocator(device_pages * 16, host_pages * 16,
                             _pcfg(page_size=4, bpt=4), scope="cache-test",
                             enable_dedup=True,
                             host_prefix_cache_pages=cache_pages)


def _prompt(seed, n=12):
    return (np.arange(n) * 7 + seed).astype(np.int64) % 97


def test_prefix_cache_keeps_host_entries_after_last_owner_frees():
    """A re-submitted shared prefix dedups even when no live request holds
    the pages anymore: the last owner's indexed host frames survive under
    the cache owner instead of dying with the request."""
    kv = _mk_cached_kv()
    p = _prompt(0)
    kv.alloc(0, 16, prompt=p)              # 3 prompt pages on host, tail dev
    assert len(kv.host_pages_of(0)) == 3
    idx_before = len(kv.index)
    kv.free(0)
    kv.check_invariants()
    assert len(kv.cached_pages()) == 3     # frames survived their owner
    assert len(kv.index) == idx_before     # content still addressable
    assert kv.host.used_pages == 3

    refs = kv.alloc(1, 16, prompt=p)       # same prefix re-submitted
    assert refs is not None
    assert kv.dedup_hit_pages(1) == [0, 1, 2]
    assert kv.cache_hits == 3
    kv.free(1)
    kv.check_invariants()
    assert len(kv.cached_pages()) == 3     # re-entered the cache


def test_prefix_cache_lru_capacity_evicts_oldest():
    kv = _mk_cached_kv(host_pages=16, cache_pages=3)
    kv.alloc(0, 16, prompt=_prompt(0))     # 3 host prompt pages
    kv.free(0)
    first_gen = set(kv.cached_pages())
    kv.alloc(1, 16, prompt=_prompt(1))     # different content: 3 more
    kv.free(1)
    kv.check_invariants()
    cached = kv.cached_pages()
    assert len(cached) == 3                # capacity bound holds
    # the survivors are the newest entries (rid 1's), oldest evicted first
    assert not (set(cached) & first_gen)
    assert kv.host.used_pages == 3


def test_prefix_cache_reclaimed_under_host_pressure():
    """Cache frames are capacity, not a leak: an allocation that needs host
    pages evicts LRU entries instead of failing."""
    kv = _mk_cached_kv(host_pages=4, cache_pages=4)
    kv.alloc(0, 16, prompt=_prompt(0))
    kv.free(0)
    assert len(kv.cached_pages()) == 3 and kv.host.free_pages == 1
    # a fresh prompt needs 3 host pages: 2 cache entries must be reclaimed
    refs = kv.alloc(1, 16, prompt=_prompt(5))
    assert refs is not None
    assert len(kv.host_pages_of(1)) == 3
    assert len(kv.cached_pages()) <= 1
    kv.check_invariants()


def test_prefix_cache_hit_frames_not_reclaimed_for_same_alloc():
    """Reclaim under pressure must spare the frames the very same
    allocation is about to share: the OTHER prompt's entries evict, the hit
    prompt's entries survive and dedup."""
    kv = _mk_cached_kv(host_pages=8, cache_pages=6)
    pa, pb = _prompt(0), _prompt(50)
    kv.alloc(0, 16, prompt=pa)
    kv.free(0)                             # pa cached (older)
    kv.alloc(1, 16, prompt=pb)
    kv.free(1)                             # pb cached (newer)
    assert len(kv.cached_pages()) == 6 and kv.host.free_pages == 2
    # pb resubmitted with a longer tail: hits pb's 3 cached pages, needs 3
    # fresh host pages (free 2) -> reclaim must evict pa's LRU entries, not
    # the pb frames this allocation shares
    refs = kv.alloc(2, 28, prompt=np.concatenate([pb, _prompt(9, 8)]))
    assert refs is not None
    assert kv.dedup_hit_pages(2)[:3] == [0, 1, 2]
    assert kv.cache_hits == 3
    kv.check_invariants()


def test_prefix_cache_disabled_by_default_frames_die_with_owner():
    kv = TieredKVAllocator(16, 8 * 16, _pcfg(page_size=4, bpt=4),
                           scope="nocache", enable_dedup=True)
    kv.alloc(0, 16, prompt=_prompt(0))
    kv.free(0)
    assert kv.host.used_pages == 0 and len(kv.index) == 0
    assert kv.cached_pages() == []


def test_prefix_cache_single_owner_over_cap_trims_at_free():
    """Regression: the LRU bound must hold even when ONE owner frees more
    indexed host pages than the capacity — the trim runs after the owner's
    own claims are released, so the excess frames are evictable
    immediately, not only under later pressure."""
    kv = _mk_cached_kv(host_pages=8, cache_pages=2)
    kv.alloc(0, 16, prompt=_prompt(0))     # 3 indexed host pages
    kv.free(0)
    kv.check_invariants()
    assert len(kv.cached_pages()) == 2     # bound holds right away
    assert kv.host.used_pages == 2


# ---------------------------------------------------------------------------
# Forked beams: per-sharer COW reserves on arbitrary shared pages
# ---------------------------------------------------------------------------

def test_fork_shares_whole_block_table_refcounts():
    kv = TieredKVAllocator(8 * 16, 8 * 16, _pcfg())
    refs = kv.alloc(1, 3 * 4)
    assert refs is not None
    forked = kv.fork(1, 2)
    assert forked is not None and len(forked) == 3
    assert kv.refs(2) == kv.refs(1)              # same frames, position-wise
    for r in kv.refs(1):
        assert kv.refcount(r) == 2
    kv.check_invariants()
    # freeing one sharer leaves the other's table fully intact
    kv.free(1)
    assert len(kv.refs(2)) == 3
    for r in kv.refs(2):
        assert kv.refcount(r) == 1
    kv.free(2)
    assert kv.device.used_pages == 0 and kv.host.used_pages == 0


def test_fork_refuses_live_dst_and_dead_src():
    kv = TieredKVAllocator(8 * 16, 8 * 16, _pcfg())
    kv.alloc(1, 4)
    kv.alloc(2, 4)
    assert kv.fork(1, 2) is None                 # dst already live
    assert kv.fork(99, 3) is None                # src unknown
    kv.check_invariants()


def test_add_reserve_per_sharer_on_arbitrary_shared_page():
    """Each sharer of each shared page gets its OWN private spare frame —
    N beams diverging at the same position must never race for one
    reserve, and a mid-table page is as reservable as the tail."""
    kv = TieredKVAllocator(8 * 16, 8 * 16, _pcfg())
    kv.alloc(1, 3 * 4)
    kv.fork(1, 2)
    r1 = kv.add_reserve(1, 1)                    # mid-table shared page
    r2 = kv.add_reserve(2, 1)
    assert r1 is not None and r2 is not None
    assert r1.page != r2.page                    # private per sharer
    assert kv.reserves_of(1) == {1: r1}
    assert kv.reserves_of(2) == {1: r2}
    assert kv.n_reserve_frames() == 2
    # idempotent: a covered page hands back the existing reserve
    assert kv.add_reserve(1, 1) == r1
    assert kv.n_reserve_frames() == 2
    kv.check_invariants()


def test_add_reserve_private_page_needs_none():
    kv = TieredKVAllocator(8 * 16, 8 * 16, _pcfg())
    kv.alloc(1, 3 * 4)
    assert kv.add_reserve(1, 0) is None          # refcount 1: no COW risk
    assert kv.n_reserve_frames() == 0


def test_add_reserve_exhausted_pools_claims_nothing():
    kv = TieredKVAllocator(2 * 16, 1 * 16, _pcfg())   # 2 dev + 1 host pages
    kv.alloc(1, 2 * 4)
    kv.fork(1, 2)
    assert kv.add_reserve(1, 0) is not None      # host fallback frame
    assert kv.add_reserve(2, 0) is None          # both pools dry: no claim
    assert kv.n_reserve_frames() == 1
    kv.check_invariants()


# ---------------------------------------------------------------------------
# PEER tier accounting: handoff export/import conservation + refusal path
# ---------------------------------------------------------------------------

def test_peer_handoff_byte_conservation_across_allocators():
    """Exporter and importer book the same page count; both sides' pending
    counters drain into exactly one SwapPlan's peer terms and zero out —
    the per-instance halves of the I12 conservation invariant."""
    src = TieredKVAllocator(4 * 16, 8 * 16, _pcfg())
    dst = TieredKVAllocator(4 * 16, 8 * 16, _pcfg())
    src.alloc(1, 3 * 4)
    assert src.park(1) is not None               # whole table host-ward
    pages = src.export_parked(1)
    assert pages is not None and len(pages) == 3
    src.free(1)
    src.note_peer_export(len(pages))

    got = dst.import_parked(1, len(pages))
    assert got is not None and len(got) == 3
    dst.note_peer_import(len(pages))

    assert src.peer_out_pages_total == dst.peer_in_pages_total == 3
    s_src, s_dst = SwapScheduler(src), SwapScheduler(dst)
    p_out, p_in = s_src.plan_iteration([]), s_dst.plan_iteration([])
    assert p_out.peer_out_bytes == p_in.peer_in_bytes == 3 * src.page_bytes
    assert src.pending_peer_out_pages == dst.pending_peer_in_pages == 0
    # drained once: the next plan charges nothing
    assert s_src.plan_iteration([]).peer_out_bytes == 0
    assert s_dst.plan_iteration([]).peer_in_bytes == 0
    src.check_invariants()
    dst.check_invariants()


def test_peer_export_refuses_partial_or_reserved_parks():
    kv = TieredKVAllocator(4 * 16, 8 * 16, _pcfg())
    kv.alloc(1, 2 * 4)
    assert kv.export_parked(1) is None           # device-resident: not parked
    kv.park(1)
    kv.fork(1, 2)
    assert kv.add_reserve(1, 0) is not None
    assert kv.export_parked(1) is None           # reserve held: stays put
    assert kv.export_parked(2) is not None       # reserve-free sharer exports
    kv.check_invariants()


def test_peer_import_refusal_claims_nothing_and_rollback_reclaims():
    """A too-small host tier refuses the import with ZERO frames claimed;
    the exporter can then re-import into the frames its own export just
    freed — the allocator-level contract the engine's rollback leans on."""
    src = TieredKVAllocator(4 * 16, 8 * 16, _pcfg())
    dst = TieredKVAllocator(4 * 16, 2 * 16, _pcfg())   # 2 host pages only
    src.alloc(1, 3 * 4)
    src.park(1)
    assert src.export_parked(1) is not None
    src.free(1)
    used_before = dst.host.used_pages
    assert dst.import_parked(1, 3) is None       # cannot absorb: refuse
    assert dst.host.used_pages == used_before    # nothing claimed
    back = src.import_parked(1, 3)               # rollback re-claim
    assert back is not None and len(back) == 3
    assert src.refs(1) == [PageRef(HOST, p) for p in back]
    src.check_invariants()
    dst.check_invariants()
