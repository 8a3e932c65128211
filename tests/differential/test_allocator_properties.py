"""Property-based test: drive ``TieredKVAllocator`` with random
alloc / extend / swap_in / swap_out / resize / free sequences and assert the
structural invariants after every single operation:

  * no page ref is on both tiers, and the per-request refs lists exactly
    match the per-tier pools (``check_invariants``),
  * every live request holds exactly ``pages_for(tokens)`` refs,
  * a failed extend rolls back to the exact prior refs list (demotions may
    remain per the documented contract: the data plane may already have
    copied them, so a DEVICE ref may have turned HOST — nothing else),
  * resize either raises without mutating (overflow > host capacity) or
    returns demotions + remap consistent with the new refs.

Pure accounting, no JAX compiles: fast CI tier.
"""
import numpy as np

from hypothesis import given, settings, strategies as st

import pytest

from repro.serving.kv_cache import PageConfig
from repro.serving.kv_offload import (CACHE_RID, DEVICE, DISK, HOST,
                                      LinkSpec, PageRef, TieredKVAllocator)

PAGE = 4   # tokens per page
BPT = 4    # bytes per token
PB = PAGE * BPT


def _page_count(kv, rid, tier):
    return len(kv.device_pages_of(rid) if tier == DEVICE
               else kv.host_pages_of(rid))


@given(codes=st.lists(st.integers(0, (1 << 30) - 1), min_size=0, max_size=50),
       dev_pages=st.integers(0, 10), host_pages=st.integers(0, 10))
@settings(max_examples=80, deadline=None)
def test_tiered_allocator_random_op_sequences(codes, dev_pages, host_pages):
    kv = TieredKVAllocator(dev_pages * PB, host_pages * PB,
                           PageConfig(PAGE, bytes_per_token=BPT))
    tokens: dict[int, int] = {}          # live rid -> token count
    next_rid = 0
    for code in codes:
        op, arg = code % 6, code // 6
        alive = sorted(tokens)
        if op == 0:                                          # alloc
            want = arg % ((dev_pages + host_pages + 2) * PAGE) + 1
            refs = kv.alloc(next_rid, want, allow_host=bool(arg % 2))
            if refs is not None:
                assert len(refs) == kv.device.pages_for(want)
                tokens[next_rid] = want
                next_rid += 1
            else:
                kv.free(next_rid)        # nothing claimed: must be a no-op
        elif op == 1 and alive:                              # extend
            rid = alive[arg % len(alive)]
            before = kv.refs(rid)
            new_total = tokens[rid] + arg % (3 * PAGE) + 1
            out = kv.extend(rid, new_total, allow_host=bool(arg % 2))
            after = kv.refs(rid)
            if out is None:
                # exact rollback: same length, and position-wise either the
                # identical ref or a documented DEVICE->HOST demotion
                assert len(after) == len(before)
                for b4, now in zip(before, after):
                    assert now == b4 or (b4.tier == DEVICE
                                         and now.tier == HOST)
            else:
                tokens[rid] = new_total
        elif op == 2 and alive:                              # swap_out
            rid = alive[arg % len(alive)]
            n_dev = _page_count(kv, rid, DEVICE)
            free_host = kv.host.free_pages
            moves = kv.swap_out(rid, arg % 3 + 1)
            assert len(moves) == min(arg % 3 + 1, n_dev, free_host)
            for m in moves:
                assert m.dst_page in kv.host_pages_of(rid)
        elif op == 3 and alive:                              # swap_in
            rid = alive[arg % len(alive)]
            n_host = _page_count(kv, rid, HOST)
            moves = kv.swap_in(rid, arg % 3 + 1)
            assert len(moves) <= min(arg % 3 + 1, n_host)
        elif op == 4:                                        # resize
            new_bytes = (arg % (dev_pages + 4)) * PB
            if kv.can_resize_device(new_bytes):
                res = kv.resize_device(new_bytes)
                # remap's new frames are exactly the surviving device pages
                live_dev = sorted(p for r in tokens
                                  for p in kv.device_pages_of(r))
                assert sorted(n for _, n in res.remap) == live_dev
                for m in res.demotions:
                    assert m.src_tier == DEVICE
                    assert m.dst_page in kv.host_pages_of(m.rid)
            else:
                snapshot = {r: kv.refs(r) for r in alive}
                with pytest.raises(RuntimeError):
                    kv.resize_device(new_bytes)
                # failed resize must not have mutated anything
                assert {r: kv.refs(r) for r in alive} == snapshot
        elif op == 5 and alive:                              # free
            rid = alive[arg % len(alive)]
            kv.free(rid)
            del tokens[rid]
            assert kv.refs(rid) == []

        # ---- invariants after every operation -----------------------------
        kv.check_invariants()            # tiers/pools/refs exactly consistent
        for rid, tok in tokens.items():
            refs = kv.refs(rid)
            assert len(refs) == kv.device.pages_for(tok)
            # no ref claims both tiers; per-tier counts match the pools
            assert (_page_count(kv, rid, DEVICE)
                    + _page_count(kv, rid, HOST)) == len(refs)

    for rid in list(tokens):
        kv.free(rid)
    kv.check_invariants()
    assert kv.device.used_pages == 0 and kv.host.used_pages == 0


# ---------------------------------------------------------------------------
# Refcounted sharing / copy-on-write property test
# ---------------------------------------------------------------------------


def _total_refcounts(kv) -> int:
    return (sum(kv.device._rc.values()) + sum(kv.host._rc.values()))


def _live_references(kv) -> int:
    """Block-table entries + COW reserves across all live requests."""
    return (sum(len(refs) for refs in kv._refs.values())
            + kv.n_reserve_frames())


@given(codes=st.lists(st.integers(0, (1 << 30) - 1), min_size=0, max_size=60),
       dev_pages=st.integers(0, 12), host_pages=st.integers(0, 12))
@settings(max_examples=80, deadline=None)
def test_refcounted_dedup_random_op_sequences(codes, dev_pages, host_pages):
    """Drive the dedup-enabled allocator with random share / write(COW) /
    swap / free / resize sequences. After EVERY operation:

      * the sum of pool refcounts equals the number of live references
        (block-table entries + COW reserves) — nothing leaked, nothing
        double-freed,
      * ``check_invariants`` holds (pool partition, refcount multiplicity,
        reserve privacy, index <-> frame consistency),
      * every live request still holds exactly ``pages_for(tokens)``
        block-table entries.

    Prompts are drawn from 3 families so shared prefixes actually occur;
    writes replay the engine's decode write sequence (position = prompt_len
    + generated so far) through ``prepare_write``, which is where COW fires.
    """
    kv = TieredKVAllocator(dev_pages * PB, host_pages * PB,
                           PageConfig(PAGE, bytes_per_token=BPT),
                           scope="prop", enable_dedup=True)
    state: dict[int, dict] = {}       # rid -> {tokens, prompt_len, written}
    next_rid = 0
    for code in codes:
        op, arg = code % 6, code // 6
        alive = sorted(state)
        if op == 0:                                          # alloc w/ prompt
            fam = arg % 3
            plen = arg // 3 % (3 * PAGE) + 1
            extra = arg // 9 % (2 * PAGE)
            prompt = (np.arange(plen, dtype=np.int64) + 10_000 * fam)
            refs = kv.alloc(next_rid, plen + extra,
                            allow_host=bool(arg % 2), prompt=prompt)
            if refs is not None:
                assert len(refs) == kv.device.pages_for(plen + extra)
                state[next_rid] = {"tokens": plen + extra, "plen": plen,
                                   "written": 0}
                next_rid += 1
            else:
                kv.free(next_rid)    # nothing claimed: must be a no-op
        elif op == 1 and alive:                              # decode write
            rid = alive[arg % len(alive)]
            s = state[rid]
            pos = s["plen"] + s["written"]
            if pos < s["tokens"]:
                before = kv.refs(rid)
                moves = kv.prepare_write(rid, pos // PAGE)
                after = kv.refs(rid)
                for m in moves:
                    # COW swaps exactly the written page, onto a private
                    # frame, without disturbing any other entry
                    assert m.rid == rid
                    assert before[pos // PAGE] == m.src
                    assert after[pos // PAGE] == m.dst
                    assert kv.refcount(m.dst) == 1
                assert [r for i, r in enumerate(before)
                        if i != pos // PAGE] == \
                    [r for i, r in enumerate(after) if i != pos // PAGE]
                # the written page is now safe: private, or rid is its origin
                wref = kv.refs(rid)[pos // PAGE]
                assert kv.refcount(wref) == 1 or \
                    kv.reserve_of(rid) is None
                s["written"] += 1
        elif op == 2 and alive:                              # swap_out
            rid = alive[arg % len(alive)]
            moves = kv.swap_out(rid, arg % 3 + 1)
            for m in moves:          # a shared frame moved for every owner
                assert all(PageRef(DEVICE, m.src_page) not in kv.refs(r)
                           for r in alive)
        elif op == 3 and alive:                              # swap_in
            rid = alive[arg % len(alive)]
            kv.swap_in(rid, arg % 3 + 1)
        elif op == 4:                                        # resize
            new_bytes = (arg % (dev_pages + 4)) * PB
            if kv.can_resize_device(new_bytes):
                res = kv.resize_device(new_bytes)
                live_dev = sorted({p for r in state
                                   for p in kv.device_pages_of(r)}
                                  | {v.page for m in kv._reserves.values()
                                     for v in m.values()
                                     if v.tier == DEVICE})
                assert sorted(n for _, n in res.remap) == live_dev
            else:
                with pytest.raises(RuntimeError):
                    kv.resize_device(new_bytes)
        elif op == 5 and alive:                              # free
            rid = alive[arg % len(alive)]
            kv.free(rid)
            del state[rid]
            assert kv.refs(rid) == []

        # ---- invariants after every operation -----------------------------
        kv.check_invariants()
        assert _total_refcounts(kv) == _live_references(kv), \
            "refcount sum != live block-table entries + reserves"
        for rid, s in state.items():
            assert len(kv.refs(rid)) == kv.device.pages_for(s["tokens"])

    for rid in list(state):
        kv.free(rid)
    kv.check_invariants()
    assert kv.device.used_pages == 0 and kv.host.used_pages == 0
    assert _total_refcounts(kv) == 0
    assert len(kv.index) == 0, "prefix index outlived its frames"


# ---------------------------------------------------------------------------
# Three-tier (device / host / disk) property test
# ---------------------------------------------------------------------------


def _total_refcounts_3t(kv) -> int:
    return sum(sum(pool._rc.values()) for pool in kv.pools.values())


def _cache_claims(kv) -> int:
    """CACHE_RID's keep-alive claims across both below-device tiers."""
    return len(kv._cache_lru) + len(kv._disk_cache)


@given(codes=st.lists(st.integers(0, (1 << 30) - 1), min_size=0, max_size=60),
       dev_pages=st.integers(0, 10), host_pages=st.integers(0, 10),
       disk_pages=st.integers(0, 10))
@settings(max_examples=80, deadline=None)
def test_three_tier_random_op_sequences(codes, dev_pages, host_pages,
                                        disk_pages):
    """Drive the THREE-tier allocator (dedup + keep-alive cache on, so
    host-pressure reclaim exercises the cache-to-disk retirement path) with
    random alloc / demote / promote / park-to-disk / resume / resize / free
    sequences. After EVERY operation:

      * per-tier refcount sums == live references (block-table entries +
        COW reserves + keep-alive cache claims on host AND disk),
      * ``check_invariants`` (pool partitions, ref/pool agreement per tier,
        reserve privacy, index <-> frame consistency, cache LRU <-> pool),
      * every live request holds exactly ``pages_for(tokens)`` refs and no
        capacity is conjured (pool invariants bound used <= total),
      * disk pages only ever belong to requests the caller treats as
        parked — an "active" request (even subset) never loses a page to
        disk.
    """
    kv = TieredKVAllocator(dev_pages * PB, host_pages * PB,
                           PageConfig(PAGE, bytes_per_token=BPT),
                           scope="3t", enable_dedup=True,
                           host_prefix_cache_pages=3,
                           disk_bytes=disk_pages * PB,
                           disk_link=LinkSpec(bw_bytes_s=1e9))
    tokens: dict[int, int] = {}
    next_rid = 0
    for code in codes:
        op, arg = code % 7, code // 7
        alive = sorted(tokens)
        # a deterministic "active" subset: parity of the rid + arg salt
        active = [r for r in alive if (r + arg) % 3 != 0]
        if op == 0:                                          # alloc w/ prompt
            fam = arg % 3
            plen = arg // 3 % (3 * PAGE) + 1
            extra = arg // 9 % (2 * PAGE)
            prompt = (np.arange(plen, dtype=np.int64) + 10_000 * fam)
            refs = kv.alloc(next_rid, plen + extra,
                            allow_host=bool(arg % 2), prompt=prompt)
            if refs is not None:
                assert len(refs) == kv.device.pages_for(plen + extra)
                assert all(r.tier != DISK for r in refs), \
                    "alloc mapped a disk page without revival"
                tokens[next_rid] = plen + extra
                next_rid += 1
            else:
                kv.free(next_rid)    # nothing claimed: must be a no-op
        elif op == 1 and alive:                              # swap_out
            rid = alive[arg % len(alive)]
            kv.swap_out(rid, arg % 3 + 1, active_rids=active)
        elif op == 2 and alive:                              # swap_in
            rid = alive[arg % len(alive)]
            kv.swap_in(rid, arg % 3 + 1)
        elif op == 3 and alive:                              # park to disk
            rid = alive[arg % len(alive)]
            if rid not in active:
                before = {r: set(kv.disk_pages_of(r)) for r in active}
                moves = kv.demote_to_disk(rid, arg % 4 + 1, active)
                for m in moves:
                    assert m.src_tier == HOST and m.dst_tier == DISK
                for r in active:
                    assert set(kv.disk_pages_of(r)) == before[r], \
                        "an active request lost a page to disk"
        elif op == 4 and alive:                              # resume
            rid = alive[arg % len(alive)]
            out = kv.resume(rid)
            if out is None:
                assert kv.disk_pages_of(rid), \
                    "resume refused without disk pages to stage"
            else:
                assert kv.disk_pages_of(rid) == [], \
                    "resume left disk pages behind"
        elif op == 5:                                        # resize
            new_bytes = (arg % (dev_pages + 4)) * PB
            if kv.can_resize_device(new_bytes):
                kv.resize_device(new_bytes)
            else:
                with pytest.raises(RuntimeError):
                    kv.resize_device(new_bytes)
        elif op == 6 and alive:                              # free
            rid = alive[arg % len(alive)]
            kv.free(rid)
            del tokens[rid]
            assert kv.refs(rid) == []

        # ---- invariants after every operation -----------------------------
        kv.check_invariants()
        live = (sum(len(refs) for refs in kv._refs.values())
                + kv.n_reserve_frames() + _cache_claims(kv))
        assert _total_refcounts_3t(kv) == live, \
            "refcount sum != live refs + reserves + cache claims"
        for rid, tok in tokens.items():
            refs = kv.refs(rid)
            assert len(refs) == kv.device.pages_for(tok)
            per_tier = sum(len(kv.tier_pages_of(rid, t))
                           for t in (DEVICE, HOST, DISK))
            assert per_tier == len(refs), "a ref claims several tiers"

    for rid in list(tokens):
        kv.free(rid)
    kv.check_invariants()
    # only keep-alive cache claims may outlive the requests
    assert _total_refcounts_3t(kv) == _cache_claims(kv)
    assert kv.device.used_pages == 0
    assert kv.host.used_pages == len(kv._cache_lru)
    assert kv.disk.used_pages == len(kv._disk_cache)
