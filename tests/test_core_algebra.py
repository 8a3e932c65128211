"""Select-N algebra: interval feasibility, simulator consistency, record
lookups, coordinator — including hypothesis property tests on the system's
invariants."""
import math

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.baselines import (deepspeed_plan, flexgen_decision,
                                  flexgen_equivalent_interval,
                                  flexgen_host_bytes)
from repro.core.coordinator import (CoordinationResult, InstanceState,
                                    coordinate, max_interval_for_memory)
from repro.core.hardware import A10
from repro.core.interval import (LayerTimes, NO_OFFLOAD, OffloadPlan,
                                 iter_time_with_interval,
                                 min_feasible_interval, optimal_interval)
from repro.core.record import PerformanceRecord
from repro.core.simulator import (schedule_deepspeed, schedule_for_interval,
                                  simulate_iteration, simulate_shared_bus)

TIMES = LayerTimes(t_compute_s=2e-3, t_transfer_s=5e-3, num_layers=32,
                   layer_bytes=400 << 20, t_rest_s=1e-3)


# ---------------------------------------------------------------------------
# Interval algebra
# ---------------------------------------------------------------------------

def test_interval_monotone_latency():
    prev = float("inf")
    for i in range(1, TIMES.num_layers + 1):
        t = iter_time_with_interval(TIMES, i)
        assert t <= prev + 1e-12, f"latency must not increase with interval {i}"
        prev = t
    assert iter_time_with_interval(TIMES, NO_OFFLOAD) == pytest.approx(
        TIMES.t_iter_no_offload_s)


def test_min_feasible_meets_slo_and_is_minimal():
    slo = 1.3 * TIMES.t_iter_no_offload_s
    i = min_feasible_interval(TIMES, slo)
    assert iter_time_with_interval(TIMES, i) <= slo
    if i > 1:
        assert iter_time_with_interval(TIMES, i - 1) > slo


@given(tc=st.floats(1e-4, 1e-1), tt=st.floats(1e-4, 1e-1),
       n=st.integers(2, 80), i=st.integers(1, 80))
@settings(max_examples=200, deadline=None)
def test_analytic_matches_simulator(tc, tt, n, i):
    """iter_time_with_interval must equal the discrete-event simulation for
    uniform layer times (the paper's Fig. 7 schedule)."""
    i = min(i, n)
    times = LayerTimes(tc, tt, n, 1 << 20, t_rest_s=0.0)
    analytic = iter_time_with_interval(times, i)
    sched = schedule_for_interval([tc] * n, i, tt)
    sim = simulate_iteration(sched)["latency_s"]
    assert sim == pytest.approx(analytic, rel=1e-9, abs=1e-12)


@given(tc=st.floats(1e-4, 5e-2), tt=st.floats(1e-4, 5e-2),
       n=st.integers(2, 64), slack=st.floats(0.0, 3.0))
@settings(max_examples=200, deadline=None)
def test_optimal_interval_is_slo_safe(tc, tt, n, slack):
    """The paper's record formula must never yield an SLO-violating interval
    (validated against the event simulator)."""
    times = LayerTimes(tc, tt, n, 1 << 20, t_rest_s=0.0)
    slo = times.t_iter_no_offload_s * (1.0 + slack)
    i = optimal_interval(times, slo)
    if i >= NO_OFFLOAD:
        return
    sched = schedule_for_interval([tc] * n, i, tt)
    sim = simulate_iteration(sched)["latency_s"]
    assert sim <= slo * (1 + 1e-9)


def test_plan_accounting():
    plan = OffloadPlan(num_units=32, interval=4)
    assert plan.num_groups == 8
    assert plan.num_offloaded == 8
    assert plan.num_resident == 24
    assert plan.offloaded_indices() == [3, 7, 11, 15, 19, 23, 27, 31]
    lb = 100
    assert plan.host_bytes(lb) == 800
    assert plan.device_bytes(lb) == (24 + 2) * lb
    assert OffloadPlan(32, NO_OFFLOAD).host_bytes(lb) == 0
    assert OffloadPlan(32, 1).num_resident == 0


@given(n=st.integers(1, 128), i=st.integers(1, 200))
@settings(max_examples=200, deadline=None)
def test_plan_partition_invariant(n, i):
    plan = OffloadPlan(n, i)
    assert plan.num_resident + plan.num_offloaded == n
    assert plan.tail_units >= 0
    assert plan.num_groups * plan.interval + plan.tail_units == n or \
        not plan.enabled


# ---------------------------------------------------------------------------
# Simulator baselines
# ---------------------------------------------------------------------------

def test_deepspeed_slowdown_matches_paper_shape():
    """When transfer >> compute (paper Fig. 2: 13.8x at decode), DeepSpeed's
    latency approaches L*t_transfer, i.e. t_t/t_c-fold slowdown."""
    tc, tt, n = 1e-3, 13.8e-3, 32
    sched = schedule_deepspeed([tc] * n, tt)
    sim = simulate_iteration(sched)
    assert sim["latency_s"] >= n * tt
    slowdown = sim["latency_s"] / (n * tc)
    assert 12.0 <= slowdown <= 16.0


def test_selectn_meets_slo_where_deepspeed_fails():
    tc, tt, n = 1e-3, 6e-3, 32
    times = LayerTimes(tc, tt, n, 1 << 20)
    slo = 1.25 * times.t_iter_no_offload_s
    ds = simulate_iteration(schedule_deepspeed([tc] * n, tt))["latency_s"]
    assert ds > slo
    i = min_feasible_interval(times, slo)
    sn = simulate_iteration(schedule_for_interval([tc] * n, i, tt))["latency_s"]
    assert sn <= slo
    assert OffloadPlan(n, i).num_offloaded > 0


def test_contention_oversubscription_stretches_transfers():
    tc, tt, n = 1e-3, 4e-3, 16
    s1 = schedule_for_interval([tc] * n, 4, tt)
    s2 = schedule_for_interval([tc] * n, 4, tt)
    alone = simulate_iteration(s1)["latency_s"]
    rate = OffloadPlan(n, 4).link_bytes_per_iter(100) / alone
    shared = simulate_shared_bus([s1, s2], total_bw=1.2 * rate,
                                 demands=[rate, rate])
    assert all(r["latency_s"] > alone for r in shared)


# ---------------------------------------------------------------------------
# Record
# ---------------------------------------------------------------------------

def test_record_roundtrip_and_conservative_lookup():
    rec = PerformanceRecord("m", "a10", "decode", batches=[4, 8, 16],
                            seqs=[128, 256])
    rec.set(0.050, 4, 128, 5)
    rec.set(0.050, 8, 128, 4)
    rec.set(0.050, 16, 128, 3)
    rec.set(0.050, 4, 256, 4)
    rec.set(0.050, 8, 256, 3)
    rec.set(0.050, 16, 256, 2)
    rec2 = PerformanceRecord.from_json(rec.to_json())
    assert rec2.lookup(0.050, 8, 256) == 3
    # batch 12 rounds DOWN to 8, seq 300 rounds DOWN to 256 (conservative)
    assert rec2.lookup(0.050, 12, 300) == 3
    # SLO 49ms rounds DOWN to 48ms bucket -> absent -> NO_OFFLOAD
    assert rec2.lookup(0.049, 8, 256) == NO_OFFLOAD
    # tighter-than-recorded SLO: NO_OFFLOAD
    assert rec2.lookup(0.001, 8, 256) == NO_OFFLOAD
    assert "inf" not in rec2.render(0.050).split("\n")[2]


@given(b=st.integers(1, 64), s=st.integers(1, 1024))
@settings(max_examples=100, deadline=None)
def test_record_lookup_never_crashes(b, s):
    rec = PerformanceRecord("m", "a10", "decode", batches=[4, 8], seqs=[128])
    rec.set(0.050, 4, 128, 5)
    rec.set(0.050, 8, 128, 3)
    assert rec.lookup(0.050, b, s) >= 1


# ---------------------------------------------------------------------------
# Coordinator
# ---------------------------------------------------------------------------

def _inst(name, min_i, max_i=NO_OFFLOAD, t_iter=0.050, nbytes=400 << 20,
          n=32, idle=False):
    return InstanceState(name=name, num_units=n, unit_bytes=nbytes,
                         t_iter_s=t_iter, min_interval=min_i,
                         max_interval=max_i, idle=idle)


def test_coordinator_inadmissible():
    res = coordinate([_inst("a", min_i=8, max_i=4)], link_bw=1e12)
    assert not res.ok and "upper-level" in res.reason


def test_coordinator_respects_bandwidth_and_maximizes_host():
    a, b = _inst("a", 2), _inst("b", 2)
    wide = coordinate([a, b], link_bw=1e14)
    assert wide.ok
    # unconstrained: both take min interval (max host usage)
    assert wide.intervals == {"a": 2, "b": 2}
    narrow = coordinate([a, b], link_bw=wide.total_link_rate / 2)
    assert narrow.ok
    assert narrow.total_link_rate <= wide.total_link_rate / 2 + 1e-6
    assert narrow.total_host_bytes <= wide.total_host_bytes


def test_coordinator_idle_peer_gets_full_bandwidth():
    a = _inst("a", 2)
    idle = _inst("b", 1, idle=True)
    res = coordinate([a, idle], link_bw=a.link_rate(2) * 1.01)
    assert res.ok and res.intervals["a"] == 2


@given(mins=st.lists(st.integers(1, 16), min_size=2, max_size=4),
       bw_scale=st.floats(0.2, 4.0))
@settings(max_examples=60, deadline=None)
def test_coordinator_greedy_feasible(mins, bw_scale):
    insts = [_inst(f"i{k}", m) for k, m in enumerate(mins)]
    full = sum(i.link_rate(i.min_interval) for i in insts)
    res = coordinate(insts, link_bw=full * bw_scale)
    if res.ok:
        assert res.total_link_rate <= full * bw_scale * (1 + 1e-9)
        for inst in insts:
            assert res.intervals[inst.name] >= inst.min_interval


def test_max_interval_for_memory():
    # 32 units x 100 bytes; budget 1500 bytes -> resident+2buf <= 15 units
    got = max_interval_for_memory(32, 100, 1500)
    assert OffloadPlan(32, got).device_bytes(100) <= 1500
    assert OffloadPlan(32, got + 1).device_bytes(100) > 1500
    assert max_interval_for_memory(4, 100, 1e9) == NO_OFFLOAD


# ---------------------------------------------------------------------------
# FlexGen baseline
# ---------------------------------------------------------------------------

def test_flexgen_underoffloads_vs_selectn():
    """Observations #2/#3: worst-case bandwidth assumption + peak-FLOPs
    estimation make FlexGen offload less than Select-N at the same SLO.
    Setting matches the paper's §5.3: SLO = the no-offload iteration latency
    (zero slack), decode phase, two instances on the bus."""
    tc_real = 2e-3
    layer_flops = A10.peak_flops * tc_real * 0.35   # real kernels run at 35% peak
    times = LayerTimes(tc_real, 4e-3, 32, 400 << 20, t_rest_s=0.0)
    slo = times.t_iter_no_offload_s                 # zero slack

    fg = flexgen_decision(times, A10, slo, layer_flops, n_bus_sharers=2)
    sn_interval = min_feasible_interval(times, slo)
    sn_host = OffloadPlan(32, sn_interval).host_bytes(times.layer_bytes)
    fg_host = flexgen_host_bytes(times, fg)
    assert fg_host < sn_host
    # Fig. 4 / Observation #2: the peak-FLOPs layer-time estimate is well
    # below the real layer time.
    assert A10.peak_exec_time(layer_flops) < tc_real
    assert fg.est_iter_s <= slo * (1 + 1e-9)
    assert flexgen_equivalent_interval(times, fg) >= sn_interval
