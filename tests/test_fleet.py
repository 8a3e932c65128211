"""Multi-instance fleet: KV-affinity routing, cross-instance preemption,
and the bitwise-composability contract (placement and migration change
timing, never numbers)."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.data.workload import SLOClass, WorkloadConfig, generate_workload
from repro.kernels import ops
from repro.serving.fleet import Fleet, Router
from repro.serving.request import Request, State

from _engine_builders import mk_reduced_engine

# compile-heavy (full JAX jit of models/kernels): excluded from the fast CI
# tier, run in the nightly full suite
pytestmark = pytest.mark.slow

MAX_SEQ, PAGE = 96, 16


def _mk_instance(name, scale=1):
    """One fleet instance; ``scale=2`` builds the consolidated big-instance
    baseline with the pooled capacity of a 2-instance fleet."""
    eng, _ = mk_reduced_engine(
        name=name, max_batch=scale * 4, max_seq=MAX_SEQ, page_size=PAGE,
        extra_device_pages=scale * 6, host_pages=scale * 40,
        prefix_dedup=True, preemption=True,
        host_prefix_cache_pages=scale * 10)
    return eng


def _tenant_reqs(n=20, seed=7):
    wcfg = WorkloadConfig(
        seed=seed, process="poisson", rate_per_s=3000.0,
        mean_rounds=2.0, mean_think_s=0.0005, tenants=2,
        system_prompt_len=48, median_turn_len=12, turn_len_sigma=0.3,
        max_prompt_len=80, mean_output_len=6.0, max_output_len=10,
        vocab_size=128,
        slo_classes=(SLOClass("standard", 4.0, 0.05, weight=1.0),))
    return generate_workload(wcfg, n)


def _clone(reqs):
    return [Request(rid=r.rid, prompt=r.prompt.copy(),
                    max_new_tokens=r.max_new_tokens,
                    ttft_slo_s=r.ttft_slo_s, tpot_slo_s=r.tpot_slo_s,
                    arrival_s=r.arrival_s, tenant=r.tenant) for r in reqs]


def _gen_tokens(engines):
    return {r.rid: tuple(r.generated) for e in engines for r in e.finished}


def test_router_rejects_unknown_policy():
    with pytest.raises(ValueError):
        Router("fastest_first")


def test_fleet_bitwise_vs_big_instance_and_round_robin():
    """The fleet differential: the same workload served by a 2-instance
    affinity fleet, a round-robin fleet, and one consolidated big instance
    produces bitwise-identical greedy tokens per request; the affinity
    router actually routes on claimed prefix pages; every audit passes."""
    reqs = _tenant_reqs()

    aff = Fleet([_mk_instance("aff0"), _mk_instance("aff1")],
                policy="affinity")
    aff.run(_clone(reqs), max_iters=50_000)
    rr = Fleet([_mk_instance("rr0"), _mk_instance("rr1")],
               policy="round_robin")
    rr.run(_clone(reqs), max_iters=50_000)
    big = _mk_instance("big", scale=2)
    big.run(_clone(reqs), max_iters=50_000)

    t_aff, t_rr = _gen_tokens(aff.engines), _gen_tokens(rr.engines)
    t_big = _gen_tokens([big])
    assert len(t_aff) == len(t_rr) == len(t_big) == len(reqs)
    assert t_aff == t_rr == t_big

    # the affinity router saw and used real prefix hits (multi-round
    # sessions re-arrive while their earlier pages are still claimed)
    assert sum(max(d.hits) for d in aff.router.decisions) > 0
    # same-tenant sessions pile onto the instance claiming their prefix:
    # with hits present, at least one instance serves a strict majority
    # of some tenant's requests
    for fleet in (aff, rr):
        ok, violations = fleet.audit()
        assert ok, violations
    assert big.trace.audit().ok


def test_prefix_reuse_bitwise_across_unequal_lengths():
    """Shape-bucketed prefill contract: a dedup hit serves KV computed
    under a DIFFERENT prompt length, and the hitter's greedy tokens still
    match a dedup-free engine bit for bit. (Prefills bucket to one
    compiled shape, so a prefix's KV bits no longer depend on the length
    of the prompt that computed them.)"""
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, 128, 4 * PAGE).astype(np.int32)   # 4 full pages
    tails = [rng.integers(0, 128, n).astype(np.int32) for n in (2, 12)]

    def reqs():
        return [Request(rid=i, prompt=np.concatenate([prefix, t]),
                        max_new_tokens=8, ttft_slo_s=5.0, tpot_slo_s=1.0)
                for i, t in enumerate(tails)]

    tokens = {}
    for dedup in (True, False):
        eng, _ = mk_reduced_engine(
            name=f"dedup_{dedup}", max_batch=2, max_seq=MAX_SEQ,
            page_size=PAGE, extra_device_pages=16, host_pages=8,
            prefix_dedup=dedup)
        eng.run(reqs(), max_iters=2_000, submit_all=True)
        assert len(eng.finished) == 2
        tokens[dedup] = _gen_tokens([eng])
    assert tokens[True] == tokens[False]


def _manual_park(eng, req):
    """Park an ACTIVE request exactly the way _apply_preemptions does (the
    test drives the park directly so the migration moment is deterministic
    rather than load-dependent)."""
    slot = req.slot
    moves = eng.kv.park(req.rid)
    assert moves is not None
    ops.copy_pages_to_host(eng.pool, [m.src_page for m in moves],
                           eng.host_pool, [m.dst_page for m in moves])
    req.state = State.PREEMPTED
    req.preempt_count += 1
    req.parked_at_s = eng.clock_s
    eng.trace.event("park", req.rid, eng.clock_s, slot=slot)
    req.next_token = int(eng.tokens[slot])
    req.resume_pos = int(eng.pos[slot])
    req.slot = -1
    eng.active[slot] = False
    eng.slot_req[slot] = None
    eng.scheduler.preempted.append(req)


def test_cross_instance_migration_resumes_bitwise():
    """A parked request migrates to the less-loaded peer mid-decode and
    finishes there with exactly the tokens a never-migrated engine
    produces; the ticket's bytes conserve fleet-wide and both sides'
    audits stay clean."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 128, 40).astype(np.int32)

    ref_eng, _ = mk_reduced_engine(name="ref", max_seq=MAX_SEQ,
                                   page_size=PAGE, extra_device_pages=12,
                                   host_pages=20, preemption=True)
    ref = Request(rid=0, prompt=prompt.copy(), max_new_tokens=12,
                  ttft_slo_s=5.0, tpot_slo_s=1.0)
    ref_eng.run([ref], max_iters=200)
    assert len(ref.generated) == 12

    e0 = _mk_instance("m0")
    e1 = _mk_instance("m1")
    fleet = Fleet([e0, e1], policy="affinity")
    victim = Request(rid=0, prompt=prompt.copy(), max_new_tokens=12,
                     ttft_slo_s=5.0, tpot_slo_s=1.0)
    e0.submit(victim)
    for _ in range(5):            # prefill + a few decode steps on e0
        e0.step()
    assert victim.state == State.DECODING and len(victim.generated) >= 3
    _manual_park(e0, victim)
    # a waiter keeps e0 "overloaded" (parked AND queued) so the fleet's
    # migration policy fires; e1 is idle and has host room
    waiter = Request(rid=1, prompt=rng.integers(0, 128, 16).astype(np.int32),
                     max_new_tokens=4, ttft_slo_s=5.0, tpot_slo_s=1.0,
                     arrival_s=e0.clock_s)
    e0.submit(waiter)
    fleet._maybe_migrate(e0)
    assert len(fleet.migrations) == 1
    assert fleet.migrations[0]["src"] == "m0"
    assert fleet.migrations[0]["dst"] == "m1"
    assert e0.n_migrated_out == 1 and e1.n_migrated_in == 1
    assert e0.mig_out_bytes_total == e1.mig_in_bytes_total > 0

    fleet.run([], max_iters=5_000)
    assert {r.rid for r in e1.finished} == {0}     # resumed on the peer
    assert {r.rid for r in e0.finished} == {1}
    migrated = e1.finished[0]
    assert tuple(migrated.generated) == tuple(ref.generated)
    ok, violations = fleet.audit()
    assert ok, violations


def test_queued_request_reroutes_to_drained_peer():
    """Satellite regression: routes bind per iteration boundary, not once
    at arrival. A request stuck QUEUED behind a long-runner re-scores after
    every fleet step and moves to the peer that has since drained — the
    withdraw/re-place path, not a migration (no KV ever moved)."""
    rng = np.random.default_rng(9)

    def mk(name):
        eng, _ = mk_reduced_engine(name=name, max_batch=1, max_seq=MAX_SEQ,
                                   page_size=PAGE, extra_device_pages=8,
                                   host_pages=20, preemption=True)
        return eng

    e0, e1 = mk("q0"), mk("q1")
    fleet = Fleet([e0, e1], policy="affinity")
    # long-runner occupies e0's single slot; a short request drains e1
    # quickly; the third arrival queues behind the long-runner and must
    # re-bind to e1 once it empties
    long_r = Request(rid=0, prompt=rng.integers(0, 128, 24).astype(np.int32),
                     max_new_tokens=24, ttft_slo_s=5.0, tpot_slo_s=1.0)
    short = Request(rid=1, prompt=rng.integers(0, 128, 16).astype(np.int32),
                    max_new_tokens=2, ttft_slo_s=5.0, tpot_slo_s=1.0)
    waiter = Request(rid=2, prompt=rng.integers(0, 128, 16).astype(np.int32),
                     max_new_tokens=4, ttft_slo_s=5.0, tpot_slo_s=1.0)
    fleet.run([long_r, short, waiter], max_iters=5_000, submit_all=True)

    assert len(_gen_tokens(fleet.engines)) == 3
    moved = [m for m in fleet.reroutes if m["rid"] == 2]
    assert moved and moved[-1]["dst"] == "q1"
    assert any(r.rid == 2 for r in e1.finished)
    ok, violations = fleet.audit()
    assert ok, violations


def test_migration_rollback_when_peer_full():
    """A peer without host room refuses the ticket; the source re-adopts
    the request into the frames the export freed and finishes it locally,
    books conserved."""
    rng = np.random.default_rng(5)
    e0 = _mk_instance("r0")
    # peer with NO host pool: never a migration target
    e1, _ = mk_reduced_engine(name="r1", max_seq=MAX_SEQ, page_size=PAGE,
                              extra_device_pages=12, host_pages=0,
                              preemption=True)
    fleet = Fleet([e0, e1], policy="affinity")
    victim = Request(rid=0, prompt=rng.integers(0, 128, 40).astype(np.int32),
                     max_new_tokens=10, ttft_slo_s=5.0, tpot_slo_s=1.0)
    e0.submit(victim)
    for _ in range(4):
        e0.step()
    _manual_park(e0, victim)
    e0.submit(Request(rid=1,
                      prompt=rng.integers(0, 128, 16).astype(np.int32),
                      max_new_tokens=4, ttft_slo_s=5.0, tpot_slo_s=1.0,
                      arrival_s=e0.clock_s))
    fleet._maybe_migrate(e0)
    assert not fleet.migrations          # nowhere to go: stays parked here
    assert e0.n_migrated_out == 0
    fleet.run([], max_iters=5_000)
    assert {r.rid for r in e0.finished} == {0, 1}
    ok, violations = fleet.audit()
    assert ok, violations


_PLACEMENT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    from repro.launch import serve
    jax.config.update("jax_transfer_guard_device_to_device", "disallow")
    out = serve.main(["--fleet", "2", "--requests", "6",
                      "--router", "round_robin"])
    assert out["finished"] == 6, out["finished"]
    for e, d in zip(out["engines"], jax.devices()):
        held = {x for a in jax.tree.leaves(e._w) for x in a.devices()}
        assert e.device == d and held == {d}, (e.name, held)
        assert e.pool.devices() == {d}, e.name
        assert e.finished, e.name
    print("OK")
""")


def test_fleet_instances_build_and_step_on_their_own_devices():
    """A 2-device fleet from the serve entry point: each instance's weights
    and pool sit on its own device, and building and stepping them moves no
    array between devices (a transfer would stage a copy on device 0)."""
    out = subprocess.run([sys.executable, "-c", _PLACEMENT_SCRIPT],
                         capture_output=True, text=True,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
