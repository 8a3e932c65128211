"""Property tests for the trace-driven workload generator (no engine/jit).

The generator's contract with ``ServingEngine.run``: a flat, arrival-sorted
request list, deterministic from the config alone, whose shapes (growing
per-session context, mixed SLO classes, long-tail turns, Poisson/diurnal
gaps) the sustained-load harness relies on."""
import dataclasses

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.data.workload import SLOClass, WorkloadConfig, generate_workload
from repro.serving.kv_offload import prefix_page_keys

CFG = WorkloadConfig(seed=7, rate_per_s=10.0, mean_rounds=3.0,
                     mean_think_s=0.05, system_prompt_len=8,
                     median_turn_len=12, max_prompt_len=96,
                     mean_output_len=8.0, max_output_len=32)


def test_deterministic_from_config():
    a = generate_workload(CFG, 200)
    b = generate_workload(CFG, 200)
    assert len(a) == len(b) == 200
    for ra, rb in zip(a, b):
        assert ra.arrival_s == rb.arrival_s
        assert ra.max_new_tokens == rb.max_new_tokens
        assert ra.tpot_slo_s == rb.tpot_slo_s
        np.testing.assert_array_equal(ra.prompt, rb.prompt)
    c = generate_workload(dataclasses.replace(CFG, seed=8), 200)
    assert any(ra.arrival_s != rc.arrival_s for ra, rc in zip(a, c))


def test_sorted_arrivals_and_rids_follow_arrival_order():
    reqs = generate_workload(CFG, 300)
    arr = [r.arrival_s for r in reqs]
    assert arr == sorted(arr)
    assert all(t > 0 for t in arr)
    assert [r.rid for r in reqs] == list(range(300))


def test_limits_respected():
    reqs = generate_workload(CFG, 300)
    for r in reqs:
        assert 1 <= r.prompt_len <= CFG.max_prompt_len
        assert 1 <= r.max_new_tokens <= CFG.max_output_len
        assert r.prompt.dtype == np.int32
        assert r.prompt.min() >= 0 and r.prompt.max() < CFG.vocab_size


def test_poisson_rate_roughly_matches():
    # 1000 requests at ~3 rounds/session and 10 sessions/s: the request
    # span is governed by session starts; just bound the mean request rate
    # loosely around rate * mean_rounds
    reqs = generate_workload(dataclasses.replace(CFG, mean_think_s=0.01),
                             1000)
    span = reqs[-1].arrival_s - reqs[0].arrival_s
    rate = len(reqs) / span
    assert 0.3 * CFG.rate_per_s * CFG.mean_rounds < rate \
        < 3.0 * CFG.rate_per_s * CFG.mean_rounds


def test_diurnal_process_differs_and_stays_sorted():
    base = dataclasses.replace(CFG, process="diurnal",
                               diurnal_amplitude=0.8, diurnal_period_s=5.0)
    reqs = generate_workload(base, 300)
    arr = [r.arrival_s for r in reqs]
    assert arr == sorted(arr)
    pois = [r.arrival_s for r in generate_workload(CFG, 300)]
    assert arr != pois


def test_unknown_process_rejected():
    with pytest.raises(ValueError, match="unknown arrival process"):
        generate_workload(dataclasses.replace(CFG, process="weekly"), 10)


def test_sessions_share_system_prompt_and_grow_history():
    # every request's prompt starts with the shared system prompt (until
    # clipping), and multi-round sessions contain strict prefix extensions
    # of earlier rounds — the structure prefix dedup content-addresses
    reqs = generate_workload(CFG, 400)
    sys_tok = reqs[0].prompt[:CFG.system_prompt_len]
    full = [r for r in reqs if r.prompt_len < CFG.max_prompt_len]
    assert len(full) > 10
    for r in full[:50]:
        np.testing.assert_array_equal(r.prompt[:CFG.system_prompt_len],
                                      sys_tok)
    # growing-history rounds: some request's prompt must be a strict prefix
    # of another's (an earlier round of the same session)
    by_len = sorted(full, key=lambda r: r.prompt_len)
    found = 0
    for i, small in enumerate(by_len):
        for big in by_len[i + 1:]:
            if big.prompt_len > small.prompt_len and np.array_equal(
                    big.prompt[:small.prompt_len], small.prompt):
                found += 1
                break
        if found >= 3:
            break
    assert found >= 3, "no growing-session prefix structure found"


def test_slo_classes_mix_with_configured_weights():
    classes = (SLOClass("a", 0.1, 0.01, weight=0.7),
               SLOClass("b", 9.0, 0.9, weight=0.3))
    reqs = generate_workload(
        dataclasses.replace(CFG, slo_classes=classes), 600)
    frac_a = np.mean([r.tpot_slo_s == 0.01 for r in reqs])
    assert 0.5 < frac_a < 0.9
    assert {r.ttft_slo_s for r in reqs} <= {0.1, 9.0}


def test_tenants_default_is_bitwise_compatible():
    # tenants=1 must reproduce the pre-tenant trace bitwise (no extra RNG
    # draws on the default path) and stamp tenant 0 everywhere
    a = generate_workload(CFG, 150)
    b = generate_workload(dataclasses.replace(CFG, tenants=1), 150)
    for ra, rb in zip(a, b):
        assert ra.arrival_s == rb.arrival_s
        np.testing.assert_array_equal(ra.prompt, rb.prompt)
        assert ra.tenant == rb.tenant == 0


@settings(max_examples=20, deadline=None)
@given(tenants=st.integers(min_value=2, max_value=5),
       seed=st.integers(min_value=0, max_value=1000))
def test_same_tenant_prompts_share_leading_prefix_page_keys(tenants, seed):
    # the router's affinity signal: every request of one tenant opens with
    # that tenant's system prompt, so the leading system pages hash to
    # IDENTICAL prefix_page_keys across same-tenant sessions, and distinct
    # tenants diverge from the very first page
    page = 8
    cfg = dataclasses.replace(CFG, seed=seed, tenants=tenants,
                              system_prompt_len=2 * page)
    reqs = [r for r in generate_workload(cfg, 120)
            if r.prompt_len < cfg.max_prompt_len]   # unclipped prompts only
    sys_pages = cfg.system_prompt_len // page
    lead: dict[int, list] = {}
    for r in reqs:
        keys = prefix_page_keys("scope", r.prompt, page)[:sys_pages]
        if r.tenant in lead:
            assert keys == lead[r.tenant], \
                f"tenant {r.tenant} prompts disagree on system pages"
        else:
            lead[r.tenant] = keys
    seen = list(lead.values())
    for i, ka in enumerate(seen):
        for kb in seen[i + 1:]:
            assert ka[0] != kb[0], "distinct tenants share page-0 key"


def test_tenant_field_distribution_covers_all_tenants():
    cfg = dataclasses.replace(CFG, tenants=3)
    reqs = generate_workload(cfg, 400)
    assert {r.tenant for r in reqs} == {0, 1, 2}


def test_single_class_and_single_round_degenerate_cases():
    cfg = dataclasses.replace(
        CFG, mean_rounds=1.0, slo_classes=(SLOClass("only", 1.0, 0.1),))
    reqs = generate_workload(cfg, 50)
    assert len(reqs) == 50
    assert all(r.tpot_slo_s == 0.1 for r in reqs)
