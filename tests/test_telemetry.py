"""Telemetry-plane unit tests (fast tier — no engine, no jit).

The trace recorder, the Chrome trace-event exporter and the conservation
auditor are exercised over hand-built traces, so every auditor invariant is
pinned both ways: a self-consistent synthetic trace must audit clean, and a
deliberately corrupted copy (bytes over-charged, occupancy over capacity,
dt above the certified bound, TTFT above its admission stamp) must be
detected with a violation naming the broken quantity.
"""
import copy
import json

import pytest

from repro.serving.telemetry import (TRACE_SCHEMA, IterationRecord,
                                     SlotGauge, TraceRecorder, audit_trace,
                                     summarize_latency)

PB = 128                              # page bytes for the synthetic trace
BW = 1e8                              # PCIe link, bytes/s


# ------------------------------------------------------- summarize_latency --
def test_summarize_latency_quantiles_and_none_filtering():
    xs = [0.001 * k for k in range(1, 101)]            # 1ms .. 100ms
    s = summarize_latency(xs + [None, None])
    assert s["n"] == 100
    assert s["max_s"] == pytest.approx(0.100)
    assert s["p50_s"] == pytest.approx(0.0505)         # np.quantile, linear
    assert s["p99_s"] == pytest.approx(0.09901)
    assert s["mean_s"] == pytest.approx(sum(xs) / 100)


def test_summarize_latency_empty():
    assert summarize_latency([]) == {"n": 0, "mean_s": 0.0, "p50_s": 0.0,
                                     "p99_s": 0.0, "max_s": 0.0}
    assert summarize_latency([None])["n"] == 0


# -------------------------------------------------------- synthetic trace --
def _occupancy(dev_used=4, host_used=2, disk_used=1):
    return {"device": {"used_pages": dev_used, "total_pages": 8,
                       "cache_pages": 0},
            "host": {"used_pages": host_used, "total_pages": 4,
                     "cache_pages": min(1, host_used)},
            "disk": {"used_pages": disk_used, "total_pages": 16,
                     "cache_pages": 0}}


def mk_recorder() -> TraceRecorder:
    """One admit -> one-shot prefill -> one decode iteration (streams 3
    pages, promotes 1, drains 2 pages of promotion debt and 1 of write-back
    debt, stages 1 page off NVMe) -> finish -> one idle drain iteration.
    Every derived quantity is computed from the same constants the auditor
    recomputes, so the trace is exactly conservation-consistent."""
    rec = TraceRecorder("synthetic", max_batch=2, page_bytes=PB)
    ttft = 1.5e-6
    rec.event("admit", 0, 0.0, slot=0, chunked=False, certified_ttft_s=2e-6)
    rec.event("prefill", 0, 0.0, slot=0, dur_s=ttft)

    streamed, promoted, pend_in, pend_out = 3 * PB, 1 * PB, 2 * PB, 1 * PB
    kv_in = streamed + promoted + pend_in                    # 768
    kv_out = pend_out                                        # 128
    compute, kv_in_s = 1e-6, kv_in / BW
    pcie = compute + kv_in_s
    disk_s = 2e-6
    dt = max(pcie, disk_s)                                   # pcie wins
    t_end = 0.0 + ttft + dt
    rec.add_iteration(IterationRecord(
        index=0, t_start_s=0.0, t_end_s=t_end, dt_s=dt, interval=10**9,
        decode_batch=1, admitted=[0], finished=[0],
        kv_in_bytes=kv_in, kv_out_bytes=kv_out, streamed_bytes=streamed,
        promoted_bytes=promoted, pending_in_bytes=pend_in,
        pending_out_bytes=pend_out,
        certified_kv_in_bytes=kv_in, certified_kv_out_bytes=kv_out,
        disk_in_bytes=1 * PB, disk_in_pages=1,
        staged_issued_pages=4, staged_completed_pages=3,
        compute_s=compute, kv_in_s=kv_in_s, kv_out_s=kv_out / BW,
        pcie_s=pcie, disk_s=disk_s, model_dt_s=dt,
        link_bw_bytes_s=BW, certified_dt_s=dt * 1.25,
        occupancy=_occupancy(),
        gauges=[SlotGauge(rid=0, slot=0, tpot_slo_s=1e-4,
                          headroom_s=1e-4 - dt)]))
    rec.event("finish", 0, t_end, slot=0)
    rec.add_iteration(IterationRecord(
        index=1, t_start_s=t_end, t_end_s=t_end, dt_s=0.0, interval=10**9,
        decode_batch=0, staged_completed_pages=1,   # drained at boundary
        occupancy=_occupancy(0, 0, 0)))

    rec._footer_fn = lambda: {
        "page_bytes": PB, "clock_s": t_end,
        "disk_in_pages_total": 1, "pending_disk_in_pages": 0,
        "disk_out_pages_total": 0, "pending_disk_out_pages": 0,
        "noted_in_pages_total": 2, "pending_in_pages": 0,
        "noted_out_pages_total": 1, "pending_out_pages": 0,
        "promoted_pages_total": 1,
        "staged_issued_pages_total": 4, "staged_completed_pages_total": 4,
        "staged_inflight_pages": 0, "disk_direct_pages_total": 0,
        "cow_in_bytes_total": 0.0, "cow_out_bytes_total": 0.0,
        "n_finished": 1, "n_rejected": 0, "n_active": 0, "n_parked": 0}
    return rec


def test_synthetic_trace_audits_clean():
    rec = mk_recorder()
    report = rec.audit()
    assert report.ok, report.violations
    assert report.checks > 20
    assert report.totals["pcie_in_bytes"] == 6 * PB
    assert rec.totals()["disk_in_bytes"] == PB


def test_trace_dict_json_roundtrip_audits_identically():
    rec = mk_recorder()
    d = rec.to_dict()
    assert d["schema"] == TRACE_SCHEMA
    rt = json.loads(json.dumps(d))
    report = audit_trace(rt)
    assert report.ok, report.violations
    assert report.checks == rec.audit().checks


# ----------------------------------------------------- wall-clock spans --
class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _bare_record(index=0) -> IterationRecord:
    return IterationRecord(index=index, t_start_s=0.0, t_end_s=0.0,
                           dt_s=0.0, interval=10**9, decode_batch=0)


def test_span_nesting_and_self_time():
    """Self time is a span's duration less what its children cover; the
    step's record gets every phase, its wall start/end and its bytes when
    the outermost span closes, and a repeated name accumulates."""
    clk = FakeClock()
    rec = TraceRecorder("spans", max_batch=1, page_bytes=PB, clock=clk)
    rec.count("h2d_bytes", 8)               # between steps: rides the next
    with rec.span("step", index=0):
        clk.t = 1.0
        with rec.span("plan"):
            clk.t = 3.0
        with rec.span("chunk", rid=5, start=0, end=16):
            clk.t = 4.0
            with rec.span("chunk.dispatch"):
                clk.t = 5.0
            with rec.span("chunk.wait"):
                rec.count("d2h_bytes", 40)
                clk.t = 7.0
            clk.t = 8.0
        with rec.span("record"):
            rec.count("h2d_bytes", 96)
            rec.add_iteration(_bare_record(0))
            clk.t = 9.0
        with rec.span("record"):
            clk.t = 9.5
        clk.t = 10.0
    r = rec.iterations[0]
    assert r.host_s == {"plan": 2.0, "chunk.dispatch": 1.0,
                        "chunk.wait": 2.0, "chunk": 2.0, "record": 1.5,
                        "step": 1.5}
    assert (r.wall_t0_s, r.wall_t1_s) == (0.0, 10.0)
    assert sum(r.host_s.values()) == r.wall_t1_s - r.wall_t0_s
    assert (r.h2d_bytes, r.d2h_bytes) == (104, 40)
    # the next step starts from nothing
    clk.t = 12.0
    with rec.span("step", index=1):
        clk.t = 12.5
        rec.add_iteration(_bare_record(1))
    r1 = rec.iterations[1]
    assert r1.host_s == {"step": 0.5}
    assert (r1.wall_t0_s, r1.wall_t1_s, r1.h2d_bytes) == (12.0, 12.5, 0)
    # a record added outside any span is stamped at once
    clk.t = 13.0
    rec.add_iteration(_bare_record(2))
    r2 = rec.iterations[2]
    assert (r2.wall_t0_s, r2.wall_t1_s, r2.host_s) == (13.0, 13.0, {})
    report = rec.audit()
    assert report.ok, report.violations


def test_audit_detects_wall_phases_that_do_not_tile_the_step():
    def over(tr):
        tr["iterations"][0].update(wall_t0_s=10.0, wall_t1_s=10.5,
                                   host_s={"step": 0.25, "plan": 0.125})
    viol = _corrupt(over)
    assert any("span self times" in v for v in viol)


def test_audit_detects_overlapping_wall_steps():
    def over(tr):
        tr["iterations"][0].update(wall_t0_s=10.0, wall_t1_s=10.5,
                                   host_s={"step": 0.5})
        tr["iterations"][1].update(wall_t0_s=10.25, wall_t1_s=10.75,
                                   host_s={"step": 0.5})
    viol = _corrupt(over)
    assert any("wall start" in v for v in viol)


# ------------------------------------------------- corruption -> detection --
def _corrupt(mutate) -> list:
    trace = copy.deepcopy(mk_recorder().to_dict())
    mutate(trace)
    report = audit_trace(trace)
    assert not report.ok
    return report.violations


def test_audit_detects_overcharged_link_bytes():
    def over(tr):                     # one page charged but never moved
        tr["iterations"][0]["kv_in_bytes"] += PB
    viol = _corrupt(over)
    assert any("kv_in" in v for v in viol)


def test_audit_detects_occupancy_over_capacity():
    def over(tr):
        tr["iterations"][0]["occupancy"]["device"]["used_pages"] = 9
    viol = _corrupt(over)
    assert any("occupancy" in v and "device" in v for v in viol)


def test_audit_detects_dt_above_certified_bound():
    def over(tr):                     # scheduler certified less than ran
        r = tr["iterations"][0]
        r["certified_dt_s"] = r["dt_s"] / 2
    viol = _corrupt(over)
    assert any("certified" in v for v in viol)


def test_audit_detects_uncertified_bytes_mismatch():
    def over(tr):                     # claims slack it never measured
        r = tr["iterations"][0]
        r["uncertified_in_bytes"] = 4 * PB
    viol = _corrupt(over)
    assert any("uncertified_in" in v for v in viol)


def test_audit_detects_ttft_above_admission_stamp():
    def over(tr):
        for e in tr["events"]:
            if e["kind"] == "admit":
                e["detail"]["certified_ttft_s"] = 1e-7    # < 1.5us observed
    viol = _corrupt(over)
    assert any("TTFT" in v for v in viol)


def test_audit_detects_broken_clock_tiling():
    def over(tr):
        tr["iterations"][1]["t_start_s"] += 1e-6
        tr["iterations"][1]["t_end_s"] += 1e-6
    viol = _corrupt(over)
    assert any("t_start" in v for v in viol)


def test_audit_detects_footer_drain_mismatch():
    def over(tr):                     # allocator says 2 pages staged in
        tr["footer"]["disk_in_pages_total"] = 2
    viol = _corrupt(over)
    assert any("disk_in" in v for v in viol)


def test_audit_detects_double_charged_staged_page():
    def over(tr):                     # a page counted complete twice
        tr["iterations"][0]["staged_completed_pages"] += 2
    viol = _corrupt(over)
    assert any("exceed plane" in v for v in viol)


def test_audit_detects_never_charged_staged_page():
    # variant A: the plane's completion counter loses a page that is not
    # in flight either -> issued != completed + inflight
    def lost(tr):
        tr["footer"]["staged_completed_pages_total"] -= 1
    viol = _corrupt(lost)
    assert any("in flight" in v for v in viol)

    # variant B: an iteration forgets pages it handed to the plane
    def forgot(tr):
        tr["iterations"][0]["staged_issued_pages"] = 0
    viol = _corrupt(forgot)
    assert any("issue counter" in v for v in viol)


def test_audit_detects_async_reordered_completion():
    def reorder(tr):                  # completion recorded before its issue
        tr["iterations"][0]["staged_issued_pages"] = 0
        tr["iterations"][1]["staged_issued_pages"] = 4
    viol = _corrupt(reorder)
    assert any("ahead of its issue" in v for v in viol)


def test_audit_detects_direct_pages_over_disk_total():
    def over(tr):                     # more direct reads than NVMe reads
        tr["footer"]["disk_direct_pages_total"] = 5
    viol = _corrupt(over)
    assert any("direct disk reads" in v for v in viol)


# ----------------------------------------------------------- Perfetto export --
def test_perfetto_export_structure():
    rec = mk_recorder()
    out = rec.to_perfetto()
    ev = out["traceEvents"]
    json.loads(json.dumps(out))                    # serializable as-is
    assert all({"ph", "pid", "tid", "name"} <= set(e) for e in ev)
    names = {e["args"]["name"] for e in ev if e["name"] == "thread_name"}
    assert {"slot 0", "slot 1", "pcie copy stream", "nvme channel",
            "scheduler", "parked"} <= names
    # modeled clock exported in microseconds
    decode = [e for e in ev if e["ph"] == "X"
              and e["name"].startswith("decode")]
    assert len(decode) == 1
    it0 = rec.iterations[0]
    assert decode[0]["ts"] == pytest.approx(
        (it0.t_end_s - it0.dt_s) * 1e6)
    assert decode[0]["dur"] == pytest.approx(it0.dt_s * 1e6)
    # copy-stream lanes carry the byte-labelled slices
    pcie = [e for e in ev if e["tid"] == TraceRecorder._PCIE_TID
            and e["ph"] == "X"]
    assert any("kv_in 768B" == e["name"] for e in pcie)
    # occupancy counters per tier
    counters = {e["name"] for e in ev if e["ph"] == "C"}
    assert {"device_pages", "host_pages", "disk_pages"} <= counters
    # admit/finish appear as instants
    instants = {e["name"] for e in ev if e["ph"] == "i"}
    assert {"admit r0", "finish r0"} <= instants


def test_perfetto_parked_lane_spans_park_to_resume():
    rec = TraceRecorder("parkspan", max_batch=1, page_bytes=PB)
    rec.event("park", 7, 1e-6, slot=0)
    rec.event("resume", 7, 5e-6, slot=0)
    spans = [e for e in rec.to_perfetto()["traceEvents"]
             if e["tid"] == TraceRecorder._PARKED_TID and e["ph"] == "X"]
    assert len(spans) == 1
    assert spans[0]["ts"] == pytest.approx(1.0)    # us
    assert spans[0]["dur"] == pytest.approx(4.0)
