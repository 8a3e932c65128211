"""The engine's wall-clock spans and transfer counters (``engine.trace``).

Each ``step()`` runs its phases in ``engine.<phase>`` spans; the record of
the step carries their self seconds (``host_s``), its wall start and end,
the bytes that really crossed between host and device, and the programs
lowered during it. Requests carry wall stamps of submission, admission and
first token beside their modeled ones.
"""
import jax
import numpy as np
import pytest

from repro.serving.fleet import Fleet
from repro.serving.request import Request

from _engine_builders import mk_reduced_engine


def _reqs(n, prompt_len=8, new=4, rid0=0):
    rng = np.random.default_rng(rid0)
    return [Request(rid=rid0 + i,
                    prompt=rng.integers(0, 100, prompt_len).astype(np.int32),
                    max_new_tokens=new, ttft_slo_s=1.0, tpot_slo_s=1.0)
            for i in range(n)]


def _serve(eng, reqs, max_steps=200):
    for r in reqs:
        eng.submit(r)
    for _ in range(max_steps):
        if not (eng.scheduler.has_work() or eng.active.any()):
            return
        eng.step()
    raise AssertionError("engine did not drain")


def _logits_nbytes(eng) -> int:
    b, nb = eng.ecfg.max_batch, eng.nb
    i32 = jax.ShapeDtypeStruct((b,), np.int32)
    logits, _ = jax.eval_shape(
        eng._jit_decode[eng.interval], eng._w, i32, i32, eng.pool,
        jax.ShapeDtypeStruct((b, nb), np.int32), i32, i32, i32)
    return logits.size * logits.dtype.itemsize


def test_decode_step_counts_the_bytes_that_crossed():
    """A decode-only step moves its six input vectors host->device and its
    logits device->host, and nothing else (no host KV tier, no chunk)."""
    eng, _ = mk_reduced_engine(max_batch=4, max_seq=48, hbm_gb=0.05)
    for r in _reqs(2, new=6):
        eng.submit(r)
    eng.step()                          # admits, prefills, first decode
    eng.step()
    rec = eng.trace.iterations[-1]
    assert rec.decode_batch == 2 and rec.n_chunks == 0 and not rec.admitted
    b, nb = eng.ecfg.max_batch, eng.nb
    # tokens, pos, context lens, write frames, write offsets: [b] int32;
    # block tables [b, nb] int32
    assert rec.h2d_bytes == 4 * (5 * b + b * nb)
    assert rec.d2h_bytes == _logits_nbytes(eng)
    assert set(rec.host_s) >= {"step", "interval", "plan", "apply",
                               "inputs", "decode.dispatch", "decode.wait",
                               "sample", "record"}
    assert rec.wall_t0_s < rec.wall_t1_s
    assert sum(rec.host_s.values()) == pytest.approx(
        rec.wall_t1_s - rec.wall_t0_s, rel=1e-9)


def test_streamed_host_pages_count_their_page_bytes():
    """Host-tier pages streamed through the slab cross once per step, each
    a whole page plus its int32 frame id; a token written into a streamed
    page comes back as a whole page, its frame id crossing the other
    way."""
    eng, _ = mk_reduced_engine(max_batch=4, max_seq=48,
                               extra_device_pages=2, host_pages=16)
    _serve(eng, _reqs(3, prompt_len=20, new=6))
    streamed = [r for r in eng.trace.iterations
                if r.streamed_bytes and not r.admitted and not r.n_chunks
                and not r.promoted_bytes and not r.kv_out_bytes]
    assert streamed, "the host tier never streamed"
    page = int(np.prod(eng.page_shape)) * 2
    assert page == eng.kv.page_bytes
    b, nb = eng.ecfg.max_batch, eng.nb
    logits = _logits_nbytes(eng)
    for rec in streamed:
        n = int(rec.streamed_bytes) // eng.kv.page_bytes
        n_wb, rest = divmod(rec.d2h_bytes - logits, page)
        assert rest == 0 and n_wb >= 0
        assert rec.h2d_bytes == (4 * (5 * b + b * nb) + n * (page + 4)
                                 + 4 * n_wb)
    assert eng.trace.audit().ok


def test_compiles_counts_a_forced_recompile_on_its_step():
    eng, _ = mk_reduced_engine(max_batch=4, max_seq=48, hbm_gb=0.05)
    for r in _reqs(1, new=8):
        eng.submit(r)
    eng.step()
    assert eng.trace.iterations[-1].compiles > 0      # first programs
    eng.step()
    assert eng.trace.iterations[-1].compiles == 0     # all cached
    # a fresh jit of the decode step lowers again, on the step that runs it
    eng._runtime.clear()
    eng._jit_decode.clear()
    eng._jit_chunk.clear()
    eng.step()
    assert eng.trace.iterations[-1].compiles >= 1
    eng.step()
    assert eng.trace.iterations[-1].compiles == 0


@pytest.mark.parametrize("chunk", [0, 16], ids=["one_shot", "chunked"])
def test_request_wall_stamps_are_ordered(chunk):
    eng, _ = mk_reduced_engine(max_batch=2, max_seq=64, hbm_gb=0.05,
                               prefill_chunk_tokens=chunk)
    reqs = _reqs(4, prompt_len=40, new=3)
    _serve(eng, reqs)
    assert len(eng.finished) == 4
    for r in reqs:
        assert r.submitted_wall_s <= r.admitted_wall_s \
            <= r.first_token_wall_s
    iters = eng.trace.iterations
    assert all(a.wall_t1_s <= b.wall_t0_s for a, b in zip(iters, iters[1:]))
    if chunk:
        chunk_steps = [r for r in iters if r.n_chunks]
        assert chunk_steps and all("chunk" in r.host_s
                                   and "chunk.dispatch" in r.host_s
                                   and "chunk.wait" in r.host_s
                                   for r in chunk_steps)
    assert eng.trace.audit().ok


def test_disaggregated_fleet_audits_clean_with_wall_phases():
    """Fleet engines step one at a time: each record carries its phases,
    the audit (I13 included) stays clean, and no two steps of the fleet
    overlap on the wall clock."""
    def mk(name, role):
        eng, _ = mk_reduced_engine(name=name, max_batch=4, max_seq=64,
                                   extra_device_pages=8, host_pages=40,
                                   preemption=True, role=role)
        return eng
    fleet = Fleet([mk("p0", "prefill"), mk("d0", "decode")],
                  policy="affinity")
    reqs = _reqs(5, prompt_len=20, new=4)
    out = fleet.run(reqs, max_iters=5_000)
    assert out["handoffs"] > 0
    ok, violations = fleet.audit()
    assert ok, violations
    recs = sorted((r for e in fleet.engines for r in e.trace.iterations),
                  key=lambda r: r.wall_t0_s)
    assert all(r.host_s and "step" in r.host_s for r in recs)
    assert all(a.wall_t1_s <= b.wall_t0_s for a, b in zip(recs, recs[1:]))
    for r in reqs:
        assert r.submitted_wall_s <= r.admitted_wall_s \
            <= r.first_token_wall_s
