"""Differential-testing harness: paged engine vs a frozen dense reference.

``DenseShadow`` is the slot-dense decode path the engine used before the
paged-kernel rewrite, preserved here as an executable specification: plain
``Model.prefill`` / ``Model.decode_step`` over stacked [R, B, max_seq, ...]
caches, with the engine's old batch-axis insert. It does no scheduling of its
own — ``DualEngine`` drives it in lock-step with the real engine, feeding it
the same prompts, tokens, and positions the paged engine used, and asserts
the two produce matching logits and identical greedy tokens at every
iteration (prefill and decode alike). Because the shadow never touches the
allocator, interval changes, host spills, streaming, and page reuse on the
engine side must all be invisible in the numbers — that is the property the
harness machine-checks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import spec as S
from repro.serving.engine import ServingEngine


def _batch_axis(cshape: tuple, nshape: tuple) -> int:
    """Locate the batch axis: first axis where shapes differ (the frozen
    helper from the pre-paged engine)."""
    for a, (cs, ns) in enumerate(zip(cshape, nshape)):
        if cs != ns:
            return a
    return 0


class DenseShadow:
    """Frozen slot-dense reference decoder (pre-paged engine decode path)."""

    def __init__(self, model, params, max_batch: int, max_seq: int):
        self.model = model
        self.params = params
        self.max_seq = max_seq
        caches = S.initialize(model.cache_spec(max_batch, max_seq),
                              jax.random.PRNGKey(1))
        self.caches = jax.tree.map(lambda x: x * 0, caches)
        self._jit_prefill = jax.jit(model.prefill,
                                    static_argnames=("cache_len",))
        self._jit_decode = jax.jit(model.decode_step)

    def prefill(self, prompt: np.ndarray, slot: int) -> np.ndarray:
        inputs = {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
        logits, caches1, _ = self._jit_prefill(self.params, inputs,
                                               cache_len=self.max_seq)

        def ins(c, n):
            axis = _batch_axis(c.shape, n.shape)
            idx = [slice(None)] * c.ndim
            idx[axis] = slice(slot, slot + 1)
            return c.at[tuple(idx)].set(n)

        self.caches = jax.tree.map(ins, self.caches, caches1)
        return np.asarray(logits[0], np.float32)

    def decode(self, tokens: np.ndarray, pos: np.ndarray) -> np.ndarray:
        logits, self.caches = self._jit_decode(
            self.params, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos, jnp.int32), self.caches)
        return np.asarray(logits, np.float32)


class DualEngine:
    """Steps a paged ``ServingEngine`` and its dense shadow in lock-step,
    asserting logits closeness and greedy-token agreement at every prefill
    and every decode iteration.

    Tolerance rationale: weights and KV are bf16 in both paths (the stored
    page bits are identical), so the only numeric difference is attention
    reduction order, quantized to a few bf16 ulps per layer; across L layers
    that reaches ~0.1 absolute on O(1) logits and does NOT compound over the
    trace (measured stationary — the repo's own split-vs-plain equivalence
    tests accept the same family of bounds). Logic bugs — wrong page,
    off-by-one write position, stale KV after reuse — produce O(1) divergence
    on many elements and trip the allclose gate immediately.

    Token gate: argmax must be identical unless the reference itself scores
    the two candidate tokens within the cross-implementation noise bound (a
    numeric tie, which cannot fork the trajectory because the shadow is
    teacher-forced with the engine's tokens). Ties are counted; trace tests
    bound their rate so systematic drift cannot hide behind the tie rule.
    """

    def __init__(self, engine: ServingEngine, rtol: float = 5e-2,
                 atol: float = 1e-1):
        self.eng = engine
        self.shadow = DenseShadow(engine.model, engine.params,
                                  engine.ecfg.max_batch, engine.ecfg.max_seq)
        self.rtol, self.atol = rtol, atol
        self.iters = 0
        self.decode_compares = 0
        self.prefill_compares = 0
        self.tied_tokens = 0
        self.max_abs_diff = 0.0

    def _check(self, got: np.ndarray, want: np.ndarray, what: str) -> None:
        np.testing.assert_allclose(got, want, rtol=self.rtol, atol=self.atol,
                                   err_msg=f"logit divergence at {what}")
        self.max_abs_diff = max(self.max_abs_diff,
                                float(np.max(np.abs(got - want))))
        gi, wi = int(np.argmax(got)), int(np.argmax(want))
        if gi == wi:
            return
        tie = self.atol + self.rtol * abs(float(want[wi]))
        assert (want[wi] - want[gi] <= tie and got[gi] - got[wi] <= tie), \
            f"sampled-token divergence beyond numeric tie at {what}"
        self.tied_tokens += 1

    def step(self, **kw) -> None:
        self.eng.step(**kw)
        d = self.eng.last_decode
        # Apply shadow prefills in engine order relative to the decode: a
        # one-shot prefill activates its slot before the decode step (the
        # slot is active in last_decode), while a chunked prefill's final
        # chunk activates it after (inactive this iteration — the shadow's
        # idle-row decode write must not land on the fresh cache).
        before, after = [], []
        for e in self.eng.prefill_log:
            (before if d is not None and d["active"][e[1]] else after).append(e)

        def apply(entries):
            for req, slot, logits in entries:
                ref = self.shadow.prefill(req.prompt, slot)
                self._check(logits, ref, f"prefill rid={req.rid} slot={slot} "
                                         f"iter={self.iters}")
                self.prefill_compares += 1

        apply(before)
        if d is not None:
            ref = self.shadow.decode(d["tokens"], d["pos"])
            for slot in np.flatnonzero(d["active"]):
                self._check(d["logits"][slot], ref[slot],
                            f"decode iter={self.iters} slot={slot}")
                self.decode_compares += 1
        apply(after)
        self.iters += 1

    def run_until_drained(self, max_iters: int = 2000, **kw) -> None:
        it = 0
        while (self.eng.scheduler.has_work()
               or self.eng._active_batch() > 0) and it < max_iters:
            self.step(**kw)
            it += 1
        assert not self.eng.scheduler.has_work() \
            and self.eng._active_batch() == 0, \
            f"trace did not drain in {max_iters} iterations"


class PagedDualEngine:
    """Locksteps a prefix-dedup engine against a dedup-OFF engine (the PR-2
    paged baseline) fed the same request stream, asserting at every
    iteration that admissions, logits, and greedy tokens are identical.

    Unlike ``DualEngine`` the two sides generate independently (no teacher
    forcing), so the traces only stay comparable if dedup is numerically
    invisible. It is, by construction: a deduped page holds the *stored
    bf16 KV bits* of the origin request's prefill, and the suites pair
    requests of equal prompt length, so the baseline engine computes
    bit-identical KV for those positions itself (causal attention: prefix
    hidden states depend only on prefix tokens). Any logic bug — scatter
    into a shared frame, missing COW, stale index entry after migration —
    corrupts whole pages and trips the gates immediately.

    Both engines must be built from the same reduced config (identical
    params via the same init key) and the same memory sizing, roomy enough
    that the BASELINE admits everything it sees the same iteration the
    dedup engine does; the dedup side then has strictly spare capacity,
    which ``device_frames_saved`` reports.
    """

    def __init__(self, baseline: ServingEngine, dedup: ServingEngine,
                 rtol: float = 5e-2, atol: float = 1e-1):
        assert not baseline.ecfg.prefix_dedup and dedup.ecfg.prefix_dedup
        self.base = baseline
        self.dedup = dedup
        self.rtol, self.atol = rtol, atol
        self.iters = 0
        self.decode_compares = 0
        self.prefill_compares = 0

    def _close(self, got: np.ndarray, want: np.ndarray, what: str) -> None:
        np.testing.assert_allclose(got, want, rtol=self.rtol, atol=self.atol,
                                   err_msg=f"logit divergence at {what}")
        assert int(np.argmax(got)) == int(np.argmax(want)), \
            f"greedy-token divergence at {what}"

    def step(self, **kw) -> None:
        self.base.step(**kw)
        self.dedup.step(**kw)
        b_pre = [(r.rid, s) for r, s, _ in self.base.prefill_log]
        d_pre = [(r.rid, s) for r, s, _ in self.dedup.prefill_log]
        assert b_pre == d_pre, \
            f"admission divergence at iter={self.iters}: {b_pre} != {d_pre}"
        for (br, bs, bl), (_, _, dl) in zip(self.base.prefill_log,
                                            self.dedup.prefill_log):
            self._close(dl, bl, f"prefill rid={br.rid} iter={self.iters}")
            self.prefill_compares += 1
        b, d = self.base.last_decode, self.dedup.last_decode
        assert (b is None) == (d is None)
        if b is not None:
            assert np.array_equal(b["active"], d["active"])
            assert np.array_equal(b["tokens"], d["tokens"])
            assert np.array_equal(b["pos"], d["pos"])
            for slot in np.flatnonzero(b["active"]):
                self._close(d["logits"][slot], b["logits"][slot],
                            f"decode iter={self.iters} slot={slot}")
                self.decode_compares += 1
        self.iters += 1

    def run_until_drained(self, max_iters: int = 2000, **kw) -> None:
        it = 0
        while (self.base.scheduler.has_work()
               or self.base._active_batch() > 0
               or self.dedup.scheduler.has_work()
               or self.dedup._active_batch() > 0) and it < max_iters:
            self.step(**kw)
            it += 1
        for eng in (self.base, self.dedup):
            assert not eng.scheduler.has_work() \
                and eng._active_batch() == 0, \
                f"trace did not drain in {max_iters} iterations"

    def device_frames_saved(self) -> int:
        return self.base.device_pages_peak - self.dedup.device_pages_peak
