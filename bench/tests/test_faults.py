"""A whole run at CPU size with the timed path broken underneath must come
out not correct; a sound one correct. The look for a chip is skipped (the
device is described), everything after it runs as in a real run. Faults:

* token altered where it is produced: every few steps each decoding row's
  new token is replaced before the engine feeds it back;
* a step that returns its state unchanged: the decode program's updated
  page pool is thrown away, so no decoded token's K/V is ever kept;
* the control: the reference, computed in fp8 in the program's place, over
  the same prompts and tokens, judged by the run's own comparison.
"""
import time

import jax.numpy as jnp
import pytest

from bench.harness import Server
from bench.run import CompileMeter, execute
from bench.tests import tiny

SEEDS = (2**33 + 17, 5)


class AlteredTokens(Server):
    def warm_up(self, seed):
        super().warm_up(seed)
        eng, step = self.eng, self.eng.step
        vocab = self.cell.config["vocab_size"]
        count = {"n": 0}

        def broken():
            step()
            count["n"] += 1
            if count["n"] % 3:
                return
            for slot, req in enumerate(eng.slot_req):
                if req is not None and eng.active[slot] and req.generated:
                    tok = (req.generated[-1] + 1) % vocab
                    req.generated[-1] = tok
                    eng.tokens[slot] = tok
        eng.step = broken


class StateUnchanged(Server):
    def warm_up(self, seed):
        super().warm_up(seed)
        eng = self.eng
        for k, fn in list(eng._jit_decode.items()):
            def broken(w, tok, pos, pool, *rest, fn=fn):
                logits, _ = fn(w, tok, pos, jnp.copy(pool), *rest)
                return logits, pool
            eng._jit_decode[k] = broken


def one_run(seed, factory=None, control=None):
    out = execute(tiny.cell(), seed, 5.0, False, tiny.DEVICE, tiny.BENCH,
                  CompileMeter(), time.perf_counter(), server_factory=factory,
                  control=control)
    return out["correct"], out["compared"]["max_logit_gap"]["value"]


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(seed):
    ok, gap = one_run(seed)
    assert ok, gap


@pytest.mark.parametrize("factory", [AlteredTokens, StateUnchanged])
def test_broken_run_is_not_correct(factory):
    ok, gap = one_run(SEEDS[0], factory)
    assert not ok, gap


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_control_is_not_correct(seed):
    ok, gap = one_run(seed, control="fp8")
    assert not ok, gap
