"""bench/traffic.py: a seed fixes the schedule; seeds share the sessions
and the gaps, in another order, and differ in token ids; requests arrive at
the stationary rate from the start; a single-round mix keeps its rate and
its cap; the chat mix draws what it drew before."""
import hashlib

import numpy as np
import pytest

from bench import traffic
from bench.tests import tiny


def shapes(items):
    return sorted((len(it.prompt), it.max_new_tokens) for it in items)


def test_same_seed_same_schedule():
    a = traffic.schedule(tiny.MIX, 2.0, 30.0, 2**40 + 3, 512, 256)
    b = traffic.schedule(tiny.MIX, 2.0, 30.0, 2**40 + 3, 512, 256)
    assert [it.due_s for it in a] == [it.due_s for it in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_block_shuffle_stays_in_blocks():
    rng = np.random.Generator(np.random.Philox(key=7))
    p = traffic.block_shuffle(rng, 11, 4)
    assert sorted(p) == list(range(11))
    assert all(i // 4 == j // 4 for i, j in enumerate(p))


def test_seeds_share_the_work_in_another_order():
    # the first 20 session slots (whole shuffle blocks, all of their rounds
    # well inside the horizon) carry the same sizes under two seeds; their
    # instants and token ids differ
    mix = dict(tiny.MIX, lead_s=0.0)
    a, b = (traffic.schedule(mix, 2.0, 200.0, seed, 512, 256)
            for seed in (1, 2))
    a, b = ([it for it in x if it.session < 20] for x in (a, b))
    assert len(a) > 20 and shapes(a) == shapes(b)
    assert [it.due_s for it in a] != [it.due_s for it in b]
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))


def test_stationary_from_the_start():
    # with the lead-in, the first and the second half of the horizon see
    # about as many requests; without it the first half sees fewer
    def halves(mix):
        n = [0, 0]
        for seed in range(8):
            for it in traffic.schedule(mix, 2.0, 40.0, seed, 512, 256):
                n[it.due_s >= 20.0] += 1
        return n
    lead = halves(tiny.MIX)
    cold = halves(dict(tiny.MIX, lead_s=0.0, mean_think_s=10.0))
    assert abs(lead[0] - lead[1]) < 0.1 * sum(lead)
    assert cold[0] < 0.8 * cold[1]


def test_session_history_and_caps():
    items = traffic.schedule(tiny.MIX, 2.0, 60.0, 9, 512, 256)
    cap = int(tiny.MIX["max_prompt_frac"] * 256)
    assert items and all(len(it.prompt) <= cap for it in items)
    assert all(1 <= it.max_new_tokens <= tiny.MIX["max_output_len"]
               for it in items)
    by_session = {}
    for it in items:
        by_session.setdefault(it.session, []).append(it)
    n_sys = tiny.MIX["system_prompt_len"]
    system = next(it.prompt[:n_sys] for it in items if len(it.prompt) < cap)
    for rounds in by_session.values():
        for prev, cur in zip(rounds, rounds[1:]):
            # a later round extends the earlier prompt, unless capped
            n = len(prev.prompt)
            assert (np.array_equal(cur.prompt[:n], prev.prompt)
                    or len(cur.prompt) == cap)
        if len(rounds[0].prompt) < cap:
            assert np.array_equal(rounds[0].prompt[:n_sys], system)


# ------------------------------------------------------------ single round
ONE_ROUND = {"kind": "sessions", "shape_seed": 11, "shuffle_block": 2,
             "lead_s": 0.0, "system_prompt_len": 0, "mean_rounds": 1.0,
             "mean_think_s": 1.0, "median_turn_len": 96,
             "turn_len_sigma": 0.5, "max_prompt_frac": 0.5,
             "mean_output_len": 8.0, "max_output_len": 16}


def test_single_round_rate_and_cap():
    # cap: max_prompt_frac x max_seq = 128
    items = traffic.schedule(ONE_ROUND, 4.0, 500.0, 2**40 + 9, 512, 256)
    assert abs(len(items) / 500.0 - 4.0) < 0.3
    lens = [len(it.prompt) for it in items]
    assert max(lens) == 128
    assert all(1 <= it.max_new_tokens <= 16 for it in items)
    assert len({it.session for it in items}) == len(items)   # one round each
    due = [it.due_s for it in items]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 500.0
    # lognormal about its median, under the cap
    assert 80 <= float(np.median(lens)) <= 112


def test_single_round_seed_rules():
    a, b, c = (traffic.schedule(ONE_ROUND, 4.0, 100.0, seed, 512, 256)
               for seed in (3, 3, 2**40 + 4))
    assert [it.due_s for it in a] == [it.due_s for it in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another run seed: the same work in each block of two requests, in
    # another order and with other token ids
    n = 200
    assert shapes(a[:n]) == shapes(c[:n])
    assert [it.due_s for it in a[:n]] != [it.due_s for it in c[:n]]
    gaps = (np.diff([0.0] + [it.due_s for it in x[:n]]) for x in (a, c))
    assert sorted(next(gaps)) == pytest.approx(sorted(next(gaps)))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c) if len(x.prompt) == len(y.prompt))
    # another shape seed: other work
    d = traffic.schedule(dict(ONE_ROUND, shape_seed=12), 4.0, 100.0, 3, 512,
                         256)
    assert shapes(d[:n]) != shapes(a[:n])


def test_sessions_schedule_is_unchanged():
    """The chat cell's schedule, byte for byte, as the generator drew it
    before single-round mixes were run on it."""
    items = traffic.schedule(traffic.load_mix("chat"), 0.36, 62.0,
                             2**40 + 7, 151936, 4096)
    h = hashlib.sha256()
    for it in items:
        h.update(repr((float(it.due_s), int(it.max_new_tokens),
                       int(it.session))).encode())
        h.update(it.prompt.astype("<i4").tobytes())
    assert len(items) == 58
    assert h.hexdigest() == ("3b1684e885c611ad38ba6cdf563704e5"
                             "59686560eaa3ca33b9ca6776d67b2a0a")
