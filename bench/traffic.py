"""Open-loop traffic: one general generator driven by a mix's data file.

A mix lives in ``bench/traffic/<name>.json``. Its kind is ``sessions``,
multi-round chat: Poisson session starts, a shared system prompt,
geometric rounds, exponential think time between a session's rounds,
lognormal user turns appended to a growing history (capped at
``max_prompt_frac`` of the engine's ``max_seq``), geometric output budgets.
With ``mean_rounds`` 1, no system prompt and no lead-in it is a
single-round mix of distinct prompts. The arithmetic is that of
``repro.data.workload.generate_workload``; here the due times are read on
the wall clock, not on the engine's modelled clock.

Steady state: sessions start from ``lead_s`` seconds before the generator
does, and only the rounds due after it starts are sent, so requests arrive
at the stationary rate (``mean_rounds`` per session) from the first second,
their prompts carrying the history of the rounds sent before.

Seeds: the mix's ``shape_seed`` draws one set of sessions (rounds, turn
lengths, output budgets, think times) and one set of gaps between session
starts. The run's ``--seed`` shuffles both inside consecutive blocks of
``shuffle_block`` sessions and draws every token id. So every seed carries
the same work in each block of time, in another order and with other
content, and a window of many blocks holds nearly the same work whatever
the seed (greedy decoding runs every request to its output budget, so
content does not change the work). Both generators are Philox
counter-based (any seed up to 2**63).
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Item:
    """One request of the schedule: due ``due_s`` after the generator
    starts."""
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    session: int


def load_mix(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def _rng(seed: int) -> np.random.Generator:
    return np.random.Generator(np.random.Philox(key=int(seed)))


def _geometric(rng, mean: float, cap: int, size: int) -> np.ndarray:
    return np.clip(rng.geometric(1.0 / max(mean, 1.0), size), 1, cap)


def block_shuffle(rng, n: int, block: int) -> np.ndarray:
    """A permutation of ``range(n)`` that moves each index only inside its
    block of ``block`` consecutive indices."""
    out = np.arange(n)
    for a in range(0, n, block):
        out[a:a + block] = a + rng.permutation(min(block, n - a))
    return out


def schedule(mix: dict, rate_per_s: float, horizon_s: float, seed: int,
             vocab_size: int, max_seq: int) -> list[Item]:
    """Every request due in ``[0, horizon_s)``, sorted by due time.
    ``rate_per_s`` counts session starts."""
    if mix["kind"] != "sessions":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    lead = mix["lead_s"]
    # enough sessions to outlast the horizon at this rate (a draw that runs
    # short is vanishingly rare and only ends the stream early)
    n = int(rate_per_s * (lead + horizon_s) * 1.5) + 16
    shape = _rng(mix["shape_seed"])
    gaps = shape.exponential(1.0 / rate_per_s, n)
    cap = int(mix["max_prompt_frac"] * max_seq)
    rounds = _geometric(shape, mix["mean_rounds"], 10**6, n)
    per_round = int(rounds.sum())
    turns = np.clip(shape.lognormal(math.log(mix["median_turn_len"]),
                                    mix["turn_len_sigma"], per_round),
                    1, cap).astype(int)
    outs = _geometric(shape, mix["mean_output_len"], mix["max_output_len"],
                      per_round)
    thinks = shape.exponential(mix["mean_think_s"], per_round)
    bounds = np.concatenate([[0], np.cumsum(rounds)])

    run = _rng(seed)
    block = mix["shuffle_block"]
    starts = np.cumsum(gaps[block_shuffle(run, n, block)]) - lead
    order = block_shuffle(run, n, block)
    system = run.integers(0, vocab_size, mix["system_prompt_len"],
                          dtype=np.int32)
    items: list[Item] = []
    for slot in range(n):
        s, t, history = order[slot], starts[slot], system
        for r in range(bounds[s], bounds[s + 1]):
            turn = run.integers(0, vocab_size, turns[r], dtype=np.int32)
            prompt = np.concatenate([history, turn])[-cap:]
            if 0.0 <= t < horizon_s:
                items.append(Item(float(t), prompt, int(outs[r]), slot))
            # the next round's history is this round's prompt: the reply
            # is not known when the schedule is drawn
            history = prompt
            t += thinks[r]
    items.sort(key=lambda it: it.due_s)
    return items
