"""Decide ``correct``: the served tokens against a plain reference.

After the window closes, a sample of the requests the timed path finished
is drawn from the run seed, always with the request that served the most
tokens. The reference runs once over each prompt with its served tokens
(teacher forced), and every served token is read against the reference's
logits at its position: its gap is how far its logit lies below the
reference's best. The number compared is the widest gap; a token the
reference itself ranks first reads 0. Greedy decoding only.

A control puts the reference in the program's place at a lower precision:
at the same positions of the same sequences, the gap of the token the
lower-precision reference ranks first.
"""
from __future__ import annotations

import importlib

import numpy as np

# served tokens per sampled request are compared in blocks of this many
# positions (padding keeps the compiled head program to a few shapes)
ROW_BLOCK = 64


def sample(finished: list, seed: int, tokens: int, max_requests: int
           ) -> list[tuple[np.ndarray, np.ndarray]]:
    """(prompt, served tokens) of a seeded sample of ``finished`` requests:
    the one with the most served tokens, then others in a seeded order
    until ``tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.generated), r.rid))
    first, rest = order[0], order[1:]
    rng = np.random.Generator(np.random.Philox(key=int(seed) + 2))
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for r in [first] + rest:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append((np.asarray(r.prompt, np.int32),
                    np.asarray(r.generated, np.int32)))
        n += len(r.generated)
    return out


def reference(name: str):
    return importlib.import_module(f"bench.references.{name}")


def _rows(prompt_len: int, n: int) -> tuple[np.ndarray, int]:
    """Positions whose logits predict served tokens 0..n-1, padded to a
    multiple of ROW_BLOCK with the last one."""
    rows = prompt_len - 1 + np.arange(n)
    m = -(-n // ROW_BLOCK) * ROW_BLOCK
    return np.concatenate([rows, np.full(m - n, rows[-1])]), n


def gaps(config: dict, samples, max_seq: int, w=None,
         controls: tuple[str, ...] = ()) -> dict[str, np.ndarray]:
    """Per served token, the gap of the served token (``program``) and, for
    each precision in ``controls``, the gap of that control's first choice
    (keyed by the precision), all read in the float32 reference's logits.
    ``w`` are the reference's weights (drawn here when not given)."""
    import jax.numpy as jnp
    ref = reference(config["reference"])
    if w is None:
        w = ref.weights(config)
    out: dict[str, list] = {k: [] for k in ("program", *controls)}
    for prompt, served in samples:
        seq = np.concatenate([prompt, served[:-1]])
        rows, n = _rows(len(prompt), len(served))
        lg = ref.logits_at(config, w, seq, rows, max_seq)
        best = jnp.max(lg, axis=-1)
        tok = jnp.asarray(np.pad(served, (0, len(rows) - n)))
        got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
        out["program"].append(np.asarray(best - got)[:n])
        for c in controls:
            pick = jnp.argmax(ref.logits_at(config, w, seq, rows, max_seq,
                                            precision=c), axis=-1)
            alt = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
            out[c].append(np.asarray(best - alt)[:n])
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}
