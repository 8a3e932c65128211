"""Helpers the metric readers share: device time of a program or a kernel in
the traced window, and the traced decode steps with their records.

Programs are found by their jit name (``paged_decode_step``,
``paged_prefill_chunk``, ``prefill``); a kernel by substrings of its op's
name and stats, all of which must appear. On a v5e the paged decode
attention kernel is the one custom call inside the decode program
(``%paged_decode_step.<n> = ... custom-call(``): Pallas names it after the
enclosing jit. Neither name is fixed by the program yet, so a rename there
silences the readers that use it.
"""
from __future__ import annotations

# substrings that together pick out each kernel the readers time
KERNELS = {"decode_attention": ("%paged_decode_step.", "custom-call(")}


def program_mean_ms(run, program: str):
    p = run.profile
    if p is None or not p.program_n.get(program):
        return None
    return 1e3 * p.program_s[program] / p.program_n[program]


def kernel_seconds(run, kernel: str) -> float | None:
    p = run.profile
    if p is None:
        return None
    pats = KERNELS[kernel]
    t = sum(s for label, s in p.label_s.items()
            if all(x in label for x in pats))
    return t or None


def traced_decode_steps(run) -> list:
    """(Step record, device seconds of its decode program) for every traced
    step that ran a decode."""
    if run.profile is None:
        return []
    by_index = {s.index: s for s in run.steps}
    out = []
    for st in run.profile.steps:
        t = st["programs"].get("paged_decode_step")
        rec = by_index.get(st["index"])
        if t and rec is not None and len(rec.contexts):
            out.append((rec, t))
    return out
