"""Engine host: mean over the traced window's steps of the bytes the engine
pulled from the device (logits, chunk logits, write-back gathers), counted
where each transfer happens, in MB."""
from bench.engine_trace import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return sum(r.d2h_bytes for r in steps) / len(steps) / 1e6
