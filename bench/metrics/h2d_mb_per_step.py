"""KV tiers: mean over the traced window's steps of the bytes the engine put
on the device (step inputs, host-tier pages streamed and promoted, prefill
KV), counted where each transfer happens, in MB."""
from bench.engine_trace import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return sum(r.h2d_bytes for r in steps) / len(steps) / 1e6
