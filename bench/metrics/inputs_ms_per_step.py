"""Engine host: median over the traced window's steps that ran a decode
of the engine's ``engine.inputs`` self time (swap planning, promotions,
copy-on-write, block tables, host-tier stream copies, and the step's
inputs put on the device), in ms."""
from bench.engine_trace import decode_steps
from bench.harness import percentile


def read(run):
    steps = decode_steps(run)
    if not steps:
        return None
    return 1e3 * percentile([r.host_s.get("inputs", 0.0) for r in steps], 50)
