"""Engine host: median over the traced window's steps that ran a decode
of the engine's ``engine.decode.dispatch`` self time (launching the decode
program, the weights' pytree flattening included), in ms."""
from bench.engine_trace import decode_steps
from bench.harness import percentile


def read(run):
    steps = decode_steps(run)
    if not steps:
        return None
    return 1e3 * percentile([r.host_s.get("decode.dispatch", 0.0)
                             for r in steps], 50)
