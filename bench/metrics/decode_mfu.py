"""Model step: operations the traced decode steps need (live rows at their
live contexts, bench/flops.py) over the decode programs' device time times
the chip's peak bf16 rate, in %."""
from bench import flops
from bench.metrics_common import traced_decode_steps


def read(run):
    pairs = traced_decode_steps(run)
    if not pairs:
        return None
    c = run.cell.config
    need = sum(flops.decode_step_flops(c, s.contexts) for s, _ in pairs)
    dev = sum(t for _, t in pairs)
    return 100.0 * need / (dev * run.peaks["bf16_flops"])
