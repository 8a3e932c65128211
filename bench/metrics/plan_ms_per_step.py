"""Scheduler: median over the traced window's steps of the engine's
``engine.plan`` plus ``engine.apply`` self time (planning, then applying
resumes, preemptions, admissions and one-shot prefills), in ms."""
from bench.engine_trace import window_steps
from bench.harness import percentile


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return 1e3 * percentile([r.host_s.get("plan", 0.0)
                             + r.host_s.get("apply", 0.0) for r in steps], 50)
