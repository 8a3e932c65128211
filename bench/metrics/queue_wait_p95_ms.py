"""Scheduler: 95th percentile, over requests due inside the window (rejected
ones left out, as ``ttft_p95_s`` does), of how long each waited for a slot:
admitted minus submitted on the engine's wall stamps, in ms. A request not
admitted by the window's end counts its wait so far."""
from bench.engine_trace import request_stamps
from bench.harness import percentile


def read(run):
    reqs = request_stamps(run)
    if reqs is None:
        return None
    waits = [(r.req.admitted_wall_s if r.req.admitted_wall_s is not None
              else run.w1) - r.req.submitted_wall_s for r in reqs]
    return 1e3 * percentile(waits, 95)
