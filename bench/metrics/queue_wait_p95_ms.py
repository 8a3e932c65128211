"""Scheduler: 95th percentile, over requests due inside the window and
submitted by its end (rejected ones left out, as ``ttft_p95_s`` does), of
how long each waited for a slot: admitted minus submitted on the engine's
wall stamps, in ms. A request not admitted by the window's end counts its
wait so far (in a traced run the host stalls while the profiler stops,
after the window has closed)."""
from bench.engine_trace import by_end, request_stamps
from bench.harness import percentile


def read(run):
    reqs = request_stamps(run)
    if reqs is None:
        return None
    end = run.w1
    waits = [by_end(r.req.admitted_wall_s, end) - r.req.submitted_wall_s
             for r in reqs
             if run.in_window(r.due) and r.req.submitted_wall_s < end]
    v = percentile(waits, 95)
    return None if v is None else 1e3 * v
