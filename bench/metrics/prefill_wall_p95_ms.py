"""Prefill: 95th percentile, over requests admitted inside the window
(rejected ones left out), of first token minus admission on the engine's
wall stamps, in ms. A request with no first token by the window's end
counts its wait so far (in a traced run the host stalls while the profiler
stops, after the window has closed). Requests due before the window but
admitted inside it count, so a window whose own arrivals all still wait
reads the prefills it ran."""
from bench.engine_trace import by_end, request_stamps
from bench.harness import percentile


def read(run):
    reqs = request_stamps(run)
    if reqs is None:
        return None
    waits = [by_end(r.req.first_token_wall_s, run.w1) - r.req.admitted_wall_s
             for r in reqs
             if r.req.admitted_wall_s is not None
             and run.in_window(r.req.admitted_wall_s)]
    v = percentile(waits, 95)
    return None if v is None else 1e3 * v
