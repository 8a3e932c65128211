"""Prefill: 95th percentile, over requests due inside the window and
admitted (rejected ones left out), of first token minus admission on the
engine's wall stamps, in ms. A request with no first token by the window's
end counts its wait so far."""
from bench.engine_trace import request_stamps
from bench.harness import percentile


def read(run):
    reqs = request_stamps(run)
    if reqs is None:
        return None
    waits = [(r.req.first_token_wall_s
              if r.req.first_token_wall_s is not None else run.w1)
             - r.req.admitted_wall_s
             for r in reqs if r.req.admitted_wall_s is not None]
    v = percentile(waits, 95)
    return None if v is None else 1e3 * v
