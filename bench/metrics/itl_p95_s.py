"""95th percentile of every inter-token gap that ends inside the window, of
every request. A gap that spans another request's prefill counts as it is.
Host clock."""
from bench.harness import percentile


def read(run):
    gaps = [b - a for r in run.requests
            for a, b in zip(r.stamps, r.stamps[1:]) if run.in_window(b)]
    return percentile(gaps, 95)
