"""95th percentile, over requests due inside the window, of first token
minus due time. A request with no first token by the window's end counts
its wait so far; rejected requests are left out here and counted as
failed. Host clock."""
from bench.harness import percentile


def read(run):
    waits = [(r.stamps[0] if r.stamps else run.w1) - r.due
             for r in run.due_in_window() if not r.rejected]
    return percentile(waits, 95)
