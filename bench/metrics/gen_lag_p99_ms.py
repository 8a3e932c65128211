"""Load generator: 99th percentile of how late requests due in the window
were handed to the engine (submit minus due), in ms. Host clock."""
from bench.harness import percentile


def read(run):
    lags = [r.submit - r.due for r in run.due_in_window()]
    v = percentile(lags, 99)
    return None if v is None else 1e3 * v
