"""Output tokens emitted inside the window, over the window's seconds.
Every request counts, whenever it arrived. Host clock."""


def read(run):
    n = sum(1 for r in run.requests for t in r.stamps if run.in_window(t))
    return n / (run.w1 - run.w0)
