"""Peak device memory in use over the run, read after the window from the
runtime (``peak_bytes_in_use``), in GB."""


def read(run):
    b = run.memory_peak_bytes
    return None if b is None else b / 1e9
