"""Engine host time per step: median over traced steps of the
``bench.step`` span minus the device-busy time inside it, in ms."""
from bench.harness import percentile


def read(run):
    if run.profile is None or not run.profile.steps:
        return None
    return 1e3 * percentile([s["host_s"] - s["device_busy_s"]
                             for s in run.profile.steps], 50)
