"""Prefill: mean device time of one incremental prefill-chunk program
(``paged_prefill_chunk``) in the traced window, in ms."""
from bench.metrics_common import program_mean_ms


def read(run):
    return program_mean_ms(run, "paged_prefill_chunk")
