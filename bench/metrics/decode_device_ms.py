"""Model step: mean device time of one decode-step program
(``paged_decode_step``) in the traced window, in ms."""
from bench.metrics_common import program_mean_ms


def read(run):
    return program_mean_ms(run, "paged_decode_step")
