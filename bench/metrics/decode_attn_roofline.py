"""Kernels: the paged decode attention kernel's share (%) of its roofline
over the traced window: max(operations / peak rate, bytes / peak
bandwidth) over the kernel's summed device time. Operations and bytes are
those of the live rows' live pages (bench/flops.py), not the dead page
columns the kernel's grid also fetches."""
from bench import flops
from bench.metrics_common import kernel_seconds, traced_decode_steps


def read(run):
    pairs = traced_decode_steps(run)
    t = kernel_seconds(run, "decode_attention")
    if not pairs or not t:
        return None
    c, pk = run.cell.config, run.peaks
    page = c["engine"]["page_size"]
    f = sum(flops.decode_attn_flops(c, s.contexts) for s, _ in pairs)
    b = sum(flops.decode_attn_bytes(c, s.contexts, page) for s, _ in pairs)
    return 100.0 * max(f / pk["bf16_flops"], b / pk["hbm_bytes_s"]) / t
