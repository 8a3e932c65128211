"""Operations and bytes that the served path's work needs, from the
configuration's published shapes and the live context lengths.

Conventions: a multiply-add is 2 operations; K/V, q and o are bfloat16 (2
bytes); a context of ``n`` tokens occupies ``ceil(n / page)`` whole pages.
Only live rows count: idle batch rows and the dead page columns a kernel
may still fetch are work the algorithm does not need.
"""
from __future__ import annotations

BF16 = 2


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "h": h, "kv": c["num_key_value_heads"], "hd": d // h,
            "f": c["intermediate_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"]}


def matmul_params_per_token(c: dict) -> int:
    """Weights one token multiplies through: Q/K/V/O and the gated MLP of
    every layer, and the output head."""
    m = dims(c)
    attn = m["d"] * m["hd"] * (2 * m["h"] + 2 * m["kv"])
    mlp = 3 * m["d"] * m["f"]
    return m["L"] * (attn + mlp) + m["d"] * m["V"]


def decode_attn_flops(c: dict, contexts) -> float:
    """QK^T and PV of one decode step, every layer, summed over live rows
    with ``contexts`` tokens of context each (the new token included)."""
    m = dims(c)
    return float(4 * m["L"] * m["h"] * m["hd"] * sum(contexts))


def decode_attn_bytes(c: dict, contexts, page: int) -> float:
    """HBM bytes the decode attention needs per step: the live K/V pages of
    each live row, and its q and o, every layer."""
    m = dims(c)
    kv = sum(-(-n // page) * page for n in contexts) * 2 * m["kv"] * m["hd"]
    qo = len(contexts) * 2 * m["h"] * m["hd"]
    return float(m["L"] * (kv + qo) * BF16)


def decode_step_flops(c: dict, contexts) -> float:
    """A whole decode step: every live row's matmuls plus its attention."""
    return 2.0 * matmul_params_per_token(c) * len(contexts) \
        + decode_attn_flops(c, contexts)
