"""bench/flops.py against counts made by hand at tiny shapes."""
from bench import flops

# d=8, 2 heads of 4, 1 KV head, d_ff=16, 2 layers, vocab 10
C = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
     "intermediate_size": 16, "num_hidden_layers": 2, "vocab_size": 10}


def test_matmul_params_per_token():
    # per layer: wq 8*8 + wk 8*4 + wv 8*4 + wo 8*8 = 192; mlp 3*8*16 = 384
    # two layers 1152, head 8*10 = 80
    assert flops.matmul_params_per_token(C) == 2 * (192 + 384) + 80


def test_decode_attn_flops():
    # per row and layer: QK^T 2*2*4*ctx + PV 2*2*4*ctx = 32*ctx; 2 layers
    assert flops.decode_attn_flops(C, [3, 5]) == 2 * 32 * (3 + 5)


def test_decode_attn_bytes():
    # page 4: ctx 3 -> 1 page (4 tokens), ctx 5 -> 2 pages (8 tokens)
    # K and V: tokens * 2 * 1 head * 4 dims; q and o: 2 * 2 heads * 4 dims
    per_layer = (4 + 8) * 2 * 1 * 4 + 2 * (2 * 2 * 4)
    assert flops.decode_attn_bytes(C, [3, 5], 4) == 2 * per_layer * 2


def test_decode_step_flops():
    want = 2 * flops.matmul_params_per_token(C) * 2 + 2 * 32 * 8
    assert flops.decode_step_flops(C, [3, 5]) == want
