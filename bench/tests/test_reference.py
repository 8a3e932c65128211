"""The plain reference draws the served system's weights bit for bit, and
computes the served system's logits to within bfloat16 rounding, at CPU
size. These tests import the program; the reference itself does not."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import model_config
from bench.references import dense_decoder as ref
from bench.tests import tiny


def configs():
    qwen = dict(tiny.CONFIG)
    ds = dict(tiny.CONFIG, registry="deepseek-7b", num_key_value_heads=4,
              tie_word_embeddings=False, attention_bias=False,
              rope_theta=10000.0)
    return [qwen, ds]


def test_weights_match_the_engines_initialisation():
    from repro.models.model import build_model
    for c in configs():
        params = build_model(model_config(c)).init(jax.random.PRNGKey(0))
        w = ref.weights(c)
        blk = params["blocks"][0]
        mine = {**blk["attn"], **blk["mlp"], "norm1": blk["norm1"]["scale"],
                "norm2": blk["norm2"]["scale"], "embed": params["embed"],
                "final_norm": params["final_norm"]["scale"]}
        if "lm_head" in params:
            mine["lm_head"] = params["lm_head"]
        assert set(mine) == set(w)
        for k in w:
            assert w[k].dtype == mine[k].dtype == jnp.bfloat16, k
            np.testing.assert_array_equal(np.asarray(w[k], np.float32),
                                          np.asarray(mine[k], np.float32),
                                          err_msg=k)


def test_logits_match_the_dense_model_path():
    from repro.models.model import build_model
    rng = np.random.default_rng(0)
    for c in configs():
        model = build_model(model_config(c))
        params = model.init(jax.random.PRNGKey(0))
        toks = rng.integers(0, c["vocab_size"], 40).astype(np.int32)
        want, _, _ = model.prefill(params, {"tokens": jnp.asarray(toks)[None]},
                                   cache_len=40)
        got = ref.logits_at(c, ref.weights(c), toks, np.array([39]), 256)
        # bfloat16 activations in the served path: agreement to ~1e-2 of
        # logits whose spread is ~1
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(want[0], np.float32),
                                   atol=0.05, rtol=0.05)
