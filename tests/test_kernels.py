"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes, dtypes, GQA groupings, masks, and paged layouts."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import paged_chunk_attention_pallas

# compile-heavy (full JAX jit of models/kernels): excluded from the fast CI
# tier, run in the nightly full suite
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


def _mk_qkv(b, h, vh, sq, sk, d, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, h, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (b, vh, sk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (b, vh, sk, d), jnp.float32).astype(dtype)
    return q, k, v


TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,vh,sq,sk,d", [
    (1, 4, 4, 64, 64, 64),       # MHA square
    (2, 8, 2, 128, 128, 64),     # GQA
    (1, 8, 1, 96, 96, 128),      # MQA, non-multiple seq (padding path)
    (1, 4, 4, 256, 256, 32),     # multi q/kv blocks
])
def test_flash_causal(dtype, b, h, vh, sq, sk, d):
    q, k, v = _mk_qkv(b, h, vh, sq, sk, d, dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [16, 64])
def test_flash_sliding_window(window):
    q, k, v = _mk_qkv(1, 4, 2, 128, 128, 64, jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32, interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_noncausal_cross():
    q, k, v = _mk_qkv(2, 4, 4, 32, 80, 64, jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                              interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_kv_len_mask():
    q, k, v = _mk_qkv(1, 4, 4, 64, 128, 64, jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False, kv_len=100,
                              block_q=32, block_k=32, interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=False, kv_len=100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_style_offset():
    """sq < sk with causal: queries are the LAST sq positions (chunked
    prefill continuation)."""
    q, k, v = _mk_qkv(1, 4, 4, 32, 128, 64, jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Paged decode
# ---------------------------------------------------------------------------


def _mk_paged(b, h, vh, d, npages, page, nb, dtype, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (npages, page, vh, d), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (npages, page, vh, d), jnp.float32).astype(dtype)
    # distinct page assignment per request
    perm = jax.random.permutation(ks[3], npages)[: b * nb]
    bt = perm.reshape(b, nb).astype(jnp.int32)
    cl = jax.random.randint(ks[4], (b,), 1, nb * page + 1, jnp.int32)
    return q, kp, vp, bt, cl


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,vh,d,page,nb", [
    (2, 4, 4, 64, 16, 4),
    (3, 8, 2, 64, 32, 3),     # GQA
    (1, 8, 1, 128, 16, 8),    # MQA
])
def test_paged_decode(dtype, b, h, vh, d, page, nb):
    q, kp, vp, bt, cl = _mk_paged(b, h, vh, d, b * nb + 3, page, nb, dtype)
    got = ops.paged_decode_attention(q, kp, vp, bt, cl, interpret=True)
    want = ref.ref_paged_decode_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_paged_decode_sliding_window():
    q, kp, vp, bt, cl = _mk_paged(2, 4, 2, 64, 19, 8, 9, jnp.float32)
    cl = jnp.asarray([60, 33], jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, bt, cl, window=20,
                                     interpret=True)
    want = ref.ref_paged_decode_attention(q, kp, vp, bt, cl, window=20)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_single_token_context():
    q, kp, vp, bt, _ = _mk_paged(2, 4, 4, 64, 16, 2, 7, jnp.float32)
    cl = jnp.asarray([1, 5], jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, bt, cl, interpret=True)
    want = ref.ref_paged_decode_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_padded_table_with_out_of_range_entries():
    """Block-table slots beyond the live context may hold garbage ids (the
    engine pads with a null frame; a buggy caller could pad with anything):
    the kernel clamps them into the pool and the context mask hides them."""
    q, kp, vp, bt, _ = _mk_paged(2, 4, 2, 64, 13, 16, 4, jnp.float32)
    cl = jnp.asarray([18, 33], jnp.int32)        # 2 resp. 3 live pages of 4
    bt = np.array(bt)
    bt[0, 2:] = 99_999
    bt[1, 3:] = -3
    got = ops.paged_decode_attention(q, kp, vp, jnp.asarray(bt), cl,
                                     interpret=True)
    want = ref.ref_paged_decode_attention(
        q, kp, vp, jnp.clip(jnp.asarray(bt), 0, 12), cl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_context_not_page_multiple():
    q, kp, vp, bt, _ = _mk_paged(3, 8, 2, 64, 15, 16, 3, jnp.float32)
    cl = jnp.asarray([7, 17, 45], jnp.int32)     # none divisible by 16
    got = ops.paged_decode_attention(q, kp, vp, bt, cl, interpret=True)
    want = ref.ref_paged_decode_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_batch_one():
    q, kp, vp, bt, _ = _mk_paged(1, 4, 4, 64, 9, 16, 4, jnp.float32)
    for c in (1, 15, 16, 17, 64):                # page-boundary straddles
        cl = jnp.asarray([c], jnp.int32)
        got = ops.paged_decode_attention(q, kp, vp, bt, cl, interpret=True)
        want = ref.ref_paged_decode_attention(q, kp, vp, bt, cl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The paged kernels over the engine's multi-layer pool
# ---------------------------------------------------------------------------

POOL_LAYERS = 3


def _mk_pool(frames, page, vh, d, seed=7):
    """A [frames, page, 3, 2, vh, d] pool whose layers hold different data."""
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (frames, page, POOL_LAYERS, 2, vh, d),
                             jnp.float32)


def _run_at_layer(kernel, layer, traced, q, pool, *rest, **kw):
    """``kernel(q, pool, layer, *rest)`` with ``layer`` a Python int, or a
    traced scalar as inside the engine's layer scan."""
    if traced:
        return jax.jit(functools.partial(kernel, interpret=True, **kw))(
            q, pool, jnp.int32(layer), *rest)
    return kernel(q, pool, layer, *rest, interpret=True, **kw)


@pytest.mark.parametrize("vh", [1, 2])
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("layer", range(POOL_LAYERS))
def test_paged_decode_reads_its_layer_of_the_pool(layer, traced, vh):
    """The decode kernel attends over layer ``layer`` of the pool: each
    layer holds different data and every row matches the reference over
    ``pool[:, :, layer, 0]`` / ``[..., 1]``, with and without a sliding
    window; the idle row (context 0) reads nothing and gives zeros."""
    b, h, d, page, nb = 3, 4, 64, 8, 4
    frames = b * nb + 2
    q, _, _, bt, _ = _mk_paged(b, h, vh, d, frames, page, nb, jnp.float32)
    pool = _mk_pool(frames, page, vh, d)
    cl = jnp.asarray([29, 0, 17], jnp.int32)
    k, v = pool[:, :, layer, 0], pool[:, :, layer, 1]
    for window in (0, 12):
        got = _run_at_layer(paged_decode_attention_pallas, layer, traced, q,
                            pool, bt, cl, window=window)
        want = ref.ref_paged_decode_attention(q, k, v, bt, cl, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert not np.asarray(got)[1].any()


def _ref_chunk_attention(q, k_pages, v_pages, block_table, start):
    """Causal attention of the chunk's queries, at absolute positions
    ``start .. start+C-1``, over the keys its block table gathers."""
    c, h, d = q.shape
    vh = k_pages.shape[2]
    k = k_pages[block_table].reshape(-1, vh, d)          # [nb*page, V, D]
    v = v_pages[block_table].reshape(-1, vh, d)
    qf = q.reshape(c, vh, h // vh, d) / math.sqrt(d)
    s = jnp.einsum("cvgd,svd->cvgs", qf, k)
    seen = jnp.arange(k.shape[0])[None] <= start + jnp.arange(c)[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("cvgs,svd->cvgd", p, v).reshape(c, h, d)


@pytest.mark.parametrize("vh", [1, 2])
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("layer", range(POOL_LAYERS))
def test_paged_chunk_reads_its_layer_of_the_pool(layer, traced, vh):
    """The chunk kernel attends a chunk that starts mid-page over the
    prefix and itself in layer ``layer`` of the pool, against plain causal
    attention over ``pool[:, :, layer, 0]`` / ``[..., 1]``; the table's last
    column lies past the context."""
    c, h, d, page, nb, start = 8, 4, 64, 8, 4, 13
    frames = nb + 3
    pool = _mk_pool(frames, page, vh, d)
    ks = jax.random.split(jax.random.PRNGKey(layer), 2)
    q = jax.random.normal(ks[0], (c, h, d), jnp.float32)
    bt = jax.random.permutation(ks[1], frames)[:nb].astype(jnp.int32)
    got = _run_at_layer(paged_chunk_attention_pallas, layer, traced, q, pool,
                        bt, jnp.int32(start), jnp.int32(start + c))
    want = _ref_chunk_attention(q, pool[:, :, layer, 0], pool[:, :, layer, 1],
                                bt, start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Copy-on-write through the engine path (prefix dedup)
# ---------------------------------------------------------------------------


def _mk_cow_engine(extra_device_pages: float, host_pages: int):
    """Dedup engine sized so two identical 10-token prompts (page 4: two
    full pages + a 2-token partial page) share all three prompt pages."""
    from _engine_builders import mk_reduced_engine

    eng, _ = mk_reduced_engine(name="cow", max_batch=2, max_seq=24,
                               page_size=4,
                               extra_device_pages=extra_device_pages,
                               host_pages=host_pages, prefix_dedup=True,
                               batches=(1, 2), seqs=(16, 32))
    return eng


def _submit_twins(eng, new=6):
    from repro.serving.request import Request

    prompt = (np.arange(10) * 7 % 97).astype(np.int32)
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=prompt.copy(),
                           max_new_tokens=new,
                           ttft_slo_s=10.0, tpot_slo_s=10.0))
    eng._admit()                 # prefill both; rid 1 dedups all 3 pages
    assert eng.kv.dedup_hit_pages(1) == [0, 1, 2]
    shared = eng.kv.refs(0)[2]
    assert eng.kv.refs(1)[2] == shared
    return shared


def test_cow_write_leaves_sibling_device_page_bitwise_unchanged():
    """Engine-path COW: both twins decode into the shared partial page in
    the same iteration — the later-admitted one must move onto its reserve
    and the sibling-visible bytes of every shared page (the prompt token
    slots) must be bitwise identical before and after, in the shared frame
    AND in the private copy."""
    eng = _mk_cow_engine(extra_device_pages=14, host_pages=0)
    shared = _submit_twins(eng)
    assert shared.tier == "device"
    full_frames = [eng.kv.refs(0)[0].page, eng.kv.refs(0)[1].page]
    ids = jnp.asarray([shared.page] + full_frames, jnp.int32)
    before = np.asarray(ops.gather_kv_pages(eng.pool, ids))

    eng.step()                   # first decode write for both twins
    assert eng.cow_events == 1   # rid 1 moved off; rid 0 appends in place
    new1 = eng.kv.refs(1)[2]
    assert new1 != shared and eng.kv.refs(0)[2] == shared
    after = np.asarray(ops.gather_kv_pages(eng.pool, ids))
    # full shared pages: bitwise untouched entirely
    assert np.array_equal(before[1:], after[1:])
    # shared partial page: the 2 prompt-token slots (all a sibling's
    # attention can see) bitwise untouched; offsets >= 2 hold rid 0's new
    # token, which rid 1's context length masks
    assert np.array_equal(before[0][:2], after[0][:2])
    # rid 1's private copy preserved the prompt bytes too
    got1 = np.asarray(ops.gather_kv_pages(
        eng.pool, jnp.asarray([new1.page], jnp.int32)))[0]
    assert np.array_equal(before[0][:2], got1[:2])
    # ... and the twins keep generating identical tokens
    for _ in range(5):
        eng.step()
    gens = [r.generated for r in sorted(eng.finished, key=lambda r: r.rid)]
    assert len(gens) == 2 and gens[0] == gens[1]
    eng.kv.check_invariants()


def test_cow_write_on_host_resident_streamed_shared_page():
    """Same protocol with ZERO device pages: the shared pages live on host,
    stream through the slab every iteration, and the decode write lands on
    a streamed page (dirty write-back). The write-back must not leak the
    writer's token into the sibling-visible bytes of the shared host slot,
    and the COW copy must land in the writer's host reserve."""
    eng = _mk_cow_engine(extra_device_pages=0.25, host_pages=16)
    assert eng.kv.device.total_pages == 0
    shared = _submit_twins(eng)
    assert shared.tier == "host"
    full_slots = [eng.kv.refs(0)[0].page, eng.kv.refs(0)[1].page]
    before_partial = eng.host_pool[shared.page].copy()
    before_full = eng.host_pool[np.asarray(full_slots)].copy()

    eng.step()
    assert eng.cow_events == 1
    new1 = eng.kv.refs(1)[2]
    assert new1.tier == "host" and new1 != shared
    assert np.array_equal(before_full,
                          eng.host_pool[np.asarray(full_slots)])
    # rid 0's write came back through the slab into the shared slot, but
    # only at offsets a sibling never reads
    assert np.array_equal(before_partial[:2],
                          eng.host_pool[shared.page][:2])
    assert not np.array_equal(before_partial[2],
                              eng.host_pool[shared.page][2])
    assert np.array_equal(before_partial[:2],
                          eng.host_pool[new1.page][:2])
    for _ in range(5):
        eng.step()
    gens = [r.generated for r in sorted(eng.finished, key=lambda r: r.rid)]
    assert len(gens) == 2 and gens[0] == gens[1]
    assert eng.kv.host.used_pages == 0
    eng.kv.check_invariants()


def test_cow_cross_tier_copy_charged_to_link_budget():
    """A COW whose reserve sits on the other tier moves a real page over
    the host link — the modeled iteration must charge both the d2h copy
    and the post-COW streaming, exactly once (regression: the pre-pass
    originally moved the bytes without billing them)."""
    import pytest as _pytest

    from repro.core.interval import iter_time_with_interval_kv

    eng = _mk_cow_engine(extra_device_pages=4, host_pages=16)
    shared = _submit_twins(eng)
    assert shared.tier == "device"           # twin 0 owns all 4 dev pages
    assert eng.kv.reserve_of(1).tier == "host"   # dev pool full: host spare
    t0 = eng.clock_s
    pb = eng.kv.page_bytes
    times = eng.times_fn(2, eng.ecfg.max_seq, "decode")
    eng.step()                               # twin 1's write COWs dev->host
    assert eng.cow_events == 1
    assert eng.kv.refs(1)[2].tier == "host"
    # link charge: the post-COW streamed pages (twin 1's tail + its new
    # private write page) gate compute; the COW page itself writes back
    streamed_after = eng.swap.streamed_bytes([0, 1])
    assert streamed_after == 2 * pb
    predicted = iter_time_with_interval_kv(times, eng.interval,
                                           streamed_after, pb)
    assert eng.clock_s - t0 == _pytest.approx(predicted, rel=1e-9)
    eng.kv.check_invariants()


def test_flash_matches_model_chunked_attention():
    """Kernel and the jnp chunked implementation used at dry-run scale must
    agree (they are the same algorithm at different layers)."""
    from repro.configs import get_config
    from repro.configs.reduced import reduce_config
    from repro.models import layers as L

    cfg = reduce_config(get_config("deepseek-7b"))
    b, s, h, d = 2, 64, cfg.num_heads, cfg.resolved_head_dim
    q = jax.random.normal(KEY, (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(7), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(8), (b, s, h, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32)
    chunked = L.attn_chunked(cfg, q, k, v, pos, pos, chunk=16)
    kern = ops.flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), causal=True,
                               block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(chunked),
                               np.asarray(kern.transpose(0, 2, 1, 3)),
                               rtol=2e-4, atol=2e-4)
