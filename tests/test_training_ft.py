"""Training loop, gradient compression, checkpoint/restore (incl. elastic),
watchdog, data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs import LM_SHAPES, get_config
from repro.launch.mesh import make_mesh_compat
from repro.configs.base import ShapeSpec
from repro.configs.reduced import reduce_config
from repro.data.pipeline import DataConfig, SyntheticTokenStream, request_stream
from repro.ft import checkpoint as ckpt
from repro.ft.watchdog import StepWatchdog, WatchdogConfig
from repro.models import spec as S
from repro.models.model import build_model
from repro.training.compression import reduce_gradients
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import (TrainConfig, build_train_step,
                                       init_train_state)

# compile-heavy (full JAX jit of models/kernels): excluded from the fast CI
# tier, run in the nightly full suite
pytestmark = pytest.mark.slow

CFG = reduce_config(get_config("deepseek-7b"), layers=2)
SHAPE = ShapeSpec("tiny", 16, 2, "train")


def _batch(step=0):
    ds = SyntheticTokenStream(CFG, SHAPE, DataConfig(seed=1))
    return ds.batch(step)


def test_train_loss_decreases():
    model = build_model(CFG)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    step = jax.jit(build_train_step(
        model, TrainConfig(optimizer=AdamWConfig(lr=1e-2, warmup_steps=1),
                           remat=True)))
    batch = _batch(0)  # overfit one batch
    losses = []
    for _ in range(8):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_train_microbatch_accumulation_matches_big_batch():
    model = build_model(CFG)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    b = _batch(0)
    # microbatched: split batch into 2 along a new leading dim
    mb = jax.tree.map(lambda x: x.reshape(2, 1, *x.shape[1:]), b)
    step1 = jax.jit(build_train_step(model, TrainConfig(microbatches=2)))
    stepf = jax.jit(build_train_step(model, TrainConfig(microbatches=1)))
    p1, _, m1 = step1(params, opt, mb)
    pf, _, mf = stepf(params, opt, b)
    np.testing.assert_allclose(float(m1["loss"]), float(mf["loss"]),
                               rtol=2e-2)


@pytest.mark.parametrize("mode", ["none", "bf16", "int8_ef"])
def test_gradient_compression_modes(mode):
    devs = jax.devices()
    mesh = make_mesh_compat((1,), ("data",))
    g = {"w": jnp.linspace(-1, 1, 64).reshape(8, 8)}

    def f(gr):
        red, err = reduce_gradients(gr, "data", mode=mode)
        return red

    red = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P()))(g)
    tol = {"none": 1e-6, "bf16": 1e-2, "int8_ef": 2e-2}[mode]
    np.testing.assert_allclose(np.asarray(red["w"]), np.asarray(g["w"]),
                               rtol=tol, atol=tol)


def test_int8_error_feedback_converges():
    """With error feedback, repeated reductions of the same gradient have
    bounded accumulated bias (residual carried, not dropped)."""
    mesh = make_mesh_compat((1,), ("data",))
    g = {"w": jnp.asarray([[1e-4, 1.0, -0.5, 0.37]] * 2)}

    def f(gr, err):
        return reduce_gradients(gr, "data", mode="int8_ef", error_state=err)

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P()),
                           out_specs=(P(), P())))
    err = {"w": jnp.zeros_like(g["w"])}
    acc = jnp.zeros_like(g["w"])
    for _ in range(16):
        red, err = fn(g, err)
        acc = acc + red["w"]
    np.testing.assert_allclose(np.asarray(acc) / 16, np.asarray(g["w"]),
                               rtol=5e-3, atol=5e-4)


def test_checkpoint_roundtrip(tmp_path):
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(2))
    d = str(tmp_path / "ckpt")
    ckpt.save_checkpoint(d, 7, params, extra={"note": "x"})
    assert ckpt.latest_step(d) == 7
    restored, extra = ckpt.restore_checkpoint(d, 7, S.abstract(model.spec))
    assert extra == {"note": "x"}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)),
        params, restored)


def test_checkpoint_async_and_gc(tmp_path):
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(2))
    d = str(tmp_path / "ckpt")
    ac = ckpt.AsyncCheckpointer(d, keep=2)
    for s in (1, 2, 3):
        ac.save(s, params)
    ac.wait()
    steps = sorted(int(x.split("_")[1]) for x in os.listdir(d))
    assert steps == [2, 3]
    assert not any(".tmp" in x for x in os.listdir(d))


def test_elastic_restore_onto_new_mesh(tmp_path):
    """Save unsharded, restore with explicit (1,1) mesh shardings — the
    single-device analogue of scaling the data axis."""
    from repro.sharding.rules import make_rules
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(3))
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, 1, params)
    mesh = make_mesh_compat((1, 1), ("data", "model"))
    rules = make_rules(CFG, mesh)
    shd = S.shardings(model.spec, mesh, rules)
    restored, _ = ckpt.restore_checkpoint(d, 1, S.abstract(model.spec), shd)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)),
        params, restored)


def test_watchdog_flags_stragglers():
    flagged = []
    wd = StepWatchdog(WatchdogConfig(warmup_steps=2, slow_factor=1.5),
                      on_straggler=lambda s, dt, e: flagged.append(s))
    for _ in range(10):
        wd.observe(0.1)
    wd.observe(0.3)
    assert flagged
    with pytest.raises(TimeoutError):
        StepWatchdog(WatchdogConfig(hard_timeout_s=0.05)).observe(0.1)


def test_data_determinism_and_sharding():
    ds = SyntheticTokenStream(CFG, SHAPE, DataConfig(seed=9))
    b1, b2 = ds.batch(3), ds.batch(3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = ds.batch(4)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    # labels are next-token shifted views of the same stream
    reqs = request_stream(DataConfig(seed=1), 10, ttft_slo_s=1.0,
                          tpot_slo_s=0.1)
    assert len(reqs) == 10
    assert all(r.arrival_s >= 0 for r in reqs)
    assert sorted(r.arrival_s for r in reqs) == [r.arrival_s for r in reqs]
