"""Runtime tests: checkpoint roundtrip, fault-tolerant restart, stragglers,
elastic resize, gradient compression."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import ShapeCell
from repro.data.pipeline import DataConfig, PrefetchingLoader, synthetic_batch
from repro.models import transformer as tf
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.compression import _dequantize, _quantize, init_error
from repro.runtime.ft import FailurePlan, FTConfig, FaultTolerantRunner

CFG = reduced(get_config("stablelm-3b"), layers=2, d_model=64)
SHAPE = ShapeCell("t", 32, 4, "train")


def tiny_state():
    params = tf.init_params(CFG, jax.random.PRNGKey(0))
    opt = adamw_init(params, AdamWConfig())
    return params, opt


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    params, opt = tiny_state()
    mgr = CheckpointManager(tmp_path)
    mgr.save(7, {"params": params, "opt": opt}, blocking=True)
    like = jax.eval_shape(lambda: {"params": params, "opt": opt})
    restored, step = mgr.restore(like)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_latest(tmp_path):
    params, opt = tiny_state()
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"params": params, "opt": opt}, blocking=True)
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async_overlaps(tmp_path):
    params, opt = tiny_state()
    mgr = CheckpointManager(tmp_path)
    t0 = time.time()
    mgr.save(1, {"params": params, "opt": opt})   # non-blocking
    submit_time = time.time() - t0
    mgr.wait()
    assert submit_time < 1.0                      # snapshot is cheap
    assert mgr.latest_step() == 1


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------
def make_runner(tmp_path, plan=None, ckpt_every=5):
    from repro.launch.steps import make_train_step
    opt_cfg = AdamWConfig(total_steps=50)
    step_fn = jax.jit(make_train_step(CFG, opt_cfg))
    mgr = CheckpointManager(tmp_path)
    return FaultTolerantRunner(
        step_fn, mgr, FTConfig(checkpoint_every=ckpt_every),
        plan), mgr


def batches():
    step = 0
    while True:
        yield synthetic_batch(CFG, SHAPE, step)
        step += 1


@pytest.mark.slow
def test_ft_runner_trains(tmp_path):
    runner, mgr = make_runner(tmp_path)
    params, opt = tiny_state()
    p, o, losses = runner.run(params, opt, batches(), num_steps=12)
    assert len(losses) == 12
    assert losses[-1] < losses[0]              # tiny model memorizes fast
    assert mgr.latest_step() == 12


@pytest.mark.slow
def test_ft_runner_recovers_from_failure(tmp_path):
    plan = FailurePlan(fail_steps=(7,))
    runner, mgr = make_runner(tmp_path, plan, ckpt_every=5)
    params, opt = tiny_state()
    p, o, losses = runner.run(params, opt, batches(), num_steps=15)
    events = [e["event"] for e in runner.events]
    assert "failure" in events
    assert "restored" in events
    # Completed the full budget despite the failure.
    assert mgr.latest_step() == 15


@pytest.mark.slow
def test_ft_runner_flags_stragglers(tmp_path):
    plan = FailurePlan(slow_steps=tuple(range(20, 24)), slow_seconds=0.4)
    runner, mgr = make_runner(tmp_path, plan, ckpt_every=50)
    params, opt = tiny_state()
    runner.run(params, opt, batches(), num_steps=26)
    events = [e["event"] for e in runner.events]
    assert "straggler" in events


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
def test_quantize_dequantize_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
    q, scale = _quantize(x)
    back = _dequantize(q, scale, x.shape, x.dtype)
    # int8 symmetric: error ≤ scale/2 per block.
    max_scale = float(scale.max())
    assert float(jnp.abs(back - x).max()) <= max_scale * 0.51


def test_compressed_psum_matches_uncompressed(tmp_path):
    """2-pod shard_map: compressed all-reduce ≈ exact mean within int8
    tolerance, and error feedback captures the residual."""
    from conftest import run_in_subprocess
    run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.runtime.compression import compressed_psum_pod, init_error
mesh = jax.make_mesh((2,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((2, 64)).astype(np.float32))}
e = init_error(g)
def f(g, e):
    out, new_e = compressed_psum_pod(g, e, "pod")
    return out, new_e
fn = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                   out_specs=(P("pod"), P("pod")))
out, new_e = fn(g, e)
exact = (np.asarray(g["w"])[0] + np.asarray(g["w"])[1]) / 2
got = np.asarray(out["w"])
err = np.abs(got[0] - exact).max()
assert err < 0.05, f"compression error too big: {err}"
resid = np.asarray(new_e["w"])
assert np.abs(resid).max() < 0.05
print("OK", err)
""", devices=2)


# ---------------------------------------------------------------------------
# elastic resize
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_elastic_resize_preserves_params():
    from conftest import run_in_subprocess
    run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.configs.base import ShapeCell
from repro.data.pipeline import synthetic_batch
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime.elastic import build, mesh_from_devices, resize
from repro.models import transformer as tf
cfg = reduced(get_config("stablelm-3b"), layers=2, d_model=64)
params = tf.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params, AdamWConfig())
devs = jax.devices()
m1 = mesh_from_devices(devs, data=4, model=2)      # 8 chips
st = build(cfg, m1, params, opt)
batch = synthetic_batch(cfg, ShapeCell("t", 32, 4, "train"), 0)
p, o, loss1 = st.step_fn(st.params, st.opt_state, batch)
st.params, st.opt_state = p, o
# shrink to 4 chips (simulated node loss / CR power cut)
m2 = mesh_from_devices(devs, data=2, model=2)
st2 = resize(st, cfg, m2)
before = jax.tree.leaves(jax.tree.map(np.asarray, st.params))
after = jax.tree.leaves(jax.tree.map(np.asarray, st2.params))
for a, b in zip(before, after):
    np.testing.assert_array_equal(a, b)
p2, o2, loss2 = st2.step_fn(st2.params, st2.opt_state, batch)
assert np.isfinite(float(loss2))
print("resize OK", float(loss1), float(loss2))
""", devices=8)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_loader_deterministic_and_sharded():
    b0 = synthetic_batch(CFG, SHAPE, 3, DataConfig(host_index=0, host_count=2))
    b0b = synthetic_batch(CFG, SHAPE, 3, DataConfig(host_index=0, host_count=2))
    b1 = synthetic_batch(CFG, SHAPE, 3, DataConfig(host_index=1, host_count=2))
    np.testing.assert_array_equal(b0["tokens"], b0b["tokens"])
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    assert b0["tokens"].shape[0] == SHAPE.global_batch // 2


def test_prefetching_loader_yields(tmp_path):
    loader = PrefetchingLoader(CFG, SHAPE, DataConfig())
    b = next(iter(loader))
    assert b["tokens"].shape == (SHAPE.global_batch, SHAPE.seq_len)
    loader.set_throttle(0.5)
    b2 = next(iter(loader))
    assert b2["tokens"].shape == b["tokens"].shape
    loader.close()
