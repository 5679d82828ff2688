"""Compile the main-path kernels for a described TPU v5e, at real widths.

No chip is attached: `jax.experimental.topologies` describes a v5e:2x2
and the TPU compiler runs ahead of time, refusing what the chip's
compiler would refuse (unlowerable primitives, misaligned slices, VMEM
overflow). The topology is described inside a module fixture, never at
import, so every test worker collects the same tests and only the
worker running this file loads the TPU library.

Also pins the `dr_features` kernel's triangular-mask prefix sum against
`jnp.cumsum` (the al_step kernel's time-major running sum is pinned in
`tests/test_al_step.py`).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.al_step.kernel import al_step_pallas
from repro.kernels.al_step.ops import kernel_layout, time_major
from repro.kernels.al_step.ref import prefix_sum
from repro.kernels.dr_features.kernel import dr_features_pallas

W, T = 100_000, 48


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("hours", [24, 48])
def test_prefix_sum_matches_cumsum(reverse, hours):
    x = np.random.default_rng(hours).normal(size=(37, hours))
    x = jnp.asarray(x, jnp.float32)
    want = (jnp.flip(jnp.cumsum(jnp.flip(x, 1), 1), 1) if reverse
            else jnp.cumsum(x, 1))
    np.testing.assert_allclose(np.asarray(prefix_sum(x, reverse=reverse)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_dr_features_compiles_for_v5e(one_chip):
    s = _shape(one_chip, (W, T))
    hlo = jax.jit(lambda d, u, j: dr_features_pallas(
        d, u, j, interpret=False)).lower(s, s, s).compile().as_text()
    assert "tpu_custom_call" in hlo


def _compile_al_step(one_chip, mode, moments, cvec_rows, hours=T):
    """The time-major kernel on operands that `kernel_layout` lays out
    from (W, hours) inputs, with a (`cvec_rows`, hours) carbon term."""
    s = _shape(one_chip, (W, hours))
    mv = _shape(one_chip, (W, hours), moments)

    def step(x, m, v, u, j, lo, hi, rows, cvec, scal):
        consts, block_w, wp = kernel_layout(u, j, lo, hi, rows, cvec)
        return al_step_pallas(*(time_major(a, wp) for a in (x, m, v)),
                              *consts, scal, mode=mode, k_steps=8,
                              block_w=block_w, interpret=False)

    hlo = jax.jit(step).lower(
        s, mv, mv, s, s, s, s, _shape(one_chip, (W, 12)),
        _shape(one_chip, (cvec_rows, hours)),
        _shape(one_chip, (1, 8))).compile()
    assert "tpu_custom_call" in hlo.as_text()


@pytest.mark.parametrize("moments", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["cr1", "cr2"])
def test_al_step_compiles_for_v5e(one_chip, mode, moments):
    """The fleet-global (T, 1) carbon term of a single-region fleet."""
    _compile_al_step(one_chip, mode, moments, 1)


@pytest.mark.parametrize("moments", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["cr1", "cr2"])
def test_al_step_per_row_cvec_compiles_for_v5e(one_chip, mode, moments):
    """The per-workload (T, Wp) carbon term of a multi-region fleet."""
    _compile_al_step(one_chip, mode, moments, W)


@pytest.mark.parametrize("cvec_rows", [1, W], ids=["fleet", "per_row"])
@pytest.mark.parametrize("hours", [30, 47])
def test_al_step_odd_horizon_compiles_for_v5e(one_chip, hours, cvec_rows):
    """Horizons that are not whole 8-row vregs: 47 leaves the running
    sums a 7-row last group, and 30 (a day tail) a 6-row one plus hours
    past the last whole day."""
    _compile_al_step(one_chip, "cr2", jnp.float32, cvec_rows, hours)


def _fleet_at_scale(sharding, fleet=None):
    """A W-row fleet (default: synthetic) and a cold engine state as
    shapes on `sharding` (built from a 16-row fleet: only shapes are
    compiled)."""
    from repro.core.engine import EngineState
    from repro.core.fleet_solver import _jit_view, synthetic_fleet

    def at_scale(a):
        a = np.asarray(a)
        shape = (W,) + a.shape[1:] if a.ndim and a.shape[0] == 16 \
            else a.shape
        dtype = jnp.float32 if a.dtype == np.float64 else a.dtype
        return _shape(sharding, shape, dtype)

    fleet = jax.tree.map(at_scale, _jit_view(
        synthetic_fleet(16, hours=T) if fleet is None else fleet))
    state = EngineState(x=_shape(sharding, (W, T)),
                        lam_eq=_shape(sharding, (0,)),
                        lam_in=_shape(sharding, (0,)),
                        mu=_shape(sharding, ()))
    return fleet, state


def test_cr1_solve_compiles_for_v5e(one_chip, monkeypatch):
    """The whole jitted CR1 solve on the kernel path, as the chip runs it
    by default, with the report's device sums and hour 0 it returns."""
    from repro.core.api import _TICK, CR1

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    fleet, state = _fleet_at_scale(one_chip)
    compiled = _TICK["cr1"].plain.lower(fleet, _shape(one_chip, ()), state,
                                        steps=CR1.default_steps,
                                        use_kernel=True, order=None,
                                        offsets=(0, W)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_resident_cr2_tick_compiles_for_v5e(one_chip, monkeypatch):
    """A warm CR2 tick on the kernel path as the `cr2_ticks` cell runs
    it: the resident 8-region fleet slid by a traced hour offset, the
    fairness references made from the fleet's equal-cap curtailment slid
    with it, 6 AL rounds, hour 0 and the report sums."""
    from repro.core.api import _TICK
    from repro.core.engine import EngineState
    from repro.core.fleet_solver import synthetic_regional_fleet

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    states = ["CA", "WA", "AZ", "CO", "TX", "IL", "NY", "FL"]
    fleet, _ = _fleet_at_scale(one_chip, synthetic_regional_fleet(
        16, states, hours=T, seed=0))
    state = EngineState(x=_shape(one_chip, (W, T)),
                        lam_eq=_shape(one_chip, (W,)),
                        lam_in=_shape(one_chip, (0,)),
                        mu=_shape(one_chip, ()))
    d_cap = _shape(one_chip, (W, T))
    R = len(states)
    compiled = _TICK["cr2"].plain.lower(
        fleet, d_cap, state, None, None, _shape(one_chip, (), jnp.int32),
        steps=100, outer=6, use_kernel=True, shift=1, reset_mu=True,
        offsets=tuple(range(0, W + 1, W // R))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def _compile_cr3_market(sharding, mesh=None):
    """A whole CR3 tick on the kernel path, compiled for `sharding`: the
    best responses and the clearing loop of 8 regional markets of W/8
    rows each (with `mesh`, each best response sharded over it)."""
    from repro.core.api import CR3, _cr3_cleared
    from repro.core.engine import EngineState
    from repro.core.fleet_solver import synthetic_regional_fleet

    states = ["CA", "WA", "AZ", "CO", "TX", "IL", "NY", "FL"]
    fleet, _ = _fleet_at_scale(sharding, synthetic_regional_fleet(
        16, states, hours=T, seed=0))
    state = EngineState(x=_shape(sharding, (W, T)),
                        lam_eq=_shape(sharding, (0,)),
                        lam_in=_shape(sharding, (W,)),
                        mu=_shape(sharding, ()))
    R = len(states)
    pair = (_shape(sharding, (R,)),) * 2
    fiscal = ((_shape(sharding, (R, T)),) * 2, pair, pair)
    return _cr3_cleared.lower(
        fleet, pair, _shape(sharding, ()), _shape(sharding, (W, 1)), state,
        _shape(sharding, (), jnp.int32), fiscal, None,
        offsets=tuple(range(0, W + 1, W // R)), steps=150, outer=CR3.outer,
        use_kernel=True, shift=1, reset_mu=True, mesh=mesh).compile()


def test_cr3_market_compiles_for_v5e(one_chip, monkeypatch):
    """A whole CR3 tick on one chip, as the `cr3_ticks` cell runs it."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    compiled = _compile_cr3_market(one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_sharded_cr3_market_compiles_for_v5e_2x2(topo, monkeypatch):
    """The CR3 market over four chips: the shard_map best response nested
    in the clearing `while_loop`, with the per-region sums of row-sharded
    plans outside the shard_map."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.launch.mesh import FLEET_AXIS

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    mesh = Mesh(np.asarray(topo.devices), (FLEET_AXIS,))
    compiled = _compile_cr3_market(NamedSharding(mesh, PartitionSpec()),
                                   mesh)
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_cr1_solve_compiles_for_v5e_2x2(topo, monkeypatch):
    """The CR1 solve sharded over four chips: every Pallas call must sit
    inside a shard_map (the compiler cannot partition a kernel)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core.api import _TICK, CR1
    from repro.launch.mesh import FLEET_AXIS

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    mesh = Mesh(np.asarray(topo.devices), (FLEET_AXIS,))
    rep = NamedSharding(mesh, PartitionSpec())
    fleet, state = _fleet_at_scale(rep)
    norms = (_shape(rep, ()),) * 3
    compiled = _TICK["cr1"].plain.lower(
        fleet, _shape(rep, ()), state, norms, mesh=mesh,
        steps=CR1.default_steps, use_kernel=True, order=None,
        offsets=(0, W)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chip_smoke_refuses_cpu():
    """No fallback: on a CPU backend the chip smoke test exits nonzero,
    names the platform, and prints no result line."""
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    res = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert res.returncode != 0
    assert "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout
