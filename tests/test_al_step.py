"""Fused AL inner-step kernel (kernels/al_step) vs its jnp oracle, the
chunked `fused_inner` dispatcher, and the fused-vs-generic solve paths.

Tolerance strategy (see the note in `kernels/al_step/ref.py`): the
analytic subgradient is discontinuous at the batch-penalty hinges, so a
1-ulp arithmetic difference (Pallas interpret mode associates cumsum
reductions differently than plain XLA) can flip an indicator and grow
into O(1) iterate differences over a few steps. Bitwise-tight (<=1e-5)
kernel-vs-oracle checks therefore use hinge-stable inputs — RTS-only
fleets (smooth cubic penalty, no hinges) for multi-step/vmap/padding
coverage, batch rows only for a single step from a hinge-stable point —
while mixed-fleet semantics are checked at the solve level against the
independent autodiff engine path with a pp-scale tolerance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental import pallas as pl

from repro.core.api import CR1, CR2, SolveContext, solve
from repro.core.engine import EngineConfig
from repro.core.fleet_solver import _bounds, _projection, synthetic_fleet
from repro.kernels.al_step.kernel import al_step_pallas
from repro.kernels.al_step.ops import (kernel_layout, make_fused_inner,
                                      pack_rows, time_major)
from repro.kernels.al_step.ref import _project, al_step_ref, running_sum

TOL = 1e-5


def _kernel(x, m, v, usage, jobs, lo, hi, rowp, cvec, scal, block_w=None,
            **kw):
    """`al_step_pallas` on `al_step_ref`'s (W, T) inputs, laid out by
    `kernel_layout` as `make_fused_inner` lays them out, and sliced
    back."""
    consts, block_w, wp = kernel_layout(usage, jobs, lo, hi, rowp, cvec,
                                        block_w)
    out = al_step_pallas(*(time_major(a, wp) for a in (x, m, v)), *consts,
                         scal, block_w=block_w, interpret=True, **kw)
    return tuple(o[:, :x.shape[0]].T for o in out)


def _rts_only(p):
    """Recast every batch row as an RTS row (smooth cubic penalty only):
    hinge-free inputs for bitwise-tight kernel-vs-oracle parity."""
    W = p.W
    cubic = np.array([2e-4, 1.5e-3, 0.04], np.float64)
    rts = np.where(np.asarray(p.is_batch)[:, None], cubic, p.rts_coeffs)
    return dataclasses.replace(p, is_batch=np.zeros(W, bool),
                               betas=np.zeros((W, 3)), rts_coeffs=rts)


def _raw_inputs(p, mode, seed=1, stable=False):
    """Random-but-feasible (x, m, v, usage, jobs, lo, hi, rowp, cvec,
    scal) in the kernel's packed layout. `stable=True` keeps x strictly
    positive and away from hinge boundaries (cumsums and the batch
    penalty argument z stay clearly one-sided for one step)."""
    rng = np.random.default_rng(seed)
    lo, hi = (np.asarray(a, np.float32) for a in _bounds(p))
    if stable:
        x = np.clip(0.25 * np.asarray(p.usage) + 0.01, lo, hi)
    else:
        x = np.clip(rng.normal(0.0, 0.3, lo.shape), lo, hi)
    x = x.astype(np.float32)
    m = rng.normal(0.0, 0.01, x.shape).astype(np.float32)
    v = np.abs(rng.normal(0.0, 1e-4, x.shape)).astype(np.float32)
    refs = (np.abs(rng.normal(1.0, 0.2, p.W)).astype(np.float32)
            if mode == "cr2" else None)
    row10 = pack_rows(jnp.asarray(p.rts_coeffs), jnp.asarray(p.betas),
                      jnp.asarray(p.k), jnp.asarray(p.x2_kind),
                      jnp.asarray(p.is_batch), refs=refs)
    lam = (rng.normal(0.0, 0.5, (p.W, 1)).astype(np.float32)
           if mode == "cr2" else np.zeros((p.W, 1), np.float32))
    rowp = jnp.concatenate([row10, jnp.asarray(lam),
                            jnp.zeros((p.W, 1), jnp.float32)], axis=1)
    cvec = rng.normal(-0.5, 0.2, (1, p.T)).astype(np.float32)
    # [coef0, mu, inv_scale, lr_scale, t0, 0, 0, 0]
    scal = np.array([[1.45, 10.0, 0.8, 0.02, 3.0, 0, 0, 0]], np.float32)
    arrs = (x, m, v, np.asarray(p.usage, np.float32),
            np.asarray(p.jobs, np.float32), lo, hi)
    return tuple(jnp.asarray(a) for a in arrs) + (rowp, jnp.asarray(cvec),
                                                  jnp.asarray(scal))


@pytest.mark.parametrize("mode", ["cr1", "cr2"])
@pytest.mark.parametrize("k_steps", [1, 4, 7])
def test_al_step_matches_ref_rts(mode, k_steps):
    """Hinge-free multi-step parity: kernel == oracle to <=1e-5 on the
    iterate AND both Adam moments."""
    p = _rts_only(synthetic_fleet(12, hours=48, seed=0))
    args = _raw_inputs(p, mode, seed=k_steps)
    out = _kernel(*args, mode=mode, k_steps=k_steps)
    ref = al_step_ref(*args, mode=mode, k_steps=k_steps)
    for o, r, name in zip(out, ref, "xmv"):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=TOL,
                                   atol=TOL, err_msg=name)


@pytest.mark.parametrize("mode", ["cr1", "cr2"])
def test_al_step_batch_rows_single_step(mode):
    """Mixed RTS+batch fleet, one step from a hinge-stable point: the
    hinged batch gradient path agrees to <=1e-5 too."""
    p = synthetic_fleet(12, hours=48, seed=0)
    assert np.asarray(p.is_batch).any()          # exercise both branches
    args = _raw_inputs(p, mode, seed=5, stable=True)
    out = _kernel(*args, mode=mode, k_steps=1)
    ref = al_step_ref(*args, mode=mode, k_steps=1)
    for o, r, name in zip(out, ref, "xmv"):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=TOL,
                                   atol=TOL, err_msg=name)


@pytest.mark.parametrize("W,block_w", [
    pytest.param(5, None, id="5"), pytest.param(130, None, id="130"),
    pytest.param(300, 128, id="300-block128")])
def test_al_step_padding(W, block_w):
    """Workload padding (W -> block_w multiples) is inert: padded lanes
    never leak into the true workloads, over one or several grid blocks,
    and outputs slice back to (W, T)."""
    p = _rts_only(synthetic_fleet(W, hours=48, seed=2))
    args = _raw_inputs(p, "cr1", seed=0)
    out = _kernel(*args, mode="cr1", k_steps=2, block_w=block_w)
    ref = al_step_ref(*args, mode="cr1", k_steps=2)
    assert out[0].shape == (W, p.T)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=TOL, atol=TOL)


def test_al_step_bf16_moments():
    """bf16 moment storage: kernel and oracle share the cast points, so
    parity stays tight; moment dtypes round-trip."""
    p = _rts_only(synthetic_fleet(8, hours=48, seed=1))
    x, m, v, *rest = _raw_inputs(p, "cr1", seed=3)
    m, v = m.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    out = _kernel(x, m, v, *rest, mode="cr1", k_steps=4)
    ref = al_step_ref(x, m, v, *rest, mode="cr1", k_steps=4)
    assert out[1].dtype == out[2].dtype == jnp.bfloat16
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=TOL, atol=TOL)


def test_al_step_vmap_over_scalars():
    """The sweep/ensemble lanes vmap the packed scalars (per-λ coef0):
    batched kernel == per-lane oracle."""
    p = _rts_only(synthetic_fleet(8, hours=48, seed=4))
    x, m, v, u, j, lo, hi, rowp, cvec, scal = _raw_inputs(p, "cr1", seed=2)
    scals = jnp.stack([scal.at[0, 0].set(c) for c in (0.5, 1.45, 3.0)])

    def run(s):
        return _kernel(x, m, v, u, j, lo, hi, rowp, cvec, s, mode="cr1",
                       k_steps=3)[0]

    batched = jax.vmap(run)(scals)
    for i in range(3):
        ref = al_step_ref(x, m, v, u, j, lo, hi, rowp, cvec, scals[i],
                          mode="cr1", k_steps=3)[0]
        np.testing.assert_allclose(np.asarray(batched[i]), np.asarray(ref),
                                   rtol=TOL, atol=TOL)


@pytest.mark.parametrize("mode", ["cr1", "cr2"])
def test_al_step_per_row_cvec(mode):
    """Multi-region fleets price carbon per workload: a (W, T) cvec is
    tiled like x (and padded) instead of replicated."""
    p = _rts_only(synthetic_fleet(130, hours=48, seed=7))
    *args, cvec, scal = _raw_inputs(p, mode, seed=4)
    cvec = jnp.asarray(np.random.default_rng(4).normal(
        -0.5, 0.2, (p.W, p.T)), jnp.float32)
    out = _kernel(*args, cvec, scal, mode=mode, k_steps=3)
    ref = al_step_ref(*args, cvec, scal, mode=mode, k_steps=3)
    for o, r, name in zip(out, ref, "xmv"):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=TOL,
                                   atol=TOL, err_msg=name)


def _in_kernel(fn, x):
    """`fn` applied to the whole of `x` inside a Pallas kernel body
    (interpret mode), as the al_step kernel applies it to a block."""
    def body(x_ref, o_ref):
        o_ref[...] = fn(x_ref[...])
    return pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct(
        x.shape, x.dtype), interpret=True)(x)


@pytest.mark.parametrize("where", ["jnp", "pallas"])
@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("T", [24, 47, 48])
def test_running_sum_matches_cumsum(T, reverse, where):
    """The time-major running sum (log-step within 8-row groups, then a
    carry between groups) is a cumsum down the hours, forward and
    reversed, for T a multiple of 8 or not."""
    x = np.random.default_rng(T).normal(size=(T, 256)).astype(np.float32)
    want = (np.cumsum(x[::-1], axis=0)[::-1] if reverse
            else np.cumsum(x, axis=0))
    fn = lambda a: running_sum(a, reverse=reverse)     # noqa: E731
    got = (jax.jit(fn)(jnp.asarray(x)) if where == "jnp"
           else _in_kernel(fn, jnp.asarray(x)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("where", ["jnp", "pallas"])
@pytest.mark.parametrize("T", [30, 60])
def test_project_matches_generic_projection(T, where):
    """Time-major day sums: the kernel's projection equals the generic
    engine's reshape-mean projection, with hours past the last whole day
    (T not a multiple of day_hours) left out of every day."""
    p = synthetic_fleet(40, hours=T, seed=3)
    assert p.day_hours == 24 and np.asarray(p.is_batch).any()
    lo, hi = (jnp.asarray(a, jnp.float32) for a in _bounds(p))
    rng = np.random.default_rng(T)
    d = jnp.asarray(rng.normal(0.0, 0.4, (p.W, T)), jnp.float32)
    want = _projection(p, lo, hi)(d)
    isb = jnp.asarray(p.is_batch, jnp.float32)[None, :]
    wp = 128
    stacked = jnp.concatenate([time_major(a, wp) for a in (d, lo, hi)]
                              + [jnp.pad(isb, ((0, 0), (0, wp - p.W)))])

    def fn(a):
        return _project(a[:T], a[T:2 * T], a[2 * T:3 * T], a[3 * T:],
                        p.day_hours)

    got = (fn(stacked) if where == "jnp"
           else _in_kernel(lambda a: jnp.concatenate(
               [fn(a), a[T:]]), stacked)[:T])
    np.testing.assert_allclose(np.asarray(got[:, :p.W]).T,
                               np.asarray(want), rtol=TOL, atol=TOL)


def _eqns(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs nested in it, except
    inside a Pallas kernel body."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("mode", ["cr1", "cr2"])
def test_fused_inner_layout_promises(mode):
    """The kernel keeps 10 operands and 3 outputs (the benchmark finds
    it by that signature), and the scan of kernel calls pads and slices
    nothing: the layout is set once, outside it."""
    p = synthetic_fleet(200, hours=48, seed=0)
    lo, hi = _bounds(p)
    cfg = EngineConfig(inner_steps=16, outer_steps=1)
    refs = np.ones(p.W, np.float32) if mode == "cr2" else None
    row = pack_rows(jnp.asarray(p.rts_coeffs), jnp.asarray(p.betas),
                    jnp.asarray(p.k), jnp.asarray(p.x2_kind),
                    jnp.asarray(p.is_batch), refs=refs)
    cvec = -0.01 * jnp.asarray(p.mci, jnp.float32)[None, :]
    fused = make_fused_inner(
        jnp.asarray(p.usage, jnp.float32), jnp.asarray(p.jobs, jnp.float32),
        lo.astype(jnp.float32), hi.astype(jnp.float32), row, cvec,
        mode=mode, cfg=cfg, step_scale=1.0, coef0=1.45, scale=2.0,
        interpret=True, day_hours=p.day_hours)
    lam = jnp.zeros(p.W if mode == "cr2" else 0)
    jaxpr = jax.make_jaxpr(fused)(jnp.zeros((p.W, p.T)), lam, jnp.zeros(0),
                                  jnp.asarray(10.0)).jaxpr
    calls = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]
    assert calls and all(len(e.invars) == 10 and len(e.outvars) == 3
                         for e in calls)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    body = {e.primitive.name for e in _eqns(scans[0].params["jaxpr"].jaxpr)}
    assert "pallas_call" in body
    assert not body & {"pad", "slice", "dynamic_slice", "transpose"}, body


def test_al_step_rejects_bad_mode():
    p = _rts_only(synthetic_fleet(4, hours=48, seed=0))
    args = _raw_inputs(p, "cr1")
    with pytest.raises(ValueError, match="cr1|cr2"):
        al_step_ref(*args, mode="cr3", k_steps=1)


@pytest.mark.parametrize("steps,k_steps", [(13, 5), (8, 8), (6, 16)])
def test_fused_inner_chunking_matches_oracle_path(steps, k_steps):
    """`make_fused_inner` splits inner_steps into full chunks + remainder
    inside a lax.scan; the Pallas route must equal the oracle route for
    uneven splits, exact fits, and k_steps > inner_steps (clamped)."""
    p = _rts_only(synthetic_fleet(8, hours=48, seed=6))
    lo, hi = _bounds(p)
    cfg = EngineConfig(inner_steps=steps, outer_steps=1)
    row = pack_rows(jnp.asarray(p.rts_coeffs), jnp.asarray(p.betas),
                    jnp.asarray(p.k), jnp.asarray(p.x2_kind),
                    jnp.asarray(p.is_batch))
    cvec = -0.01 * jnp.asarray(p.mci, jnp.float32)[None, :]
    kw = dict(mode="cr1", cfg=cfg, step_scale=1.0, coef0=1.45,
              k_steps=k_steps, day_hours=p.day_hours)
    mk = lambda **o: make_fused_inner(           # noqa: E731
        jnp.asarray(p.usage, jnp.float32), jnp.asarray(p.jobs, jnp.float32),
        lo.astype(jnp.float32), hi.astype(jnp.float32), row, cvec,
        **kw, **o)
    x0 = jnp.zeros((p.W, p.T), jnp.float32)
    zl = jnp.zeros(0)
    mu = jnp.asarray(10.0)
    a = mk(interpret=True)(x0, zl, zl, mu)
    b = mk(use_ref=True)(x0, zl, zl, mu)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=TOL,
                               atol=TOL)


@pytest.mark.slow
@pytest.mark.parametrize("policy,steps", [(CR1(lam=1.45), 200),
                                          (CR2(cap_frac=0.78, outer=3),
                                           120)])
def test_fused_solve_matches_generic_engine(policy, steps):
    """Semantic check on the real mixed fleet: the fused-kernel solve and
    the generic autodiff engine land on the same optimum (pp scale) —
    independent gradient implementations, so hinge-chaos tolerance."""
    p = synthetic_fleet(16, hours=48, seed=0)
    a = solve(p, policy, ctx=SolveContext(use_kernel=False, steps=steps))
    b = solve(p, policy, ctx=SolveContext(use_kernel=True, steps=steps))
    assert abs(a.carbon_reduction_pct - b.carbon_reduction_pct) < 0.05
    assert abs(a.total_penalty_pct - b.total_penalty_pct) < 0.05
    assert b.preservation_violation < 1e-3
