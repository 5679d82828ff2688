"""Device-sharded fleet engine: parity vs single-device, W padding, and the
donated-buffer streaming tick.

Multi-device cases run in subprocesses with 8 virtual CPU devices
(`conftest.run_in_subprocess`) — the main pytest process must stay
single-device. `scripts/ci.sh` also runs this file in its multi-device
lane on every PR.
"""
import numpy as np
import pytest

from conftest import run_in_subprocess


# ---------------------------------------------------------------------------
# Host-side helpers (no mesh needed)
# ---------------------------------------------------------------------------
def test_pad_fleet_rows_are_inert():
    import jax.numpy as jnp
    from repro.core.fleet_solver import (_bounds, fleet_penalties, pad_fleet,
                                         synthetic_fleet)
    p = synthetic_fleet(13)
    pp, W = pad_fleet(p, 8)
    assert (W, pp.W) == (13, 16)
    assert pp.usage.shape == (16, p.T)
    # true rows untouched
    np.testing.assert_array_equal(pp.usage[:13], p.usage)
    # pad rows: box pinned to [0, 0], zero penalties, finite divisors
    lo, hi = _bounds(pp)
    assert float(np.abs(np.asarray(hi)[13:]).max()) == 0.0
    assert float(np.abs(np.asarray(lo)[13:]).max()) == 0.0
    D = jnp.asarray(np.r_[0.1 * p.usage, np.zeros((3, p.T))])
    pens = np.asarray(fleet_penalties(pp, D))
    assert np.isfinite(pens).all()
    assert (pens[13:] == 0).all()


def test_pad_fleet_divisible_is_passthrough():
    from repro.core.fleet_solver import pad_fleet, synthetic_fleet
    p = synthetic_fleet(16)
    pp, W = pad_fleet(p, 8)
    assert (W, pp.W) == (16, 16)
    np.testing.assert_array_equal(pp.usage, p.usage)
    assert pp.upper is not None          # materialized for the spec tree


def test_pad_state_noop_when_already_padded():
    import jax.numpy as jnp
    from repro.core.engine import EngineState
    from repro.core.fleet_solver import _pad_state
    st = EngineState.cold(jnp.ones((8, 4)), n_eq=8)
    assert _pad_state(st, 8) is st
    padded = _pad_state(EngineState.cold(jnp.ones((5, 4)), n_eq=5), 8)
    assert padded.x.shape == (8, 4)
    assert padded.lam_eq.shape == (8,)
    np.testing.assert_array_equal(np.asarray(padded.x[5:]), 0.0)


# ---------------------------------------------------------------------------
# Sharded == single-device (8 virtual devices)
# ---------------------------------------------------------------------------
def test_sharded_parity_paper_fleet(paper_fleet):
    """Acceptance: all three policies on the 4-workload paper fleet (padded
    4 -> 8 rows) match the single-device solve to <0.01 pp."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1, CR2, CR3, SolveContext, solve
from repro.core.carbon import caiso_2021
from repro.core.fleet_solver import from_models
from repro.core.fleetcache import cached_paper_fleet
from repro.launch.mesh import make_fleet_mesh

fleet = cached_paper_fleet()
models = tuple(fleet[n] for n in ("RTS1", "RTS2", "AITraining",
                                  "DataPipeline"))
p = from_models(models, caiso_2021(48).mci)
mesh = make_fleet_mesh()
assert len(mesh.devices.ravel()) == 8

a = solve(p, CR1(lam=1.4), ctx=SolveContext(steps=300))
b = solve(p, CR1(lam=1.4), ctx=SolveContext(steps=300, mesh=mesh))
gap = abs((1.4 * a.total_penalty_pct - a.carbon_reduction_pct)
          - (1.4 * b.total_penalty_pct - b.carbon_reduction_pct))
assert gap < 0.01, f"CR1 gap {gap}"
assert b.D.shape == (4, 48)
assert b.state.x.shape == (8, 48)      # padded state for re-solve chaining

a = solve(p, CR2(outer=3), ctx=SolveContext(steps=200))
b = solve(p, CR2(outer=3), ctx=SolveContext(steps=200, mesh=mesh))
assert abs(a.carbon_reduction_pct - b.carbon_reduction_pct) < 0.01
assert abs(a.total_penalty_pct - b.total_penalty_pct) < 0.01

cr3 = CR3(outer=2, clearing_iters=3)
a = solve(p, cr3, ctx=SolveContext(steps=200))
b = solve(p, cr3, ctx=SolveContext(steps=200, mesh=mesh))
assert abs(a.carbon_reduction_pct - b.carbon_reduction_pct) < 0.01
assert abs(a.total_penalty_pct - b.total_penalty_pct) < 0.01
# identical Eq.-6 clearing trajectory
assert abs(a.extras["rho"] - b.extras["rho"]) < 1e-9
assert b.extras["balanced"] == a.extras["balanced"]
# pad rows are inert: their allowance constraints stay feasible, so their
# multipliers stay exactly zero (no growth to leak into chained re-solves)
assert float(np.abs(np.asarray(b.state.lam_in)[4:]).max()) == 0.0
print("OK")
""")


def test_sharded_parity_synthetic_mixed_and_padding():
    """Synthetic mixed fleet: W=13 (not divisible by 8) pads to 16 and still
    matches the single-device solve; warm re-solves accept both padded and
    unpadded states."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1, SolveContext, solve
from repro.core.fleet_solver import synthetic_fleet
from repro.launch.mesh import make_fleet_mesh

mesh = make_fleet_mesh()
p = synthetic_fleet(13)
cr1 = CR1(lam=1.45)
a = solve(p, cr1, ctx=SolveContext(steps=300))
b = solve(p, cr1, ctx=SolveContext(steps=300, mesh=mesh))
assert b.D.shape == (13, 48)
gap = abs((1.45 * a.total_penalty_pct - a.carbon_reduction_pct)
          - (1.45 * b.total_penalty_pct - b.carbon_reduction_pct))
assert gap < 0.01, f"gap {gap}"

# warm chaining: unpadded state (from the single-device solve) pads on
# entry; padded state (from the sharded solve) passes straight through.
w1 = solve(p, cr1, ctx=SolveContext(steps=100, mesh=mesh, warm=a.state))
w2 = solve(p, cr1, ctx=SolveContext(steps=100, mesh=mesh, warm=b.state))
assert np.abs(w1.D - w2.D).max() < 1e-4
print("OK")
""")


def test_sharded_donated_streaming_tick():
    """The fused donated-buffer streaming tick (shift + mu reset + re-solve
    in one XLA call, state buffers donated) commits the same plan as the
    legacy unfused path, and its warm re-solves keep the streaming_resolve
    objective gap vs a cold solve at the full budget."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1, SolveContext, solve
from repro.core.carbon import ForecastStream
from repro.core.fleet_solver import synthetic_fleet
from repro.core.streaming import RollingHorizonSolver
from repro.launch.mesh import make_fleet_mesh

lam, cold, warm = 1.45, 400, 120
p = synthetic_fleet(8)
mesh = make_fleet_mesh()

rep_plain = RollingHorizonSolver(
    p, ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=5),
    policy=CR1(lam=lam), cold_steps=cold, warm_steps=warm).run(4)
rep_don = RollingHorizonSolver(
    p, ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=5),
    policy=CR1(lam=lam), cold_steps=cold, warm_steps=warm, mesh=mesh,
    donate=True).run(4)
assert np.abs(rep_plain.committed - rep_don.committed).max() < 1e-5
assert [t.inner_steps for t in rep_don.ticks] == [cold, warm, warm, warm]

# warm-vs-cold objective gap on the last window (PR-2 criterion)
stream = ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=5)
rhs = RollingHorizonSolver(p, stream, policy=CR1(lam=lam),
                           cold_steps=cold, warm_steps=warm, mesh=mesh)
rhs.run(4)
last = rhs._history[-1]
p_t = rhs._window_problem(last.tick, stream.forecast(last.tick))
cold_r = solve(p_t, CR1(lam=lam), ctx=SolveContext(steps=cold, mesh=mesh))
obj = lambda r: lam * r.total_penalty_pct - r.carbon_reduction_pct
gap = obj(last.plan) - obj(cold_r)
assert gap <= 0.1, f"warm obj gap {gap}"
print("OK")
""")


def test_sharded_fused_kernel_parity():
    """The fused al_step kernel inside the W-axis shard_map body: each
    device runs the kernel on its local row block (W=13 -> 16 padded, 2
    rows/device). Must match the single-device fused solve to <0.01 pp —
    the kernel math is row-independent, so shard tiling cannot move the
    optimum."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1, CR2, SolveContext, solve
from repro.core.fleet_solver import synthetic_fleet
from repro.launch.mesh import make_fleet_mesh

mesh = make_fleet_mesh()
p = synthetic_fleet(13)

obj = lambda r: 1.45 * r.total_penalty_pct - r.carbon_reduction_pct
a1 = solve(p, CR1(lam=1.45), ctx=SolveContext(steps=250, use_kernel=True))
b1 = solve(p, CR1(lam=1.45),
           ctx=SolveContext(steps=250, use_kernel=True, mesh=mesh))
gap = abs(obj(a1) - obj(b1))
assert gap < 0.01, f"CR1 fused shard gap {gap}"
assert b1.D.shape == (13, 48)

a2 = solve(p, CR2(outer=2), ctx=SolveContext(steps=150, use_kernel=True))
b2 = solve(p, CR2(outer=2),
           ctx=SolveContext(steps=150, use_kernel=True, mesh=mesh))
assert abs(a2.carbon_reduction_pct - b2.carbon_reduction_pct) < 0.01
assert abs(a2.total_penalty_pct - b2.total_penalty_pct) < 0.01

# bf16 moments thread through the sharded path too
c1 = solve(p, CR1(lam=1.45),
           ctx=SolveContext(steps=250, use_kernel=True, mesh=mesh,
                            moment_dtype="bfloat16"))
gap = abs(obj(c1) - obj(b1))
assert gap < 0.05, f"bf16 shard gap {gap}"
print("OK")
""")


def test_sharded_ensemble_parity():
    """Acceptance (ISSUE 5): `evaluate_ensemble` with `ctx.mesh` — the
    scenario axis vmapped INSIDE the W-axis shard_map — matches the
    sequential single-device `api.solve` loop to <0.01 pp for CR1 and
    CR2, with W=13 exercising inert-row padding of the scenario
    overlays (usage/entitlement/jobs/upper stacks)."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1, CR2, SolveContext
from repro.core.ensemble import evaluate_ensemble
from repro.core.fleet_solver import synthetic_fleet
from repro.core.scenario import (DuckPerturb, FleetJitter, FlexMixShift,
                                 resolve_scenarios)
from repro.launch.mesh import make_fleet_mesh

mesh = make_fleet_mesh()
assert len(mesh.devices.ravel()) == 8
p = synthetic_fleet(13)
stack = resolve_scenarios([DuckPerturb(n_scenarios=2, seed=1),
                           FleetJitter(n_scenarios=1, seed=2),
                           FlexMixShift(n_scenarios=1, seed=3)], p)

for pol, steps in ((CR1(lam=1.45), 300), (CR2(cap_frac=0.8, outer=2), 200)):
    r8 = evaluate_ensemble(p, pol, stack,
                           ctx=SolveContext(steps=steps, mesh=mesh))
    r1 = evaluate_ensemble(p, pol, stack, ctx=SolveContext(steps=steps),
                           batched=False)
    assert r8.batched and not r1.batched
    assert r8.D.shape == (4, 13, 48)
    gc = np.abs(r8.carbon_reduction_pct - r1.carbon_reduction_pct).max()
    gp = np.abs(r8.total_penalty_pct - r1.total_penalty_pct).max()
    assert gc < 0.01, f"{pol.name} carbon gap {gc}"
    assert gp < 0.01, f"{pol.name} penalty gap {gp}"
print("OK")
""")


def test_sharded_ensemble_lane_takes_warm_states():
    """The mesh ensemble lane refines from a stacked warm state of the
    true rows (W=13, padded to the mesh's 16) under the tick entry's
    shift and mu reset, as the streaming ensemble's solo lane does: its
    plans match the warm solo lane's."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1, CR2
from repro.core.ensemble import _run_batched
from repro.core.fleet_solver import synthetic_fleet
from repro.core.scenario import DuckPerturb, resolve_scenarios
from repro.launch.mesh import make_fleet_mesh

mesh = make_fleet_mesh()
p = synthetic_fleet(13)
stack = resolve_scenarios(DuckPerturb(n_scenarios=2, seed=1), p)
for pol, steps in ((CR1(lam=1.45), 300), (CR2(cap_frac=0.8, outer=2), 200)):
    _, _, st = _run_batched(pol, p, stack, steps=steps, use_kernel=False)
    assert st.x.shape == (2, 13, 48)
    kw = dict(steps=steps // 4, use_kernel=False, init=st, shift=1,
              reset_mu=True)
    D1, pen1, _ = _run_batched(pol, p, stack, **kw)
    D8, pen8, st8 = _run_batched(pol, p, stack, mesh=mesh, **kw)
    assert D8.shape == (2, 13, 48) and st8.x.shape == (2, 16, 48)
    scale = np.abs(p.usage).max()
    assert np.abs(D8 - D1).max() < 1e-3 * scale, pol.name
    assert np.abs(pen8 - pen1).max() < 1e-3 * max(np.abs(pen1).max(), 1.0)
print("OK")
""")


def test_sharded_2d_mesh_multiregion_parity():
    """Acceptance (ISSUE 7): a 2-D (REGION_AXIS, FLEET_AXIS) mesh from
    `make_fleet_mesh(regions=2)` — the W axis sharded over BOTH axes —
    matches the single-device solve to <0.01 pp for a single-region
    fleet and for a multi-region R=2 fleet under all three policies,
    and the host-side migration post-stage rides the sharded solve."""
    run_in_subprocess("""
import dataclasses
import numpy as np
from repro.core.api import CR1, CR2, CR3, SolveContext, solve
from repro.core.fleet_solver import synthetic_fleet, synthetic_regional_fleet
from repro.launch.mesh import (FLEET_AXIS, REGION_AXIS, fleet_axes,
                               fleet_device_count, make_fleet_mesh)

mesh = make_fleet_mesh(regions=2)
assert mesh.axis_names == (REGION_AXIS, FLEET_AXIS)
assert fleet_axes(mesh) == (REGION_AXIS, FLEET_AXIS)
assert fleet_device_count(mesh) == 8
try:
    make_fleet_mesh(regions=3)
except ValueError as e:
    assert "divide" in str(e)
else:
    raise AssertionError("regions=3 must reject 8 devices")

# single-region fleet on the 2-D mesh: W=13 pads to 16 over 2x4 devices
p = synthetic_fleet(13)
a = solve(p, CR1(lam=1.45), ctx=SolveContext(steps=300))
b = solve(p, CR1(lam=1.45), ctx=SolveContext(steps=300, mesh=mesh))
gap = abs((1.45 * a.total_penalty_pct - a.carbon_reduction_pct)
          - (1.45 * b.total_penalty_pct - b.carbon_reduction_pct))
assert gap < 0.01, f"single-region 2-D gap {gap}"
assert b.D.shape == (13, 48)

# multi-region R=2 fleet (no topology: keep the comparison pure solve)
pr = dataclasses.replace(
    synthetic_regional_fleet(13, ["CA", "TX"], hours=48, seed=0,
                             utc_offsets="auto"),
    topology=None)
for pol, steps in ((CR1(lam=1.45), 300), (CR2(cap_frac=0.8, outer=2), 200),
                   (CR3(outer=2, clearing_iters=2), 200)):
    a = solve(pr, pol, ctx=SolveContext(steps=steps))
    b = solve(pr, pol, ctx=SolveContext(steps=steps, mesh=mesh))
    gc = abs(a.carbon_reduction_pct - b.carbon_reduction_pct)
    gp = abs(a.total_penalty_pct - b.total_penalty_pct)
    assert gc < 0.01, f"{pol.name} 2-D carbon gap {gc}"
    assert gp < 0.01, f"{pol.name} 2-D penalty gap {gp}"
    assert b.D.shape == (13, 48)
    # the same multi-region problem also accepts the 1-D fleet mesh
    c = solve(pr, pol, ctx=SolveContext(steps=steps, mesh=make_fleet_mesh()))
    assert abs(a.carbon_reduction_pct - c.carbon_reduction_pct) < 0.01

# migration post-stage (host-side) rides the sharded solve: same credit
# as off-mesh up to the D parity tolerance
pm = synthetic_regional_fleet(13, ["CA", "TX"], hours=48, seed=0,
                              utc_offsets="auto")
rm1 = solve(pm, CR1(lam=1.45), ctx=SolveContext(steps=300))
rm8 = solve(pm, CR1(lam=1.45), ctx=SolveContext(steps=300, mesh=mesh))
assert rm8.extras["migration"].net_saved > 0.0
assert abs(rm1.extras["migration"].net_saved
           - rm8.extras["migration"].net_saved) \
    < 0.05 * rm1.extras["migration"].net_saved + 1e-6
print("OK")
""")


def test_sharded_scanned_day_runs_on_mesh():
    """The whole-day `run_scanned` scan now accepts `mesh=` (the PR-6
    guard is lifted): the day scan inside the fleet shard_map commits
    the same plans as the unsharded per-tick loop."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1
from repro.core.carbon import ForecastStream
from repro.core.fleet_solver import synthetic_fleet
from repro.core.streaming import RollingHorizonSolver
from repro.launch.mesh import make_fleet_mesh

p = synthetic_fleet(13)
mk = lambda: ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=5)
plain = RollingHorizonSolver(p, mk(), policy=CR1(lam=1.45),
                             cold_steps=300, warm_steps=100).run(4)
mesh = make_fleet_mesh()
scan = RollingHorizonSolver(p, mk(), policy=CR1(lam=1.45),
                            cold_steps=300, warm_steps=100,
                            mesh=mesh).run_scanned(4)
assert np.abs(plain.committed - scan.committed).max() < 1e-3
assert abs(plain.realized_reduction_pct
           - scan.realized_reduction_pct) < 0.01
print("OK")
""")


def test_sharded_sweep_parity():
    """Acceptance: `sweep(p, grid, ctx=SolveContext(mesh=...))` — the
    hyper axis vmapped INSIDE the W-axis shard_map — matches per-policy
    single-device solves to <0.01 pp on 8 virtual devices, for both the
    CR1 and CR2 families, with W=13 exercising inert-row padding."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1, CR2, SolveContext, solve, sweep
from repro.core.fleet_solver import synthetic_fleet
from repro.launch.mesh import make_fleet_mesh

mesh = make_fleet_mesh()
p = synthetic_fleet(13)

grid = [1.0, 1.45, 2.2]
sharded = sweep(p, [CR1(lam=l) for l in grid],
                ctx=SolveContext(steps=300, mesh=mesh))
for l, r8 in zip(grid, sharded):
    r1 = solve(p, CR1(lam=l), ctx=SolveContext(steps=300))
    gap = abs((l * r8.total_penalty_pct - r8.carbon_reduction_pct)
              - (l * r1.total_penalty_pct - r1.carbon_reduction_pct))
    assert gap < 0.01, f"CR1 lam={l} gap {gap}"
    assert r8.D.shape == (13, 48)

caps = [0.74, 0.8]
sharded = sweep(p, [CR2(cap_frac=c, outer=2) for c in caps],
                ctx=SolveContext(steps=200, mesh=mesh))
for c, r8 in zip(caps, sharded):
    r1 = solve(p, CR2(cap_frac=c, outer=2), ctx=SolveContext(steps=200))
    assert abs(r8.carbon_reduction_pct - r1.carbon_reduction_pct) < 0.01, c
    assert abs(r8.total_penalty_pct - r1.total_penalty_pct) < 0.01, c
print("OK")
""")


def test_sharded_sweep_takes_and_returns_warm_states():
    """The mesh sweep returns each lane's state, as the solo sweep does,
    and refines from a stacked warm state: from its own (device-padded)
    states or the solo sweep's (W=13, padded here), the warm mesh sweep
    matches the warm solo sweep to <0.01 pp, for CR1 and CR2."""
    run_in_subprocess("""
from repro.core.api import CR1, CR2, SolveContext, stack_states, sweep
from repro.core.fleet_solver import synthetic_fleet
from repro.launch.mesh import make_fleet_mesh

mesh = make_fleet_mesh()
p = synthetic_fleet(13)
for pols, steps in (([CR1(lam=1.0), CR1(lam=2.2)], 300),
                    ([CR2(cap_frac=0.74, outer=2),
                      CR2(cap_frac=0.8, outer=2)], 200)):
    cold8 = sweep(p, pols, ctx=SolveContext(steps=steps, mesh=mesh))
    cold1 = sweep(p, pols, ctx=SolveContext(steps=steps))
    assert all(r.state is not None for r in cold8)
    assert cold8[0].state.x.shape == (16, 48)
    ref = sweep(p, pols, ctx=SolveContext(
        steps=steps // 4, warm=stack_states([r.state for r in cold1])))
    for start in (cold8, cold1):
        got = sweep(p, pols, ctx=SolveContext(
            steps=steps // 4, mesh=mesh,
            warm=stack_states([r.state for r in start])))
        for pl, a, b in zip(pols, got, ref):
            assert a.D.shape == (13, 48) and a.state.x.shape == (16, 48)
            lam = getattr(pl, "lam", 0.0)
            gap = abs((lam * a.total_penalty_pct - a.carbon_reduction_pct)
                      - (lam * b.total_penalty_pct - b.carbon_reduction_pct))
            assert gap < 0.01, (pl, gap)
            if pl.name == "cr2":
                assert abs(a.total_penalty_pct - b.total_penalty_pct) < 0.01
print("OK")
""")


def test_sharded_scanned_day_multiregion_parity():
    """Acceptance (ISSUE 8): multi-region `run_scanned`/`solve_day` under
    BOTH the 1-D fleet mesh and the 2-D (region, fleet) mesh — per-tick
    per-region norms ride the scan as row-sharded stacks — match the
    unsharded per-tick loop to <0.01 pp realized carbon."""
    run_in_subprocess("""
import dataclasses
import numpy as np
from repro.core.api import CR1, CR2
from repro.core.fleet_solver import synthetic_regional_fleet
from repro.core.scenario import ForecastRegime
from repro.core.streaming import RollingHorizonSolver
from repro.launch.mesh import make_fleet_mesh

pr = dataclasses.replace(
    synthetic_regional_fleet(13, ["CA", "TX"], hours=48, seed=0,
                             utc_offsets="auto"),
    topology=None)
mk = lambda: ForecastRegime(n_scenarios=1, seed=5,
                            sigma=(0.03, 0.03)).streams(pr, n_ticks=4)[0]
for pol, cold, warm in ((CR1(lam=1.45), 300, 100),
                        (CR2(cap_frac=0.8, outer=2), 150, 50)):
    plain = RollingHorizonSolver(pr, mk(), policy=pol, cold_steps=cold,
                                 warm_steps=warm).run(4)
    for mesh in (make_fleet_mesh(), make_fleet_mesh(regions=2)):
        scan = RollingHorizonSolver(pr, mk(), policy=pol, cold_steps=cold,
                                    warm_steps=warm,
                                    mesh=mesh).run_scanned(4)
        gap = abs(plain.realized_reduction_pct
                  - scan.realized_reduction_pct)
        assert gap < 0.01, f"{pol.name} {mesh.axis_names} gap {gap}"
        assert np.abs(plain.committed - scan.committed).max() < 1e-2
print("OK")
""")


def test_sharded_scanned_day_r1_regional_bitwise():
    """The degenerate R=1 regional fleet mesh-scans bitwise-identically
    to the plain single-region fleet (the `_single_region_view`
    canonicalization reaches the day scan too)."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1
from repro.core.carbon import ForecastStream
from repro.core.fleet_solver import regional_fleet, synthetic_fleet
from repro.core.streaming import RollingHorizonSolver
from repro.launch.mesh import make_fleet_mesh

fp = synthetic_fleet(13)
pr = regional_fleet([fp], np.asarray(fp.mci)[None])
mk = lambda: ForecastStream.caiso(n_ticks=3, horizon=fp.T, seed=5)
mesh = make_fleet_mesh()
a = RollingHorizonSolver(fp, mk(), policy=CR1(lam=1.45), cold_steps=200,
                         warm_steps=60, mesh=mesh).run_scanned(3)
b = RollingHorizonSolver(pr, mk(), policy=CR1(lam=1.45), cold_steps=200,
                         warm_steps=60, mesh=mesh).run_scanned(3)
np.testing.assert_array_equal(a.committed, b.committed)
assert a.realized_reduction_pct == b.realized_reduction_pct
print("OK")
""")
