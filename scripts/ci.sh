#!/usr/bin/env bash
# CI entry, runnable from a fresh checkout:
#   pip install -r requirements.txt && scripts/ci.sh          # fast lane
#   scripts/ci.sh --full                                      # tier-1 suite
#
# The fast lane deselects @pytest.mark.slow (the long solver-convergence
# and end-to-end tests, ~8 min on CPU) and finishes in a couple of
# minutes. The tier-1 verify documented in ROADMAP.md is the --full lane:
#   PYTHONPATH=src python -m pytest -x -q
#
# Both lanes finish with the multi-device lane: the fleet-sharding parity
# tests run under 8 virtual CPU devices
# (XLA_FLAGS=--xla_force_host_platform_device_count=8), so every PR
# exercises the sharded == single-device contract. The main suite's
# pytest process must stay single-device (see tests/conftest.py), so the
# sharding file is split out into its own invocation.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== drlint (JAX invariants as an AST pass) =="
# Millisecond static pass, so it runs first and fails fast: host leaks in
# jit-reachable code, donation twins, check_vma justifications, tuple
# seeding, np-on-traced, deprecated shims, ad-hoc PartitionSpecs. Exits
# nonzero with path:line:col output on any unsuppressed violation.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m repro.analysis.lint --fail-on-violation src/repro

lane=(-m "not slow")
if [[ "${1:-}" == "--full" ]]; then
  shift
  lane=()
fi
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest -x -q ${lane[@]+"${lane[@]}"} \
  --ignore=tests/test_fleet_sharding.py "$@"

# Targeted runs (extra pytest args) skip the extra lanes so e.g.
# `scripts/ci.sh -k fleetcache` stays fast; both default lanes run them.
if [[ $# -eq 0 ]]; then
  echo "== deprecation lane (legacy shims warn exactly once) =="
  # Re-run the API tests with DeprecationWarning as error: every legacy
  # shim call in tests/test_api.py is wrapped in an explicit capture that
  # asserts exactly one warning, so any stray DeprecationWarning — a shim
  # warning twice, or the new solve()/sweep() surface emitting one —
  # fails this lane.
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -x -q tests/test_api.py -W error::DeprecationWarning

  echo "== sanitizer smoke (CR1 + CR2 under sanitize=True) =="
  # The checkify debug lane end-to-end on both twinned policies: bitwise
  # parity with the unchecked lane, and an injected NaN in the carbon
  # trace must raise SanitizeError instead of silently shipping a NaN
  # plan.
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import dataclasses
import numpy as np
from repro.analysis import SanitizeError
from repro.core.api import CR1, CR2, SolveContext, solve
from repro.core.fleet_solver import synthetic_fleet

p = synthetic_fleet(8, seed=3)
mci = np.asarray(p.mci, float).copy(); mci[5] = np.nan
bad = dataclasses.replace(p, mci=mci)
for pol in (CR1(lam=1.45), CR2(cap_frac=0.8, outer=2)):
    plain = solve(p, pol, ctx=SolveContext(steps=80))
    guard = solve(p, pol, ctx=SolveContext(steps=80, sanitize=True))
    np.testing.assert_array_equal(plain.D, guard.D)
    try:
        solve(bad, pol, ctx=SolveContext(steps=80, sanitize=True))
    except SanitizeError:
        pass
    else:
        raise AssertionError(f"{pol.name}: NaN injection did not fire")
print("sanitizer smoke OK")
PY

  echo "== examples smoke (quickstart + 2 streaming ticks) =="
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python examples/quickstart.py > /dev/null
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python examples/streaming_dr.py --ticks 2 > /dev/null

  echo "== ensemble smoke (S=4 x W=16 x 2 policies + risk example) =="
  # The scenario-ensemble subsystem end-to-end: batched CR1 + CR2 over a
  # mixed MCI/fleet scenario stack, with the batched-vs-loop parity
  # contract asserted, plus the risk-report example.
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import numpy as np
from repro.core.api import CR1, CR2, SolveContext
from repro.core.ensemble import evaluate_ensemble
from repro.core.fleet_solver import synthetic_fleet
from repro.core.scenario import DuckPerturb, FleetJitter, resolve_scenarios

p = synthetic_fleet(16)
stack = resolve_scenarios([DuckPerturb(n_scenarios=2, seed=0),
                           FleetJitter(n_scenarios=2, seed=1)], p)
ctx = SolveContext(steps=80)
for pol in (CR1(lam=1.45), CR2(cap_frac=0.8, outer=2)):
    got = evaluate_ensemble(p, pol, stack, ctx=ctx)
    ref = evaluate_ensemble(p, pol, stack, ctx=ctx, batched=False)
    assert got.batched and got.D.shape == (4, 16, 48)
    gap = np.abs(got.carbon_reduction_pct - ref.carbon_reduction_pct).max()
    assert gap < 0.01, f"{pol.name} ensemble parity gap {gap}"
    got.report().lines()
print("ensemble smoke OK")
PY
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python examples/scenario_risk.py --scenarios 4 --workloads 8 \
    --steps 120 > /dev/null

  echo "== al_step kernel smoke (interpret parity + scanned day) =="
  # The fused AL inner-step kernel against its jnp oracle at small W,T,
  # and a 4-tick run_scanned() day against the per-tick step() loop —
  # the one-dispatch-day contract on every PR.
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import dataclasses
import numpy as np
import jax.numpy as jnp
from repro.core.carbon import ForecastStream
from repro.core.fleet_solver import _bounds, synthetic_fleet
from repro.core.streaming import RollingHorizonSolver
from repro.kernels.al_step.kernel import al_step_pallas
from repro.kernels.al_step.ops import kernel_layout, pack_rows, time_major
from repro.kernels.al_step.ref import al_step_ref

# kernel vs oracle, hinge-free rows (see kernels/al_step/ref.py)
p = synthetic_fleet(8, hours=48, seed=0)
p = dataclasses.replace(
    p, is_batch=np.zeros(8, bool), betas=np.zeros((8, 3)),
    rts_coeffs=np.where(np.asarray(p.is_batch)[:, None],
                        [2e-4, 1.5e-3, 0.04], p.rts_coeffs))
lo, hi = (np.asarray(a, np.float32) for a in _bounds(p))
rng = np.random.default_rng(0)
x = np.clip(rng.normal(0, .3, lo.shape), lo, hi).astype(np.float32)
m = np.zeros_like(x); v = np.zeros_like(x)
rowp = jnp.concatenate([pack_rows(p.rts_coeffs, p.betas, p.k, p.x2_kind,
                                  p.is_batch),
                        jnp.zeros((8, 2), jnp.float32)], axis=1)
cvec = rng.normal(-.5, .2, (1, p.T)).astype(np.float32)
scal = np.array([[1.45, 10., 0., .02, 0., 0, 0, 0]], np.float32)
args = [jnp.asarray(a) for a in
        (x, m, v, p.usage, p.jobs, lo, hi, rowp, cvec, scal)]
# the kernel on the time-major layout make_fused_inner gives it
consts, bw, wp = kernel_layout(*args[3:9], block_w=None)
out = al_step_pallas(*(time_major(a, wp) for a in args[:3]), *consts,
                     args[9], mode="cr1", k_steps=4, block_w=bw,
                     interpret=True)
out = tuple(o[:, :8].T for o in out)
ref = al_step_ref(*args, mode="cr1", k_steps=4)
err = max(float(jnp.abs(o - r).max()) for o, r in zip(out, ref))
assert err <= 1e-5, f"al_step kernel-vs-oracle err {err}"

# 4-tick scanned day == per-tick loop
p = synthetic_fleet(6, seed=0)
mk = lambda: ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=3)
kw = dict(policy="cr1", cold_steps=120, warm_steps=30)
loop = RollingHorizonSolver(p, mk(), **kw).run(4)
scan = RollingHorizonSolver(p, mk(), **kw).run_scanned(4)
gap = abs(loop.realized_reduction_pct - scan.realized_reduction_pct)
assert gap < 0.01, f"scanned-day parity gap {gap}pp"
print(f"al_step smoke OK (kernel err {err:.1e}, day gap {gap:.1e}pp)")
PY

  echo "== bench-record sanity (write + parse BENCH_*.json) =="
  # The micro-bench must run end-to-end and its freshly written record
  # must parse through the report renderer.
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --only al_step > /dev/null
  # (grep without -q: it must read the stream to EOF, otherwise the
  # early exit closes the pipe mid-print and pipefail trips on the
  # renderer's BrokenPipeError.)
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.report --bench | grep al_step_fused_solve \
    > /dev/null

  echo "== multi-region smoke (R=2 x W=16, CR1 + CR2, migration on/off) =="
  # The (region x workload) engine end-to-end: per-region pricing under
  # both policy families, the zero-bandwidth topology staying credit-free,
  # and the migration post-stage leaving D untouched while crediting the
  # net saving.
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import dataclasses
import numpy as np
from repro.core.api import CR1, CR2, SolveContext, solve
from repro.core.fleet_solver import RegionTopology, synthetic_regional_fleet

p = synthetic_regional_fleet(16, ["CA", "TX"], hours=48, seed=0,
                             utc_offsets="auto")
off = dataclasses.replace(
    p, topology=RegionTopology(cost=np.full((2, 2), 2.0),
                               bandwidth=np.zeros((2, 2))))
ctx = SolveContext(steps=120)
for pol in (CR1(lam=1.45), CR2(cap_frac=0.8, outer=2)):
    r_on = solve(p, pol, ctx=ctx)
    r_off = solve(off, pol, ctx=ctx)
    assert "migration" not in r_off.extras
    plan = r_on.extras["migration"]
    np.testing.assert_array_equal(r_on.D, r_off.D)
    assert plan.net_saved > 0.0
    assert r_on.carbon_reduction_pct > r_off.carbon_reduction_pct
print("multi-region smoke OK")
PY

  echo "== multi-region day-scan smoke (R=2 on 2 virtual devices) =="
  # The ISSUE-8 regional-reductions layer end-to-end on a tiny mesh: the
  # whole-day scan with per-region norms riding the shard_map matches the
  # unsharded per-tick loop, and one coupled-migration solve never loses
  # to the post-stage at equal total curtailment.
  XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import dataclasses
import numpy as np
from repro.core.api import CR1, SolveContext, solve
from repro.core.fleet_solver import synthetic_regional_fleet
from repro.core.scenario import ForecastRegime
from repro.core.streaming import RollingHorizonSolver
from repro.launch.mesh import make_fleet_mesh

pr = dataclasses.replace(
    synthetic_regional_fleet(8, ["CA", "TX"], hours=48, seed=0,
                             utc_offsets="auto"),
    topology=None)
mk = lambda: ForecastRegime(n_scenarios=1, seed=5,
                            sigma=(0.03, 0.03)).streams(pr, n_ticks=3)[0]
kw = dict(policy=CR1(lam=1.45), cold_steps=150, warm_steps=50)
loop = RollingHorizonSolver(pr, mk(), **kw).run(3)
mesh = make_fleet_mesh()
assert len(mesh.devices.ravel()) == 2
scan = RollingHorizonSolver(pr, mk(), **kw, mesh=mesh).run_scanned(3)
gap = abs(loop.realized_reduction_pct - scan.realized_reduction_pct)
assert gap < 0.01, f"multi-region scanned-day parity gap {gap}pp"

p = synthetic_regional_fleet(12, ["CA", "TX"], hours=48, seed=0,
                             utc_offsets="auto")
post = solve(p, CR1(lam=1.45), ctx=SolveContext(steps=150))
coup = solve(p, CR1(lam=1.45),
             ctx=SolveContext(steps=150, coupled_migration=True))
assert coup.carbon_reduction_pct >= post.carbon_reduction_pct
tot_p, tot_c = (float(np.asarray(r.D).sum()) for r in (post, coup))
assert abs(tot_c - tot_p) <= 2e-3 * max(abs(tot_p), 1.0)
print(f"multi-region day-scan smoke OK (gap {gap:.1e}pp, coupled "
      f"{coup.carbon_reduction_pct:.2f}% vs post "
      f"{post.carbon_reduction_pct:.2f}%)")
PY

  echo "== observability smoke (telemetry ledger -> report) =="
  # PR 10's contract end-to-end: a telemetry-enabled 4-tick streaming
  # day (one scanned dispatch) writes the JSONL ledger via
  # examples/streaming_dr.py --telemetry, and the report CLI parses and
  # renders it with exit 0. drlint already ran above with the
  # host-sync-in-jit rule, so the instrumented tree is lint-clean.
  obs_ledger="$(mktemp -t obs_smoke.XXXXXX.jsonl)"
  rm -f "$obs_ledger"   # EventWriter writes the header on empty files
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python examples/streaming_dr.py --ticks 4 --cold-steps 120 \
    --warm-steps 30 --scan --telemetry "$obs_ledger" > /dev/null
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.obs.report "$obs_ledger" | grep "tick ledger" \
    > /dev/null
  rm -f "$obs_ledger"
  echo "observability smoke OK"

  echo "== multi-device lane (8 virtual CPU devices) =="
  XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -x -q tests/test_fleet_sharding.py
fi
