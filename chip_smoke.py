#!/usr/bin/env python3
"""Smoke test of the fleet DR decision path on a TPU.

Runs the system's main path once, through the entry points an operator
calls, at a fleet size operators run (W=100,000 workloads, T=48 hours),
and checks every result against the same call on the jnp path
(`use_kernel=False`) on the same chip.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the sharded path on a 2x2 host only

One process, no children. Exits nonzero, and prints no result line,
when the first JAX device is not a TPU, when `REPRO_PALLAS_INTERPRET`
asks for interpret mode, or when any phase fails. Wall times printed on
the way are first readings of one run, cold (compile included) and
warm; they are not benchmark metrics. The last line of standard output
is the JSON result:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
W, T = 100_000, 48
#: Kernel vs jnp bound, pp (the fused-vs-generic tolerance of
#: tests/test_al_step.py).
KERNEL_TOL = 0.05
#: Sharded vs single-device bound, pp (tests/test_fleet_sharding.py).
MESH_TOL = 0.01


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def check_finite(label: str, r) -> None:
    vals = (r.carbon_reduction_pct, r.total_penalty_pct)
    if not (np.isfinite(np.asarray(r.D)).all() and np.isfinite(vals).all()):
        fail(f"{label}: non-finite result {vals}")


def check_pair(label: str, a, b, tol: float) -> None:
    """`a` against the reference `b`: finite, and within `tol` pp."""
    check_finite(label, a)
    check_finite(label + " (reference)", b)
    dc = abs(a.carbon_reduction_pct - b.carbon_reduction_pct)
    dp = abs(a.total_penalty_pct - b.total_penalty_pct)
    log(f"parity {label}: carbon {a.carbon_reduction_pct:.6f} vs "
        f"{b.carbon_reduction_pct:.6f} (|d|={dc:.2e} pp), penalty "
        f"{a.total_penalty_pct:.6f} vs {b.total_penalty_pct:.6f} "
        f"(|d|={dp:.2e} pp), bound {tol} pp")
    if not (dc < tol and dp < tol):
        fail(f"{label}: parity outside {tol} pp (carbon {dc}, "
             f"penalty {dp})")


def reading(phase: str, cold_s: float, warm_s: float | None = None) -> None:
    warm = "" if warm_s is None else f" warm_s={warm_s:.3f}"
    log(f"first chip reading (not a benchmark metric): {phase} "
        f"cold_s={cold_s:.3f}{warm}")


def streaming(p, use_kernel):
    """CR1 rolling horizon: one cold tick and three warm ticks through
    `step()`, then a fresh controller's `run_scanned(4)`."""
    from repro.core.api import CR1
    from repro.core.carbon import ForecastStream
    from repro.core.streaming import RollingHorizonSolver

    def controller():
        return RollingHorizonSolver(
            p, ForecastStream.caiso(n_ticks=8, horizon=T, seed=0),
            policy=CR1(lam=1.45), use_kernel=use_kernel)

    rhs = controller()
    ticks, times = [], []
    for _ in range(4):
        tick, s = timed(rhs.step)
        ticks.append(tick.plan)
        times.append(s)
    day, s_day = timed(lambda: controller().run_scanned(4))
    return ticks, times, day, s_day


STATES = ["CA", "WA", "AZ", "CO", "TX", "IL", "NY", "FL"]


def cr3_market() -> None:
    """CR3 streaming ticks (one cold, one warm) on the 8-region Cambium
    fleet: every region's Eq.-6 market cleared in one on-device loop a
    tick, kernel path against the jnp path."""
    from repro.core.api import CR3
    from repro.core.carbon import ForecastStream, regional_traces
    from repro.core.fleet_solver import synthetic_regional_fleet
    from repro.core.streaming import RollingHorizonSolver

    p = synthetic_regional_fleet(W, STATES, hours=T, seed=0,
                                 utc_offsets="auto")
    actual, _ = regional_traces(STATES, 2050, hours=8 + T, seed=0,
                                utc_offsets="auto")

    def ticks(use_kernel):
        rhs = RollingHorizonSolver(
            p, ForecastStream.regional(actual, horizon=T, seed=0),
            policy=CR3(), use_kernel=use_kernel)
        return [(*timed(lambda: rhs.step().plan),
                 rhs.cold_steps if i == 0 else rhs.warm_steps)
                for i in range(2)]

    kernel, jnp_path = ticks(None), ticks(False)
    for i, ((a, s, steps), (b, s_j, _)) in enumerate(zip(kernel, jnp_path)):
        label = f"CR3 market tick {i} ({'cold' if i == 0 else 'warm'})"
        for path, r in (("kernel", a), ("jnp", b)):
            log(f"{label} ({path}): rounds="
                f"{r.iters // (steps * CR3().outer)} rho="
                f"{np.round(r.extras['rho'], 6).tolist()} balanced="
                f"{r.balanced} fiscal_deficit={r.fiscal_deficit:.6g}")
        check_pair(label, a, b, KERNEL_TOL)
        log(f"first chip reading (not a benchmark metric): {label} "
            f"kernel_s={s:.3f} jnp_s={s_j:.3f}")
    fiscal_sums(p, a)


def fiscal_sums(p, r) -> None:
    """How far the rebates the market's deficit test reads on the device
    (`api._cr3_rebates`: double-float sums from float32 operations) sit
    from exact float64 host sums of the same plan, in parts of each
    region's taxes collected, beside plain float32 sums of the same."""
    import math

    import jax
    import jax.numpy as jnp
    from repro.core import api, dfloat

    order, offsets = api._region_layout(p.region, p.R)
    region = np.asarray(p.region)
    mci = np.asarray(p.mci, np.float64)
    rho = np.asarray(r.extras["rho"], np.float64)
    D = np.asarray(r.D, np.float32)
    collected = api.CR3().tax_frac * api._cr3_taxes(p)
    dev = jax.jit(api._cr3_rebates, static_argnums=(3, 4))(
        D, dfloat.split(rho), dfloat.split(mci), order, offsets)
    dev = np.asarray(dev[0], np.float64) + np.asarray(dev[1], np.float64)
    f32 = np.asarray(jax.jit(lambda D, m: jnp.stack(
        [(D[region == k] * m[k]).sum() for k in range(p.R)]))(
            D, jnp.asarray(mci, jnp.float32)), np.float64) * rho
    D64 = D.astype(np.float64)
    want = rho * np.array([math.fsum((D64[region == k] * mci[k]).ravel())
                           for k in range(p.R)])
    log(f"CR3 rebates paid, device vs exact float64, in parts of "
        f"collected_r: double-float "
        f"{(np.abs(dev - want) / collected).max():.3e}, plain float32 "
        f"{(np.abs(f32 - want) / collected).max():.3e}")


def single_chip(jax) -> None:
    from repro.core.api import _TICK, CR1, CR2, CR3, SolveContext, solve
    from repro.core.engine import EngineState
    from repro.core.fleet_solver import (_jit_view, resolve_use_kernel,
                                         synthetic_fleet)

    if not resolve_use_kernel(None):
        fail("the default solve path does not select the Pallas kernels")
    p, s = timed(lambda: synthetic_fleet(W, hours=T, seed=0))
    log(f"fleet: W={p.W} T={p.T} built in {s:.1f} s (host)")

    # Streaming coordinator, kernel path (default) vs jnp path.
    ticks_k, times_k, day_k, s_day_k = streaming(p, None)
    ticks_j, times_j, day_j, s_day_j = streaming(p, False)
    for i, (a, b) in enumerate(zip(ticks_k, ticks_j)):
        check_pair(f"CR1 tick {i} ({'cold' if i == 0 else 'warm'})", a, b,
                   KERNEL_TOL)
    check_pair("CR1 run_scanned(4) last plan", day_k.ticks[-1].plan,
               day_j.ticks[-1].plan, KERNEL_TOL)
    d = abs(day_k.realized_reduction_pct - day_j.realized_reduction_pct)
    log(f"parity CR1 run_scanned(4) realized reduction: "
        f"{day_k.realized_reduction_pct:.6f} vs "
        f"{day_j.realized_reduction_pct:.6f} (|d|={d:.2e} pp)")
    if not d < KERNEL_TOL:
        fail(f"run_scanned realized reduction outside {KERNEL_TOL} pp")
    for path, times, s_day in (("kernel", times_k, s_day_k),
                               ("jnp", times_j, s_day_j)):
        reading(f"CR1 streaming tick 0, cold solve ({path})", times[0])
        reading(f"CR1 streaming ticks 1-3, warm solves ({path})", times[1],
                min(times[2:]))
        reading(f"CR1 run_scanned(4) ({path})", s_day)

    # One solve each of CR2 and CR3 on the same fleet.
    jnp_path = SolveContext(use_kernel=False)
    for pol in (CR2(), CR3()):
        a, cold = timed(lambda: solve(p, pol))
        _, warm = timed(lambda: solve(p, pol))
        b, cold_j = timed(lambda: solve(p, pol, ctx=jnp_path))
        _, warm_j = timed(lambda: solve(p, pol, ctx=jnp_path))
        check_pair(f"{pol.name} solve", a, b, KERNEL_TOL)
        reading(f"{pol.name} solve (kernel)", cold, warm)
        reading(f"{pol.name} solve (jnp)", cold_j, warm_j)

    # The compiled CR1 solve must hold the Pallas kernel.
    state = EngineState.cold(jax.numpy.zeros(p.usage.shape))
    hlo = _TICK["cr1"].plain.lower(_jit_view(p), 1.45, state,
                                   steps=CR1.default_steps,
                                   use_kernel=True).compile().as_text()
    if "tpu_custom_call" not in hlo:
        fail("compiled CR1 solve holds no tpu_custom_call")
    log("compiled CR1 solve holds a tpu_custom_call (the al_step kernel)")


def four_chips(jax) -> None:
    from repro.core.api import CR1, SolveContext, solve
    from repro.core.carbon import ForecastStream
    from repro.core.fleet_solver import (synthetic_fleet,
                                         synthetic_regional_fleet)
    from repro.core.streaming import RollingHorizonSolver
    from repro.launch.mesh import make_fleet_mesh

    if len(jax.devices()) != 4:
        fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    mesh = make_fleet_mesh()
    p = synthetic_fleet(W, hours=T, seed=0)
    cr1 = CR1(lam=1.45)

    a, cold = timed(lambda: solve(p, cr1, ctx=SolveContext(mesh=mesh)))
    _, warm = timed(lambda: solve(p, cr1, ctx=SolveContext(mesh=mesh)))
    b = solve(p, cr1)
    check_pair("CR1 solve, 4-chip mesh vs one chip", a, b, MESH_TOL)
    reading("CR1 solve on the 4-chip mesh", cold, warm)

    def tick(m):
        rhs = RollingHorizonSolver(
            p, ForecastStream.caiso(n_ticks=8, horizon=T, seed=0),
            policy=cr1, mesh=m, donate=m is not None)
        return rhs.step().plan, rhs.step().plan

    (cold_a, warm_a), s = timed(lambda: tick(mesh))
    cold_b, warm_b = tick(None)
    check_pair("CR1 cold tick, 4-chip mesh vs one chip", cold_a, cold_b,
               MESH_TOL)
    check_pair("CR1 donated warm tick, 4-chip mesh vs one chip", warm_a,
               warm_b, MESH_TOL)
    reading("CR1 cold + donated warm tick on the 4-chip mesh", s)

    pr = dataclasses.replace(
        synthetic_regional_fleet(W, ["CA", "TX"], hours=T, seed=0,
                                 utc_offsets="auto"), topology=None)
    mesh2 = make_fleet_mesh(regions=2)
    a, cold = timed(lambda: solve(pr, cr1, ctx=SolveContext(mesh=mesh2)))
    b = solve(pr, cr1)
    check_pair("R=2 CR1 solve, (2, 2) region x fleet mesh vs one chip",
               a, b, MESH_TOL)
    reading("R=2 CR1 solve on the (2, 2) mesh", cold)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its "
                         "single-device reference")
    args = ap.parse_args()

    if not (HERE / "src" / "repro").is_dir():
        fail(f"no repro package under {HERE / 'src'}")
    sys.path.insert(0, str(HERE / "src"))
    from repro.jaxcache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    from repro.kernels.dispatch import interpret_default
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"first JAX device is {dev.platform!r} ({dev.device_kind}), "
             "not a TPU")
    if interpret_default():
        fail("REPRO_PALLAS_INTERPRET="
             f"{os.environ.get('REPRO_PALLAS_INTERPRET')!r} asks for "
             "interpret mode; the chip path runs compiled kernels only")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache}")
    if args.chips == 4:
        four_chips(jax)
    else:
        single_chip(jax)
        cr3_market()
    log(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
