"""Quickstart: build the paper's four-service fleet, fit penalty models,
and run Carbon Responder through the unified policy API
(`repro.core.api`): policies are values (`CR1(lam=...)`, `CR3(...)`),
`solve()` is the single entry point, `sweep()` runs a whole
hyperparameter grid as one vmapped XLA call, and `ensemble()` evaluates
a policy across a stack of Monte Carlo grid scenarios the same way
(the "Scenario ensembles & risk" section at the end). The closing
section solves a multi-region (region × workload) fleet — per-region
MCI pricing plus cross-region load migration — through the very same
`solve()` call.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core.api import CR1, CR3, SolveContext, ensemble, solve, sweep
from repro.core.carbon import caiso_2021
from repro.core.fleet_solver import FleetProblem, fleet_penalties
from repro.core.fleetcache import cached_paper_fleet
from repro.core.metrics import capacity_scaled_entropy
from repro.core.policies import DRProblem
from repro.core.scenario import DuckPerturb, RenewableDrought


def main() -> None:
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    print("== Carbon Responder quickstart ==")
    print("building fleet (4 services, EDD-simulated batch penalty models;"
          " cached after first run)...")
    fleet = cached_paper_fleet()
    models = tuple(fleet[n]
                   for n in ("RTS1", "RTS2", "AITraining", "DataPipeline"))
    signal = caiso_2021(48)
    print(f"grid signal: CAISO-2021-shaped MCI, trough/peak = "
          f"{signal.peak_to_trough():.2f}")
    problem = FleetProblem.from_problem(
        DRProblem(models=models, mci=signal.mci))

    print("\nsolving CR1 (Efficient DR) via the unified fleet engine:"
          "\n  result = solve(problem, CR1(lam=1.45))")
    result = solve(problem, CR1(lam=1.45))

    print(f"\ncarbon reduction : {result.carbon_reduction_pct:.2f}% "
          f"of baseline operational carbon (paper Fig. 7: 4.6%)")
    print(f"performance loss : {result.total_penalty_pct:.2f}% "
          f"capacity-equivalent (paper: ~4%)")
    per_pen = np.asarray(fleet_penalties(problem, jnp.asarray(result.D)))
    ent = capacity_scaled_entropy(per_pen, problem.entitlement)
    print(f"fairness entropy : {ent:.2f} (max 2.0)")
    mci = np.asarray(problem.mci)
    base = float((problem.usage.sum(0) * mci).sum())
    print("\nper-service outcome:")
    for i, name in enumerate(problem.names):
        c = 100 * float(result.D[i] @ mci) / base
        q = 100 * per_pen[i] / float(problem.entitlement.sum())
        hours_cut = int((result.D[i] > 0.01 * problem.usage[i]).sum())
        print(f"  {name:13s} carbon ↓{c:5.2f}%  penalty {q:5.2f}%  "
              f"curtailed {hours_cut}/48 hours")
    print("\nhourly adjustment profile (Σ over services, NP):")
    tot = result.D.sum(axis=0)
    for day in range(2):
        line = "".join("▼" if x > 0.3 else ("▲" if x < -0.3 else "·")
                       for x in tot[day * 24:(day + 1) * 24])
        print(f"  day {day}: {line}  (▼ curtail, ▲ boost/recover)")

    # The Fig.-8 trade-off curve: a policy grid is a list of values, and
    # sweep() runs the whole λ axis through one vmapped compile.
    print("\nCR1 λ sweep — sweep(problem, [CR1(lam=l) for l in grid]):")
    grid = (1.2, 1.45, 1.8)
    for lam, r in zip(grid, sweep(problem, [CR1(lam=la) for la in grid],
                                  ctx=SolveContext(steps=300))):
        print(f"  λ={lam:<5g} carbon ↓{r.carbon_reduction_pct:5.2f}%  "
              f"penalty {r.total_penalty_pct:5.2f}%")

    # Decentralized taxes-and-rebates: same entry point, policy-specific
    # outputs (clearing ρ, fiscal balance) ride result.extras.
    print("\nCR3 (Fair-Decentralized) — solve(problem, CR3()):")
    r3 = solve(problem, CR3(), ctx=SolveContext(steps=300))
    print(f"  carbon ↓{r3.carbon_reduction_pct:.2f}%  "
          f"penalty {r3.total_penalty_pct:.2f}%  "
          f"clearing ρ={r3.extras['rho']:.4f}  "
          f"balanced={r3.extras['balanced']}")

    # Scenario ensembles & risk: stress the policy across Monte Carlo
    # grid futures (duck-curve jitter, renewable droughts, Cambium
    # projections — repro.core.scenario) in ONE batched XLA call, then
    # read the risk layer: quantiles, CVaR tail risk, fairness
    # dispersion, SLO-violation probability. See
    # examples/scenario_risk.py for the full report.
    print("\nscenario ensemble — ensemble(problem, CR1(...), generators):")
    res = ensemble(
        problem, CR1(lam=1.45),
        [DuckPerturb(n_scenarios=4), RenewableDrought(n_scenarios=2)],
        ctx=SolveContext(steps=300))
    rep = res.report()
    print(f"  {res.S} scenarios, one batched solve: carbon p50="
          f"{rep.carbon_quantiles['p50']:.2f}% "
          f"[p5={rep.carbon_quantiles['p5']:.2f}], "
          f"CVaR25={rep.carbon_cvar:.2f}%")
    print(f"  fairness (Jain) p50={rep.jain_quantiles['p50']:.2f}, "
          f"SLO breach in {100 * rep.slo_violation_prob:.0f}% "
          f"of scenarios")

    # One-dispatch day: solve_day() runs a whole rolling-horizon day —
    # window roll + plan shift + warm re-solve per tick — inside one
    # lax.scan, so 24 online ticks cost ONE XLA dispatch instead of 24
    # (examples/streaming_dr.py --scan drives the full controller).
    # SolveContext(use_kernel=True) additionally routes the inner Adam
    # loop through the fused al_step Pallas kernel, and
    # moment_dtype="bfloat16" halves the optimizer-state footprint
    # (f32 master iterate, bf16 Adam moments).
    from repro.core.api import solve_day
    mci_stack = np.stack([np.roll(mci, -i) for i in range(4)])
    day = solve_day(problem, CR1(lam=1.45), mci_stack,
                    ctx=SolveContext(use_kernel=True,
                                     moment_dtype="bfloat16"),
                    cold_steps=300)
    print("\none-dispatch day — solve_day(problem, CR1, mci_stack):")
    print(f"  {day.committed.shape[0]} ticks in one XLA call, "
          f"committed NP {day.committed.sum():.1f}, "
          f"steps/tick {list(day.inner_steps)}")

    # Multi-region fleets: a FleetProblem with an (R, T) `mci` prices
    # each region on its own grid trace (Cambium state projections here,
    # rolled onto the coordinator's UTC clock), and a RegionTopology
    # lets solve() migrate deferrable batch slack toward cleaner regions
    # as a host-side post-stage — same entry point, same policies, and
    # R=1 degenerates bitwise to everything above. The full R=3 story
    # (per-region pricing vs the best single signal, migration flows,
    # 2-D device meshes) lives in examples/multi_region.py.
    from repro.core.fleet_solver import synthetic_regional_fleet
    pr = synthetic_regional_fleet(9, ["CA", "TX", "NY"], hours=48,
                                  utc_offsets="auto")
    rr = solve(pr, CR1(lam=1.45), ctx=SolveContext(steps=300))
    plan = rr.extras["migration"]
    print("\nmulti-region fleet — solve(regional_problem, CR1(...)):")
    print(f"  R={pr.R} regions {pr.topology.labels}, W={pr.W} workloads: "
          f"carbon ↓{rr.carbon_reduction_pct:.2f}% "
          f"(migration moved {plan.moved_total:.1f} NP for "
          f"{plan.net_saved:.1f} kgCO2 net)")

    # Coupled migration: SolveContext(coupled_migration=True) moves the
    # interconnect flows INTO the AL solve — curtailment and migration
    # refine jointly against bandwidth caps and tolls, instead of
    # migrating a frozen plan afterwards. The coupled candidate is kept
    # only when it beats the post-stage at equal total curtailment, so
    # this can match but never lose; extras["coupled_migration"] says
    # which stage won.
    rc = solve(pr, CR1(lam=1.45),
               ctx=SolveContext(steps=300, coupled_migration=True))
    kept = "in-loop" if rc.extras.get("coupled_migration") else "post-stage"
    print("\ncoupled migration — SolveContext(coupled_migration=True):")
    print(f"  carbon ↓{rc.carbon_reduction_pct:.2f}% vs post-stage "
          f"↓{rr.carbon_reduction_pct:.2f}% ({kept} candidate kept)")

    # Debugging & sanitizers (repro.analysis): when a solve misbehaves,
    # (1) SolveContext(sanitize=True) reruns the SAME jitted CR1/CR2
    # solve through a checkify twin — a NaN/inf in the gradient,
    # iterate, or multipliers raises SanitizeError naming the first
    # failing check instead of silently corrupting the plan and every
    # warm re-solve after it (~1x overhead, bitwise parity when clean);
    # (2) recompile_guard(0) asserts a region is compile-free, catching
    # the drifting static argument that turns "one trace per tick" into
    # "a compile per tick" (RollingHorizonSolver(guard_recompiles=True)
    # wires it into streaming); (3) `python -m repro.analysis.lint`
    # checks the tree's JAX invariants statically — see
    # src/repro/analysis/README.md for the rulebook.
    from repro.analysis import recompile_guard
    rs = solve(problem, CR1(lam=1.45),
               ctx=SolveContext(steps=300, sanitize=True))
    # Warm re-solve of the opening solve: same static config, warm and
    # cold share one trace, so the guarded block must stay compile-free.
    with recompile_guard(0, label="warm quickstart re-solve"):
        solve(problem, CR1(lam=1.45), ctx=SolveContext(warm=result.state))
    print("\ndebug lane — SolveContext(sanitize=True) + recompile_guard:")
    print(f"  sanitized solve clean (carbon ↓{rs.carbon_reduction_pct:.2f}%"
          f", bitwise = unchecked lane), warm re-solve compile-free")

    # Observability (repro.obs): SolveContext(telemetry=...) captures a
    # convergence trace INSIDE the jitted AL loop — objective, grad
    # norm, max constraint violation, mu — as stacked scan outputs (no
    # host callbacks, no extra dispatches; the returned plan is bitwise
    # identical to a telemetry-off solve). obs.span times host-side
    # work (`bind` synchronizes on device results before the clock is
    # read). Every span — the program's own `dr.tick` / `dr.solve` /
    # `dr.solve.dispatch` / `.wait` / `.pull` / `.report` ones too —
    # records start_ns/end_ns (perf_counter), its parent and tick ids
    # and its counts (h2d_bytes, d2h_bytes, dispatches) into a bounded,
    # always-on ring (`obs.spans.recorded()`), and shows up by name in
    # any jax.profiler trace. Streaming runs write a JSONL ledger
    # (RollingHorizonSolver(events=..., telemetry=...) or
    # `examples/streaming_dr.py --telemetry run.jsonl`), rendered by
    # `python -m repro.obs.report run.jsonl`.
    from repro import obs
    with obs.span("telemetry solve") as sp:
        rt = sp.bind(solve(problem, CR1(lam=1.45),
                           ctx=SolveContext(
                               steps=300,
                               telemetry=obs.TelemetryConfig(every=30))))
    trace = rt.extras["telemetry"]
    print("\nobservability — SolveContext(telemetry=TelemetryConfig()):")
    print(f"  {trace.n_samples} in-solve samples in {sp.elapsed_s:.2f}s: "
          f"objective {trace.objective[0]:.2f} -> {trace.objective[-1]:.2f},"
          f" grad norm {trace.grad_norm[-1]:.2e} at step {trace.step[-1]}")
    print(f"  plan bitwise = untelemetered solve: "
          f"{bool(np.array_equal(rt.D, rs.D))}")
    ring = obs.spans.recorded()
    solve_span = next(r for r in ring if r.parent == sp.id)
    print(f"  spans under {sp.name!r}: {solve_span.name} "
          f"{solve_span.elapsed_ns * 1e-6:.1f} ms, of it")
    for r in ring:
        if r.parent == solve_span.id:
            print(f"    {r.name:<20s} {r.elapsed_ns * 1e-6:8.2f} ms  "
                  f"{r.counts or ''}")


if __name__ == "__main__":
    main()
