"""Scenario-ensemble risk demo: stress a DR policy across Monte Carlo
grid futures and read the risk report an operator would sign off on.

Builds a synthetic fleet, generates a mixed scenario ensemble — duck-curve
shape uncertainty, renewable-drought days, evening-ramp spikes, Cambium
2024/2050 projection mixes, fleet composition jitter — and evaluates
CR1 (Efficient) vs CR2 (Fair-Centralized) across ALL scenarios as one
batched XLA call each (`repro.core.api.ensemble`). Prints per-policy
quantiles, CVaR tail risk, fairness dispersion and SLO-violation
probability, then the policy-vs-policy comparison table.

  PYTHONPATH=src python examples/scenario_risk.py \
      [--scenarios 16] [--workloads 16] [--steps 200]
"""
import argparse

from repro.core.api import CR1, CR2, SolveContext, ensemble
from repro.core.ensemble import comparison_table
from repro.core.fleet_solver import synthetic_fleet
from repro.core.scenario import (CambiumMix, DuckPerturb, EveningRampSpike,
                                 FleetJitter, RenewableDrought,
                                 resolve_scenarios)


def main() -> None:
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", type=int, default=16,
                    help="scenarios per generator family")
    ap.add_argument("--workloads", type=int, default=16)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    print("== Carbon Responder: scenario-ensemble risk report ==")
    fleet = synthetic_fleet(args.workloads, seed=args.seed)
    per = max(1, args.scenarios // 4)
    gens = [DuckPerturb(n_scenarios=max(1, args.scenarios - 3 * per),
                        seed=args.seed),
            RenewableDrought(n_scenarios=per, seed=args.seed + 1),
            EveningRampSpike(n_scenarios=per, seed=args.seed + 2),
            CambiumMix(n_scenarios=per, seed=args.seed + 3)]
    if args.scenarios >= 8:
        gens.append(FleetJitter(n_scenarios=per, seed=args.seed + 4))
    stack = resolve_scenarios(gens, fleet)
    print(f"fleet: {fleet.W} workloads x {fleet.T} h; "
          f"ensemble: {stack.S} scenarios from {len(gens)} generators")
    ctx = SolveContext(steps=args.steps)

    res = ensemble(fleet, CR1(lam=1.45), stack, ctx=ctx)
    rep = res.report()
    print(f"\nCR1 across {res.S} scenarios "
          f"({'one batched XLA call' if res.batched else 'solve loop'}):")
    print("\n".join("  " + ln for ln in rep.lines()))

    print("\npolicy-vs-policy risk comparison "
          "(same scenarios, batched per policy):")
    rep2 = ensemble(fleet, CR2(cap_frac=0.8, outer=2), stack,
                    ctx=ctx).report()
    print("\n".join("  " + ln for ln in comparison_table(
        {rep.policy: rep, rep2.policy: rep2})))

    worst = rep.worst_scenarios[0]
    idx = res.labels.index(worst)
    print(f"\nworst CR1 scenario: {worst} — carbon "
          f"{res.carbon_reduction_pct[idx]:.2f}% vs median "
          f"{float(sorted(res.carbon_reduction_pct)[res.S // 2]):.2f}%")


if __name__ == "__main__":
    main()
