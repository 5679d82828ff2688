"""Sweep all seven policies (CR1–3, B1–4) and print the Fig.-8 Pareto data
plus the efficiency headline (CR1 ≈ 1.5–2x baselines).

  PYTHONPATH=src python examples/policy_pareto.py
"""
import sys


def main() -> None:
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    sys.path.insert(0, ".")
    from benchmarks.common import get_problem, policy_sweeps
    sweep = policy_sweeps()
    by: dict[str, list] = {}
    for r in sweep:
        by.setdefault(r["policy"], []).append(r)
    print(f"{'policy':8s} {'hyper':>7s} {'carbon↓%':>9s} {'penalty%':>9s}")
    for pol in ("CR1", "CR2", "CR3", "B1", "B2", "B3", "B4"):
        for r in sorted(by.get(pol, []), key=lambda x: x["carbon_pct"]):
            print(f"{pol:8s} {r['hyper']:7.3f} {r['carbon_pct']:9.2f}"
                  f" {r['penalty_pct']:9.2f}")
    # efficiency at matched penalty
    def carbon_at(policy, pen_t):
        c = by.get(policy, [])
        return (min(c, key=lambda r: abs(r["penalty_pct"] - pen_t))
                ["carbon_pct"] if c else 0.0)
    for pen_t in (2.0, 4.0):
        cr1 = carbon_at("CR1", pen_t)
        base = max(carbon_at(b, pen_t) for b in ("B1", "B2", "B3", "B4"))
        print(f"\nat ~{pen_t:.0f}% penalty: CR1 removes {cr1:.2f}% carbon vs"
              f" best baseline {base:.2f}% -> {cr1/max(base,1e-9):.2f}x"
              f" (paper: 1.5-2x)")

    # Fleet-engine cross-check: the same CR1 frontier through the unified
    # policy API — the policy grid is a list of values and the whole λ
    # axis is one vmapped XLA call (DRProblem -> FleetProblem via
    # from_problem; SLSQP rows above are the validation reference).
    from repro.core.api import CR1, sweep
    from repro.core.fleet_solver import FleetProblem
    fp = FleetProblem.from_problem(get_problem())
    lams = [1.0, 1.2, 1.45, 1.6, 2.2]
    print("\nCR1 fleet-engine sweep (one compile for the grid):")
    for lam, r in zip(lams, sweep(fp, [CR1(lam=la) for la in lams])):
        print(f"CR1-flt  {lam:7.3f} {r.carbon_reduction_pct:9.2f}"
              f" {r.total_penalty_pct:9.2f}")


if __name__ == "__main__":
    main()
