"""Multi-region fleet demo: one coordinator, three grids.

Builds an R=3 fleet whose regions track the Cambium 2050 mid-case mixes
of California, Texas, and New York (`carbon.regional_traces`, rolled
onto the coordinator's UTC clock so each duck-curve trough lands at its
own hour), then shows the two levers a single-signal coordinator does
not have:

  1. per-region MCI pricing — each region curtails against ITS grid's
     marginal carbon, not a fleet-wide proxy; and
  2. cross-region load migration — deferrable batch slack moves toward
     the momentarily-cleaner region through a `RegionTopology`
     (bandwidth-capped, tolled), either as a host-side post-stage on the
     frozen plan or — `SolveContext(coupled_migration=True)` — refined
     jointly with curtailment inside the AL solve (compared below).

The comparison is at equal total curtailment: each single-signal plan
is scaled down to the multi-region plan's curtailment (a uniformly
down-scaled feasible plan stays feasible), so the gap is pure signal
quality, not extra sacrifice.

  PYTHONPATH=src python examples/multi_region.py

On a multi-device host the same problem shards over a 2-D
(REGION_AXIS, FLEET_AXIS) mesh — `make_fleet_mesh(regions=3)` — with
<0.01 pp parity; see tests/test_fleet_sharding.py.
"""
import dataclasses

import numpy as np

from repro.core.api import CR1, SolveContext, ensemble, solve
from repro.core.fleet_solver import RegionTopology, synthetic_regional_fleet
from repro.core.scenario import RegionalDivergence

STATES = ["CA", "TX", "NY"]


def main() -> None:
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    print("== multi-region fleet: CA + TX + NY on one coordinator ==")
    p = synthetic_regional_fleet(9, STATES, hours=48, seed=0,
                                 utc_offsets="auto")
    # a well-interconnected fleet: per-link bandwidth at 15% of fleet
    # entitlement (the synthetic default is a conservative 5%)
    ent = float(np.asarray(p.entitlement).sum())
    bw = np.full((3, 3), 0.15 * ent / 2)
    np.fill_diagonal(bw, 0.0)
    p = dataclasses.replace(
        p, topology=RegionTopology(cost=np.full((3, 3), 1.0), bandwidth=bw,
                                   labels=tuple(STATES)))
    region = np.asarray(p.region)
    mcis = np.asarray(p.mci)
    wmci = mcis[region]
    base = float((np.asarray(p.usage) * wmci).sum())
    print(f"fleet: W={p.W} workloads across R={p.R} regions "
          f"{p.topology.labels}, T={p.T}h")
    for r, s in enumerate(STATES):
        trough = int(np.argmin(mcis[r][:24]))
        print(f"  {s}: {int((region == r).sum())} workloads, cleanest "
              f"hour {trough:02d}:00 UTC, trough/peak "
              f"{mcis[r].min() / mcis[r].max():.2f}")

    ctx = SolveContext(steps=400)
    pol = CR1(lam=1.45)
    multi = solve(p, pol, ctx=ctx)
    curtail = float(np.asarray(multi.D).sum())
    plan = multi.extras["migration"]
    print(f"\nper-region pricing + migration: "
          f"carbon ↓{multi.carbon_reduction_pct:.2f}% "
          f"at {curtail:.0f} NP total curtailment")
    print(f"  migration: moved {plan.moved_total:.1f} NP for "
          f"{plan.carbon_saved:.1f} kgCO2 gross "
          f"- {plan.migration_cost:.1f} toll = {plan.net_saved:.1f} net")
    for r, s in enumerate(STATES):
        out = plan.by_region()[r]
        arrow = "exports" if out > 0 else "imports"
        print(f"  {s}: {arrow} {abs(out):.1f} NP of batch slack")

    # In-loop vs post-stage migration: the post-stage above migrates a
    # FROZEN plan; coupled_migration=True gives the AL solve the
    # interconnect flow variables too, so curtailment can shift toward
    # hours where a profitable (spread > toll) link has spare bandwidth.
    # The coupled candidate is only kept when it beats the post-stage at
    # equal total curtailment — it can match but never lose.
    coup = solve(p, pol,
                 ctx=dataclasses.replace(ctx, coupled_migration=True))
    kept = ("in-loop candidate kept"
            if coup.extras.get("coupled_migration")
            else "post-stage kept (coupled did not beat it)")
    print(f"\nin-loop (coupled) migration: "
          f"↓{coup.carbon_reduction_pct:.2f}% vs post-stage "
          f"↓{multi.carbon_reduction_pct:.2f}% — {kept}")

    # What any ONE signal would have done, scaled to the same total
    # curtailment so the comparison is apples-to-apples.
    print("\nbest single-signal alternative (equal total curtailment):")
    best = -np.inf
    for r, s in enumerate(STATES):
        single = dataclasses.replace(p, mci=mcis[r], region=None,
                                     topology=None)
        rs = solve(single, pol, ctx=ctx)
        realized = 100.0 * float((np.asarray(rs.D) * wmci).sum()) / base
        scale = curtail / float(np.asarray(rs.D).sum())
        print(f"  price everything on {s}: ↓{realized * scale:.2f}%")
        best = max(best, realized * scale)
    print(f"multi-region advantage: "
          f"+{multi.carbon_reduction_pct - best:.2f} pp fleet-wide carbon")

    # Robustness: RegionalDivergence stresses the ensemble layer with
    # per-region level shifts and regional renewable droughts.
    res = ensemble(p, pol, [RegionalDivergence(n_scenarios=8, seed=0)],
                   ctx=SolveContext(steps=300))
    rep = res.report()
    print(f"\nregional-divergence ensemble ({res.S} scenarios): "
          f"carbon p50={rep.carbon_quantiles['p50']:.2f}% "
          f"[p5={rep.carbon_quantiles['p5']:.2f}], "
          f"CVaR25={rep.carbon_cvar:.2f}%")


if __name__ == "__main__":
    main()
