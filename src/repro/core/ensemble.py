"""Batched scenario-ensemble evaluation and risk reports.

`repro.core.scenario` materializes S grid/fleet scenarios as a
`ScenarioStack` — per-field overlays with a leading S axis. This module
evaluates a `DRPolicy` across the whole stack:

  * `evaluate_ensemble(problem, policy, scenarios, ctx=...)` — the entry
    point (also exposed as `repro.core.api.ensemble`). For the engine
    policy families whose solve is a single XLA call (CR1/CR2), the S
    axis rides `jax.vmap` through the same tick body `api.solve` jits
    (`api._batch_lane`, which `api.sweep` runs too) — ONE batched XLA
    call for the whole ensemble, no Python loop over scenarios. With
    `ctx.mesh`, the scenario vmap nests *inside* the W-axis shard_map,
    so fleet-scale ensembles run sharded too. Every other
    policy (CR3's market, closed-form baselines, warm/donated
    contexts) falls back to an equivalent sequential loop of
    `api.solve` over the materialized scenarios — `evaluate_ensemble` is
    always safe to call.

  * `run_streaming_ensemble(problem, policy, streams, ...)` — the
    rolling-horizon variant: S independent `ForecastStream`s (e.g. from
    `scenario.ForecastRegime.streams`) drive one batched controller.
    Each tick stacks the S revised forecasts, warm-starts every
    scenario's lane from its own previous `EngineState` (shift + mu
    reset folded into the same batched call), and commits hour 0 per
    scenario — S online controllers for the price of one batched
    re-solve per tick.

  * `EnsembleResult.report()` / `EnsembleReport` — the risk layer:
    quantiles and CVaR of realized carbon reduction and fleet penalty,
    per-workload penalty distributions and SLO-violation probabilities,
    fairness dispersion per scenario (Jain index, max/min share ratio),
    and `compare_policies` tables for benchmarks and examples.

Parity contract (tested in tests/test_ensemble.py and the sharding
suite): the batched lane matches the sequential `api.solve` loop to
<0.01 pp on every scenario, single-device and on a device mesh — vmap
reorders floating-point reductions, so bitwise equality is not promised,
convergence-level equality is.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.api import (CR1, CR2, SolveContext, _BATCH, _lane_inputs,
                            resolve_policy, solve)
from repro.core.engine import EngineState
from repro.core.fleet_solver import (PAD_FILLS, FleetProblem,
                                     fleet_penalties, held_report_sums,
                                     report_numbers, resolve_use_kernel)
from repro.core.metrics import jain_index, max_min_ratio
from repro.core.regional import region_totals
from repro.core.scenario import ScenarioStack, resolve_scenarios

__all__ = ["EnsembleReport", "EnsembleResult", "compare_policies",
           "comparison_table", "evaluate_ensemble",
           "run_streaming_ensemble", "StreamingEnsembleReport"]

# ---------------------------------------------------------------------------
# Batched engine lanes (CR1/CR2): vmap over the scenario axis
# ---------------------------------------------------------------------------
def _overlay_args(stack: ScenarioStack) -> tuple[tuple[str, ...], tuple]:
    over = stack.overlay_fields()
    keys = tuple(over)
    return keys, tuple(jnp.asarray(over[k]) for k in keys)


def _pad_overlays(keys: tuple[str, ...], vals: tuple, W: int, W_pad: int):
    """Pad stacked per-workload overlays with `pad_fleet`'s inert fills
    (`fleet_solver.PAD_FILLS` — shared so the conventions cannot drift)."""
    if W_pad == W:
        return vals
    out = []
    for k, v in zip(keys, vals):
        if k == "mci":
            out.append(v)
            continue
        pad_shape = (v.shape[0], W_pad - W) + v.shape[2:]
        out.append(jnp.concatenate(
            [v, jnp.full(pad_shape, PAD_FILLS[k], v.dtype)], axis=1))
    return tuple(out)


def _scenario_hypers(policy, p: FleetProblem, stack: ScenarioStack) -> list:
    """Per-scenario engine hypers (`CR1._hyper`/`CR2._hyper`). CR2's
    fairness references depend on usage/entitlement/jobs (the capped
    penalty under an equal power cap) but never on the MCI, so MCI-only
    ensembles — every streaming tick — compute them once and share them
    across all S lanes."""
    if not {"usage", "entitlement", "jobs"} & set(stack.overlay_fields()):
        return [policy._hyper(p)] * stack.S
    return [policy._hyper(ps) for ps in stack.problems(p)]


def _run_batched(policy, p: FleetProblem, stack: ScenarioStack, *,
                 steps: int, use_kernel: bool, mesh=None,
                 init: EngineState | None = None, shift: int = 0,
                 reset_mu: bool = False):
    """One batched XLA call solving all S scenarios under `policy`
    (CR1/CR2) through `api._batch_lane`: the tick body vmapped over the
    scenario overlays, inside the W-axis shard_map with `mesh` (per-
    scenario norms from the TRUE fleets, overlays padded with inert
    rows). Returns (D (S, W, T) np, pens (S, W) np, states stacked).

    `init` (stacked `EngineState`, e.g. the previous streaming tick's;
    padded here to a mesh's rows) warm-starts every lane; `shift`/
    `reset_mu` fold the rolling-horizon tick entry into the same call."""
    keys, vals = _overlay_args(stack)
    mode = policy.name
    pj, W, hypers, norms, init = _lane_inputs(
        mode, p, _scenario_hypers(policy, p, stack),
        None if mesh is None else list(stack.problems(p)), mesh, init)
    D, pens, states = _BATCH.plain(
        pj, _pad_overlays(keys, vals, W, pj.W), hypers, init, norms,
        keys=keys, mode=mode, mesh=mesh, steps=steps, outer=policy.outer,
        use_kernel=use_kernel, shift=shift, reset_mu=reset_mu)
    return np.asarray(D)[:, :W], np.asarray(pens)[:, :W], states


def _batched_capable(policy) -> bool:
    return type(policy) in (CR1, CR2)


# ---------------------------------------------------------------------------
# Ensemble results + the risk layer
# ---------------------------------------------------------------------------
def _quantiles(x: np.ndarray, qs: Sequence[float]) -> dict[str, float]:
    return {f"p{int(q)}": float(np.percentile(x, q)) for q in qs}


def _cvar(x: np.ndarray, alpha: float, worst: str) -> np.ndarray:
    """Mean of the worst `alpha` tail — `worst='low'` for quantities where
    small is bad (carbon reduction), `'high'` where large is bad
    (penalty)."""
    x = np.sort(np.asarray(x, float))
    k = max(1, int(np.ceil(alpha * x.shape[0])))
    tail = x[:k] if worst == "low" else x[-k:]
    return float(tail.mean())


@dataclasses.dataclass(frozen=True)
class EnsembleResult:
    """Per-scenario outcomes of one policy across a scenario stack."""

    policy: Any                          # the DRPolicy evaluated
    labels: tuple[str, ...]              # scenario labels, length S
    carbon_reduction_pct: np.ndarray     # (S,)
    total_penalty_pct: np.ndarray        # (S,)
    penalties: np.ndarray                # (S, W) raw per-workload penalties
    entitlement: np.ndarray              # (S, W) per-scenario entitlements
    preservation_violation: np.ndarray   # (S,)
    D: np.ndarray                        # (S, W, T) adjustment plans
    extras: tuple[dict, ...]             # per-scenario policy extras
    batched: bool                        # one vmapped call vs solve() loop

    @property
    def S(self) -> int:
        return int(self.carbon_reduction_pct.shape[0])

    def penalty_shares(self) -> np.ndarray:
        """(S, W) capacity-scaled penalty shares pen_i/E_i."""
        return np.maximum(self.penalties, 0.0) / self.entitlement

    def report(self, *, slo_frac: float = 0.05, cvar_alpha: float = 0.25,
               quantiles: Sequence[float] = (5, 25, 50, 75, 95),
               ) -> "EnsembleReport":
        """Distill the ensemble into risk metrics (see `EnsembleReport`)."""
        # p5/p50/p95 are always computed — `lines()`/`comparison_table`
        # render them regardless of the caller's quantile choice.
        quantiles = sorted({*quantiles, 5, 50, 95})
        car = self.carbon_reduction_pct
        pen = self.total_penalty_pct
        shares = self.penalty_shares()
        jain = jain_index(self.penalties, self.entitlement, axis=-1)
        mm = max_min_ratio(self.penalties, self.entitlement, axis=-1)
        viol = shares > slo_frac                   # (S, W)
        k = max(1, int(np.ceil(cvar_alpha * self.S)))
        worst = tuple(self.labels[i] for i in np.argsort(car)[:k])
        return EnsembleReport(
            policy=getattr(self.policy, "name", str(self.policy)),
            n_scenarios=self.S,
            carbon_quantiles=_quantiles(car, quantiles),
            carbon_mean=float(car.mean()),
            carbon_cvar=_cvar(car, cvar_alpha, "low"),
            penalty_quantiles=_quantiles(pen, quantiles),
            penalty_mean=float(pen.mean()),
            penalty_cvar=_cvar(pen, cvar_alpha, "high"),
            jain_quantiles=_quantiles(jain, quantiles),
            jain_min=float(jain.min()),
            maxmin_median=float(np.median(mm)),
            slo_frac=slo_frac, cvar_alpha=cvar_alpha,
            slo_violation_prob=float(viol.any(axis=1).mean()),
            workload_slo_prob=viol.mean(axis=0),
            workload_penalty_p95=np.percentile(shares, 95, axis=0),
            worst_scenarios=worst)


@dataclasses.dataclass(frozen=True)
class EnsembleReport:
    """Risk summary of a policy over S scenarios.

    CVaR_α is the expected outcome over the worst α-fraction of
    scenarios — lowest carbon reductions, highest penalties — the
    number an operator signs off on, not the median. SLO violation:
    a workload breaches when its capacity-scaled penalty share
    pen_i/E_i exceeds `slo_frac`; `slo_violation_prob` is the fraction
    of scenarios where ANY workload breaches."""

    policy: str
    n_scenarios: int
    carbon_quantiles: dict[str, float]   # carbon reduction, % of baseline
    carbon_mean: float
    carbon_cvar: float
    penalty_quantiles: dict[str, float]  # fleet penalty, % of entitlement
    penalty_mean: float
    penalty_cvar: float
    jain_quantiles: dict[str, float]     # fairness dispersion per scenario
    jain_min: float
    maxmin_median: float                 # median max/min share ratio
    slo_frac: float
    cvar_alpha: float
    slo_violation_prob: float
    workload_slo_prob: np.ndarray        # (W,) per-workload breach prob
    workload_penalty_p95: np.ndarray     # (W,) p95 penalty share
    worst_scenarios: tuple[str, ...]     # labels of the CVaR tail

    def lines(self) -> list[str]:
        cq, pq, jq = (self.carbon_quantiles, self.penalty_quantiles,
                      self.jain_quantiles)
        a = int(100 * self.cvar_alpha)
        return [
            f"policy {self.policy} over {self.n_scenarios} scenarios:",
            f"  carbon reduction : p50={cq['p50']:.2f}%  "
            f"[p5={cq['p5']:.2f}, p95={cq['p95']:.2f}]  "
            f"CVaR{a}={self.carbon_cvar:.2f}%",
            f"  fleet penalty    : p50={pq['p50']:.2f}%  "
            f"[p5={pq['p5']:.2f}, p95={pq['p95']:.2f}]  "
            f"CVaR{a}={self.penalty_cvar:.2f}%",
            f"  fairness (Jain)  : p50={jq['p50']:.3f}  "
            f"min={self.jain_min:.3f}  "
            f"max/min share p50="
            + (f"{self.maxmin_median:.1f}x" if self.maxmin_median < 9999.5
               else ">=10000x (saturated: some workload pays ~nothing)"),
            f"  SLO (> {100 * self.slo_frac:.0f}% of E_i) breach prob: "
            f"{100 * self.slo_violation_prob:.0f}% of scenarios",
            f"  worst scenarios  : {', '.join(self.worst_scenarios[:3])}",
        ]

    def as_dict(self) -> dict:
        """JSON-ready record (benchmark trajectory files)."""
        d = dataclasses.asdict(self)
        d["workload_slo_prob"] = np.asarray(
            self.workload_slo_prob).tolist()
        d["workload_penalty_p95"] = np.asarray(
            self.workload_penalty_p95).tolist()
        d["worst_scenarios"] = list(self.worst_scenarios)
        return d


def _stack_arrays(base: FleetProblem, stack: ScenarioStack):
    """Per-scenario (mci, usage, entitlement) with the S axis broadcast
    from the base where not overlaid."""
    S = stack.S
    mci = stack.mci if stack.mci is not None else np.broadcast_to(
        np.asarray(base.mci, float), (S,) + np.asarray(base.mci).shape)
    usage = stack.usage if stack.usage is not None else np.broadcast_to(
        np.asarray(base.usage, float), (S, base.W, base.T))
    ent = stack.entitlement if stack.entitlement is not None else \
        np.broadcast_to(np.asarray(base.entitlement, float), (S, base.W))
    return np.asarray(mci, float), np.asarray(usage, float), \
        np.asarray(ent, float)


def _result_from_stacks(base: FleetProblem, stack: ScenarioStack, policy,
                        D: np.ndarray, pens: np.ndarray, batched: bool,
                        ) -> tuple[EnsembleResult, np.ndarray]:
    """`fleet_solver._report` over the scenario axis: every scenario's
    report sums in one call (`held_report_sums`), finished in float64.
    Returns the result and each scenario's carbon base (S,)."""
    mci, usage, ent = _stack_arrays(base, stack)
    sums = held_report_sums(base, D, pens, usage=usage, lanes=True)
    car, pen, viol, carbon_base = report_numbers(
        mci.reshape(stack.S, -1, base.T), ent.sum(axis=1), sums)
    labels = tuple(stack.label(s) for s in range(stack.S))
    return EnsembleResult(
        policy=policy, labels=labels, carbon_reduction_pct=car,
        total_penalty_pct=pen, penalties=pens, entitlement=ent,
        preservation_violation=viol, D=D,
        extras=tuple({} for _ in range(stack.S)),
        batched=batched), carbon_base


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def evaluate_ensemble(problem: FleetProblem, policy, scenarios, *,
                      ctx: SolveContext | None = None,
                      batched: bool | None = None) -> EnsembleResult:
    """Evaluate `policy` on S scenarios of `problem` — the ensemble entry
    point (also `repro.core.api.ensemble`).

    `scenarios` is a `ScenarioStack`, a scenario generator (or
    `SCENARIO_REGISTRY` name), or a sequence of those (concatenated).
    CR1/CR2 run all S scenarios as ONE vmapped XLA call (nested inside
    the W-axis shard_map when `ctx.mesh` is set); other policies and
    warm/donated contexts fall back to a sequential loop of `api.solve`
    with identical semantics — `ctx.telemetry` also takes the loop, so
    each scenario's `ConvergenceTrace` rides its own entry of
    `result.extras`. `batched` forces the lane (True raises if the
    policy has no batched backend; False forces the loop — the
    parity-test hook)."""
    ctx = ctx or SolveContext()
    policy = resolve_policy(policy)
    stack = resolve_scenarios(scenarios, problem)
    can_batch = (_batched_capable(policy) and ctx.warm is None
                 and not ctx.donate and not ctx.shift and not ctx.reset_mu
                 and ctx.telemetry is None)
    if batched is True and not can_batch:
        raise ValueError(
            f"no batched ensemble lane for policy "
            f"{getattr(policy, 'name', policy)!r} under this context "
            "(CR1/CR2, no warm/donate/shift/reset_mu/telemetry)")
    if batched is False or not can_batch:
        probs = list(stack.problems(problem))
        results = [solve(ps, policy,
                         ctx=dataclasses.replace(ctx, donate=False))
                   for ps in probs]
        mci, usage, ent = _stack_arrays(problem, stack)
        uk = resolve_use_kernel(ctx.use_kernel)
        return EnsembleResult(
            policy=policy,
            labels=tuple(stack.label(s) for s in range(stack.S)),
            carbon_reduction_pct=np.asarray(
                [r.carbon_reduction_pct for r in results]),
            total_penalty_pct=np.asarray(
                [r.total_penalty_pct for r in results]),
            # per-workload penalties are not part of FleetSolveResult, so
            # they are evaluated once per scenario on the solved plans
            penalties=np.stack([
                np.asarray(fleet_penalties(ps, jnp.asarray(r.D), uk))
                for ps, r in zip(probs, results)]),
            entitlement=ent,
            preservation_violation=np.asarray(
                [r.preservation_violation for r in results]),
            D=np.stack([r.D for r in results]),
            extras=tuple(r.extras for r in results), batched=False)
    steps = ctx.resolved_steps(policy)
    use_kernel = resolve_use_kernel(ctx.use_kernel)
    D, pens, _ = _run_batched(policy, problem, stack, steps=steps,
                              use_kernel=use_kernel, mesh=ctx.mesh)
    res, carbon_base = _result_from_stacks(problem, stack, policy, D, pens,
                                           batched=True)
    return _apply_migration_credit(problem, stack, res, carbon_base)


def _apply_migration_credit(base: FleetProblem, stack: ScenarioStack,
                            res: EnsembleResult,
                            carbon_base: np.ndarray) -> EnsembleResult:
    """Per-scenario migration post-stage for the batched lane — exactly
    what `api.solve`'s `_maybe_migrate` applies in the loop lane, so the
    two lanes stay in parity on multi-region problems with a usable
    topology. `carbon_base` is each scenario's report denominator."""
    if (base.topology is None or not base.is_multiregion
            or not np.any(np.asarray(base.topology.bandwidth) > 0.0)):
        return res
    from repro.core.migration import fleet_migration
    car = res.carbon_reduction_pct.copy()
    extras = []
    for s, ps in enumerate(stack.problems(base)):
        plan = fleet_migration(ps, np.asarray(res.D[s]))
        car[s] += 100.0 * plan.net_saved / carbon_base[s]
        extras.append({"migration": plan})
    return dataclasses.replace(res, carbon_reduction_pct=car,
                               extras=tuple(extras))


def compare_policies(problem: FleetProblem, policies: Sequence, scenarios,
                     *, ctx: SolveContext | None = None,
                     **report_kw) -> dict[str, EnsembleReport]:
    """Risk reports for several policies on the SAME scenario stack —
    the policy-vs-policy comparison feeding `benchmarks/` and examples.
    Keys are registry names (duplicate families get `name#i` suffixes)."""
    stack = resolve_scenarios(scenarios, problem)
    out: dict[str, EnsembleReport] = {}
    for pl in policies:
        pl = resolve_policy(pl)
        rep = evaluate_ensemble(problem, pl, stack, ctx=ctx).report(
            **report_kw)
        key = rep.policy
        if key in out:
            key = f"{key}#{sum(k.split('#')[0] == rep.policy for k in out)}"
        out[key] = rep
    return out


def comparison_table(reports: dict[str, EnsembleReport]) -> list[str]:
    """Fixed-width policy-vs-policy table (one row per report)."""
    a = int(100 * next(iter(reports.values())).cvar_alpha) if reports else 0
    head = (f"{'policy':10s} {'carbon p50':>10s} {'carbon p5':>10s} "
            f"{f'CVaR{a}':>8s} {'pen p50':>8s} {'pen CVaR':>9s} "
            f"{'jain p50':>9s} {'SLO prob':>9s}")
    rows = [head, "-" * len(head)]
    for name, r in reports.items():
        rows.append(
            f"{name:10s} {r.carbon_quantiles['p50']:>9.2f}% "
            f"{r.carbon_quantiles['p5']:>9.2f}% {r.carbon_cvar:>7.2f}% "
            f"{r.penalty_quantiles['p50']:>7.2f}% "
            f"{r.penalty_cvar:>8.2f}% {r.jain_quantiles['p50']:>9.3f} "
            f"{100 * r.slo_violation_prob:>8.0f}%")
    return rows


# ---------------------------------------------------------------------------
# Rolling-horizon ensemble: S streams, one batched controller
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StreamingEnsembleReport:
    """S rolling-horizon runs, batched: per-scenario committed plans and
    carbon ledgers (the streaming analogue of `EnsembleResult`)."""

    labels: tuple[str, ...]
    committed: np.ndarray          # (S, W, n_ticks)
    realized_carbon: np.ndarray    # (S,) kg CO2 eliminated at actual MCI
    forecast_carbon: np.ndarray    # (S,) same hours at solve-time forecast
    realized_baseline: np.ndarray  # (S,) no-DR carbon of committed hours
    total_inner_steps: int         # engine iterations per scenario lane
    batched: bool

    @property
    def S(self) -> int:
        return int(self.realized_carbon.shape[0])

    @property
    def n_ticks(self) -> int:
        return int(self.committed.shape[-1])

    @property
    def realized_reduction_pct(self) -> np.ndarray:
        return 100.0 * self.realized_carbon / np.maximum(
            self.realized_baseline, 1e-12)

    def risk(self, *, cvar_alpha: float = 0.25,
             quantiles: Sequence[float] = (5, 25, 50, 75, 95),
             ) -> dict[str, float]:
        red = self.realized_reduction_pct
        out = _quantiles(red, quantiles)
        out["mean"] = float(red.mean())
        out[f"cvar{int(100 * cvar_alpha)}"] = _cvar(red, cvar_alpha, "low")
        return out


def run_streaming_ensemble(problem: FleetProblem, policy, streams, *,
                           n_ticks: int | None = None,
                           cold_steps: int = 600, warm_steps: int = 150,
                           use_kernel: bool | None = None,
                           ) -> StreamingEnsembleReport:
    """Drive S independent forecast streams through batched warm-started
    rolling-horizon ticks.

    `streams` is a sequence of `ForecastStream`s (every horizon must equal
    `problem.T`) or a `scenario.ForecastRegime` (its `streams()` factory
    is called with `n_ticks`). Multi-region problems take one stream
    *per region* per scenario — a sequence of R-tuples (exactly what
    `ForecastRegime.streams` yields for a multi-region base) — and the
    scenario axis batches whole (R, T) forecast stacks, so regional
    regimes like `RegionalDivergence` run through the one-dispatch
    batched lane. Per tick, the S revised forecasts stack into one
    scenario axis and the whole ensemble re-solves as one batched XLA
    call, each lane warm-started from its own previous `EngineState`
    (shift + mu reset inside the call) — the `RollingHorizonSolver`
    loop, vmapped over scenarios. Policies without a batched lane fall
    back to S sequential `RollingHorizonSolver` runs. As in
    `RollingHorizonSolver`, only hour 0 of each plan commits, so no
    migration post-stage applies to streaming ticks."""
    from repro.core.scenario import ForecastRegime
    from repro.core.streaming import RollingHorizonSolver
    policy = resolve_policy(policy)
    multi = problem.is_multiregion
    R = problem.R if multi else 1
    if isinstance(streams, ForecastRegime):
        streams = streams.streams(problem, n_ticks=n_ticks or 1)
    groups = []
    for item in streams:
        g = tuple(item) if isinstance(item, (tuple, list)) else (item,)
        if len(g) != R:
            raise ValueError(
                f"need {R} stream(s) per scenario (one per region), "
                f"got {len(g)}")
        groups.append(g)
    groups = tuple(groups)
    if not groups:
        raise ValueError("run_streaming_ensemble needs >= 1 stream")
    for g in groups:
        for st in g:
            if st.horizon != problem.T:
                raise ValueError(
                    f"stream horizon {st.horizon} != problem.T {problem.T}")
    max_ticks = min(st.n_ticks for g in groups for st in g)
    n = max_ticks if n_ticks is None else n_ticks
    if not 0 < n <= max_ticks:
        raise ValueError(f"n_ticks {n} outside (0, {max_ticks}]")
    S = len(groups)
    labels = tuple(
        f"stream[sigma={g[0].revision_sigma:.3f},seed={g[0].seed}]"
        for g in groups)
    base_usage = np.asarray(problem.usage, float)
    if multi:
        region = np.asarray(problem.region)
        onehot = np.zeros((problem.W, R))
        onehot[np.arange(problem.W), region] = 1.0
        usage_by_region = region_totals(region, base_usage, R)  # (R, T)

    if not _batched_capable(policy):
        reports = [RollingHorizonSolver(
            problem, g if multi else g[0], policy=policy,
            cold_steps=cold_steps, warm_steps=warm_steps,
            use_kernel=use_kernel).run(n)
            for g in groups]
        return StreamingEnsembleReport(
            labels=labels,
            committed=np.stack([r.committed for r in reports]),
            realized_carbon=np.asarray(
                [r.realized_carbon for r in reports]),
            forecast_carbon=np.asarray(
                [r.forecast_carbon for r in reports]),
            realized_baseline=np.asarray(
                [r.realized_baseline for r in reports]),
            total_inner_steps=reports[0].total_inner_steps,
            batched=False)

    use_kernel = resolve_use_kernel(use_kernel)
    committed = np.zeros((S, problem.W, n))
    realized = np.zeros(S)
    forecast = np.zeros(S)
    baseline = np.zeros(S)
    states: EngineState | None = None
    total_steps = 0
    for t in range(n):
        if multi:
            mcis = np.stack([[st.forecast(t) for st in g] for g in groups])
        else:
            mcis = np.stack([g[0].forecast(t) for g in groups])
        p_t = dataclasses.replace(
            problem, mci=np.asarray(problem.mci),
            usage=np.roll(problem.usage, -t, axis=1),
            jobs=np.roll(problem.jobs, -t, axis=1),
            upper=None if problem.upper is None
            else np.roll(problem.upper, -t, axis=1))
        steps = cold_steps if states is None else warm_steps
        D, _, states = _run_batched(
            policy, p_t, ScenarioStack(mci=mcis, labels=labels),
            steps=steps, use_kernel=use_kernel, init=states,
            shift=0 if t == 0 else 1, reset_mu=t > 0)
        committed[:, :, t] = D[:, :, 0]
        total_steps += steps * policy.outer
        if multi:
            real_t = np.asarray(
                [[st.realized(t) for st in g] for g in groups])  # (S, R)
            by_reg = committed[:, :, t] @ onehot                 # (S, R)
            realized += (by_reg * real_t).sum(axis=1)
            forecast += (by_reg * mcis[:, :, 0]).sum(axis=1)
            baseline += (usage_by_region[:, t % base_usage.shape[1]]
                         * real_t).sum(axis=1)
        else:
            real_t = np.asarray([g[0].realized(t) for g in groups])
            realized += committed[:, :, t].sum(axis=1) * real_t
            forecast += committed[:, :, t].sum(axis=1) * mcis[:, 0]
            baseline += real_t * base_usage[:, t % base_usage.shape[1]].sum()
    return StreamingEnsembleReport(
        labels=labels, committed=committed, realized_carbon=realized,
        forecast_carbon=forecast, realized_baseline=baseline,
        total_inner_steps=total_steps, batched=True)
