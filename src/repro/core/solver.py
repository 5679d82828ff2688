"""DR optimization solvers.

Two interchangeable backends consume a `PolicySpec`:

  * `solve_slsqp` — scipy Sequential Least Squares Programming, the paper's
    solver (§VI-A: "We solve optimization problems with Scipy's Sequential
    Least Squares Programming"), with JAX-supplied exact gradients. This is
    the **paper-faithful reference**: fine for 4 workloads × 48 hours.

  * `solve_adam` — beyond-paper fleet-scale solver: a thin adapter over the
    shared engine (`repro.core.engine.al_minimize`): jit-compiled projected
    Adam on an augmented Lagrangian. Box bounds and batch-preservation are
    handled by exact projection (both are cheap closed forms); equality /
    inequality constraints get multiplier + quadratic terms. One XLA call
    solves the whole problem; `vmap` over hyperparameters sweeps a Pareto
    frontier in a single compile (see `repro.core.api.sweep`).

Both report final metrics with the *unsmoothed* models so numbers are
comparable across solvers. With the vectorized `FleetProblem` stack (see
`repro.core.fleet_solver`) carrying the production path, the SLSQP solver
here is the *validation reference*: `FleetProblem.from_problem/to_problem`
convert between the two representations so every fleet policy can be
cross-checked against the paper's solver on small instances.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import EngineConfig, al_minimize
from repro.core.policies import DRProblem, PolicySpec

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SolveResult:
    """Outcome of one policy solve, reported with unsmoothed models."""

    name: str
    solver: str
    D: np.ndarray                    # (W, T)
    objective: float
    carbon_reduction: float          # kg CO2 eliminated (Σ⟨mci, d_i⟩)
    carbon_reduction_pct: float      # % of baseline operational carbon
    total_penalty: float             # NP capacity-equivalent
    total_penalty_pct: float         # % of Σ entitlements
    per_penalty: np.ndarray          # (W,)
    per_carbon: np.ndarray           # (W,)
    peak: float
    violations: dict[str, float]
    nit: int


def evaluate(spec: PolicySpec, D: np.ndarray, solver: str, nit: int,
             objective: float | None = None) -> SolveResult:
    """Final reporting with smooth=0 (the true, kinked models)."""
    p = spec.problem
    Dj = jnp.asarray(D)
    per_pen = np.asarray(p.penalties(Dj, smooth=0.0))
    per_car = np.asarray(p.carbon_reduction_per_workload(Dj))
    lower, upper = p.bounds()
    if spec.lower is not None:
        lower = spec.lower
    if spec.upper is not None:
        upper = spec.upper
    viol = {
        "capacity": max(0.0, float(p.peak(Dj)) - p.capacity_limit),
        "box": float(np.maximum(np.maximum(D - upper, lower - D), 0.0).max()),
    }
    if spec.use_preservation and p.preservation != "none":
        res = np.asarray(p.preservation_residual(Dj))
        viol["preservation"] = (float(np.abs(res).max()) if res.size else 0.0) \
            if p.preservation == "equality" else \
            (float(np.maximum(-res, 0.0).max()) if res.size else 0.0)
    for j, g in enumerate(spec.ineq_constraints):
        viol[f"ineq{j}"] = max(0.0, -float(np.min(np.asarray(g(Dj)))))
    for j, h in enumerate(spec.eq_constraints):
        viol[f"eq{j}"] = float(np.abs(np.asarray(h(Dj))).max())
    total_pen = float(per_pen.sum())
    car = float(per_car.sum())
    return SolveResult(
        name=spec.name, solver=solver, D=np.asarray(D),
        objective=float(objective) if objective is not None
        else float(spec.objective(Dj)),
        carbon_reduction=car,
        carbon_reduction_pct=100.0 * car / p.total_carbon_baseline,
        total_penalty=total_pen,
        total_penalty_pct=100.0 * total_pen / float(p.entitlements.sum()),
        per_penalty=per_pen, per_carbon=per_car,
        peak=float(p.peak(Dj)), violations=viol, nit=nit)


def _spec_bounds(spec: PolicySpec) -> tuple[np.ndarray, np.ndarray]:
    p = spec.problem
    lower, upper = p.bounds()
    if spec.lower is not None:
        lower = spec.lower
    if spec.upper is not None:
        upper = spec.upper
    free = np.ones(p.W, bool) if spec.free is None else spec.free
    lower = np.where(free[:, None], lower, 0.0)
    upper = np.where(free[:, None], upper, 0.0)
    return lower, upper


# ---------------------------------------------------------------------------
# scipy SLSQP (paper-faithful)
# ---------------------------------------------------------------------------
def solve_slsqp(spec: PolicySpec, x0: np.ndarray | None = None,
                maxiter: int = 300, ftol: float = 1e-8) -> SolveResult:
    import scipy.optimize as sopt

    p = spec.problem
    W, T = p.W, p.T
    lower, upper = _spec_bounds(spec)

    def make_con(fn: Callable[[Array], Array], kind: str) -> dict:
        """jit'd (fun, jac) pair in its own scope — no closure rebinding."""
        f = jax.jit(lambda x: jnp.atleast_1d(fn(x.reshape(W, T))))
        j = jax.jit(jax.jacrev(lambda x: jnp.atleast_1d(fn(x.reshape(W, T)))))
        return {"type": kind,
                "fun": lambda x: np.asarray(f(jnp.asarray(x))),
                "jac": lambda x: np.asarray(j(jnp.asarray(x)))}

    with jax.enable_x64(True):
        obj_grad = jax.jit(jax.value_and_grad(
            lambda x: spec.objective(x.reshape(W, T))))

        cons = []
        if spec.use_preservation and p.preservation != "none":
            kind = "eq" if p.preservation == "equality" else "ineq"
            cons.append(make_con(p.preservation_residual, kind))
        for g in spec.ineq_constraints:
            cons.append(make_con(g, "ineq"))
        for h in spec.eq_constraints:
            cons.append(make_con(h, "eq"))

        def fun(x: np.ndarray) -> tuple[float, np.ndarray]:
            v, g = obj_grad(jnp.asarray(x))
            return float(v), np.asarray(g, dtype=np.float64)

        x_init = (np.zeros(W * T) if x0 is None
                  else np.asarray(x0, np.float64).ravel())
        bounds = list(zip(lower.ravel(), upper.ravel()))
        res = sopt.minimize(fun, x_init, jac=True, method="SLSQP",
                            bounds=bounds, constraints=cons,
                            options={"maxiter": maxiter, "ftol": ftol})
    D = res.x.reshape(W, T)
    return evaluate(spec, D, solver="slsqp", nit=int(res.nit))


# ---------------------------------------------------------------------------
# JAX augmented-Lagrangian projected Adam (fleet-scale, beyond paper)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AdamALConfig:
    inner_steps: int = 400
    outer_steps: int = 8
    lr: float = 0.05
    mu0: float = 10.0          # initial quadratic weight
    mu_growth: float = 2.0
    seed: int = 0


def solve_adam(spec: PolicySpec, cfg: AdamALConfig = AdamALConfig(),
               x0: np.ndarray | None = None) -> SolveResult:
    p = spec.problem
    W, T = p.W, p.T
    lower, upper = _spec_bounds(spec)
    lo = jnp.asarray(lower, jnp.float32)
    hi = jnp.asarray(upper, jnp.float32)
    # Scale step sizes to the problem's magnitude.
    scale = float(np.maximum(upper - lower, 1e-6).mean())

    eqs = list(spec.eq_constraints)
    preservation_eq = (spec.use_preservation
                       and p.preservation == "equality")
    preservation_ineq = (spec.use_preservation
                         and p.preservation == "inequality")
    if preservation_ineq:
        ineqs = list(spec.ineq_constraints) + [
            lambda D: p.preservation_residual(D)]
    else:
        ineqs = list(spec.ineq_constraints)

    def project(D: Array) -> Array:
        D = jnp.clip(D, lo, hi)
        if preservation_eq:
            # Alternate the two projections; both are cheap and the pair
            # converges fast (verified residuals reported in the result).
            for _ in range(3):
                D = p.project_preservation(D)
                D = jnp.clip(D, lo, hi)
        return D

    eq_residual = None
    if eqs:
        def eq_residual(D: Array, _) -> Array:
            return jnp.concatenate([jnp.atleast_1d(h(D)).ravel()
                                    for h in eqs])

    ineq_residual = None
    if ineqs:
        def ineq_residual(D: Array, _) -> Array:
            return jnp.concatenate([jnp.atleast_1d(g(D)).ravel()
                                    for g in ineqs])

    def objective(D: Array, _) -> Array:
        return spec.objective(D)

    run = jax.jit(lambda D0: al_minimize(
        objective, project, D0,
        eq_residual=eq_residual, ineq_residual=ineq_residual,
        step_scale=scale,
        cfg=EngineConfig(inner_steps=cfg.inner_steps,
                         outer_steps=cfg.outer_steps, lr=cfg.lr,
                         mu0=cfg.mu0, mu_growth=cfg.mu_growth))[0])

    D0 = (jnp.zeros((W, T), jnp.float32) if x0 is None
          else jnp.asarray(x0, jnp.float32))
    D = np.asarray(run(D0), np.float64)
    return evaluate(spec, D, solver="adam-al",
                    nit=cfg.inner_steps * cfg.outer_steps)


# ---------------------------------------------------------------------------
# CR3 driver — decentralized solves + fiscal-balance clearing (Eqs. 5–8)
# ---------------------------------------------------------------------------
def solve_cr3(p: DRProblem, rho: float, tax_frac: float = 0.2,
              solver: str = "slsqp", clearing_iters: int = 8,
              ) -> tuple[SolveResult, float]:
    """Each workload solves its own problem at carbon price ρ; the
    coordinator lowers ρ until taxes cover rebates (Eq. 6). Returns the
    fleet result assembled from the decentralized solutions and the
    market-clearing ρ."""
    from repro.core.policies import cr3_fiscal_balance, cr3_workload_spec

    def solve_all(rho_: float) -> np.ndarray:
        D = np.zeros((p.W, p.T))
        for i in range(p.W):
            s = cr3_workload_spec(p, i, rho_, tax_frac)
            r = solve_slsqp(s) if solver == "slsqp" else solve_adam(s)
            D[i] = r.D[i]
        return D

    rho_cur = rho
    D = solve_all(rho_cur)
    for _ in range(clearing_iters):
        paid, collected = cr3_fiscal_balance(p, D, rho_cur, tax_frac)
        if paid <= collected + 1e-9:
            break
        rho_cur *= max(0.5, 0.9 * collected / max(paid, 1e-9))
        D = solve_all(rho_cur)

    # Report as a fleet outcome.
    spec = PolicySpec(name=f"CR3(ρ={rho:g})", problem=p,
                      objective=lambda D_: p.total_penalty(D_))
    return evaluate(spec, D, solver=solver, nit=clearing_iters), rho_cur
