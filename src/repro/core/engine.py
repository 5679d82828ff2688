"""Shared fleet-scale DR optimization engine.

Every gradient-based solver in this repo — CR1/CR2/CR3 at fleet scale
(`fleet_solver.py`) and the generic `PolicySpec` backend
(`solver.solve_adam`) — is the same algorithm: projected Adam on an
augmented Lagrangian. This module is the single implementation:

  * `al_minimize` — the pure, traceable core. Caller supplies
    (objective, projection, eq/ineq constraint residuals); the engine runs
    `outer_steps` rounds of multiplier updates around `inner_steps` of
    bias-corrected Adam, projecting after every step. Box bounds and batch
    day-preservation are handled by the caller's projection (both are cheap
    closed forms); equality residuals h(x)=0 and inequality residuals
    g(x)>=0 get classic AL multiplier + quadratic terms with a growing
    penalty weight mu.

  * `al_minimize_batched` — `vmap` over a stacked hyperparameter axis, so a
    whole Pareto sweep (Fig. 8's lambda or cap grid) compiles once and runs
    as one XLA call. Pass `return_aux=True` to also get the stacked aux
    (including the per-lane `EngineState`) and `init=` a stacked state to
    warm-start every lane of the next sweep.

  * `al_minimize_sharded` — `shard_map` the same loop over the leading
    workload axis of a device mesh, for fleets too large for one device.
    The primal `x`, per-workload multipliers, and the Adam moments all live
    sharded; each device runs the identical AL loop on its row block.

Sharding contract (`al_minimize_sharded`): the caller's problem must be
row-separable — objective a sum of per-row terms, every residual attached
to a row — which holds for CR1/CR3 exactly and CR2 after its global
normalizers are precomputed. Each device then differentiates its *local*
partial objective; because the gradient of a cross-device sum w.r.t. a
local row equals the local gradient, no collective appears in the hot
loop at all. The genuinely global reductions — objective normalizers,
shared step scales, CR3's Eq.-6 fiscal-clearing sums (taxes vs rebates) —
are computed once *outside* the sharded region (or on the gathered
solution) and enter as replicated scalars; for multi-region fleets the
per-region variants of those reductions (segment-summed norms, padding
fills, and the row-sharded specs that carry them into sharded bodies)
live in `repro.core.regional`. The one solve that steps outside this
contract is coupled cross-region migration
(`api.SolveContext(coupled_migration=True)`): its joint (D, y) objective
couples every region's rows through the interconnect flows, so it is
not row-separable and always runs unsharded. Do NOT `psum` inside the
differentiated objective: under `shard_map`, `jax.grad` of a psum'd
scalar multiplies cotangents by the device count (psum's transpose is a
psum), silently scaling every gradient by `n_devices`.

`al_minimize` is deliberately *not* jitted here: adapters wrap it in their
own `jax.jit` entry points (with policy knobs as traced `hyper` arguments),
so repeated solves of the same-shaped problem reuse one trace.

Warm starts (rolling-horizon streaming): `al_minimize` accepts an optional
`init: EngineState` — the `(x, lam_eq, lam_in, mu)` carry of a previous
solve — and always returns the final `EngineState` in `aux["state"]`.
`EngineState` is a registered pytree whose leaves are all arrays, so a
warm re-solve is *the same trace* as a cold solve: cold is just
`EngineState.cold(...)` (zeros) flowing through the identical jitted entry
point. A rolling-horizon controller shifts `state.x` along the time axis,
keeps the multipliers (they price per-workload constraints, not hours),
and re-solves with far fewer inner steps than a cold solve needs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

Array = jax.Array
# objective(x, hyper) -> scalar; residual(x, hyper) -> (n,) vector.
Objective = Callable[[Array, Any], Array]
Residual = Callable[[Array, Any], Array]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs for the projected-Adam / augmented-Lagrangian loop."""

    inner_steps: int = 400     # Adam steps per multiplier round
    outer_steps: int = 1       # multiplier rounds (1 = plain projected Adam)
    lr: float = 0.05           # step size, scaled by the caller's step_scale
    mu0: float = 10.0          # initial quadratic constraint weight
    mu_growth: float = 2.0     # mu multiplier per outer round
    mu_max: float = 1e6        # cap — keeps chained warm re-solves finite
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    # Storage dtype for the Adam moments (m, v). "bfloat16" halves the
    # optimizer-state footprint; x stays a float32 master copy and every
    # arithmetic step runs in f32 (moments are up-cast on load, down-cast
    # on store). "float32" is bit-identical to the historical behaviour.
    moment_dtype: str = "float32"
    # Emit checkify non-finite guards on the gradient, iterate, and
    # multipliers (`repro.analysis.sanitize`). ONLY legal when the
    # jitted caller wraps the whole solve in `checkify.checkify` — the
    # `SolveContext(sanitize=True)` lanes in `core.api` own that
    # pairing. False compiles zero check code.
    sanitize: bool = False
    # Convergence telemetry: sample (objective, grad norm, constraint
    # violation, step size, mu) every `telemetry_every` inner steps and
    # return the fixed-size trace as `aux["telemetry"]` — captured as
    # stacked scan outputs inside the SAME dispatch (no host callbacks).
    # 0 (default) compiles zero telemetry code: the inner scan body is
    # the historical one, byte for byte. Incompatible with
    # `fused_inner` (the Pallas kernel's k-step loop is opaque).
    telemetry_every: int = 0


@dataclasses.dataclass(frozen=True)
class EngineState:
    """Reusable solver carry: primal point + AL multipliers + penalty weight.

    A pure-array pytree, so adapters jit over it directly and a warm
    re-solve shares the cold solve's trace. Obtain one from
    `aux["state"]` of a previous `al_minimize`, or build a cold start
    with `EngineState.cold`.
    """

    x: Array           # primal iterate (the previous solution)
    lam_eq: Array      # (n_eq,) equality multipliers
    lam_in: Array      # (n_in,) inequality multipliers (>= 0)
    mu: Array          # scalar quadratic penalty weight

    @classmethod
    def cold(cls, x0: Array, n_eq: int = 0, n_in: int = 0,
             mu0: float = EngineConfig.mu0) -> "EngineState":
        """Zero-multiplier start — the classic cold solve."""
        x0 = jnp.asarray(x0)
        return cls(x=x0, lam_eq=jnp.zeros((n_eq,), x0.dtype),
                   lam_in=jnp.zeros((n_in,), x0.dtype),
                   mu=jnp.asarray(mu0, x0.dtype))

    def shifted(self, hours: int = 1, fill: float = 0.0) -> "EngineState":
        """Roll the primal along its trailing (time) axis by `hours` —
        the rolling-horizon warm start. Vacated trailing hours get
        `fill`; multipliers and mu are carried unchanged (they attach to
        workloads/constraints, not to wall-clock hours)."""
        x = jnp.roll(self.x, -hours, axis=-1)
        if hours > 0:
            x = x.at[..., -hours:].set(fill)
        return dataclasses.replace(self, x=x)


jax.tree_util.register_dataclass(
    EngineState, data_fields=["x", "lam_eq", "lam_in", "mu"],
    meta_fields=[])


def _residual_dim(fn: Residual | None, x0: Array, hyper: Any) -> int:
    """Static length of a residual vector (abstract eval — no FLOPs)."""
    if fn is None:
        return 0
    out = jax.eval_shape(
        lambda x, h: jnp.atleast_1d(fn(x, h)).ravel(), x0, hyper)
    return int(out.shape[0])


def al_minimize(objective: Objective, project: Callable[[Array], Array],
                x0: Array, *, hyper: Any = None,
                eq_residual: Residual | None = None,
                ineq_residual: Residual | None = None,
                step_scale: Array | float = 1.0,
                grad_transform: Callable[[Array], Array] | None = None,
                cfg: EngineConfig = EngineConfig(),
                init: EngineState | None = None,
                fused_inner: Callable[[Array, Array, Array, Array], Array]
                | None = None,
                ) -> tuple[Array, dict[str, Array]]:
    """Minimize objective(x, hyper) s.t. eq(x)=0, ineq(x)>=0, x = project(x).

    Pure and traceable: safe to call under `jit`/`vmap`/`grad`-of-solution.
    `hyper` is an arbitrary pytree threaded to the callbacks (traced, so
    sweeping it does not retrace). Returns (x, aux) with the final
    multipliers in aux, plus `aux["state"]`: an `EngineState` to warm-start
    a subsequent solve of the same-shaped problem.

    `init` (optional) warm-starts the whole carry — primal iterate AND
    multipliers AND mu — from a previous solve's `aux["state"]`. When given,
    `x0` is ignored and `init.x` is projected instead; `init.lam_eq`/
    `init.lam_in` must have the residual dimensions of *this* problem.
    Because `EngineState` leaves are plain arrays, warm and cold solves
    share one trace under the caller's `jit`.

    `grad_transform` (optional) preconditions the raw gradient before the
    Adam update — e.g. projection onto the tangent space of an equality
    manifold the post-step projection enforces. Without it, Adam's
    per-coordinate sign normalization can emit near-uniform steps that the
    projection then annihilates (uniform push − day-mean ≈ 0), stalling
    progress along the manifold.

    `fused_inner` (optional) replaces the generic inner Adam scan with a
    caller-supplied fused implementation — e.g. the Pallas `al_step`
    kernel (`repro.kernels.al_step`) that keeps x and the Adam moments
    VMEM-resident. Signature: ``fused_inner(x, lam_eq, lam_in, mu) -> x``;
    it must run exactly `cfg.inner_steps` projected-Adam steps from fresh
    (zero) moments. The multiplier updates between rounds stay generic.

    Telemetry (`cfg.telemetry_every = k > 0`): the inner scan emits per-
    step scalars (AL objective, squared gradient norm, max constraint
    violation at the post-step iterate, mean |Δx|) as stacked scan
    outputs; after the outer scan they are downsampled to every k-th
    step and returned as `aux["telemetry"]` — a dict of `(n_samples,)`
    arrays (`step`, `objective`, `grad_sq`, `violation`, `dx`, `mu`)
    plus the scalar `step_scale` mean. Everything stays inside the one
    jitted dispatch; the gradient comes from `jax.value_and_grad` of the
    same Lagrangian, so the iterate trajectory is bitwise the
    telemetry-off one. `grad_sq` (not the norm) is emitted so the
    sharded lane can `psum` partial sums before the host takes the
    square root.
    """
    n_eq = _residual_dim(eq_residual, x0, hyper)
    n_in = _residual_dim(ineq_residual, x0, hyper)
    tel_every = cfg.telemetry_every
    if tel_every and fused_inner is not None:
        raise ValueError(
            "EngineConfig.telemetry_every is incompatible with "
            "fused_inner: the fused Pallas kernel runs all inner steps "
            "in one opaque call, so per-step telemetry cannot be "
            "captured — drop the kernel or the telemetry for this solve")

    def eq_vec(x: Array) -> Array:
        return jnp.atleast_1d(eq_residual(x, hyper)).ravel()

    def ineq_vec(x: Array) -> Array:
        return jnp.atleast_1d(ineq_residual(x, hyper)).ravel()

    def lagrangian(x: Array, lam_eq: Array, lam_in: Array, mu: Array) -> Array:
        val = objective(x, hyper)
        if n_eq:
            h = eq_vec(x)
            val = val + lam_eq @ h + 0.5 * mu * (h @ h)
        if n_in:
            # AL for g(x) >= 0:  (mu/2)·[max(0, lam/mu − g)² − (lam/mu)²]
            g = ineq_vec(x)
            s = jnp.maximum(lam_in / mu - g, 0.0)
            val = val + 0.5 * mu * (s @ s - (lam_in / mu) @ (lam_in / mu))
        return val

    grad_fn = jax.grad(lagrangian)
    value_and_grad_fn = jax.value_and_grad(lagrangian)

    def max_violation(x: Array) -> Array:
        """Worst constraint residual: max(|h|, relu(−g)); 0 when none."""
        v = jnp.asarray(0.0, x.dtype)
        if n_eq:
            v = jnp.maximum(v, jnp.max(jnp.abs(eq_vec(x))))
        if n_in:
            v = jnp.maximum(v, jnp.max(jnp.maximum(-ineq_vec(x), 0.0)))
        return v

    mdt = jnp.dtype(cfg.moment_dtype)

    def outer_body(carry, _):
        x, lam_eq, lam_in, mu = carry

        def inner(c, _):
            x, m, v, t = c
            g = grad_fn(x, lam_eq, lam_in, mu)
            if grad_transform is not None:
                g = grad_transform(g)
            t = t + 1
            m = cfg.beta1 * m.astype(x.dtype) + (1.0 - cfg.beta1) * g
            v = cfg.beta2 * v.astype(x.dtype) + (1.0 - cfg.beta2) * g * g
            mhat = m / (1.0 - cfg.beta1 ** t)
            vhat = v / (1.0 - cfg.beta2 ** t)
            x = project(x - cfg.lr * step_scale * mhat
                        / (jnp.sqrt(vhat) + cfg.eps))
            if cfg.sanitize:
                from repro.analysis.sanitize import check_all_finite
                check_all_finite("al-inner", grad=g, x=x)
            return (x, m.astype(mdt), v.astype(mdt), t), None

        def inner_tel(c, _):
            # Telemetry twin of `inner`: identical update (the gradient
            # is value_and_grad's grad output — jax.grad IS that grad,
            # so the iterate trajectory is bitwise unchanged) plus
            # stacked per-step scalars as scan ys.
            x0_, m, v, t = c
            L, g = value_and_grad_fn(x0_, lam_eq, lam_in, mu)
            if grad_transform is not None:
                g = grad_transform(g)
            t = t + 1
            m = cfg.beta1 * m.astype(x0_.dtype) + (1.0 - cfg.beta1) * g
            v = cfg.beta2 * v.astype(x0_.dtype) + (1.0 - cfg.beta2) * g * g
            mhat = m / (1.0 - cfg.beta1 ** t)
            vhat = v / (1.0 - cfg.beta2 ** t)
            x = project(x0_ - cfg.lr * step_scale * mhat
                        / (jnp.sqrt(vhat) + cfg.eps))
            if cfg.sanitize:
                from repro.analysis.sanitize import check_all_finite
                check_all_finite("al-inner", grad=g, x=x)
            tel = (L, jnp.sum(g * g), max_violation(x),
                   jnp.mean(jnp.abs(x - x0_)))
            return (x, m.astype(mdt), v.astype(mdt), t), tel

        tel = None
        if fused_inner is not None:
            x = fused_inner(x, lam_eq, lam_in, mu)
            if cfg.sanitize:
                from repro.analysis.sanitize import check_all_finite
                check_all_finite("al-fused-inner", x=x)
        else:
            (x, _, _, _), tel = jax.lax.scan(
                inner_tel if tel_every else inner,
                (x, jnp.zeros(x.shape, mdt), jnp.zeros(x.shape, mdt),
                 0), None, length=cfg.inner_steps)
        if tel_every:
            tel = (*tel, jnp.broadcast_to(mu, (cfg.inner_steps,)))
        if n_eq:
            lam_eq = lam_eq + mu * eq_vec(x)
        if n_in:
            lam_in = jnp.maximum(lam_in - mu * ineq_vec(x), 0.0)
        if cfg.sanitize and (n_eq or n_in):
            from repro.analysis.sanitize import check_all_finite
            check_all_finite("al-multipliers", lam_eq=lam_eq, lam_in=lam_in)
        return (x, lam_eq, lam_in,
                jnp.minimum(mu * cfg.mu_growth, cfg.mu_max)), \
            (tel if tel_every else None)

    if init is None:
        init = EngineState.cold(x0, n_eq, n_in, cfg.mu0)
    carry0 = (project(init.x), init.lam_eq.astype(init.x.dtype),
              init.lam_in.astype(init.x.dtype),
              jnp.asarray(init.mu, init.x.dtype))
    if cfg.sanitize:
        from repro.analysis.sanitize import check_all_finite
        check_all_finite("al-init", x0=carry0[0], lam_eq=carry0[1],
                         lam_in=carry0[2], mu=carry0[3])
    (x, lam_eq, lam_in, mu), tel_ys = jax.lax.scan(
        outer_body, carry0, None, length=cfg.outer_steps)
    aux = {"lam_eq": lam_eq, "lam_in": lam_in, "mu": mu,
           "state": EngineState(x=x, lam_eq=lam_eq, lam_in=lam_in, mu=mu)}
    if tel_every:
        # Flatten (outer, inner) → (outer*inner,) then keep every
        # tel_every-th sample — a fixed-size trace decided at trace time.
        L, g2, viol, dx, mus = (y.reshape(-1) for y in tel_ys)
        sl = slice(tel_every - 1, None, tel_every)
        total = cfg.outer_steps * cfg.inner_steps
        aux["telemetry"] = {
            "step": jnp.arange(1, total + 1, dtype=jnp.int32)[sl],
            "objective": L[sl], "grad_sq": g2[sl],
            "violation": viol[sl], "dx": dx[sl], "mu": mus[sl],
            "step_scale": jnp.asarray(step_scale, x.dtype).mean(),
        }
    return x, aux


def al_minimize_batched(objective: Objective,
                        project: Callable[[Array], Array], x0: Array,
                        hypers: Any, *, init: EngineState | None = None,
                        return_aux: bool = False, **kwargs):
    """vmap `al_minimize` over a stacked hyperparameter axis.

    `hypers` is a pytree whose leaves carry a leading sweep axis; the whole
    sweep shares one trace/compile (the Fig.-8 Pareto pattern). Returns the
    stacked solutions (n_sweep, *x0.shape); with `return_aux=True`, returns
    `(solutions, aux)` where every `aux` leaf — multipliers, mu, and
    `aux["state"]` (an `EngineState` pytree) — carries the same leading
    sweep axis, so a sweep can warm-start the next tick's sweep lane-by-lane
    by passing that stacked state back as `init`.

    `init` (optional) is a stacked `EngineState` (leading sweep axis on
    every leaf, including `mu`), e.g. `aux["state"]` from a previous
    batched solve.
    """
    if init is None:
        def one(h):
            return al_minimize(objective, project, x0, hyper=h, **kwargs)
        xs, aux = jax.vmap(one)(hypers)
    else:
        def one_warm(h, st):
            return al_minimize(objective, project, x0, hyper=h, init=st,
                               **kwargs)
        xs, aux = jax.vmap(one_warm)(hypers, init)
    return (xs, aux) if return_aux else xs


# How each telemetry leaf combines across shards of the workload axis.
# Objective and squared grad norm are partial sums (row-separable
# problems), worst violation is a max, mean |Δx| and step_scale average
# (exact for equal block sizes — pad_fleet guarantees them). `step` and
# `mu` are device-identical and pass through.
_TEL_REDUCE = {"objective": jax.lax.psum, "grad_sq": jax.lax.psum,
               "violation": jax.lax.pmax, "dx": jax.lax.pmean,
               "step_scale": jax.lax.pmean}


def _telemetry_allreduce(tel: dict, axis_name) -> dict:
    """Merge per-shard telemetry into global traces (inside shard_map)."""
    return {k: (_TEL_REDUCE[k](v, axis_name) if k in _TEL_REDUCE else v)
            for k, v in tel.items()}


def al_minimize_sharded(build_pieces: Callable[[Any], dict], data: Any, *,
                        mesh, data_specs: Any, init: EngineState,
                        cfg: EngineConfig = EngineConfig(),
                        axis_name: str | tuple[str, ...] | None = None,
                        ) -> tuple[Array, dict[str, Array]]:
    """Device-parallel `al_minimize`: shard the leading workload axis.

    Runs the identical AL loop on every device's row block of a fleet-scale
    problem, with `x`, per-workload multipliers, and Adam moments all
    sharded over `axis_name` (default: the mesh's only axis). A *tuple*
    of axis names shards the leading axis over several mesh axes at once
    — the 2-D (REGION_AXIS, FLEET_AXIS) fleet mesh from
    `launch.mesh.make_fleet_mesh(regions=...)`, where a region-sorted W
    axis folds over both. The row-separability contract is unchanged:
    nothing here psums, so the device grid's shape is irrelevant to the
    math.

    Args:
      build_pieces: called *inside* `shard_map` with the per-device block of
        `data`; returns a dict of `al_minimize` keyword pieces —
        ``{"objective", "project"}`` required, plus any of ``{"hyper",
        "eq_residual", "ineq_residual", "step_scale", "grad_transform"}``.
        The pieces see only local rows, so the objective each device
        differentiates is its partial sum — exactly the global gradient for
        row-separable problems (see the module docstring for why a psum
        here would be wrong). Global scalars (normalizers, shared step
        scales) must be precomputed by the caller and ride through `data`
        as replicated leaves.
      data: pytree of problem arrays — per-workload leaves lead with W
        (divisible by the axis size; see `fleet_solver.pad_fleet`),
        shared signals (MCI trace, scalars) replicated.
      data_specs: pytree of `PartitionSpec`s matching `data` —
        `P(axis_name)` for per-workload leaves, `P()` for replicated ones.
      init: `EngineState` with global (full-W) arrays; `x`/`lam_eq`/`lam_in`
        are sharded on their leading axis, `mu` replicated.

    Returns (x, aux) exactly like `al_minimize`, with global arrays
    (sharded jax.Arrays over `mesh`).
    """
    from jax.sharding import PartitionSpec as P

    if axis_name is None:
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"axis_name required for multi-axis mesh {mesh.axis_names}")
        axis_name = mesh.axis_names[0]
    state_specs = EngineState(x=P(axis_name), lam_eq=P(axis_name),
                              lam_in=P(axis_name), mu=P())
    aux_specs = {"lam_eq": P(axis_name), "lam_in": P(axis_name), "mu": P(),
                 "state": state_specs}
    if cfg.telemetry_every:
        # All-reduced inside `body` to device-identical traces → P().
        aux_specs["telemetry"] = {
            k: P() for k in ("step", "objective", "grad_sq", "violation",
                             "dx", "mu", "step_scale")}

    def body(data_blk, state_blk):
        pieces = dict(build_pieces(data_blk))
        objective = pieces.pop("objective")
        project = pieces.pop("project")
        x, aux = al_minimize(objective, project, state_blk.x,
                             init=state_blk, cfg=cfg, **pieces)
        if cfg.telemetry_every:
            # Post-hoc collectives on aux outputs only — never inside the
            # differentiated objective (see module docstring): each
            # device's trace reflects its partial Lagrangian, so sum /
            # max / mean them into the global curves here.
            aux["telemetry"] = _telemetry_allreduce(aux["telemetry"],
                                                    axis_name)
        return x, aux

    # check_vma=False: the body may invoke a pallas_call (the fused
    # al_step kernel), which has no shard_map replication rule; all
    # outputs here are explicitly spec'd, so the check adds nothing.
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(data_specs, state_specs),
                         out_specs=(P(axis_name), aux_specs),
                         check_vma=False)(data, init)
