"""First-class DR policies and the fleet engine's single entry point.

The paper frames Carbon Responder as ONE framework with three alternative
policies — Efficient (CR1), Fair-Centralized (CR2), Fair-Decentralized
(CR3). This module makes that framing literal:

  * Policies are frozen dataclasses — `CR1(lam=...)`,
    `CR2(cap_frac=..., outer=...)`, `CR3(rho=..., tax_frac=...,
    clearing_iters=...)`, plus the closed-form baseline wrappers
    `B1(F=...)` / `B3(depth=...)` — values you can put in a list, sweep,
    compare for equality, and serialize with `dataclasses.asdict` into
    stable cache keys. Only *hyperparameters* are dataclass fields;
    execution concerns never leak into a policy's identity.

  * Each policy owns its engine backend: the objective/constraint pieces,
    the fleet-global normalizers, and the `EngineConfig` it feeds the
    shared projected-Adam + augmented-Lagrangian loop
    (`repro.core.engine.al_minimize`). CR1 and CR2 differ only in their
    pieces (`_ENGINES`): every lane of theirs — the tick, solo or on a
    mesh, the sweep, the day scan and the ensemble — wraps one tick
    body, `_tick`. CR3 additionally owns its Eq.-6 fiscal clearing (the
    coordinator lowering each region's carbon price ρ until taxes cover
    rebates), an on-device loop around its best responses, one dispatch
    a solve.

  * `solve(problem, policy, ctx=SolveContext(...))` is the single entry
    point. `SolveContext` bundles everything orthogonal to policy
    semantics: device `mesh` (W-axis sharding), `donate`d buffers, the
    fused streaming tick (`shift`/`reset_mu`, and the hour `window` that
    a device-resident `fleet` slides to), `warm` starts, kernel dispatch,
    and the inner-`steps` budget (None = the policy's default).
    Every policy returns the same `FleetSolveResult`; policy-specific
    outputs (CR3's clearing ρ, fiscal balance) ride `result.extras`.

  * `sweep(problem, policies, ctx=...)` runs a whole policy grid. A
    same-family grid rides ONE XLA call: the hyper axis is vmapped
    through the engine (the Fig.-8 Pareto pattern), and with `ctx.mesh`
    the vmap nests *inside* the W-axis shard_map so fleet-scale Pareto
    fronts run sharded too (the ROADMAP's sharded-sweep follow-up, for
    every single-call policy family at once). Mixed-family grids,
    non-uniform static knobs, warm/donated contexts, and CR3-with-mesh
    fall back to an equivalent loop of `solve()` calls.

  * `POLICY_REGISTRY` maps policy names ("cr1", "cr2", "cr3", "b1",
    "b3") to their classes, so string-typed configs (CLI flags, the
    streaming controller) resolve to policy objects in one place, and
    `solve(p, "cr1")` works for quick default-hyper runs.

  * `ensemble(problem, policy, scenarios, ctx=...)` evaluates one
    policy across S Monte Carlo grid/fleet scenarios
    (`repro.core.scenario`) the same way `sweep` runs a policy grid:
    CR1/CR2 ride ONE vmapped XLA call over the scenario axis (nesting
    inside the W-axis shard_map under `ctx.mesh`), and the result's
    `.report()` distills quantile/CVaR/fairness risk
    (`repro.core.ensemble`).

Sharding contract, padding semantics, and the donated streaming tick are
documented on `repro.core.fleet_solver` (data model) and
`repro.core.engine` (loop); the policy backends here only assemble those
pieces. The legacy `fleet_solver.solve_cr{1,2,3}_fleet` entry points are
deprecated shims over this module.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import (Any, Callable, ClassVar, NamedTuple, Protocol,
                    Sequence, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis.sanitize import checked_jit
from repro.core import dfloat as _df
from repro.core.engine import (EngineConfig, EngineState, al_minimize,
                               al_minimize_sharded)
from repro.core.fleet_solver import (CR1_MU0, CR2_MU0, CR3_MU0,
                                     FleetProblem, FleetSolveResult,
                                     OnDevice, _bounds, _enter_tick,
                                     _fleet_specs, _jit_view, _pad_state,
                                     _projection, _report,
                                     _single_region_view,
                                     cr2_cap, cr2_reference_fleet,
                                     fleet_penalties,
                                     pad_fleet, report_layout, report_sums,
                                     resolve_use_kernel)
from repro.core.regional import (CR1_NORM_FILLS, CR2_NORM_FILLS,
                                 cr1_norms as _cr1_norms,
                                 cr2_norms as _cr2_norms,
                                 cr3_reg_scale as _cr3_reg_scale,
                                 norm_specs as _norm_specs,
                                 pad_row_norms as _pad_row_norms,
                                 region_sums as _region_sums,
                                 region_totals as _region_totals)
from repro.launch.mesh import fleet_axes, fleet_device_count
# repro.obs is import-light and never imports repro.core (no cycle).
from repro.obs.spans import count as _count, span
from repro.obs.telemetry import ConvergenceTrace, TelemetryConfig

Array = jax.Array

__all__ = ["B1", "B3", "CR1", "CR2", "CR3", "DRPolicy", "DayResult",
           "POLICY_REGISTRY", "SolveContext", "configured_policy",
           "ensemble", "resolve_policy", "solve", "solve_day",
           "stack_states", "sweep"]


# ---------------------------------------------------------------------------
# Execution context + policy protocol
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SolveContext:
    """Execution concerns of a fleet solve, bundled once for every policy.

    Attributes:
      mesh: optional 1-D device mesh (`repro.launch.mesh.make_fleet_mesh`)
        — the solve shards the W axis over it (W padded to the device
        count with inert rows; `result.state` keeps the padded shape so
        re-solves chain without re-padding).
      donate: route through a `jax.jit(donate_argnums)` twin that reuses
        the warm state's buffers in place. The passed `warm` state becomes
        invalid afterwards.
      shift: roll the warm plan this many hours inside the solve's own
        XLA call (the rolling-horizon window slide).
      window: the hour offset of a rolling-horizon window, passed into
        the solve as traced data: the problem's usage, jobs and cap are
        the whole periodic trace, and the solve's own program slides
        them `window` hours earlier along time (`np.roll(a, -window,
        axis=1)`), so every offset shares one program; CR2 then makes
        its fairness references from the slid window in that program.
        None = the problem is the window. Solo `solve()` lanes of a
        policy with `slides` only (CR1, CR2, CR3).
      reset_mu: restart the AL μ schedule at the policy's μ0 inside the
        same call (the per-tick reset; multipliers keep their prices).
      warm: a previous result's `.state` to warm-start from (cold start
        when None).
      use_kernel: Pallas kernel dispatch — None = auto (kernels on TPU,
        jnp elsewhere). Covers both the `dr_features` penalty kernel and
        the fused `al_step` inner-loop kernel (CR1/CR2 hot path).
      steps: inner Adam steps per multiplier round; None = the policy's
        `default_steps`.
      moment_dtype: storage dtype for the engine's Adam moments
        ("float32" or "bfloat16") — threaded to `EngineConfig` on the
        CR1/CR2 solo and sharded paths and `solve_day`; x always keeps a
        float32 master copy. Sweeps/ensembles stay float32.
      coupled_migration: move cross-region migration INTO the solve.
        After the base (per-region) solve, curtailment and interconnect
        flows refine *jointly* under the same AL engine — per-link
        bandwidth caps in the projection, tolls in the objective, supply
        and ceiling limits as coupled inequality residuals — then the
        flows pass `core.migration`'s exact-feasibility repair. The
        host-side post-stage stays the validation reference: the coupled
        plan is kept only at equal total curtailment and when it beats
        the post-stage on fleet-wide carbon, so enabling this never
        loses carbon. CR1/CR2 multi-region only; everything else falls
        back to the post-stage.
      sanitize: route the solve through a `checkify`-wrapped twin of the
        same jitted impl: the AL loop emits non-finite guards on the
        gradient, iterate, and multipliers (`EngineConfig.sanitize`),
        so a NaN/inf raises `repro.analysis.SanitizeError` naming the
        first failing check instead of silently corrupting the plan
        and every warm re-solve chained after it. Debug lane: CR1/CR2
        solo and `solve_day` day-scan lanes (mesh/donate/
        coupled_migration raise `NotImplementedError`), <2x wall-clock
        of the unchecked lane.
      telemetry: in-solve convergence telemetry
        (`repro.obs.TelemetryConfig`). CR1/CR2 engine lanes sample
        (objective, grad norm, max violation, |Δx|, μ) every
        `telemetry.every` inner steps INSIDE the jitted AL loop — the
        trace rides the same dispatch as stacked aux outputs (no host
        callbacks) and lands in `result.extras["telemetry"]` as a
        `repro.obs.ConvergenceTrace` (`solve_day`: one trace per tick).
        None (default) compiles zero telemetry code: the off path is
        bitwise the pre-telemetry engine, and the on path's plan is
        bitwise the off path's. Incompatible with `use_kernel` (the
        fused Pallas inner loop is opaque — raises); under a sweep it
        forces the per-policy loop lane.
      fleet: the problem's fleet kept on the device (`Resident`), sent in
        place of its host arrays with the problem's `mci` installed: a
        solve that takes it uploads none of the fleet. Same lanes as
        `window`.
    """
    mesh: Any = None
    donate: bool = False
    shift: int = 0
    window: int | None = None
    reset_mu: bool = False
    warm: EngineState | None = None
    use_kernel: bool | None = None
    steps: int | None = None
    moment_dtype: str = "float32"
    coupled_migration: bool = False
    sanitize: bool = False
    telemetry: TelemetryConfig | None = None
    fleet: "Resident | None" = None

    def resolved_steps(self, policy: "DRPolicy") -> int:
        return self.steps if self.steps is not None else policy.default_steps


class Resident:
    """A fleet kept on the device between solves (`SolveContext.fleet`):
    its jit view less the mci, in the dtypes a lane computes in (float32
    where x64 is off). Nothing goes up when it is made: the first solve
    that sends it puts it on the device inside that solve's
    `dr.solve.dispatch` span, which counts its bytes (`h2d_bytes`) and one
    `fleet_uploads`; later solves send none of it. CR2's equal-cap
    curtailment of the fleet (`curtailment`) goes up the same way, with
    the first solve that asks for it at a `cap_frac`."""

    def __init__(self, p: FleetProblem):
        self._fleet = dataclasses.replace(_jit_view(p), mci=None)
        self._host = p
        self._cap = (None, None)
        self._placed = False

    def send(self, mci, sp) -> FleetProblem:
        """The device fleet with `mci` installed, placed under the open
        span `sp` if this is its first send."""
        if not self._placed:
            sp.count("h2d_bytes", _host_bytes(self._fleet))
            sp.count("fleet_uploads", 1)
            self._fleet = jax.device_put(self._fleet)
            self._placed = True
        return dataclasses.replace(self._fleet, mci=mci)

    def curtailment(self, cap_frac, sp):
        """`cr2_cap` of the fleet at `cap_frac` on the device, placed under
        the open span `sp` (counted in its `h2d_bytes`) when `cap_frac` is
        new: only a lane that asks for it (a sliding CR2 tick) holds it."""
        if self._cap[0] != cap_frac:
            d_cap = cr2_cap(self._host, cap_frac)
            sp.count("h2d_bytes", _host_bytes(d_cap))
            self._cap = (cap_frac, jax.device_put(d_cap))
        return self._cap[1]


class _Sent(NamedTuple):
    """A lane argument made from a resident fleet: `_dispatch` calls
    `put` with its open span, so what goes up is counted there."""
    put: Callable


def _sent(p: FleetProblem, ctx: SolveContext):
    """What a solo lane sends for the fleet `p`: `p`'s jit view, or the
    resident `ctx.fleet` with `p`'s mci installed."""
    if ctx.fleet is None:
        return _jit_view(p)
    return _Sent(functools.partial(ctx.fleet.send, p.mci))


def _offset(ctx: SolveContext):
    """`ctx.window` as the int32 a lane traces (None: nothing slides)."""
    return None if ctx.window is None else np.asarray(ctx.window, np.int32)


def _require_window_ok(policy, ctx: SolveContext) -> None:
    """`window` and `fleet` serve the solo lanes that slide a window in
    their own program (a policy with `slides`, no mesh, no coupled
    migration; `policy` None for every other lane). Fail loudly rather
    than solve hours the caller did not ask for."""
    if ctx.window is None and ctx.fleet is None:
        return
    if (not getattr(policy, "slides", False) or ctx.mesh is not None
            or ctx.coupled_migration):
        raise NotImplementedError(
            "SolveContext(window=/fleet=) runs on the solo solve() lanes "
            "of CR1, CR2 and CR3; slide the problem on the host "
            "(np.roll along time) for this lane")


@runtime_checkable
class DRPolicy(Protocol):
    """A demand-response policy: a frozen hyperparameter record that knows
    how to solve a `FleetProblem` under a `SolveContext`.

    Implementations are frozen dataclasses whose *fields are exactly the
    policy's hyperparameters* (so `dataclasses.asdict` is a stable cache
    key) with `name`/`default_steps` as ClassVars and a
    `solve(problem, ctx)` method returning a `FleetSolveResult`."""

    name: ClassVar[str]
    default_steps: ClassVar[int]

    def solve(self, problem: FleetProblem,
              ctx: SolveContext) -> FleetSolveResult: ...


#: Policy name -> policy class; the one place string-typed configs resolve.
POLICY_REGISTRY: dict[str, type] = {}


def _register(cls):
    POLICY_REGISTRY[cls.name] = cls
    return cls


def resolve_policy(policy) -> DRPolicy:
    """Coerce a registry name, policy class, or policy object to an object."""
    if isinstance(policy, str):
        try:
            return POLICY_REGISTRY[policy]()
        except KeyError:
            raise ValueError(
                f"unknown policy {policy!r}; registered policies: "
                f"{', '.join(sorted(POLICY_REGISTRY))}") from None
    if isinstance(policy, type):
        policy = policy()
    if not isinstance(policy, DRPolicy):
        raise TypeError(
            f"policy must be a DRPolicy (e.g. CR1(lam=1.45)) or a "
            f"registered name; got {type(policy).__name__}")
    return policy


def configured_policy(policy, *, lam: float = 1.45, cap_frac: float = 0.78,
                      rho: float = 0.02, tax_frac: float = 0.2,
                      outer: int = 4) -> DRPolicy:
    """`resolve_policy` with the legacy keyword knobs: registry names
    become objects configured from the matching knobs (CR1: `lam`; CR2:
    `cap_frac`/`outer`; CR3: `rho`/`tax_frac`/`outer` — `outer` defaults
    to 4, the historical streaming-controller budget); other registered
    names get default hypers; `DRPolicy` objects pass through unchanged
    (the knobs are ignored). The one place string-typed configs with
    per-policy knobs (`RollingHorizonSolver`, `FleetCoordinator`) turn
    into policy values."""
    if not isinstance(policy, str):
        return resolve_policy(policy)
    if policy not in POLICY_REGISTRY:
        raise ValueError(
            f"unknown policy {policy!r}; registered policies: "
            f"{', '.join(sorted(POLICY_REGISTRY))}")
    by_name = {
        "cr1": lambda: CR1(lam=lam),
        "cr2": lambda: CR2(cap_frac=cap_frac, outer=outer),
        "cr3": lambda: CR3(rho=rho, tax_frac=tax_frac, outer=outer),
    }
    return by_name.get(policy, POLICY_REGISTRY[policy])()


def _require_sanitizable(policy, ctx: SolveContext) -> None:
    """`sanitize=True` covers the CR1/CR2 solo engine lanes — the paths
    with checkify-wrapped jit twins. Everything else fails loudly here
    rather than silently skipping the guards the caller asked for."""
    name = getattr(policy, "name", type(policy).__name__)
    if name not in ("cr1", "cr2"):
        raise NotImplementedError(
            f"SolveContext(sanitize=True) supports CR1/CR2 (the checkify-"
            f"twinned engine lanes); policy {name!r} has no sanitized lane")
    for field, flag in (("mesh", ctx.mesh is not None),
                        ("donate", ctx.donate),
                        ("coupled_migration", ctx.coupled_migration)):
        if flag:
            raise NotImplementedError(
                f"SolveContext(sanitize=True) is a solo debug lane; "
                f"combining it with {field} is not supported — drop "
                f"{field} while sanitizing")


def _tel_every(ctx: SolveContext) -> int:
    """`EngineConfig.telemetry_every` value for this context (0 = off)."""
    return 0 if ctx.telemetry is None else int(ctx.telemetry.every)


def _require_telemetry_ok(ctx: SolveContext, use_kernel: bool) -> None:
    """Telemetry needs the generic inner scan — the fused Pallas kernel
    runs all k steps in one opaque call, so per-step samples cannot be
    captured. Fail loudly instead of silently dropping the trace."""
    if ctx.telemetry is not None and use_kernel:
        raise NotImplementedError(
            "SolveContext(telemetry=...) is incompatible with the fused "
            "al_step kernel (use_kernel=True): the kernel's inner loop "
            "is opaque to per-step telemetry — drop use_kernel (or the "
            "telemetry) for this solve")


def _host_bytes(args) -> int:
    """Bytes the device will hold for the host (non-`jax.Array`) leaves of
    `args`: each leaf's size in its canonical dtype (float64 counts as
    float32 where x64 is off)."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(args):
        if not isinstance(leaf, jax.Array):
            a = np.asarray(leaf)
            n += a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
    return n


def _dispatch(run, *args, **kw):
    """`run(*args, **kw)` — a jitted lane, dynamic arguments positional,
    static ones and a host `order` array by keyword — under a
    `dr.solve.dispatch` span: argument conversion, upload and enqueue,
    until the call returns. Counts the dispatch and the host bytes it
    uploads (`h2d_bytes`); what a resident fleet sends (`_Sent`) is put
    here, so its one upload counts under this span."""
    with span("dr.solve.dispatch") as sp:
        args = tuple(a.put(sp) if isinstance(a, _Sent) else a for a in args)
        sp.count("dispatches", 1)
        sp.count("h2d_bytes", _host_bytes(
            (args, [v for v in kw.values() if isinstance(v, np.ndarray)])))
        return run(*args, **kw)


def _fetch(*outs) -> tuple:
    """Wait for the device (`dr.solve.wait`, where the host blocks
    anyway), then pull `outs` (arrays or pytrees of them) to the host
    (`dr.solve.pull`, counting `d2h_bytes`)."""
    with span("dr.solve.wait"):
        jax.block_until_ready(outs[0])
    with span("dr.solve.pull") as sp:
        out = jax.device_get(outs)
        sp.count("d2h_bytes", sum(a.nbytes for a in
                                  jax.tree_util.tree_leaves(out)))
    return out


def _plan_sums(p: FleetProblem, D, pens, order, offsets: tuple,
               roll: int = 0):
    """`report_sums` of the plan `D` of (possibly device-padded) `p`,
    traced inside a lane's own program; `roll` slides `p.usage` to the
    window the plan was solved on (a day scan's last tick)."""
    usage = jnp.asarray(p.usage)
    if roll:
        usage = jnp.roll(usage, -roll, axis=1)
    return report_sums(D, pens, usage, p.is_batch, order, offsets,
                       p.day_hours)


def _tick_report(p: FleetProblem, D, pens, order, offsets):
    """The extra outputs of a solve lane that reports from the device:
    `()` when `offsets` is None, else hour 0 of the plan and its
    `_plan_sums` — pulled instead of the plan."""
    if offsets is None:
        return ()
    return D[:, 0], _plan_sums(p, D, pens, order, offsets)


def _device_report(p: FleetProblem, out, *, iters: int,
                   tel: int) -> FleetSolveResult:
    """The result of a CR1/CR2 solve lane's outputs `out` (plan,
    penalties, state[, telemetry], hour 0, report sums) for the true
    fleet `p`: pulls hour 0 and the sums; the plan stays on the device
    until `.D` is read."""
    hour0, sums = _fetch(*out[-2:])
    extras = {"telemetry": ConvergenceTrace.from_aux(out[3])} \
        if tel else None
    return _report(p, OnDevice(out[0], p.W), iters=iters, state=out[2],
                   extras=extras, sums=sums, hour0=hour0[:p.W])


def solve(problem: FleetProblem, policy, *,
          ctx: SolveContext | None = None) -> FleetSolveResult:
    """Solve `problem` under `policy` — the single fleet entry point.

    `policy` is a `DRPolicy` object (`CR1(lam=1.45)`, ...) or a
    `POLICY_REGISTRY` name for default hypers; `ctx` carries the
    execution concerns (mesh/donate/shift/reset_mu/warm/use_kernel/
    steps). Returns a uniform `FleetSolveResult`; policy-specific outputs
    (e.g. CR3's clearing ρ) live in `result.extras`."""
    if not isinstance(problem, FleetProblem):
        raise TypeError(
            f"solve() takes a FleetProblem (convert a DRProblem with "
            f"FleetProblem.from_problem); got {type(problem).__name__}")
    problem = _single_region_view(problem)
    ctx = ctx or SolveContext()
    policy = resolve_policy(policy)
    if ctx.sanitize:
        _require_sanitizable(policy, ctx)
    _require_window_ok(policy, ctx)
    with span("dr.solve", meta={"policy": policy.name,
                                "lane": "solo" if ctx.mesh is None
                                else "mesh"}):
        res = policy.solve(problem, ctx)
        if ctx.coupled_migration:
            return _coupled_migrate(problem, policy, res, ctx)
        return _maybe_migrate(problem, res)


def sweep(problem: FleetProblem, policies: Sequence, *,
          ctx: SolveContext | None = None) -> list[FleetSolveResult]:
    """Solve `problem` under every policy in `policies`.

    A grid from one policy family with uniform static knobs (e.g.
    `[CR1(lam=l) for l in grid]`, or CR2s sharing `outer`) rides the
    engine's vmap lane as ONE XLA call; with `ctx.mesh` the hyper vmap
    nests inside the W-axis shard_map (sharded Pareto fronts). Everything
    else — mixed families, non-uniform static knobs, donated contexts,
    CR3 with a mesh, `ctx.telemetry` (each solve gets its own
    convergence trace) — falls back to a loop of `solve()` calls with
    identical per-policy semantics, so `sweep` is always safe to call.
    Sweeps are cold solves unless warm-started:
    `ctx.donate`/`shift`/`reset_mu` force the fallback loop, where a
    shared `warm` state is reused read-only by every policy (so `donate`
    is dropped for multi-policy loops — a buffer can only be donated
    once). A *stacked* warm state (leading axis = len(policies), e.g.
    `stack_states([r.state for r in last_sweep])`) instead rides the
    CR1/CR2 vmap lane as a warm-started refinement sweep — each lane
    warm-starts from its own slice, so a Pareto front can be polished
    with a fraction of the cold step budget, on a mesh as off it.

    Results are returned in `policies` order."""
    ctx = ctx or SolveContext()
    if ctx.sanitize:
        raise NotImplementedError(
            "SolveContext(sanitize=True) is a solo-solve debug lane — the "
            "vmapped sweep lanes have no checkify twins (and a silent "
            "fallback would skip the guards you asked for); sanitize "
            "policies one at a time through solve()")
    _require_window_ok(None, ctx)
    problem = _single_region_view(problem)
    pols = [resolve_policy(pl) for pl in policies]
    if not pols:
        return []
    fam = type(pols[0])
    stacked = _stacked_warm(ctx.warm, len(pols))
    warm_ok = ctx.warm is None or (stacked and fam in (CR1, CR2))
    # ctx.telemetry forces the loop lane: the vmapped sweep impls have
    # no telemetry plumbing, and the loop gives each policy its own
    # per-solve ConvergenceTrace in result.extras anyway.
    vmappable = (all(type(pl) is fam for pl in pols)
                 and hasattr(fam, "_sweep_family")
                 and fam._sweep_uniform(pols)
                 and warm_ok and not ctx.donate
                 and not ctx.shift and not ctx.reset_mu
                 and ctx.telemetry is None)
    if not vmappable:
        if ctx.donate and len(pols) > 1:
            ctx = dataclasses.replace(ctx, donate=False)
        if stacked:
            res = [pl.solve(problem, dataclasses.replace(
                       ctx, warm=jax.tree_util.tree_map(
                           lambda a, i=i: a[i], ctx.warm)))
                   for i, pl in enumerate(pols)]
        else:
            res = [pl.solve(problem, ctx) for pl in pols]
    else:
        res = fam._sweep_family(problem, pols, ctx)
    if ctx.coupled_migration:
        return [_coupled_migrate(problem, pl, r, ctx)
                for pl, r in zip(pols, res)]
    return [_maybe_migrate(problem, r) for r in res]


def stack_states(states: Sequence[EngineState]) -> EngineState:
    """Stack per-lane `EngineState`s (e.g. `[r.state for r in sweep(...)]`)
    along a new leading axis — the warm-start shape `sweep()` expects for
    a warm refinement sweep (`ctx.warm=stack_states(...)`).

    Leaf shapes must agree across lanes; multi-region and mesh-padded
    states keep the same (W, T) leaf layout as single-region ones, but a
    mesh-padded state (W rounded up to the device grid) cannot stack
    with an unpadded one — re-solve on the same mesh, or slice back to
    the true fleet, before stacking. Mismatches raise here with the
    offending lane instead of deep inside `jnp.stack`."""
    states = list(states)
    if not states:
        raise ValueError("stack_states needs at least one EngineState")
    ref = [jnp.shape(leaf) for leaf in jax.tree_util.tree_leaves(states[0])]
    for i, st in enumerate(states[1:], 1):
        shapes = [jnp.shape(leaf) for leaf in jax.tree_util.tree_leaves(st)]
        if shapes != ref:
            raise ValueError(
                f"stack_states: state {i} has leaf shapes {shapes}, but "
                f"state 0 has {ref} — all lanes must come from solves of "
                "the same (identically padded) fleet")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def _stacked_warm(warm, n: int) -> bool:
    """True when `warm` is a lane-stacked EngineState for an n-policy
    sweep (fleet plans are always 2-D, so a 3-D x means stacked)."""
    return (isinstance(warm, EngineState) and jnp.ndim(warm.x) == 3
            and warm.x.shape[0] == n)


def _maybe_migrate(p: FleetProblem, res: FleetSolveResult):
    """Cross-region migration post-stage (see `core.migration`): on
    multi-region problems with a usable topology, move curtailed batch
    load along the migration network and credit the net carbon saved.
    The committed plan D is unchanged — total curtailment and every
    penalty stay exactly as solved."""
    if (p.topology is None or not p.is_multiregion
            or not np.any(np.asarray(p.topology.bandwidth) > 0.0)):
        return res
    from repro.core.migration import fleet_migration
    plan = fleet_migration(p, np.asarray(res.D))
    return dataclasses.replace(
        res,
        carbon_reduction_pct=res.carbon_reduction_pct
        + 100.0 * plan.net_saved / res.carbon_base,
        extras={**res.extras, "migration": plan})


def _coupled_impl(p: FleetProblem, D0, hyper, fr, to, bw, cost,
                  ceil, *, mode: str, steps: int, outer: int,
                  use_kernel: bool, has_ceiling: bool):
    """Joint (curtailment, interconnect-flow) refinement — the coupled
    in-loop migration solve. The primal is `z = concat([D (W, T),
    y (L, T)])` over the L positive-bandwidth links; one `al_minimize`
    call minimizes the policy objective on D minus the normalized
    toll-adjusted flow value, with link caps in the projection, per-
    region supply (movable batch curtailment >= outflow) and ceiling
    (headroom >= inflow) limits as coupled inequality residuals, and a
    total-curtailment pin back to the base plan `D0` as an equality
    residual (CR2 keeps its per-row fairness equalities alongside).

    The coupling terms segment-sum across rows, so this solve is NOT
    row-separable — it runs as one unsharded call (like the post-stage,
    the coupled refine operates at (R, T)/(L, T) aggregate scale on top
    of the fleet solve; the fused `al_step` kernel only accelerates the
    row-separable base solve that precedes it). Returns (D, y, pens)
    eps-feasible; the caller repairs y exactly via `migration._repair`.
    """
    f32 = jnp.float32
    W, T = p.usage.shape
    L = bw.shape[0]
    mci = jnp.asarray(p.mci, f32)
    R = mci.shape[0]
    region = jnp.asarray(p.region)
    usage = jnp.asarray(p.usage, f32)
    isb = jnp.asarray(p.is_batch)[:, None]
    D0 = jnp.asarray(D0, f32)
    margin = mci[fr] - mci[to] - cost[:, None]            # (L, T)
    flow_norm = 100.0 / (usage * mci[region]).sum()
    eng = _ENGINES[mode]
    pieces = eng.pieces(p, hyper, eng.norms(p, hyper), use_kernel, None)
    obj_D, project_D, step_D = (pieces["objective"], pieces["project"],
                                pieces["step_scale"])
    eq_D = pieces.get("eq_residual")

    movable0 = jax.ops.segment_sum(
        jnp.where(isb, jnp.maximum(usage - D0, 0.0), 0.0), region,
        num_segments=R)
    sscale = jnp.maximum(movable0.max(), 1.0)
    curt_scale = jnp.maximum(jnp.abs(D0).sum(), 1.0)
    D0_sum = D0.sum()
    bwcol = bw[:, None]

    def objective(z, hyp):
        D, y = z[:W], z[W:]
        return obj_D(D, hyp) - flow_norm * (y * margin).sum()

    def project(z):
        return jnp.concatenate(
            [project_D(z[:W]), jnp.clip(z[W:], 0.0, bwcol)])

    def eq(z, hyp):
        curt = ((z[:W].sum() - D0_sum) / curt_scale)[None]
        if eq_D is None:
            return curt
        return jnp.concatenate([eq_D(z[:W], hyp), curt])

    def ineq(z, hyp):
        D, y = z[:W], z[W:]
        movable = jax.ops.segment_sum(
            jnp.where(isb, jnp.maximum(usage - D, 0.0), 0.0), region,
            num_segments=R)
        outflow = jax.ops.segment_sum(y, fr, num_segments=R)
        res = ((movable - outflow) / sscale).ravel()
        if has_ceiling:
            load = jax.ops.segment_sum(usage - D, region, num_segments=R)
            inflow = jax.ops.segment_sum(y, to, num_segments=R)
            res = jnp.concatenate(
                [res, ((ceil - load - inflow) / sscale).ravel()])
        return res

    step = jnp.concatenate(
        [jnp.broadcast_to(jnp.asarray(step_D, f32), (W, 1)),
         jnp.full((L, 1), 0.1 * sscale, f32)])
    cfg = EngineConfig(inner_steps=steps, outer_steps=outer, mu0=10.0,
                       mu_growth=3.0)
    z0 = jnp.concatenate([D0, jnp.zeros((L, T), f32)])
    z, _ = al_minimize(objective, project, z0, hyper=pieces.get("hyper"),
                       eq_residual=eq, ineq_residual=ineq,
                       step_scale=step, cfg=cfg)
    D = z[:W]
    return D, z[W:], fleet_penalties(p, D, use_kernel)


_COUPLED_STATIC = ("mode", "steps", "outer", "use_kernel", "has_ceiling")
_coupled_run = jax.jit(_coupled_impl, static_argnames=_COUPLED_STATIC)


def _coupled_migrate(p: FleetProblem, policy, res: FleetSolveResult,
                     ctx: SolveContext) -> FleetSolveResult:
    """In-loop coupled migration (see `SolveContext.coupled_migration`):
    jointly refine (D, flows) from the base solve's plan, repair the
    flows to exact feasibility, and keep the refined plan only when it
    preserves total curtailment (1e-3 relative) AND beats the host-side
    post-stage on fleet-wide carbon — otherwise the post-stage result is
    returned, so coupled never loses to the validation reference."""
    from repro.core.migration import (MigrationPlan, _repair,
                                      positive_links, region_aggregates)
    if (p.topology is None or not p.is_multiregion
            or type(policy) not in (CR1, CR2)):
        return _maybe_migrate(p, res)
    fr, to, bw, cost = positive_links(p.topology)
    if fr.size == 0:
        return _maybe_migrate(p, res)
    post = _maybe_migrate(p, res)
    use_kernel = resolve_use_kernel(ctx.use_kernel)
    steps = ctx.resolved_steps(policy)
    R, T = p.R, p.T
    mci = np.asarray(p.mci, float)
    ceiling = p.topology.ceiling
    has_ceiling = ceiling is not None
    if has_ceiling:
        ceil = np.asarray(ceiling, float)
        if ceil.ndim == 1:
            ceil = np.broadcast_to(ceil[:, None], (R, T))
    else:
        ceil = np.zeros((R, T))
    outer = max(4, policy.outer)
    D0 = np.asarray(res.D, float)
    D_f, y_l, pens = _coupled_run(
        _jit_view(p), jnp.asarray(D0, jnp.float32), policy._hyper(p),
        jnp.asarray(fr), jnp.asarray(to), jnp.asarray(bw, jnp.float32),
        jnp.asarray(cost, jnp.float32), jnp.asarray(ceil, jnp.float32),
        mode=policy.name, steps=steps, outer=outer, use_kernel=use_kernel,
        has_ceiling=has_ceiling)
    D_f = np.asarray(D_f, float)
    tot0 = float(D0.sum())
    if abs(float(D_f.sum()) - tot0) > 1e-3 * max(abs(tot0), 1.0):
        return post
    # Exact-feasibility repair against the refined plan's aggregates —
    # the same projection the post-stage validates with.
    cost_f = np.asarray(p.topology.cost, float)
    bw_f = np.asarray(p.topology.bandwidth, float).copy()
    np.fill_diagonal(bw_f, 0.0)
    cap = np.broadcast_to(bw_f[:, :, None], (R, R, T))
    grad = mci[:, None, :] - mci[None, :, :]
    margin = grad - cost_f[:, :, None]
    movable, headroom = region_aggregates(p, D_f)
    y = np.zeros((R, R, T))
    y[fr, to] = np.asarray(y_l, float)
    y = _repair(y, margin, cap, movable, headroom)
    plan = MigrationPlan(
        y=y, carbon_saved=float((y * grad).sum()),
        migration_cost=float((y * cost_f[:, :, None]).sum()),
        moved_total=float(y.sum()))
    cand = _report(p, D_f, np.asarray(pens),
                   iters=res.iters + steps * outer, state=res.state)
    cand = dataclasses.replace(
        cand,
        carbon_reduction_pct=cand.carbon_reduction_pct
        + 100.0 * plan.net_saved / cand.carbon_base,
        extras={**res.extras, "migration": plan,
                "coupled_migration": True})
    if cand.carbon_reduction_pct <= post.carbon_reduction_pct:
        return post
    return cand


def ensemble(problem: FleetProblem, policy, scenarios, *,
             ctx: SolveContext | None = None, batched: bool | None = None):
    """Evaluate `policy` across S Monte Carlo scenarios of `problem`.

    The scenario-ensemble entry point: `scenarios` is a
    `repro.core.scenario.ScenarioStack`, a scenario generator (or
    `SCENARIO_REGISTRY` name), or a sequence of those. CR1/CR2 solve all
    S scenarios as ONE vmapped XLA call (nested inside the W-axis
    shard_map when `ctx.mesh` is set); other policies loop over
    `solve()`. Returns `repro.core.ensemble.EnsembleResult`; call
    `.report()` for the quantile/CVaR/fairness risk summary. Thin
    delegate to `repro.core.ensemble.evaluate_ensemble` (kept lazy —
    the ensemble layer imports this module)."""
    if ctx is not None and ctx.sanitize:
        raise NotImplementedError(
            "SolveContext(sanitize=True) is a solo-solve debug lane — the "
            "vmapped ensemble lanes have no checkify twins; sanitize "
            "single scenarios through solve()")
    if ctx is not None:
        _require_window_ok(None, ctx)
    from repro.core.ensemble import evaluate_ensemble
    return evaluate_ensemble(problem, policy, scenarios, ctx=ctx,
                             batched=batched)


# ---------------------------------------------------------------------------
# CR1/CR2 engine lanes: one pieces function per policy, one tick body
# ---------------------------------------------------------------------------
def _al_fused_inner(p: FleetProblem, mode: str, cfg: EngineConfig, *,
                    car_norm, step_scale, coef0=0.0, scale=None, refs=None):
    """Build the `fused_inner` hook for `al_minimize`: pack this fleet's
    penalty parameters into the `al_step` kernel layout and return the
    chunked dispatcher (`repro.kernels.al_step.ops.make_fused_inner`).
    One kernel invocation runs k fused projected-Adam steps with x and
    the Adam moments VMEM-resident, instead of ~10 HBM round-trips per
    step. Works under vmap (sweep/ensemble lanes) and inside shard_map
    bodies (pass the local row block as `p`).

    Multi-region fleets hand the kernel per-ROW norms (from
    `regional.cr1_norms`/`cr2_norms`) by *folding* instead of changing
    the kernel's scalar slots: the carbon term becomes a (W, T) cvec
    over each row's region trace; CR1's per-row penalty weight
    `lam·pen_w` folds into row-6 `k` (gradient is linear in k) with
    `coef0 = 1`; CR2's per-row residual scale folds `1/scale_w` into
    both `k` and `refs` (h and coef·dpen are algebraically unchanged)
    with `scale = 1`; the per-row step scale rides rowp row 11. The
    kernel itself stays region-blind, and the single-region path packs
    the exact same arrays as before (bitwise-identical)."""
    from repro.kernels.al_step.ops import make_fused_inner, pack_rows
    lo, hi = _bounds(p)
    f32 = jnp.float32
    mci = jnp.asarray(p.mci, f32)
    k = jnp.asarray(p.k, f32)
    if mci.ndim == 2:
        cvec = -jnp.asarray(car_norm, f32)[:, None] \
            * mci[jnp.asarray(p.region)]
        if mode == "cr1":
            k = k * jnp.asarray(coef0, f32)
            coef0 = 1.0
        else:
            inv_w = 1.0 / jnp.asarray(scale, f32)
            k = k * inv_w
            refs = jnp.asarray(refs, f32) * inv_w
            scale = 1.0
    else:
        cvec = (-car_norm * mci)[None, :]
    row_base = pack_rows(jnp.asarray(p.rts_coeffs), jnp.asarray(p.betas),
                         k, jnp.asarray(p.x2_kind),
                         jnp.asarray(p.is_batch), refs=refs)
    return make_fused_inner(
        jnp.asarray(p.usage, f32), jnp.asarray(p.jobs, f32),
        lo.astype(f32), hi.astype(f32), row_base, cvec, mode=mode, cfg=cfg,
        step_scale=step_scale, coef0=coef0, scale=scale,
        day_hours=p.day_hours)


def _cr1_pieces(p: FleetProblem, lam, norms, use_kernel: bool,
                cfg: EngineConfig | None) -> dict:
    """CR1's `al_minimize` pieces on the rows of `p` (a fleet, or one
    device's block of it): the λ-weighted penalty less the carbon, the
    box and day-preservation projection, and with the kernel the fused
    inner loop under `cfg` (none where `cfg` is None: the caller runs
    its own loop). `norms` are `regional.cr1_norms` of the true fleet."""
    lo, hi = _bounds(p)
    mci = jnp.asarray(p.mci)
    pen_norm, car_norm, step_scale = norms

    if mci.ndim == 2:
        wmci = mci[jnp.asarray(p.region)]

        def objective(D: Array, lam) -> Array:
            return ((lam * pen_norm
                     * fleet_penalties(p, D, use_kernel)).sum()
                    - (car_norm[:, None] * D * wmci).sum())
    else:
        def objective(D: Array, lam) -> Array:
            return (lam * pen_norm * fleet_penalties(p, D, use_kernel).sum()
                    - car_norm * (D @ mci).sum())

    pieces = dict(objective=objective, project=_projection(p, lo, hi),
                  hyper=lam, step_scale=step_scale)
    if use_kernel and cfg is not None:
        pieces["fused_inner"] = _al_fused_inner(
            p, "cr1", cfg, car_norm=car_norm, step_scale=step_scale,
            coef0=lam * pen_norm)
    return pieces


def _cr2_pieces(p: FleetProblem, refs, norms, use_kernel: bool,
                cfg: EngineConfig | None) -> dict:
    """CR2's pieces, as `_cr1_pieces`: the carbon objective and one
    equality per workload, its penalty at its fairness reference in
    `refs`. `norms` are `regional.cr2_norms` of the true fleet."""
    lo, hi = _bounds(p)
    mci = jnp.asarray(p.mci)
    car_norm, scale, step_scale = norms

    if mci.ndim == 2:
        wmci = mci[jnp.asarray(p.region)]

        def objective(D: Array, _) -> Array:
            return -(car_norm[:, None] * D * wmci).sum()
    else:
        def objective(D: Array, _) -> Array:
            return -car_norm * (D @ mci).sum()

    def eq(D: Array, _) -> Array:
        return (fleet_penalties(p, D, use_kernel) - refs) / scale

    pieces = dict(objective=objective, eq_residual=eq,
                  project=_projection(p, lo, hi), step_scale=step_scale)
    if use_kernel and cfg is not None:
        pieces["fused_inner"] = _al_fused_inner(
            p, "cr2", cfg, car_norm=car_norm, step_scale=step_scale,
            scale=scale, refs=refs)
    return pieces


def _cr2_sent(policy, p: FleetProblem, ctx: SolveContext):
    """What a sliding CR2 tick sends: the whole fleet's `cr2_cap` at its
    `cap_frac`, kept on the device by the resident `ctx.fleet` (placed by
    `_dispatch`) or made from the host problem `p`."""
    if ctx.fleet is None:
        return cr2_cap(p, policy.cap_frac)
    return _Sent(functools.partial(ctx.fleet.curtailment, policy.cap_frac))


def _cr2_window_refs(p: FleetProblem, cap, use_kernel: bool, window=None):
    """CR2's fairness references of the window `p`, in the program: the
    jnp twin of `cr2_reference_fleet`. A day tick sends `cap_frac` and
    caps here, in float32. A sliding tick sends the whole fleet's
    `cr2_cap`, made on the host in float64, and slides it `window` hours
    as `p` was, so its references are the host's of the same window."""
    if window is not None:
        return fleet_penalties(p, _slide_hours(cap, window), use_kernel)
    E = jnp.asarray(p.entitlement)[:, None]
    d_cap = jnp.maximum(jnp.asarray(p.usage) - cap * E, 0.0)
    return fleet_penalties(p, d_cap, use_kernel)


class _Engine(NamedTuple):
    """What the shared lanes need to know of an engine policy."""
    pieces: Callable    # (p, hyper, norms, use_kernel, cfg) -> pieces
    norms: Callable     # (true fleet, hyper) -> its fleet-global norms
    knob: str           # the policy field that the hyper is made from
    hyper: Callable     # (fleet, knob) -> the hyper, made on the host
    window: Callable    # (window, knob | sent, use_kernel[, offset]) -> it
    sent: Callable      # (policy, fleet, ctx) -> what a sliding tick sends
    mu0: float
    fills: tuple        # inert pad-row values of its multi-region norms
    row_hyper: bool     # the hyper holds one value per row


#: Engine policy name -> its lane inputs. The traced hyper is CR1's λ or
#: CR2's (W,) fairness references, each row's penalty under an equal cap
#: at `cap_frac`; a day tick is sent the knob, and a tick that slides in
#: its program what `sent` gives (λ, or CR2's whole-fleet `cr2_cap`), and
#: each makes the hyper there.
_ENGINES = {
    "cr1": _Engine(_cr1_pieces, lambda p, lam: _cr1_norms(p), "lam",
                   lambda p, lam: lam, lambda p, lam, *_: lam,
                   lambda pol, p, ctx: pol.lam,
                   CR1_MU0, CR1_NORM_FILLS, row_hyper=False),
    "cr2": _Engine(_cr2_pieces, _cr2_norms, "cap_frac",
                   lambda p, c: jnp.asarray(cr2_reference_fleet(p, c)),
                   _cr2_window_refs, _cr2_sent, CR2_MU0, CR2_NORM_FILLS,
                   row_hyper=True),
}


def _shard(fn, mesh, in_specs, out_specs):
    """`fn`, written for one device's rows, inside one shard_map over
    `mesh`: the mesh form of a lane."""
    # check_vma=False: `fn` may dispatch the fused al_step or the
    # dr_features pallas_call (use_kernel), which have no shard_map
    # replication rule; every output is explicitly spec'd.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _penalties(p: FleetProblem, D: Array, use_kernel: bool,
               mesh=None) -> Array:
    """`fleet_penalties` of the plan `D`; on a mesh row block by row
    block, since the TPU cannot partition a Pallas kernel on its own."""
    def pens(pb, d):
        return fleet_penalties(pb, d, use_kernel)

    if mesh is None:
        return pens(p, D)
    axis = fleet_axes(mesh)
    return _shard(pens, mesh, (_fleet_specs(p, axis), P(axis)),
                  P(axis))(p, D)


def _minimize(build, data, specs, state0: EngineState, cfg: EngineConfig,
              mesh=None):
    """`al_minimize` from `state0` on the pieces `build(data)` returns;
    on a mesh `al_minimize_sharded` of the same pieces on each device's
    rows, `specs(axis)` being the shard_map specs of `data`."""
    if mesh is None:
        pieces = dict(build(data))
        return al_minimize(pieces.pop("objective"), pieces.pop("project"),
                           state0.x, init=state0, cfg=cfg, **pieces)
    axis = fleet_axes(mesh)
    return al_minimize_sharded(build, data, mesh=mesh, axis_name=axis,
                               data_specs=specs(axis), init=state0, cfg=cfg)


def _slide_hours(a, window):
    """The (W, T) array `a` slid `window` hours earlier along time, in the
    program (`np.roll(a, -window, axis=1)`); None stays None."""
    return None if a is None else jnp.roll(jnp.asarray(a), -window, axis=1)


def _slide(p: FleetProblem, window) -> FleetProblem:
    """`p` with its usage, jobs and cap slid `window` hours earlier along
    time, in the program: hour 0 of the result is hour `window` of `p`.
    `window` is traced, so every offset shares one program."""
    return dataclasses.replace(p, usage=_slide_hours(p.usage, window),
                               jobs=_slide_hours(p.jobs, window),
                               upper=_slide_hours(p.upper, window))


def _tick(mode: str, p: FleetProblem, hyper, state0: EngineState, norms,
          order, window=None, *, mesh=None, steps: int, outer: int = 1,
          use_kernel: bool, shift: int = 0, reset_mu: bool = False,
          moment_dtype: str = "float32", sanitize: bool = False,
          telemetry_every: int = 0, offsets: tuple | None = None):
    """The CR1/CR2 tick body, solo or on `mesh`: enter the tick
    (`shift`/`reset_mu`), solve `p` under `hyper` from `state0`, and
    return (plan, penalties, state[, telemetry][, hour 0, report sums]).

    `norms` are the fleet-global norms, computed here from `p` when None;
    on a mesh `p` is the padded fleet and `norms` are the true fleet's.
    With a traced hour offset `window` (solo), `p` is the whole fleet,
    slid here first (`_slide`), and `hyper` is what the policy's sliding
    tick sends (`_Engine.sent`), from which the slid window's hyper is
    made here (`_Engine.window`: CR2's fairness references), so its norms
    come from that window too.
    Static knobs: the telemetry trace, then (with `offsets`) hour 0 and
    the report sums (`_tick_report`) ride as extra outputs; without them
    the plain 3-tuple that the sweep, day scan and ensemble lanes wrap."""
    eng = _ENGINES[mode]
    if window is not None:
        p = _slide(p, window)
        hyper = eng.window(p, hyper, use_kernel, window)
    state0 = _enter_tick(state0, shift, reset_mu, eng.mu0)
    cfg = EngineConfig(inner_steps=steps, outer_steps=outer, mu0=eng.mu0,
                       moment_dtype=moment_dtype, sanitize=sanitize,
                       telemetry_every=telemetry_every)
    if norms is None:
        norms = eng.norms(p, hyper)
    D, aux = _minimize(
        lambda blk: eng.pieces(*blk, use_kernel, cfg), (p, hyper, norms),
        lambda axis: (_fleet_specs(p, axis),
                      P(axis) if eng.row_hyper else P(),
                      _norm_specs(p, axis)),
        state0, cfg, mesh)
    out = (D, _penalties(p, D, use_kernel, mesh), aux["state"])
    if telemetry_every:
        out += (aux["telemetry"],)
    return out + _tick_report(p, D, out[1], order, offsets)


def _cr1_impl(p: FleetProblem, lam, state0: EngineState, norms=None,
              order=None, window=None, **static):
    """A CR1 tick (`_tick`); its compiled module is `jit__cr1_impl`."""
    return _tick("cr1", p, lam, state0, norms, order, window, **static)


def _cr2_impl(p: FleetProblem, refs, state0: EngineState, norms=None,
              order=None, window=None, **static):
    """A CR2 tick (`_tick`); its compiled module is `jit__cr2_impl`."""
    return _tick("cr2", p, refs, state0, norms, order, window, **static)


def _batch_lane(p: FleetProblem, vals, hypers, states: EngineState, norms,
                *, keys: tuple, mode: str, mesh=None, steps: int,
                outer: int, use_kernel: bool, shift: int = 0,
                reset_mu: bool = False):
    """Many solves of one fleet in one program: `_tick` under `vmap`, one
    lane per entry of the leading axis of `hypers`, `states`, `norms`
    (None: each lane computes its own) and the overlays `vals` of the
    fleet fields `keys` (a scenario ensemble's; none in a sweep). On a
    mesh the same runs inside one shard_map, each device solving its
    rows for every lane with lane norms of the true fleets. Returns the
    stacked (plans, penalties, states)."""
    def lanes(pb, vals, hypers, states, norms):
        def one(v, h, st, nm):
            ps = dataclasses.replace(pb, **dict(zip(keys, v)))
            return _tick(mode, ps, h, st, nm, None, steps=steps,
                         outer=outer, use_kernel=use_kernel, shift=shift,
                         reset_mu=reset_mu)

        return jax.vmap(one)(vals, hypers, states, norms)

    if mesh is None:
        return lanes(p, vals, hypers, states, norms)
    axis = fleet_axes(mesh)
    row = P(None, axis)
    st = EngineState(x=row, lam_eq=row, lam_in=row, mu=P())
    return _shard(lanes, mesh,
                  (_fleet_specs(p, axis),
                   tuple(P() if k == "mci" else row for k in keys),
                   row if _ENGINES[mode].row_hyper else P(), st,
                   _norm_specs(p, axis, stacked=True)),
                  (row, row, st))(p, vals, hypers, states, norms)


class _Jits(NamedTuple):
    """The jitted twins of one lane."""
    plain: Callable
    donated: Callable | None    # reuses the engine state's buffers
    checked: Callable | None    # the checkify twin that `sanitize` runs


def _jits(impl, static: tuple, *, donate: int | None = None,
          checked: bool = False) -> _Jits:
    """A lane's plain jit, its twin donating positional argument `donate`
    (the engine state) and, where `sanitize` applies, its checkify twin
    (`analysis.sanitize.checked_jit`: returns (err, out)), all from one
    tuple of static names."""
    return _Jits(
        jax.jit(impl, static_argnames=static),
        None if donate is None else jax.jit(
            impl, static_argnames=static, donate_argnums=(donate,)),
        checked_jit(impl, static_argnames=static) if checked else None)


_TICK_STATIC = ("mesh", "steps", "outer", "use_kernel", "shift", "reset_mu",
                "moment_dtype", "sanitize", "telemetry_every", "offsets")
#: The tick's twins per engine policy, each named for its policy.
_TICK = {"cr1": _jits(_cr1_impl, _TICK_STATIC, donate=2, checked=True),
         "cr2": _jits(_cr2_impl, _TICK_STATIC, donate=2, checked=True)}
_BATCH = _jits(_batch_lane, ("keys", "mode", "mesh", "steps", "outer",
                             "use_kernel", "shift", "reset_mu"))


def _run_lane(jits: _Jits, ctx: SolveContext, *args, **kw):
    """`_dispatch` a lane through the twin `ctx` asks for: the checkify
    twin under `sanitize` (a failed guard raises `SanitizeError`), the
    donated one under `donate`, else the plain one."""
    if ctx.sanitize:
        err, out = _dispatch(jits.checked, *args, sanitize=True, **kw)
        err.throw()
        return out
    return _dispatch(jits.donated if ctx.donate else jits.plain, *args,
                     **kw)


def _stack(trees):
    """Stack pytrees of like shapes along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _cold(mode: str, W: int, T: int, lanes: int | None = None):
    """A cold engine state for `W` rows of `T` hours (`lanes` stacked)."""
    eng = _ENGINES[mode]
    st = EngineState.cold(jnp.zeros((W, T)), n_eq=W if eng.row_hyper else 0,
                          mu0=eng.mu0)
    return st if lanes is None else _stack([st] * lanes)


def _mesh_inputs(mode: str, p: FleetProblem, hyper, W_pad: int):
    """(`hyper`, the norms of the true fleet `p` under it), each padded to
    the `W_pad` rows of `p`'s device-padded fleet with inert fills."""
    eng = _ENGINES[mode]
    norms = eng.norms(p, hyper)
    if p.is_multiregion:
        norms = _pad_row_norms(norms, W_pad, eng.fills)
    if eng.row_hyper:
        hyper = jnp.concatenate([hyper, jnp.zeros(W_pad - p.W, hyper.dtype)])
    return hyper, norms


def _lane_inputs(mode: str, p: FleetProblem, hypers: list, problems, mesh,
                 init: EngineState | None = None):
    """(fleet, true W, stacked hypers, stacked norms, stacked states) for
    `_batch_lane` lanes of `p` under `hypers`: off-mesh the fleet's jit
    view, and no norms (each lane computes its own); on a mesh the padded
    fleet and each lane's hyper and norms of its true fleet in
    `problems`. The states are `init`, each padded to the fleet's rows,
    or cold ones."""
    n = len(hypers)
    if mesh is None:
        pj, W, hypers, norms = _jit_view(p), p.W, _stack(hypers), None
    else:
        pj, W = pad_fleet(p, fleet_device_count(mesh))
        hypers, norms = zip(*(_mesh_inputs(mode, q, h, pj.W)
                              for q, h in zip(problems, hypers)))
        hypers, norms = _stack(hypers), _stack(norms)
    init = _cold(mode, pj.W, pj.T, lanes=n) if init is None \
        else jax.vmap(lambda s: _pad_state(s, pj.W))(init)
    return pj, W, hypers, norms, init


class _EngineLanes:
    """`solve` and the same-family sweep of an engine policy (CR1, CR2).
    Both run the one tick body `_tick`: a tick dispatches `_TICK`, a
    sweep `_BATCH`, solo or on `ctx.mesh`."""

    def _hyper(self, p: FleetProblem):
        """The engine's traced hyper on the fleet `p` (`_Engine.hyper`)."""
        eng = _ENGINES[self.name]
        return eng.hyper(p, getattr(self, eng.knob))

    def solve(self, p: FleetProblem,
              ctx: SolveContext = SolveContext()) -> FleetSolveResult:
        mode = self.name
        use_kernel = resolve_use_kernel(ctx.use_kernel)
        _require_telemetry_ok(ctx, use_kernel)
        steps = ctx.resolved_steps(self)
        tel = _tel_every(ctx)
        # A sliding lane makes the window's hyper in its program.
        hyper = self._hyper(p) if ctx.window is None \
            else _ENGINES[mode].sent(self, p, ctx)
        W, norms = p.W, None
        if ctx.mesh is None:
            pj = _sent(p, ctx)
        else:
            pj, _ = pad_fleet(p, fleet_device_count(ctx.mesh))
            W = pj.W
            hyper, norms = _mesh_inputs(mode, p, hyper, W)
        warm = _cold(mode, W, p.T) if ctx.warm is None \
            else _pad_state(ctx.warm, W)
        order, offsets = report_layout(p)
        out = _run_lane(_TICK[mode], ctx, pj, hyper, warm, norms,
                        order=order, window=_offset(ctx),
                        mesh=ctx.mesh, steps=steps,
                        outer=self.outer, use_kernel=use_kernel,
                        shift=ctx.shift, reset_mu=ctx.reset_mu,
                        moment_dtype=ctx.moment_dtype, telemetry_every=tel,
                        offsets=offsets)
        _count(al_rounds=self.outer, al_steps=steps * self.outer)
        return _device_report(p, out, iters=steps * self.outer, tel=tel)

    # -- vmapped sweep lane -------------------------------------------------
    @classmethod
    def _sweep_uniform(cls, policies) -> bool:
        # `outer` is a static engine knob: one compile needs one value.
        return len({pl.outer for pl in policies}) == 1

    @classmethod
    def _sweep_family(cls, p: FleetProblem, policies,
                      ctx: SolveContext) -> list[FleetSolveResult]:
        mode, outer, N = cls.name, policies[0].outer, len(policies)
        steps = ctx.resolved_steps(policies[0])
        pj, W, hypers, norms, init = _lane_inputs(
            mode, p, [pl._hyper(p) for pl in policies], [p] * N, ctx.mesh,
            ctx.warm)
        Ds, pens, states = _BATCH.plain(
            pj, (), hypers, init, norms, keys=(), mode=mode, mesh=ctx.mesh,
            steps=steps, outer=outer,
            use_kernel=resolve_use_kernel(ctx.use_kernel))
        return [_report(p, np.asarray(D)[:W], np.asarray(pen)[:W],
                        iters=steps * outer,
                        state=jax.tree_util.tree_map(lambda a, i=i: a[i],
                                                     states))
                for i, (D, pen) in enumerate(zip(np.asarray(Ds),
                                                 np.asarray(pens)))]


# ---------------------------------------------------------------------------
# CR1 — Efficient DR (unconstrained trade-off objective)
# ---------------------------------------------------------------------------
@_register
@dataclasses.dataclass(frozen=True)
class CR1(_EngineLanes):
    """Efficient DR (paper Eq. 3): maximize λ-weighted penalty/carbon
    trade-off over the whole fleet — unconstrained but for the box and
    batch day-preservation, both handled by projection."""

    lam: float = 1.45

    name: ClassVar[str] = "cr1"
    default_steps: ClassVar[int] = 600
    mu0: ClassVar[float] = CR1_MU0
    outer: ClassVar[int] = 1       # one multiplier round: nothing to price
    #: The solo lane takes `SolveContext.window` and `.fleet` (λ is the
    #: same in every window).
    slides: ClassVar[bool] = True


# ---------------------------------------------------------------------------
# CR2 — Fair-Centralized DR (per-workload penalty-equality targets)
# ---------------------------------------------------------------------------
@_register
@dataclasses.dataclass(frozen=True)
class CR2(_EngineLanes):
    """Fair-Centralized DR (paper Eq. 4): min −carbon s.t.
    C_i(d_i) = C_i(cap_frac·E_i) for every workload — one equality
    multiplier per workload, `outer` AL multiplier rounds."""

    cap_frac: float = 0.78
    outer: int = 6

    name: ClassVar[str] = "cr2"
    default_steps: ClassVar[int] = 400
    mu0: ClassVar[float] = CR2_MU0
    #: The solo lane takes `SolveContext.window` and `.fleet`: the fleet
    #: holds its equal-cap curtailment (`cr2_cap`), and the tick's program
    #: slides it and makes the window's fairness references
    #: (`_cr2_window_refs`) and norms.
    slides: ClassVar[bool] = True


# ---------------------------------------------------------------------------
# CR3 — Fair-Decentralized DR (taxes and rebates, Eqs. 5–8)
# ---------------------------------------------------------------------------
def _cr3_pieces(p: FleetProblem, use_kernel: bool, reg_scale):
    """Best-response pieces for one device's row block (or the whole fleet).

    Everything here is row-separable; `reg_scale` is the regularizer
    normalizer 1e-3/(W_true·T), passed in so a padded sharded solve
    regularizes identically to the unpadded single-device one. On
    multi-region problems it is the per-row (W, 1) vector
    1e-3/(W_region·T) and ρ is a per-region (R,) price vector, so each
    region's market is exactly its standalone single-region market.

    Numerics, validated against the per-workload SLSQP reference:
      * tiny quadratic regularizer — a selfish workload takes the *minimal*
        adjustment satisfying its allowance; the regularizer breaks the
        zero-penalty plateau of batch models toward that minimal response
        (without it, any deep-feasible point is an equally 'optimal' best
        response with wildly overpaid rebates).
      * day-tangent gradient projection (see engine.al_minimize docs).
      * gentle μ schedule: the KKT multipliers here are O(1e-3), so a stiff
        wall (μ≫1) just makes projected Adam bounce off the boundary.
    """
    lo, hi = _bounds(p)
    usage = jnp.asarray(p.usage)
    E = jnp.asarray(p.entitlement)
    mci = jnp.asarray(p.mci)
    tau = 0.02 * E
    multi = mci.ndim == 2
    if multi:
        region = jnp.asarray(p.region)
        wmci = mci[region]

        def objective(D: Array, hyper) -> Array:
            reg = (reg_scale * (D / E[:, None]) ** 2).sum()
            return (fleet_penalties(p, D, use_kernel) / E).sum() + reg

        def ineq(D: Array, hyper) -> Array:
            rho_, tax_ = hyper
            rebate = rho_[region] * (D * wmci).sum(1)
            peak = tau * jax.nn.logsumexp((usage - D) / tau[:, None],
                                          axis=1)
            return ((1.0 - tax_) * E + rebate - peak) / E
    else:
        def objective(D: Array, hyper) -> Array:
            reg = reg_scale * ((D / E[:, None]) ** 2).sum()
            return (fleet_penalties(p, D, use_kernel) / E).sum() + reg

        def ineq(D: Array, hyper) -> Array:
            rho_, tax_ = hyper
            # A row-wise reduction, not a matvec: XLA:CPU's dot rounds
            # each row differently depending on how many rows it holds,
            # so a sharded solve would not match the single-device one.
            rebate = rho_ * (D * mci).sum(1)
            peak = tau * jax.nn.logsumexp((usage - D) / tau[:, None],
                                          axis=1)
            return ((1.0 - tax_) * E + rebate - peak) / E

    W, T = p.usage.shape
    n_days = max(1, T // p.day_hours)
    span = n_days * p.day_hours
    is_batch = jnp.asarray(p.is_batch)[:, None, None]

    def day_tangent(g: Array) -> Array:
        Gd = g[:, :span].reshape(W, n_days, p.day_hours)
        Gd = jnp.where(is_batch, Gd - Gd.mean(axis=-1, keepdims=True), Gd)
        return jnp.concatenate([Gd.reshape(W, span), g[:, span:]], axis=1)

    step_scale = jnp.maximum(hi - lo, 1e-6).mean(axis=1, keepdims=True)
    return objective, ineq, _projection(p, lo, hi), step_scale, day_tangent


def _cr3_cfg(steps: int, outer: int) -> EngineConfig:
    return EngineConfig(inner_steps=steps, outer_steps=outer, lr=0.005,
                        mu0=CR3_MU0, mu_growth=2.0, beta2=0.99)


def _cr3_impl(p: FleetProblem, rho, tax_frac, reg_scale,
              state0: EngineState, steps: int, outer: int, use_kernel: bool,
              mesh=None):
    """All W selfish problems in one AL solve. Each workload i minimizes its
    own penalty s.t. the peak-allowance inequality (Eq. 5/8)

        max_t (U_i − d_i) ≤ E_i − T_i + ρ·⟨mci, d_i⟩,   T_i = tax_frac·E_i

    (smooth max as in `policies.cr3_workload_spec`). Objective, residual and
    projection are all row-separable, so this single (W, T) engine call IS
    the vmapped per-workload best response: one clearing round.

    With `mesh` the best response runs sharded: the allowance inequality,
    its multipliers and the per-row step scale all live with their rows;
    only ρ/tax/reg_scale are replicated (multi-region: ρ stays a
    replicated (R,) vector and reg_scale shards with its rows). The Eq.-6
    fiscal sums live in `_cr3_market`, outside the shard_map.
    """
    def build(blk):
        pb, hyper_b, reg_b = blk
        objective, ineq, project, step_scale, day_tangent = _cr3_pieces(
            pb, use_kernel, reg_b)
        return dict(objective=objective, project=project, hyper=hyper_b,
                    ineq_residual=ineq, step_scale=step_scale,
                    grad_transform=day_tangent)

    D, aux = _minimize(
        build, (p, (rho, tax_frac), reg_scale),
        lambda axis: (_fleet_specs(p, axis), (P(), P()),
                      P() if np.ndim(p.mci) == 1 else P(axis)),
        state0, _cr3_cfg(steps, outer), mesh)
    return D, _penalties(p, D, use_kernel, mesh), aux["state"]


def _cr3_rebates(D, rho, mci, order, offsets: tuple):
    """Rebates paid per region, ρ_r·Σ_{i∈r}⟨d_i, mci_r⟩, as a `dfloat`
    pair of (R,) arrays: ρ_r and mci_r (R, T) are pairs, each region's
    hour totals of D are `regional.region_sums`, and the products and
    the sum over hours stay in double-float."""
    cols = _region_sums(D, order, offsets)
    return _df.mul(rho, _df.tree_sum(_df.mul(mci, cols), axis=1))


def _cr3_price_cut(c9, paid, live):
    """max(0.5, 0.9·collected_r / paid_r) as a pair, `c9` = 0.9·collected
    (1 where not `live`)."""
    one, zero = jnp.ones_like(paid[0]), jnp.zeros_like(paid[0])
    q = _df.div(c9, (jnp.where(live, paid[0], one),
                     jnp.where(live, paid[1], zero)))
    keep = _df.gt(q, (0.5 * one, zero))
    return jnp.where(keep, q[0], 0.5), jnp.where(keep, q[1], 0.0)


def _cr3_market(p: FleetProblem, rho, tax_frac, reg_scale,
                state0: EngineState, iters, fiscal, order, window=None, *,
                offsets: tuple, steps: int, outer: int, use_kernel: bool,
                shift: int = 0, reset_mu: bool = False, mesh=None):
    """One CR3 decision in one XLA program: the selfish best responses
    and the coordinator's Eq.-6 clearing rounds, in a `lax.while_loop`
    over the (R,) price vector `rho` (R = 1 for a single-region fleet).

    Round 1 answers the given prices from `state0` (the tick's
    `shift`/`reset_mu` applied first). Each later round, for every region
    r still in deficit (paid_r = ρ_r·Σ_{i∈r}⟨d_i, mci_r⟩ above collected_r
    + 1e-9, collected_r = tax_frac·Σ_{i∈r} E_i), lowers ρ_r ← ρ_r·max(0.5,
    0.9·collected_r / paid_r) and re-solves from the previous round's
    state with the μ schedule restarted; the rows of regions that already
    cleared keep their plan, penalties, primal and allowance multipliers,
    so each region follows exactly its standalone market. The loop stops
    when no region is in deficit or after `iters` (traced: one program
    serves every `clearing_iters`) rounds past the first.

    The fiscal test and the price rule run in double-float (`dfloat`), so
    they decide as float64 host arithmetic does: `rho` is a pair of (R,)
    arrays (the best response answers its float32 `hi`), and `fiscal` is
    (mci_r (R, T), collected_r + 1e-9, 0.9·collected_r) as pairs computed
    in float64 on the host. The rebates are `_cr3_rebates` over
    `order`/`offsets`, global sums under `mesh` (pad rows count nowhere).
    A traced hour offset `window` slides `p` first (`_slide`).

    Returns (D, penalties, state, rho, rounds, the plan's report sums
    (`_plan_sums`))."""
    mci, bar, c9 = fiscal
    if window is not None:
        p = _slide(p, window)
    state = _enter_tick(state0, shift, reset_mu, CR3_MU0)
    multi = np.ndim(p.mci) == 2
    W = p.usage.shape[0]
    rid = jnp.asarray(p.region) if multi else jnp.zeros(W, jnp.int32)

    def best_response(rho_, st):
        r = rho_[0] if multi else rho_[0][0]
        return _cr3_impl(p, r, tax_frac, reg_scale, st, steps, outer,
                         use_kernel, mesh)

    def cond(c):
        k, paid = c[0], c[5]
        return (k == 0) | ((k <= iters) & jnp.any(_df.gt(paid, bar)))

    def body(c):
        k, D, pens, st, rho_, paid = c
        first = k == 0
        active = first | _df.gt(paid, bar)
        cut = active & ~first
        lowered = _df.mul(rho_, _cr3_price_cut(c9, paid, cut))
        rho_ = tuple(jnp.where(cut, n, o) for n, o in zip(lowered, rho_))
        # Carry primal + allowance multipliers; restart the μ schedule
        # after the first round so every round keeps the gentle wall the
        # best response relies on.
        Dn, pn, sn = best_response(rho_, dataclasses.replace(
            st, mu=jnp.where(first, st.mu, CR3_MU0)))
        row = active[rid]
        D = jnp.where(row[:, None], Dn, D)
        st = EngineState(x=jnp.where(row[:, None], sn.x, st.x),
                         lam_eq=sn.lam_eq,
                         lam_in=jnp.where(row, sn.lam_in, st.lam_in),
                         mu=sn.mu)
        paid = _cr3_rebates(D, rho_, mci, order, offsets)
        return k + 1, D, jnp.where(row, pn, pens), st, rho_, paid

    x = state.x
    state = EngineState(x=x, lam_eq=state.lam_eq.astype(x.dtype),
                        lam_in=state.lam_in.astype(x.dtype),
                        mu=jnp.asarray(state.mu, x.dtype))
    rho = tuple(jnp.asarray(a, x.dtype) for a in rho)
    k, D, pens, state, rho, _paid = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.zeros_like(x), jnp.zeros(W, x.dtype),
                     state, rho, tuple(jnp.zeros_like(a) for a in rho)))
    return D, pens, state, rho, k, _plan_sums(p, D, pens, order, offsets)


_CR3_STATIC = ("offsets", "steps", "outer", "use_kernel", "shift",
               "reset_mu", "mesh")
_cr3_cleared = jax.jit(_cr3_market, static_argnames=_CR3_STATIC)
_cr3_cleared_donated = jax.jit(_cr3_market, static_argnames=_CR3_STATIC,
                               donate_argnums=(4,))


@functools.partial(jax.jit, static_argnames=("offsets", "steps", "outer",
                                             "use_kernel"))
def _cr3_cleared_lanes(p: FleetProblem, rhos, taxes, reg_scale, states,
                       iters, fiscal, *, offsets: tuple, steps: int,
                       outer: int, use_kernel: bool):
    """Every sweep lane's market in one program: the (ρ, tax,
    clearing_iters, taxes collected) lane axis rides vmap through
    `_cr3_market`; a lane whose market has cleared keeps its carry while
    the others go on."""
    mci, bars, c9s = fiscal

    def one(rho, tax, st, n, bar, c9):
        return _cr3_market(p, rho, tax, reg_scale, st, n, (mci, bar, c9),
                           None, offsets=offsets, steps=steps, outer=outer,
                           use_kernel=use_kernel)

    return jax.vmap(one)(rhos, taxes, states, iters, bars, c9s)


def _cr3_unbalanced_warn(clearing_iters: int, deficit: float, rho: float,
                         caller: str) -> None:
    warnings.warn(
        f"{caller}: fiscal clearing did not converge in "
        f"{clearing_iters} iterations — rebates exceed taxes by "
        f"{deficit:.4g} at rho={rho:.4g} (Eq. 6 unmet)",
        RuntimeWarning, stacklevel=3)


def _cr3_taxes(p: FleetProblem) -> np.ndarray:
    """Σ_{i∈r} E_i per region, float64 on the host ((1,) for one region)."""
    E = np.asarray(p.entitlement, float)
    if p.is_multiregion:
        return _region_totals(p.region, E, p.R)
    return np.array([E.sum()])


def _cr3_fiscal(p: FleetProblem, collected: np.ndarray):
    """The market's Eq.-6 constants as `dfloat` pairs: mci_r (R, T), the
    deficit bar collected_r + 1e-9 and 0.9·collected_r (`collected` may
    carry a leading lane axis)."""
    return (_df.split(np.atleast_2d(np.asarray(p.mci, float))),
            _df.split(collected + 1e-9), _df.split(0.9 * collected))


def _cr3_balance(p: FleetProblem, D: np.ndarray, rho: np.ndarray,
                 collected: np.ndarray, order, offsets: tuple):
    """Per-region (balanced, deficit) of the pulled plan in float64: the
    rebates ρ_r·Σ_{i∈r}⟨d_i, mci_r⟩ at the prices the market stopped at
    against collected_r + 1e-9. This, not the device's test, is what
    `balanced` and `fiscal_deficit` report."""
    mci = np.atleast_2d(np.asarray(p.mci, float))
    rows = D if order is None else D[order]
    paid = rho * np.array([(rows[a:b] @ m).sum() for a, b, m in
                           zip(offsets[:-1], offsets[1:], mci)])
    balanced = paid <= collected + 1e-9
    return balanced, np.where(balanced, 0.0, paid - collected)


@_register
@dataclasses.dataclass(frozen=True)
class CR3:
    """Fair-Decentralized DR: vmapped selfish best responses + the
    coordinator's fiscal-balance clearing (Eqs. 5–8).

    The coordinator lowers the carbon price ρ until rebates are covered by
    taxes (Eq. 6, `policies.cr3_fiscal_balance` semantics). Each clearing
    round warm-starts from the previous round's engine state (the
    allowance multipliers track the shrinking ρ smoothly); `ctx.warm`
    seeds round 0 the same way for rolling-horizon re-solves.

    A solve is one dispatch and one pull whatever its round count: the
    best responses and the clearing rounds run in `_cr3_market`'s
    on-device loop. A multi-region fleet runs one market per region at
    its own price ρ_r, so `extras["rho"]` is an (R,) vector (a float for
    one region); regions that already cleared keep their rows frozen, so
    each region's trajectory is exactly its standalone single-region
    market (the zero-bandwidth decomposition tests rely on this). With
    `ctx.mesh`, each best response runs sharded over the fleet axis and
    the Eq.-6 sums are global sums over the true rows. The loop tests
    the balance in double-float, so it stops where float64 would; the
    reported `balanced` and `fiscal_deficit` are then recomputed from
    the pulled plan in float64: `balanced` means each region's rebates
    ρ_r·Σ⟨d_i, mci_r⟩ are at most its taxes + 1e-9, in float64.

    If `clearing_iters` is exhausted with rebates still exceeding taxes,
    `result.extras` carries `balanced=False` and the remaining
    `fiscal_deficit` (rebates − taxes summed over the regions in deficit,
    NP·kgCO2/MWh), and a `RuntimeWarning` is emitted — callers must not
    treat `extras["rho"]` as market-clearing then. The solve's `dr.solve`
    span counts `clear_rounds` (best responses run) and
    `unbalanced_regions`."""

    rho: float = 0.02
    tax_frac: float = 0.2
    outer: int = 3
    clearing_iters: int = 8

    name: ClassVar[str] = "cr3"
    default_steps: ClassVar[int] = 600
    mu0: ClassVar[float] = CR3_MU0
    #: The solo lane takes `SolveContext.window` and `.fleet`.
    slides: ClassVar[bool] = True

    def solve(self, p: FleetProblem,
              ctx: SolveContext = SolveContext()) -> FleetSolveResult:
        use_kernel = resolve_use_kernel(ctx.use_kernel)
        steps = ctx.resolved_steps(self)
        R = p.R if p.is_multiregion else 1
        if ctx.mesh is None:
            pj, W, W_pad = _sent(p, ctx), p.W, p.W
        else:
            pj, W = pad_fleet(p, fleet_device_count(ctx.mesh))
            W_pad = pj.W
        state = _pad_state(ctx.warm, W_pad) if ctx.warm is not None \
            else EngineState.cold(jnp.zeros((W_pad, p.T)), n_in=W_pad,
                                  mu0=CR3_MU0)
        if p.is_multiregion:
            reg_scale = _cr3_reg_scale(p)
            if W_pad > W:
                reg_scale = jnp.concatenate(
                    [reg_scale, jnp.ones((W_pad - W, 1), reg_scale.dtype)])
        else:
            reg_scale = 1e-3 / (W * p.T)
        order, offsets = report_layout(p)
        collected = self.tax_frac * _cr3_taxes(p)
        twin = _cr3_cleared_donated if ctx.donate else _cr3_cleared
        D, _pens, state, rho, rounds, sums = _dispatch(
            twin, pj, _df.split(np.full(R, float(self.rho))), self.tax_frac,
            reg_scale, state, self.clearing_iters, _cr3_fiscal(p, collected),
            order, _offset(ctx), offsets=offsets, steps=steps,
            outer=self.outer, use_kernel=use_kernel, shift=ctx.shift,
            reset_mu=ctx.reset_mu, mesh=ctx.mesh)
        D, rho, rounds, sums = _fetch(D, rho, rounds, sums)
        D, rho = D[:W], rho[0].astype(float) + rho[1]
        balanced, deficit = _cr3_balance(p, D, rho, collected, order,
                                         offsets)
        _count(clear_rounds=int(rounds),
               unbalanced_regions=int((~balanced).sum()))
        if not balanced.all():
            worst = int(np.argmax(deficit))
            _cr3_unbalanced_warn(
                self.clearing_iters, float(deficit.sum()),
                float(rho[worst]), "CR3.solve (multi-region)"
                if p.is_multiregion else "CR3.solve")
        return _report(p, D, sums=sums,
                       iters=steps * self.outer * int(rounds), state=state,
                       extras={"rho": rho if p.is_multiregion
                               else float(rho[0]),
                               "balanced": bool(balanced.all()),
                               "fiscal_deficit": float(deficit.sum())})

    # -- vmapped sweep lane -------------------------------------------------
    @classmethod
    def _sweep_uniform(cls, policies: Sequence["CR3"]) -> bool:
        # `outer` is static (one compile); per-lane ρ/tax/clearing_iters
        # are traced.
        return len({pl.outer for pl in policies}) == 1

    @classmethod
    def _sweep_family(cls, p: FleetProblem, policies: Sequence["CR3"],
                      ctx: SolveContext) -> list[FleetSolveResult]:
        if ctx.mesh is not None or p.is_multiregion:
            # vmap-of-shard_map markets, and per-lane (R,) price vectors,
            # are a ROADMAP follow-up; both solve per policy.
            return [pl.solve(p, ctx) for pl in policies]
        use_kernel = resolve_use_kernel(ctx.use_kernel)
        steps = ctx.steps if ctx.steps is not None else cls.default_steps
        outer = policies[0].outer
        N = len(policies)
        states = EngineState(
            x=jnp.zeros((N,) + p.usage.shape),
            lam_eq=jnp.zeros((N, 0)), lam_in=jnp.zeros((N, p.W)),
            mu=jnp.full((N,), CR3_MU0))
        collected = np.asarray([pl.tax_frac for pl in policies],
                               float)[:, None] * _cr3_taxes(p)
        Ds, _pens, states, rho, rounds, sums = _dispatch(
            _cr3_cleared_lanes, _jit_view(p),
            _df.split([[pl.rho] for pl in policies]),
            np.asarray([pl.tax_frac for pl in policies], float),
            1e-3 / (p.W * p.T), states,
            np.asarray([pl.clearing_iters for pl in policies], np.int32),
            _cr3_fiscal(p, collected), offsets=(0, p.W), steps=steps,
            outer=outer, use_kernel=use_kernel)
        Ds, rho, rounds, sums = _fetch(Ds, rho, rounds, sums)
        rho = rho[0].astype(float) + rho[1]
        out = []
        for i, pl in enumerate(policies):
            balanced, deficit = _cr3_balance(p, Ds[i], rho[i],
                                             collected[i], None, (0, p.W))
            if not balanced.all():
                _cr3_unbalanced_warn(pl.clearing_iters, float(deficit[0]),
                                     float(rho[i, 0]), "CR3 sweep")
            out.append(_report(
                p, Ds[i], sums=jax.tree_util.tree_map(lambda a: a[i], sums),
                iters=steps * outer * int(rounds[i]),
                state=jax.tree_util.tree_map(lambda a: a[i], states),
                extras={"rho": float(rho[i, 0]),
                        "balanced": bool(balanced[0]),
                        "fiscal_deficit": float(deficit[0])}))
        return out


# ---------------------------------------------------------------------------
# Baseline wrappers — closed-form prior-work policies as DRPolicy values
# ---------------------------------------------------------------------------
@_register
@dataclasses.dataclass(frozen=True)
class B1:
    """Proportional Power Capping (paper §V-B, eBuff-style): cap every
    workload at L_i = F·E_i, d = max(U − L, 0) — the fleet-array form of
    `baselines.b1_adjustments`. Closed form: `ctx` execution knobs are
    no-ops (no engine state to warm/shard)."""

    F: float = 0.75

    name: ClassVar[str] = "b1"
    default_steps: ClassVar[int] = 0

    def solve(self, p: FleetProblem,
              ctx: SolveContext = SolveContext()) -> FleetSolveResult:
        D = np.maximum(
            np.asarray(p.usage)
            - self.F * np.asarray(p.entitlement)[:, None], 0.0)
        pens = np.asarray(fleet_penalties(
            p, jnp.asarray(D), resolve_use_kernel(ctx.use_kernel)))
        return _report(p, D, pens, iters=0)


# ---------------------------------------------------------------------------
# Whole-day scan — a rolling-horizon day as ONE XLA dispatch
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DayResult:
    """Result of `solve_day`: a whole rolling-horizon day in one dispatch.

    committed: (n_ticks, W) — hour-0 curtailment of every tick's plan (the
      hours the controller actually commits).
    last: the final tick's full `FleetSolveResult` (its `.state` chains
      into the next day's `solve_day`/`solve` warm start).
    inner_steps: per-tick engine iterations (cold budget first unless the
      day itself was warm-started)."""
    committed: np.ndarray
    last: FleetSolveResult
    inner_steps: tuple[int, ...]


def _day_impl(p: FleetProblem, xs, state0: EngineState, tick_solve,
              warm_steps: int, first_steps: int, first_shift: int,
              first_reset: bool, telemetry: bool = False):
    """Shared whole-day loop: tick 0 outside the scan (its step budget /
    shift / mu-reset differ), then `lax.scan` over the remaining forecast
    rows, each iteration fusing window-roll + `EngineState.shifted` +
    mu-reset + warm re-solve. `xs` is any pytree with a leading n_ticks
    axis (per-tick forecasts, plus per-tick norms on the sharded path);
    `tick_solve(p_t, x_t, st, steps, shift, reset_mu) -> (D, pens,
    state)` is a policy impl (pure/traceable) that installs its slice
    `x_t` into the windowed problem.

    With `telemetry` (static), `tick_solve` returns a 4th element — the
    engine's per-solve telemetry dict — and the warm ticks' traces ride
    the scan ys (stacked on a leading (n-1) tick axis), so the whole
    instrumented day is STILL one dispatch. Tick 0's trace stays
    separate: its step budget (and hence sample count) differs. Returns
    `(..., tel0, tel_warm)` where `tel_warm` is None for a 1-tick day.
    """
    usage = jnp.asarray(p.usage)
    jobs = jnp.asarray(p.jobs)
    upper = None if p.upper is None else jnp.asarray(p.upper)
    tmap = jax.tree_util.tree_map

    def roll(a):
        return None if a is None else jnp.roll(a, -1, axis=1)

    out0 = tick_solve(p, tmap(lambda a: a[0], xs), state0,
                      first_steps, first_shift, first_reset)
    D, pens, st = out0[:3]
    tel0 = out0[3] if telemetry else None

    def body(carry, x_t):
        st, usage, jobs, upper, _, _ = carry
        usage, jobs, upper = roll(usage), roll(jobs), roll(upper)
        p_t = dataclasses.replace(p, usage=usage, jobs=jobs, upper=upper)
        out = tick_solve(p_t, x_t, st, warm_steps, 1, True)
        D, pens, st = out[:3]
        ys = (D[:, 0], out[3]) if telemetry else D[:, 0]
        return (st, usage, jobs, upper, D, pens), ys

    carry = (st, usage, jobs, upper, D, pens)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    tel_w = None
    if n > 1:
        carry, ys = jax.lax.scan(body, carry, tmap(lambda a: a[1:], xs))
        committed_w, tel_w = ys if telemetry else (ys, None)
        committed = jnp.concatenate([D[:, 0][None], committed_w], axis=0)
    else:
        committed = D[:, 0][None]
    st, _, _, _, D_last, pens_last = carry
    out = (committed, D_last, pens_last, st)
    return out + (tel0, tel_w) if telemetry else out


def _day_sums(p: FleetProblem, out, n: int, order, offsets):
    """A day scan's outputs `out` (commitments, last plan, its
    penalties, ...), with the last plan's `_plan_sums` appended unless
    `offsets` is None: its window is the first one rolled `n - 1`
    hours."""
    if offsets is None:
        return out
    return tuple(out) + (_plan_sums(p, out[1], out[2], order, offsets,
                                    roll=n - 1),)


def _day_lane(p: FleetProblem, hyper, mci_stack, state0: EngineState,
              norms_stack, order, *, mode: str, mesh=None, warm_steps: int,
              first_steps: int, first_shift: int, first_reset: bool,
              outer: int, use_kernel: bool, moment_dtype: str,
              sanitize: bool = False, telemetry_every: int = 0,
              offsets: tuple | None = None):
    """A whole rolling-horizon day of CR1/CR2 ticks in one program:
    `_day_impl`'s scan over `_tick`, one row of `mci_stack` per tick.
    `hyper` is the policy's knob (`_Engine.knob`: CR1's λ, CR2's
    cap_frac), from which each tick makes its window's hyper
    (`_Engine.window`). Off-mesh each tick computes its norms; on a
    mesh the scan runs inside one shard_map, each device scanning its
    rows through the day, with per-tick norms of the true fleet in
    `norms_stack` (`_day_norm_stacks`)."""
    window = _ENGINES[mode].window

    def day(pb, hyper, mci_s, norms_s, st0):
        def tick_solve(p_t, x_t, st, steps, shift, reset_mu):
            mci_t, norms_t = x_t
            p_t = dataclasses.replace(p_t, mci=mci_t)
            h = window(p_t, hyper, use_kernel)
            return _tick(mode, p_t, h, st, norms_t, None, steps=steps,
                         outer=outer, use_kernel=use_kernel, shift=shift,
                         reset_mu=reset_mu, moment_dtype=moment_dtype,
                         sanitize=sanitize, telemetry_every=telemetry_every)

        return _day_impl(pb, (mci_s, norms_s), st0, tick_solve, warm_steps,
                         first_steps, first_shift, first_reset,
                         telemetry=telemetry_every > 0)

    if mesh is None:
        out = day(p, hyper, mci_stack, norms_stack, state0)
    else:
        axis = fleet_axes(mesh)
        st = EngineState(x=P(axis), lam_eq=P(axis), lam_in=P(axis), mu=P())
        out = _shard(day, mesh,
                     (_fleet_specs(p, axis), P(), P(),
                      _norm_specs(p, axis, stacked=True), st),
                     (P(None, axis), P(axis), P(axis), st))(
            p, hyper, mci_stack, norms_stack, state0)
    return _day_sums(p, out, mci_stack.shape[0], order, offsets)


_DAY = _jits(_day_lane, ("mode", "mesh", "warm_steps", "first_steps",
                         "first_shift", "first_reset", "outer", "use_kernel",
                         "moment_dtype", "sanitize", "telemetry_every",
                         "offsets"), donate=3, checked=True)


def _day_norm_stacks(problem: FleetProblem, mci_stack, policy, W_pad: int):
    """Per-tick norms for the sharded day scan, computed from the TRUE
    (unpadded) fleet exactly as the solo path computes them inside each
    tick: the tick-t window is the day rolled -t. Single-region fleets
    stack fleet-global scalars (replicated under the mesh); multi-region
    fleets stack the per-row vectors from `regional.cr1_norms`/
    `cr2_norms`, padded to the device-padded `W_pad` with inert fills so
    the tick axis leads and the row axis shards (`norm_specs(...,
    stacked=True)`)."""
    rolled = problem
    norms = []
    for t in range(mci_stack.shape[0]):
        if t:
            rolled = dataclasses.replace(
                rolled,
                usage=np.roll(np.asarray(rolled.usage), -1, axis=1),
                jobs=np.roll(np.asarray(rolled.jobs), -1, axis=1),
                upper=None if rolled.upper is None
                else np.roll(np.asarray(rolled.upper), -1, axis=1))
        p_t = dataclasses.replace(rolled, mci=mci_stack[t])
        norms.append(_mesh_inputs(policy.name, p_t, policy._hyper(p_t),
                                  W_pad)[1])
    return _stack(norms)


def solve_day(problem: FleetProblem, policy, mci_stack, *,
              ctx: SolveContext | None = None, cold_steps: int | None = None,
              warm_steps: int | None = None) -> DayResult:
    """Solve a whole rolling-horizon day as ONE donated-buffer XLA call.

    `mci_stack` is the (n_ticks, T) forecast-revision stack — row i is the
    MCI forecast the controller would see at tick i (e.g.
    `ForecastStream.forecast(t)` for consecutive t). Tick 0 solves with
    `cold_steps` (the policy default when None) from `ctx.warm` or a cold
    state; every later tick fuses window-roll + plan shift + mu-reset +
    a `warm_steps` re-solve (default `cold_steps // 4`) inside one
    `lax.scan`. Matches the per-tick `RollingHorizonSolver.step()` loop
    to <0.01 pp realized carbon while issuing a single dispatch.

    Supports CR1/CR2 — the policies whose day scan is built. CR3's
    market (a `while_loop` per tick) has no day scan yet and B1/B3 are
    closed-form per-tick evaluations; both keep the per-tick path. With
    `ctx.mesh` the whole day scan nests INSIDE the W-axis shard_map
    (per-tick norms ride in from the true fleet — replicated scalars for
    single-region, row-sharded `regional` vectors for multi-region), so
    a sharded day is still one dispatch under both 1-D and 2-D fleet
    meshes. Multi-region rows of `mci_stack` are (R, T) forecast
    stacks. Migration is not applied per tick — run the committed plan
    through `solve()` for migration credit.

    Debug/observability lanes (solo path only): `ctx.sanitize` routes
    the whole day through a checkify twin — a NaN/inf in ANY tick's
    gradient/iterate/multipliers raises `SanitizeError` naming the
    first failing check. `ctx.telemetry` returns one
    `repro.obs.ConvergenceTrace` per tick in
    `result.last.extras["telemetry"]` (captured inside the same single
    dispatch; incompatible with `use_kernel`/`mesh`).

    Returns `DayResult`; `result.last.state` warm-starts the next day
    (pass it via `ctx.warm` — the first tick then runs `warm_steps` with
    the usual shift/mu-reset instead of a cold solve).
    """
    ctx = ctx or SolveContext()
    policy = resolve_policy(policy)
    with span("dr.solve", meta={"policy": policy.name,
                                "lane": "day" if ctx.mesh is None
                                else "day_mesh"}):
        return _solve_day(problem, policy, mci_stack, ctx, cold_steps,
                          warm_steps)


def _solve_day(problem: FleetProblem, policy, mci_stack,
               ctx: SolveContext, cold_steps: int | None,
               warm_steps: int | None) -> DayResult:
    if ctx.sanitize:
        # The day scan's checkify twin runs on the solo lane; the same
        # CR1/CR2 + no-mesh/donate restrictions as solve() apply.
        _require_sanitizable(policy, ctx)
    _require_window_ok(None, ctx)
    if not isinstance(problem, FleetProblem):
        raise TypeError(
            f"solve_day() takes a FleetProblem; got "
            f"{type(problem).__name__}")
    problem = _single_region_view(problem)
    mci_stack = np.asarray(mci_stack, np.float32)
    if np.ndim(problem.mci) == 1 and mci_stack.ndim == 3 \
            and mci_stack.shape[1] == 1:
        mci_stack = mci_stack[:, 0]   # degenerate R=1 stack, canonicalized
    want = np.asarray(problem.mci).shape
    if mci_stack.ndim != len(want) + 1 or mci_stack.shape[1:] != want:
        raise ValueError(
            f"mci_stack must be (n_ticks,) + {want} (one forecast per "
            f"tick); got shape {mci_stack.shape}")
    n = mci_stack.shape[0]
    use_kernel = resolve_use_kernel(ctx.use_kernel)
    _require_telemetry_ok(ctx, use_kernel)
    tel = _tel_every(ctx)
    if tel and ctx.mesh is not None:
        raise NotImplementedError(
            "SolveContext(telemetry=...) on solve_day is a solo-lane "
            "feature for now — the sharded day scan has no telemetry "
            "plumbing; drop the mesh (or the telemetry) for this day")
    if cold_steps is None:
        cold_steps = ctx.resolved_steps(policy)
    if warm_steps is None:
        warm_steps = max(1, cold_steps // 4)
    cold = ctx.warm is None
    first_steps = cold_steps if cold else warm_steps
    first_shift, first_reset = (0, False) if cold else (ctx.shift or 1,
                                                        True)
    order, offsets = report_layout(problem)
    if not isinstance(policy, (CR1, CR2)):
        raise NotImplementedError(
            f"solve_day supports CR1/CR2 (pure scannable engine "
            f"backends); {policy.name} needs host-side control flow — "
            f"use the per-tick solve()/step() loop")
    mode = policy.name
    if ctx.mesh is None:
        pj, W, norms = _jit_view(problem), problem.W, None
    else:
        pj, W = pad_fleet(problem, fleet_device_count(ctx.mesh))
        norms = _day_norm_stacks(problem, mci_stack, policy, pj.W)
    state0 = _cold(mode, pj.W, pj.T) if cold else _pad_state(ctx.warm, pj.W)
    out = _run_lane(
        _DAY, ctx, pj, getattr(policy, _ENGINES[mode].knob),
        jnp.asarray(mci_stack), state0, norms,
        order=order, mode=mode, mesh=ctx.mesh, warm_steps=warm_steps,
        first_steps=first_steps, first_shift=first_shift,
        first_reset=first_reset, outer=policy.outer, use_kernel=use_kernel,
        moment_dtype=ctx.moment_dtype, telemetry_every=tel, offsets=offsets)
    if tel:
        # One ConvergenceTrace per tick: tick 0's trace is separate
        # (different step budget → different sample count), warm
        # ticks come back stacked on a leading (n-1) axis.
        traces = (ConvergenceTrace.from_aux(out[4]),)
        if out[5] is not None:
            traces += ConvergenceTrace.split(out[5])
    # (commitments, last plan, its penalties, state, [telemetry,] sums):
    # the commitments and the sums come back, the plan stays on device.
    committed, sums = _fetch(out[0], out[-1])
    committed = committed[:, :W]
    iters = (first_steps * policy.outer,) \
        + (warm_steps * policy.outer,) * (n - 1)
    # Reporting view: the final tick's forecast (the sums already cover
    # its rolled window).
    last = _report(dataclasses.replace(problem, mci=mci_stack[-1]),
                   OnDevice(out[1], W), iters=iters[-1], state=out[3],
                   extras={"telemetry": traces} if tel else None,
                   sums=sums, hour0=committed[-1])
    return DayResult(committed=committed, last=last, inner_steps=iters)


@_register
@dataclasses.dataclass(frozen=True)
class B3:
    """Prioritized Power Capping (paper §V-B, Dynamo): curtail RTS
    workloads only, lowest priority (= last RTS row) first, each up to
    `max_cut` depth — the fleet-array form of `baselines.b3_adjustments`
    with row order as the priority order. Closed form like `B1`."""

    depth: float = 0.3
    max_cut: float = 0.2

    name: ClassVar[str] = "b3"
    default_steps: ClassVar[int] = 0

    def solve(self, p: FleetProblem,
              ctx: SolveContext = SolveContext()) -> FleetSolveResult:
        usage = np.asarray(p.usage)
        D = np.zeros_like(usage)
        remaining = float(self.depth)
        rts_rows = [i for i in range(p.W) if not bool(p.is_batch[i])]
        for i in reversed(rts_rows):
            if remaining <= 0:
                break
            c = min(remaining, self.max_cut)
            L = (1.0 - c) * float(p.entitlement[i])
            D[i] = np.maximum(usage[i] - L, 0.0)
            remaining -= c
        pens = np.asarray(fleet_penalties(
            p, jnp.asarray(D), resolve_use_kernel(ctx.use_kernel)))
        return _report(p, D, pens, iters=0)
