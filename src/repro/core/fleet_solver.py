"""Fleet-scale DR data model and shared solver plumbing.

The paper solves 4 workloads × 48 h with SLSQP. A datacenter fleet has
thousands of workloads; SLSQP's dense QP subproblems scale as O((W·T)³) and
the per-workload python penalty loop doesn't jit. This module stacks every
workload's penalty model into arrays:

  RTS:    C_i = k_i Σ_t f(a_i; d/U)            (cubic polynomial)
  batch:  C_i = (k_i (β₀ + β₁ x₁ + β₂ x₂))⁺    (Table-IV features)

so the whole fleet evaluates as a handful of (W, T) tensor ops — vmapped,
jit-compiled, MXU-shaped (T padded to 128 lanes on TPU), with the Table-IV
features computed by the `dr_features` Pallas kernel on TPU (jnp fallback
elsewhere; see `repro.kernels.dispatch`).

Solving lives in `repro.core.api`: policies are first-class frozen
dataclasses (`CR1(lam=...)`, `CR2(cap_frac=...)`, `CR3(rho=...,
tax_frac=...)`, baseline wrappers `B1`/`B3`) and every solve goes through
one entry point —

    from repro.core.api import CR1, SolveContext, solve
    result = solve(problem, CR1(lam=1.45), ctx=SolveContext(mesh=...))

with `SolveContext` bundling the execution concerns (mesh, donated
buffers, the fused streaming tick, warm starts, kernel dispatch, step
budgets) and `sweep()` running whole policy grids as one vmapped XLA call.
This module keeps what the policies share:

  * `FleetProblem` — the stacked-workload instance, a registered JAX
    pytree (arrays are leaves; `day_hours` etc. are static), plus
    `from_problem`/`to_problem` conversion so the per-workload SLSQP
    stack (`repro.core.solver`) serves as a validation reference.
  * `fleet_penalties` — the vectorized Table-IV/RTS penalty evaluation
    with backend-aware kernel dispatch.
  * `FleetSolveResult` — the uniform result every policy returns.
    Policy-specific outputs ride `result.extras` (CR3 puts its clearing
    `"rho"`, `"balanced"` and `"fiscal_deficit"` there).
  * Device-sharding plumbing (100k-workload fleets): `pad_fleet` pads W
    to a multiple of the device count with *inert* workloads (box [0, 0],
    k=0, safe divisors), `_fleet_specs` builds the shard_map
    PartitionSpecs, `_pad_state`/`_enter_tick` carry warm `EngineState`s
    across padded/streaming re-solves. Reported results are sliced back
    to true rows, but `FleetSolveResult.state` keeps the padded shape so
    streaming re-solves chain without re-padding.

The historical per-policy entry points `solve_cr{1,2,3}_fleet` and
`solve_cr1_fleet_sweep` remain as deprecated shims that delegate to
`api.solve`/`api.sweep` (one `DeprecationWarning` per call); they will be
removed once nothing imports them.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import dfloat as _df
from repro.core import features as feat
from repro.core.engine import EngineState
from repro.core.penalty import PenaltyModel
from repro.obs.spans import span

Array = jax.Array

# Initial AL penalty weights per policy — the single source for the policy
# backends in `repro.core.api` and the streaming controller's per-tick μ
# reset. CR3's gentle wall is deliberate; see `api.CR3`.
CR1_MU0 = 10.0
CR2_MU0 = 10.0
CR3_MU0 = 0.01


@dataclasses.dataclass(frozen=True)
class RegionTopology:
    """Cross-region migration network for a multi-region fleet.

    All matrices are indexed [from_region, to_region]. `bandwidth` caps
    how much deferrable load (NP) can move over a link per hour — zero
    (including the diagonal, which is ignored) disables the link, so
    `bandwidth=0` everywhere reduces the fleet to independent per-region
    solves. `cost` is the carbon toll per unit moved (kgCO2/MWh-NP
    equivalent — network/overhead energy), subtracted from the migration
    margin. `ceiling` is an optional per-region power cap (R,) or (R, T)
    that bounds how much migrated load a region can absorb on top of its
    own; None means uncapped.

    Kept out of every jit trace (`_jit_view`/`pad_fleet` strip it):
    migration planning is a host-side post-stage on gathered region
    aggregates (`repro.core.migration`), not part of the sharded hot
    loop.
    """
    cost: np.ndarray                    # (R, R)
    bandwidth: np.ndarray               # (R, R)
    ceiling: np.ndarray | None = None   # (R,) or (R, T)
    labels: tuple[str, ...] | None = None

    @property
    def R(self) -> int:
        return np.asarray(self.cost).shape[0]

    def validate(self, R: int, T: int) -> None:
        cost = np.asarray(self.cost)
        bw = np.asarray(self.bandwidth)
        if cost.shape != (R, R) or bw.shape != (R, R):
            raise ValueError(
                f"RegionTopology cost/bandwidth must be ({R}, {R}); got "
                f"{cost.shape} / {bw.shape}")
        if self.ceiling is not None:
            ceil = np.asarray(self.ceiling)
            if ceil.shape not in ((R,), (R, T)):
                raise ValueError(
                    f"RegionTopology ceiling must be ({R},) or ({R}, {T}); "
                    f"got {ceil.shape}")
        if self.labels is not None and len(self.labels) != R:
            raise ValueError(
                f"RegionTopology labels must have {R} entries; got "
                f"{len(self.labels)}")


@dataclasses.dataclass(frozen=True)
class FleetProblem:
    """Stacked-workload DR instance (a JAX pytree; jit over it directly).

    Single-region fleets have `mci: (T,)` and `region is None`.
    Multi-region fleets stack per-region signals as `mci: (R, T)` and
    assign every workload a region via `region: (W,) int`; an optional
    `topology` adds the cross-region migration network. R=1 is the
    degenerate case and is canonicalized back to the single-region form
    at the `api.solve`/`sweep`/`solve_day` entry points, so it is
    bitwise-identical to a plain (T,) problem.
    """
    usage: np.ndarray          # (W, T)
    entitlement: np.ndarray    # (W,)
    k: np.ndarray              # (W,)
    rts_coeffs: np.ndarray     # (W, 3) a3,a2,a1 (zeros for batch)
    betas: np.ndarray          # (W, 3) β0,β1,β2 (zeros for RTS)
    x2_kind: np.ndarray        # (W,) 0=num_jobs_delayed, 1=waiting_sq
    jobs: np.ndarray           # (W, T)
    is_batch: np.ndarray       # (W,) bool
    mci: np.ndarray            # (T,) or (R, T) per-region
    day_hours: int = 24
    max_curtail_frac: float = 0.5
    names: tuple[str, ...] | None = None
    # Optional (W, T) operational cap on curtailment, intersected with the
    # entitlement/usage box — e.g. the dynamic-power range a job can
    # actually shed by throttling (FleetCoordinator realizability). Not a
    # penalty-model property, so `to_problem` drops it.
    upper: np.ndarray | None = None
    # Multi-region fields: per-workload region ids (W,) int in [0, R) and
    # the optional migration network. None for single-region fleets.
    region: np.ndarray | None = None
    topology: RegionTopology | None = None

    @property
    def W(self) -> int:
        return self.usage.shape[0]

    @property
    def T(self) -> int:
        return self.usage.shape[1]

    @property
    def R(self) -> int:
        """Number of regions (1 for single-region problems)."""
        mci = np.asarray(self.mci) if isinstance(self.mci, np.ndarray) \
            else self.mci
        return 1 if mci.ndim == 1 else mci.shape[0]

    @property
    def is_multiregion(self) -> bool:
        return np.ndim(self.mci) == 2

    @classmethod
    def from_problem(cls, p) -> "FleetProblem":
        """Stack a per-workload `DRProblem` into the fleet representation.

        The fleet path implements the default DRProblem subset: equality
        day-preservation, curtail-only RTS, and no datacenter capacity
        inequality (Eq. 10 — never active for the paper fleet's 1.2
        buffer; fleet-scale support is a ROADMAP item). Non-default
        `preservation`/`rts_boost` settings would silently change meaning
        here, so they are rejected."""
        if p.preservation != "equality" or p.rts_boost:
            raise ValueError(
                "FleetProblem supports preservation='equality' and "
                f"rts_boost=False only (got preservation={p.preservation!r},"
                f" rts_boost={p.rts_boost})")
        return from_models(p.models, p.mci, day_hours=p.day_hours,
                           max_curtail_frac=p.max_curtail_frac)

    def to_problem(self, **overrides):
        """Rebuild the per-workload `DRProblem` (SLSQP reference) view."""
        from repro.core.policies import DRProblem
        if self.is_multiregion:
            raise ValueError(
                "to_problem() needs a single-region fleet (mci (T,)); the "
                "per-workload SLSQP reference has no region concept")
        names = self.names or tuple(f"w{i}" for i in range(self.W))
        models = []
        for i in range(self.W):
            if bool(self.is_batch[i]):
                slo = float(self.x2_kind[i]) > 0.5
                models.append(PenaltyModel(
                    name=names[i],
                    kind="batch_slo" if slo else "batch_noslo",
                    usage=np.asarray(self.usage[i]),
                    entitlement=float(self.entitlement[i]),
                    k=float(self.k[i]),
                    params=tuple(float(b) for b in self.betas[i]),
                    jobs=np.asarray(self.jobs[i]),
                    feature_names=("waiting_time_power",
                                   "waiting_time_squared" if slo
                                   else "num_jobs_delayed")))
            else:
                models.append(PenaltyModel(
                    name=names[i], kind="realtime",
                    usage=np.asarray(self.usage[i]),
                    entitlement=float(self.entitlement[i]),
                    k=float(self.k[i]),
                    params=tuple(float(a) for a in self.rts_coeffs[i])))
        kw = dict(models=tuple(models), mci=np.asarray(self.mci),
                  max_curtail_frac=self.max_curtail_frac,
                  day_hours=self.day_hours)
        kw.update(overrides)
        return DRProblem(**kw)


jax.tree_util.register_dataclass(
    FleetProblem,
    data_fields=["usage", "entitlement", "k", "rts_coeffs", "betas",
                 "x2_kind", "jobs", "is_batch", "mci", "upper", "region",
                 "topology"],
    meta_fields=["day_hours", "max_curtail_frac", "names"])


def from_models(models: Sequence[PenaltyModel], mci: np.ndarray,
                day_hours: int = 24, max_curtail_frac: float = 0.5,
                ) -> FleetProblem:
    W = len(models)
    T = mci.shape[0]
    usage = np.stack([m.usage for m in models])
    ent = np.asarray([m.entitlement for m in models])
    k = np.asarray([m.k for m in models])
    rts = np.zeros((W, 3))
    betas = np.zeros((W, 3))
    x2k = np.zeros(W)
    jobs = np.ones((W, T))
    is_batch = np.zeros(W, bool)
    for i, m in enumerate(models):
        if m.kind == "realtime":
            rts[i] = m.params
        else:
            is_batch[i] = True
            betas[i] = m.params
            jobs[i] = m.jobs
            x2k[i] = 1.0 if m.feature_names[1] == "waiting_time_squared" \
                else 0.0
    return FleetProblem(usage=usage, entitlement=ent, k=k, rts_coeffs=rts,
                        betas=betas, x2_kind=x2k, jobs=jobs,
                        is_batch=is_batch, mci=mci, day_hours=day_hours,
                        max_curtail_frac=max_curtail_frac,
                        names=tuple(m.name for m in models))


def synthetic_fleet(num: int, hours: int = 48, seed: int = 0,
                    templates: dict[str, PenaltyModel] | None = None,
                    ) -> FleetProblem:
    """Clone the calibrated paper models into a fleet of `num` workloads
    with randomized scales/mix — the scaling benchmark's input."""
    from repro.core.carbon import caiso_2021
    from repro.core.fleetcache import cached_paper_fleet
    templates = templates or cached_paper_fleet(hours=hours)
    rng = np.random.default_rng(seed)
    names = list(templates)
    models = []
    for i in range(num):
        base = templates[names[i % len(names)]]
        scale = float(rng.uniform(0.2, 3.0))
        models.append(dataclasses.replace(
            base, name=f"{base.name}-{i}", usage=base.usage * scale,
            entitlement=base.entitlement * scale,
            jobs=None if base.jobs is None else base.jobs * scale))
    return from_models(models, caiso_2021(hours).mci)


# ---------------------------------------------------------------------------
# Multi-region construction and canonicalization
# ---------------------------------------------------------------------------
def regional_fleet(fleets: Sequence[FleetProblem], mcis: np.ndarray,
                   topology: RegionTopology | None = None) -> FleetProblem:
    """Concatenate R single-region fleets into one (region × workload)
    fleet.

    `fleets[r]` supplies region r's workloads (its own `mci` is ignored)
    and `mcis` is the (R, T) per-region signal stack, e.g. from
    `carbon.regional_traces`. Workloads are kept region-sorted, so a 2-D
    (REGION_AXIS, FLEET_AXIS) mesh lands each region's rows on one
    region slice.
    """
    mcis = np.asarray(mcis, float)
    R = len(fleets)
    if mcis.ndim != 2 or mcis.shape[0] != R:
        raise ValueError(
            f"mcis must be ({R}, T) — one trace per fleet; got {mcis.shape}")
    T = mcis.shape[1]
    if any(f.T != T for f in fleets):
        raise ValueError("every regional fleet must share the trace length")
    if any(f.is_multiregion for f in fleets):
        raise ValueError("regional_fleet composes single-region fleets")
    if topology is not None:
        topology.validate(R, T)

    def cat(field):
        parts = [getattr(f, field) for f in fleets]
        if any(a is None for a in parts):
            if all(a is None for a in parts):
                return None
            parts = [np.asarray(a, float) if a is not None
                     else _inf_upper(f.usage.shape)
                     for f, a in zip(fleets, parts)]
        return np.concatenate([np.asarray(a) for a in parts])

    names = None
    if all(f.names is not None for f in fleets):
        labels = topology.labels if topology is not None \
            and topology.labels is not None else tuple(range(R))
        names = tuple(f"{labels[r]}/{n}"
                      for r, f in enumerate(fleets) for n in f.names)
    region = np.concatenate(
        [np.full(f.W, r, np.int32) for r, f in enumerate(fleets)])
    return FleetProblem(
        usage=cat("usage"), entitlement=cat("entitlement"), k=cat("k"),
        rts_coeffs=cat("rts_coeffs"), betas=cat("betas"),
        x2_kind=cat("x2_kind"), jobs=cat("jobs"), is_batch=cat("is_batch"),
        mci=mcis, day_hours=fleets[0].day_hours,
        max_curtail_frac=fleets[0].max_curtail_frac, names=names,
        upper=cat("upper"), region=region, topology=topology)


def synthetic_regional_fleet(num: int, states: Sequence[str],
                             hours: int = 48, seed: int = 0,
                             year: int = 2050,
                             topology: RegionTopology | None = None,
                             utc_offsets=None) -> FleetProblem:
    """`synthetic_fleet` across R Cambium state mixes: ~num/R workloads
    per region, each region priced on its own `carbon.projection` trace
    (`utc_offsets` passes through to `carbon.regional_traces` — `"auto"`
    rolls each trace onto the coordinator's UTC clock). Default topology:
    uniform bandwidth at 5% of fleet entitlement with a small uniform
    migration toll."""
    from repro.core.carbon import regional_traces
    R = len(states)
    mcis, _ = regional_traces(states, year=year, hours=hours, seed=seed,
                              utc_offsets=utc_offsets)
    per = [num // R + (1 if r < num % R else 0) for r in range(R)]
    fleets = [synthetic_fleet(per[r], hours=hours, seed=seed + r)
              for r in range(R)]
    if topology is None:
        ent = float(sum(np.asarray(f.entitlement).sum() for f in fleets))
        bw = np.full((R, R), 0.05 * ent / max(R - 1, 1))
        np.fill_diagonal(bw, 0.0)
        topology = RegionTopology(
            cost=np.full((R, R), 2.0), bandwidth=bw, labels=tuple(states))
    return regional_fleet(fleets, mcis, topology=topology)


def _single_region_view(p: FleetProblem) -> FleetProblem:
    """Canonicalize the degenerate R=1 multi-region problem to the plain
    single-region form (mci (T,), no region/topology) so it takes the
    exact pre-refactor code path — bitwise-identical results. No-op for
    everything else."""
    if np.ndim(p.mci) == 2 and np.asarray(p.mci).shape[0] == 1:
        return dataclasses.replace(p, mci=np.asarray(p.mci)[0],
                                   region=None, topology=None)
    return p


# ---------------------------------------------------------------------------
# Vectorized penalties (backend-aware kernel dispatch)
# ---------------------------------------------------------------------------
def resolve_use_kernel(flag: bool | None) -> bool:
    """None = auto: Pallas kernel on TPU, jnp path elsewhere."""
    if flag is None:
        from repro.kernels.dispatch import on_tpu
        return on_tpu()
    # use_kernel rides static_argnames in every jitted lane, so `flag`
    # is always a concrete host bool here, never a tracer.
    # drlint: disable=jit-host-leak -- static jit argument, not traced
    return bool(flag)


def _features(d: Array, usage: Array, jobs: Array, use_kernel: bool) -> Array:
    """(W, 4): wait_jobs, wait_power, wait_sq, njobs — Table IV."""
    if use_kernel:
        from repro.kernels.dr_features.ops import dr_features
        return dr_features(d, usage, jobs)
    return jnp.stack([feat.waiting_time_jobs(d, usage, jobs),
                      feat.waiting_time_power(d),
                      feat.waiting_time_squared(d, usage, jobs),
                      feat.num_jobs_delayed(d, usage, jobs)], axis=1)


def fleet_penalties(p: FleetProblem, D: Array,
                    use_kernel: bool | None = None) -> Array:
    """(W,) calibrated penalties — fully vectorized."""
    use_kernel = resolve_use_kernel(use_kernel)
    usage = jnp.asarray(p.usage)
    delta = D / usage
    a3, a2, a1 = (jnp.asarray(p.rts_coeffs[:, i])[:, None] for i in range(3))
    f_rts = (a3 * delta**3 + a2 * delta**2 + a1 * delta).sum(axis=1)
    X = _features(D, usage, jnp.asarray(p.jobs), use_kernel)
    x1 = X[:, 1]
    x2 = jnp.where(jnp.asarray(p.x2_kind) > 0.5, X[:, 2], X[:, 3])
    b = jnp.asarray(p.betas)
    f_batch = jnp.maximum(b[:, 0] + b[:, 1] * x1 + b[:, 2] * x2, 0.0)
    raw = jnp.where(jnp.asarray(p.is_batch), f_batch, f_rts)
    return jnp.asarray(p.k) * raw


def cr2_cap(p: FleetProblem, cap_frac: float) -> np.ndarray:
    """The (W, T) curtailment max(usage − cap_frac·E, 0) that an equal
    power cap at cap_frac·E imposes, in float64 on the host: a difference
    of near-equal numbers, which float32 would round tens of ulps off."""
    L = cap_frac * np.asarray(p.entitlement)[:, None]
    return np.maximum(np.asarray(p.usage) - L, 0.0)


def cr2_reference_fleet(p: FleetProblem, cap_frac: float) -> np.ndarray:
    """C_i under a hypothetical equal power cap at cap_frac·E (vectorized
    version of policies.cr2_reference_losses) — CR2's fairness targets."""
    return np.asarray(fleet_penalties(p, jnp.asarray(cr2_cap(p, cap_frac))))


# ---------------------------------------------------------------------------
# Shared adapter plumbing: bounds, projection, padding, reporting
# ---------------------------------------------------------------------------
def _jit_view(p: FleetProblem) -> FleetProblem:
    """Strip reporting-only static metadata (`names`) before jit calls —
    names live in the pytree treedef, so leaving them in would recompile
    the policy backends for every same-shaped fleet with different job
    names. The migration `topology` is stripped too: it is host-side
    numpy consumed by the `repro.core.migration` post-stage, never by
    the jitted solvers."""
    return dataclasses.replace(p, names=None, topology=None)


#: Read-only +inf `upper` templates by shape — `pad_fleet` runs on every
#: streaming tick, and a 100k-row fleet's no-op cap is ~40 MB we should
#: not reallocate hourly.
_INF_UPPER: dict[tuple[int, int], np.ndarray] = {}


def _inf_upper(shape: tuple[int, int]) -> np.ndarray:
    out = _INF_UPPER.get(shape)
    if out is None:
        out = np.full(shape, np.inf)
        out.setflags(write=False)
        _INF_UPPER[shape] = out
    return out


#: Inert-row fill value per FleetProblem field — the single source for
#: `pad_fleet` AND the scenario-overlay padding in `repro.core.ensemble`
#: (stacked overlays must pad byte-identically or pad rows stop being
#: inert in sharded ensemble lanes). The values are load-bearing; see
#: `pad_fleet`'s docstring for why usage=0.01 specifically.
PAD_FILLS: dict[str, float] = {
    "usage": 0.01, "entitlement": 1.0, "k": 0.0, "rts_coeffs": 0.0,
    "betas": 0.0, "x2_kind": 0.0, "jobs": 1.0, "is_batch": False,
    "upper": 0.0, "region": 0,
}


def pad_fleet(p: FleetProblem, multiple: int) -> tuple[FleetProblem, int]:
    """Pad W up to a multiple of `multiple` with inert workloads.

    Pad rows get usage=0.01 NP, entitlement=1, k=0 and an operational cap
    (`upper`) of 0: their box is [0, 0] so the projection pins them at zero
    curtailment, their penalties and penalty gradients are exactly zero
    (k=0 with finite features), and every division the policies perform
    (by entitlement, by usage, by tau=0.02·E) stays finite. The tiny usage
    keeps CR3's smooth peak (tau·logsumexp(usage/tau) ≈ 0.09·E at D=0)
    well inside the pad allowance for any tax_frac ≲ 0.9, so pad allowance
    constraints stay feasible and their multipliers stay exactly zero —
    even across arbitrarily long chained warm re-solves. `upper` is
    materialized (+inf where the true fleet had none) so the padded pytree
    has a fixed structure. Returns (padded problem, true W); reports and
    fiscal sums must slice rows [:W_true].
    """
    pad = (-p.W) % multiple
    upper = np.asarray(p.upper, float) if p.upper is not None \
        else _inf_upper(p.usage.shape)
    if pad == 0:
        return dataclasses.replace(p, upper=upper, names=None,
                                   topology=None), p.W

    def rows(field, a=None):
        a = np.asarray(getattr(p, field) if a is None else a)
        return np.concatenate(
            [a, np.full((pad,) + a.shape[1:], PAD_FILLS[field],
                        dtype=a.dtype)])

    return dataclasses.replace(
        p, usage=rows("usage"), entitlement=rows("entitlement"),
        k=rows("k"), rts_coeffs=rows("rts_coeffs"), betas=rows("betas"),
        x2_kind=rows("x2_kind"), jobs=rows("jobs"),
        is_batch=rows("is_batch"), upper=rows("upper", upper),
        region=None if p.region is None else rows("region"),
        names=None, topology=None), p.W


def _pad_state(state: EngineState, W_pad: int) -> EngineState:
    """Zero-pad a warm start's per-workload leaves to the padded W (no-op
    when already padded — the streaming donation chain relies on that)."""
    W = state.x.shape[0]
    if W == W_pad:
        return state

    def pad(a):
        a = jnp.asarray(a)
        if a.ndim and a.shape[0] == W:
            return jnp.concatenate(
                [a, jnp.zeros((W_pad - W,) + a.shape[1:], a.dtype)])
        return a

    return EngineState(x=pad(state.x), lam_eq=pad(state.lam_eq),
                       lam_in=pad(state.lam_in), mu=state.mu)


def _fleet_specs(p: FleetProblem, axis) -> FleetProblem:
    """shard_map PartitionSpecs for a (padded) FleetProblem: every
    per-workload field sharded on its leading W axis, the MCI replicated.
    `axis` may be one mesh axis name or a tuple of them (2-D fleet mesh:
    W shards over both)."""
    row = P(axis)
    return dataclasses.replace(
        p, usage=row, entitlement=row, k=row, rts_coeffs=row, betas=row,
        x2_kind=row, jobs=row, is_batch=row, mci=P(), upper=row,
        region=None if p.region is None else row)


def _enter_tick(state: EngineState, shift: int, reset_mu: bool,
                mu0: float) -> EngineState:
    """Fused streaming-tick entry, traced inside the solve's own XLA call:
    roll the plan `shift` hours and restart the mu schedule at the policy's
    mu0 (multipliers still carry their constraint prices)."""
    if shift:
        state = state.shifted(shift)
    if reset_mu:
        state = dataclasses.replace(
            state, mu=jnp.full_like(state.mu, mu0))
    return state


class OnDevice(NamedTuple):
    """A plan the solve left on the device: the plan is `array[:rows]`
    (`array` may carry device pad rows)."""
    array: jax.Array
    rows: int


class _PulledOnRead:
    """`FleetSolveResult.D`: a host array, or an `OnDevice` plan that the
    first read pulls to the host (a `dr.solve.pull` span counting its
    `d2h_bytes` and one `plan_pulls`) and keeps."""

    def __get__(self, obj, owner=None):
        if obj is None:
            raise AttributeError("D")    # a dataclass field with no default
        D = obj.__dict__["_D"]
        if isinstance(D, OnDevice):
            with span("dr.solve.pull") as sp:
                host = np.asarray(D.array)
                sp.count("d2h_bytes", host.nbytes)
                sp.count("plan_pulls", 1)
            D = obj.__dict__["_D"] = host[:D.rows]
        return D

    def __set__(self, obj, value):
        obj.__dict__["_D"] = value


@dataclasses.dataclass(frozen=True)
class FleetSolveResult:
    """Uniform result of one fleet policy solve (any policy).

    `D` is the (W, T) plan as a host array. A solve may leave it on the
    device (`OnDevice`); it is then pulled on first read. `hour0` is the
    plan's hour 0, the hour a streaming tick commits, always on the host.
    """
    D: np.ndarray = _PulledOnRead()
    carbon_reduction_pct: float
    total_penalty_pct: float
    iters: int
    preservation_violation: float
    # Reusable engine carry for warm-started re-solves (rolling horizon).
    state: EngineState | None = None
    # Policy-specific outputs. CR3's fiscal clearing (Eq. 6) reports
    # "rho" (the clearing carbon price), "balanced" (did taxes cover
    # rebates) and "fiscal_deficit" (rebates − taxes when they didn't,
    # NP·kgCO2/MWh) here; CR1/CR2 leave it empty.
    extras: dict = dataclasses.field(default_factory=dict)
    hour0: np.ndarray | None = None
    # Carbon of the window's usage, the denominator of
    # carbon_reduction_pct (a migration credit's too).
    carbon_base: float | None = None

    @property
    def balanced(self) -> bool:
        """CR3 Eq.-6 clearing converged (always True for other policies)."""
        return bool(self.extras.get("balanced", True))

    @property
    def fiscal_deficit(self) -> float:
        """Rebates − taxes left when clearing failed (0.0 when balanced)."""
        return float(self.extras.get("fiscal_deficit", 0.0))


def _bounds(p: FleetProblem) -> tuple[Array, Array]:
    """Box bounds: curtail ≤ min(frac·E, U); batch may boost to U−d ≤ E.
    An operational `p.upper` cap (e.g. throttleable dynamic power)
    tightens the curtail side further."""
    usage = jnp.asarray(p.usage)
    E = jnp.asarray(p.entitlement)[:, None]
    hi = jnp.minimum(p.max_curtail_frac * E, usage)
    if p.upper is not None:
        hi = jnp.minimum(hi, jnp.asarray(p.upper))
    lo = jnp.where(jnp.asarray(p.is_batch)[:, None], -(E - usage), 0.0)
    return lo, hi


def _projection(p: FleetProblem, lo: Array, hi: Array):
    """Alternating clip + batch day-preservation projection (3 rounds)."""
    W, T = p.usage.shape
    n_days = max(1, T // p.day_hours)
    span = n_days * p.day_hours
    is_batch = jnp.asarray(p.is_batch)[:, None, None]

    def project(D: Array) -> Array:
        D = jnp.clip(D, lo, hi)
        for _ in range(3):
            Dd = D[:, :span].reshape(W, n_days, p.day_hours)
            mean = Dd.mean(axis=-1, keepdims=True)
            Dd = jnp.where(is_batch, Dd - mean, Dd)
            D = jnp.clip(jnp.concatenate(
                [Dd.reshape(W, span), D[:, span:]], axis=1), lo, hi)
        return D

    return project


class ReportSums(NamedTuple):
    """A plan's report as the device sums it (`report_sums`), each a
    `dfloat` pair; `report_numbers` finishes it in float64."""
    plan: tuple      #: (R, T): Σ of the plan over each region's rows, by hour
    usage: tuple     #: (R, T): the same of the window's usage
    penalty: tuple   #: (): Σ of the penalties
    day_max: tuple   #: (): largest |Σ over a day| of a batch row's plan


def report_layout(p: FleetProblem) -> tuple:
    """`(order, offsets)` of `p`'s rows for `report_sums`: grouped by
    region (`regional.region_layout`), or one run of all W rows."""
    if not p.is_multiregion:
        return None, (0, p.W)
    from repro.core.regional import region_layout
    return region_layout(p.region, p.R)


def report_sums(D, pens, usage, is_batch, order, offsets: tuple,
                day_hours: int) -> ReportSums:
    """The sums a solve's report reads, traced, in double-float
    (`dfloat`: float64 accuracy from float32 operations): the hour
    totals of the plan `D` and of `usage` over each region's rows
    (`regional.region_sums` over `order`/`offsets`, see `report_layout`),
    the penalties' total and the largest |day total| over batch rows.
    `D`, `pens` and `usage` are arrays or pairs; rows past `offsets[-1]`
    (device pad rows) count nowhere.

    Each sum is one pass of the compiler's reduction (`dfloat.reduce_sum`),
    within n·2^-48 of Σ|x| for n terms. The usage is
    the float32 copy the solve ran on, each entry within 2^-24 of the
    fleet's float64 usage, so the carbon base can stand up to 6e-8 (in
    practice ~1e-10, the roundings being independent) from a float64 sum
    of the float64 usage."""
    from repro.core.regional import region_sums
    W = offsets[-1]
    D, usage = _df.pair(D), _df.pair(usage)
    T = D[0].shape[1]
    n_days = max(1, T // day_hours)
    hours = n_days * day_hours
    days = _df.reduce_sum(tuple(
        a[:W, :hours].reshape(W, n_days, day_hours) for a in D), axis=2)
    isb = jnp.asarray(is_batch)[:W, None]
    hi = jnp.where(isb, jnp.abs(days[0]), 0.0)
    lo = jnp.where(isb, jnp.where(days[0] < 0, -days[1], days[1]), 0.0)
    top = hi.max()
    return ReportSums(
        plan=region_sums(D, order, offsets, total=_df.reduce_sum),
        usage=region_sums(usage, order, offsets, total=_df.reduce_sum),
        penalty=_df.reduce_sum(tuple(a[:W] for a in _df.pair(pens)), 0),
        day_max=(top, jnp.where(hi == top, lo, -jnp.inf).max()))


@functools.partial(jax.jit, static_argnames=("offsets", "day_hours",
                                             "lanes"))
def _held_sums(D, pens, usage, is_batch, order, *, offsets: tuple,
               day_hours: int, lanes: bool = False) -> ReportSums:
    """`report_sums` of arrays a caller holds, in one call; with `lanes`
    over a leading lane axis of `D`, `pens` and `usage`."""
    def one(d, pn, u):
        return report_sums(d, pn, u, is_batch, order, offsets, day_hours)
    return jax.vmap(one)(D, pens, usage) if lanes else one(D, pens, usage)


def held_report_sums(p: FleetProblem, D, pens, *, usage=None,
                     lanes: bool = False) -> ReportSums:
    """`report_sums` of a plan of `p` that the caller holds, pulled to
    the host. A host plan and penalties go up as float32 pairs
    (`dfloat.split`: a float64 plan is summed exactly), device arrays as
    they are; the usage goes up in float32, as every solve holds it, so
    a plan reports the same numbers from any lane. With `lanes`, `D`,
    `pens` and `usage` (default `p.usage`) carry a leading lane axis."""
    def held(a):
        a = a.array if isinstance(a, OnDevice) else a
        return a if isinstance(a, jax.Array) else _df.split(a)

    order, offsets = report_layout(p)
    usage = p.usage if usage is None else usage
    return jax.device_get(_held_sums(
        held(D), held(pens), np.asarray(usage, np.float32),
        np.asarray(p.is_batch), order, offsets=offsets,
        day_hours=p.day_hours, lanes=lanes))


def report_numbers(mci: np.ndarray, entitlement_total, sums: ReportSums):
    """(carbon_reduction_pct, total_penalty_pct, preservation_violation,
    carbon_base) in float64 from pulled `sums`: carbon is
    Σ_{r,t} mci[r,t]·plan[r,t], i.e. Σ_w ⟨D_w, mci_region(w)⟩ grouped by
    region, over `carbon_base`, the same sum of the usage. `mci` is
    (R, T) float64; a leading lane axis on `mci`, `entitlement_total`
    and `sums` rides along."""
    car = (mci * _df.value(sums.plan)).sum(axis=(-2, -1))
    base = (mci * _df.value(sums.usage)).sum(axis=(-2, -1))
    return (100 * car / base,
            100 * _df.value(sums.penalty) / entitlement_total,
            _df.value(sums.day_max), base)


def _report(p: FleetProblem, D, pens=None, *, iters: int,
            state: EngineState | None = None, extras: dict | None = None,
            sums: ReportSums | None = None,
            hour0: np.ndarray | None = None) -> FleetSolveResult:
    """The result of a solve of `p` (true rows) from its plan `D` (a host
    array, or `OnDevice`) and its `sums` (`report_sums`, pulled); without
    `sums` they are computed from `D` and `pens` by one jitted call
    (`held_report_sums`). The host finishes them in float64 under the
    `dr.solve.report` span (`report_numbers`); `hour0` defaults to a
    copy of the host plan's hour 0 (a copy, so that whoever keeps the
    hour does not keep the plan)."""
    with span("dr.solve.report"):
        if sums is None:
            sums = held_report_sums(p, D, pens)
        car, pen, viol, base = report_numbers(
            np.asarray(p.mci, float).reshape(-1, p.T),
            np.asarray(p.entitlement, float).sum(), sums)
        if hour0 is None:
            hour0 = np.array(D[:, 0])
        return FleetSolveResult(
            D=D, carbon_reduction_pct=float(car),
            total_penalty_pct=float(pen), iters=iters,
            preservation_violation=float(viol), state=state,
            extras=extras or {}, hour0=hour0, carbon_base=float(base))


# ---------------------------------------------------------------------------
# Deprecated per-policy entry points (thin shims over repro.core.api)
# ---------------------------------------------------------------------------
def _warn_deprecated(old: str, new: str) -> None:
    warnings.warn(
        f"{old} is deprecated; use {new} from repro.core.api",
        DeprecationWarning, stacklevel=3)


def solve_cr1_fleet(p: FleetProblem, lam: float = 1.45, steps: int = 600,
                    use_kernel: bool | None = None,
                    warm: EngineState | None = None, *,
                    mesh=None, donate: bool = False, shift: int = 0,
                    reset_mu: bool = False) -> FleetSolveResult:
    """Deprecated: `api.solve(p, CR1(lam=...), ctx=SolveContext(...))`."""
    from repro.core.api import CR1, SolveContext, solve
    _warn_deprecated("solve_cr1_fleet",
                     "solve(p, CR1(lam=...), ctx=SolveContext(...))")
    return solve(p, CR1(lam=lam), ctx=SolveContext(
        mesh=mesh, donate=donate, shift=shift, reset_mu=reset_mu,
        warm=warm, use_kernel=use_kernel, steps=steps))


def solve_cr1_fleet_sweep(p: FleetProblem, lams: Sequence[float],
                          steps: int = 600, use_kernel: bool | None = None,
                          ) -> list[FleetSolveResult]:
    """Deprecated: `api.sweep(p, [CR1(lam=l) for l in lams], ctx=...)`."""
    from repro.core.api import CR1, SolveContext, sweep
    _warn_deprecated("solve_cr1_fleet_sweep",
                     "sweep(p, [CR1(lam=l) for l in lams], ctx=...)")
    return sweep(p, [CR1(lam=float(lam)) for lam in lams],
                 ctx=SolveContext(steps=steps, use_kernel=use_kernel))


def solve_cr2_fleet(p: FleetProblem, cap_frac: float = 0.78,
                    steps: int = 400, outer: int = 6,
                    use_kernel: bool | None = None,
                    warm: EngineState | None = None, *,
                    mesh=None, donate: bool = False, shift: int = 0,
                    reset_mu: bool = False) -> FleetSolveResult:
    """Deprecated: `api.solve(p, CR2(cap_frac=..., outer=...), ctx=...)`."""
    from repro.core.api import CR2, SolveContext, solve
    _warn_deprecated("solve_cr2_fleet",
                     "solve(p, CR2(cap_frac=...), ctx=SolveContext(...))")
    return solve(p, CR2(cap_frac=cap_frac, outer=outer), ctx=SolveContext(
        mesh=mesh, donate=donate, shift=shift, reset_mu=reset_mu,
        warm=warm, use_kernel=use_kernel, steps=steps))


def solve_cr3_fleet(p: FleetProblem, rho: float = 0.02,
                    tax_frac: float = 0.2, steps: int = 600, outer: int = 3,
                    clearing_iters: int = 8,
                    use_kernel: bool | None = None,
                    warm: EngineState | None = None, *,
                    mesh=None, donate: bool = False, shift: int = 0,
                    reset_mu: bool = False,
                    ) -> tuple[FleetSolveResult, float]:
    """Deprecated: `api.solve(p, CR3(rho=..., tax_frac=...), ctx=...)`.

    The unified API returns a single `FleetSolveResult`; the clearing ρ
    this shim's tuple carried lives in `result.extras["rho"]`."""
    from repro.core.api import CR3, SolveContext, solve
    _warn_deprecated(
        "solve_cr3_fleet",
        "solve(p, CR3(rho=..., tax_frac=...), ctx=SolveContext(...)) "
        "(clearing rho is result.extras['rho'])")
    result = solve(p, CR3(rho=rho, tax_frac=tax_frac, outer=outer,
                          clearing_iters=clearing_iters),
                   ctx=SolveContext(mesh=mesh, donate=donate, shift=shift,
                                    reset_mu=reset_mu, warm=warm,
                                    use_kernel=use_kernel, steps=steps))
    return result, result.extras["rho"]
