"""Rolling-horizon streaming DR: re-solve as MCI forecasts revise.

The paper plans against a *static* day-ahead marginal-carbon-intensity
trace. A deployed Carbon Responder runs online: every hour the forecast
provider re-issues the day-ahead horizon (WattTime-style revisions), the
coordinator re-solves, commits only the first hour of the new plan, and
the window slides forward. This module is that control loop:

  * `ForecastStream` (`repro.core.carbon`) supplies the revised horizons —
    a persistence + lead-time-noise revision model, or replayed snapshots.
  * `RollingHorizonSolver` holds a `FleetProblem` template plus a
    `DRPolicy` (`repro.core.api`) and, per tick:
      1. slides the usage/jobs window one hour and swaps in the fresh
         `(T,)` forecast — for CR1, CR2 and CR3 off a mesh inside the
         solve's own program, from a fleet kept on the device
         (`SolveContext.window`/`.fleet`), so a tick sends only its
         forecast and hour offset,
      2. warm-starts `api.solve(problem, policy, ctx=...)` from the
         previous tick's `EngineState`, shifted one hour along time
         (`EngineState.shifted`) — multipliers carry over as-is since
         they price per-workload constraints, not hours,
      3. commits hour 0 of the new plan and logs forecast vs realized
         carbon for the committed hour.

Because `EngineState` is a pure-array pytree and every tick's problem has
identical shapes, all warm re-solves reuse ONE jitted trace (per policy):
the hot path is a single XLA call per tick — `SolveContext.shift`/
`reset_mu` fold the one-hour state roll and the per-tick mu restart into
that same call, and `donate=True` additionally donates the previous
tick's `EngineState` buffers so XLA re-solves in place
(`jax.jit(donate_argnums)`). The warm start lets each tick run with a
fraction of the cold solve's inner Adam steps
(`benchmarks.perf_micro.streaming_resolve` measures the latency and
solution gap).

Fleet scale: pass `mesh=` (see `repro.launch.mesh.make_fleet_mesh`) to run
every tick's re-solve sharded over the mesh's fleet axis. The engine state
then carries the device-padded workload count between ticks (no per-tick
re-padding), and the donated tick reuses the per-device buffers in place.

Receding-horizon caveat: batch day-preservation is enforced over the
sliding window's 24 h blocks each re-solve (the standard receding-horizon
relaxation); only committed hours are binding, so small per-window
residuals wash out as the window slides.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Sequence

import numpy as np

from repro.core.api import (DRPolicy, Resident, SolveContext,
                            configured_policy, solve)
from repro.core.carbon import ForecastStream
from repro.core.engine import EngineState
from repro.core.fleet_solver import (FleetProblem, FleetSolveResult,
                                     _single_region_view)
from repro.core.regional import region_totals
from repro.obs.events import EventWriter, TelemetryEvent, TickEvent
from repro.obs.spans import SpanScope, span
from repro.obs.telemetry import TelemetryConfig


def _rel_revision(prev: np.ndarray | None, cur: np.ndarray) -> float:
    """Relative forecast-revision magnitude between consecutive horizons:
    `‖cur[:-1] − prev[1:]‖₂ / ‖prev[1:]‖₂` over the re-forecast hours
    both horizons cover (0.0 for the first horizon seen)."""
    if prev is None:
        return 0.0
    tail = prev[..., 1:]
    return float(np.linalg.norm((cur[..., :-1] - tail).ravel())
                 / max(np.linalg.norm(tail.ravel()), 1e-12))


@dataclasses.dataclass(frozen=True)
class TickResult:
    """One committed hour of online operation.

    `plan` (the full-horizon solve: D matrix, engine state, ...) is only
    retained on the *latest* tick — older history entries drop it so a
    long-lived controller holds O(W) per tick, not O(W·T). Its D stays
    on the device until `plan.D` is read; a tick pulls hour 0 only."""
    tick: int
    committed: np.ndarray        # (W,) NP adjustments enforced this hour
    forecast_mci: float | np.ndarray   # hour-0 forecast ((R,) multi-region)
    realized_mci: float | np.ndarray   # actual MCI once the hour elapsed
    inner_steps: int             # engine iterations spent on this re-solve
    plan: FleetSolveResult | None
    committed_by_region: np.ndarray | None = None  # (R,) multi-region only

    @property
    def forecast_carbon(self) -> float:
        """kg CO2 the plan *expected* to eliminate this hour."""
        if np.ndim(self.forecast_mci):
            return float((self.committed_by_region
                          * np.asarray(self.forecast_mci)).sum())
        return float(self.committed.sum() * self.forecast_mci)

    @property
    def realized_carbon(self) -> float:
        """kg CO2 actually eliminated this hour."""
        if np.ndim(self.realized_mci):
            return float((self.committed_by_region
                          * np.asarray(self.realized_mci)).sum())
        return float(self.committed.sum() * self.realized_mci)


@dataclasses.dataclass(frozen=True)
class StreamingReport:
    """Aggregate of a rolling-horizon run."""
    ticks: tuple[TickResult, ...]
    committed: np.ndarray        # (W, n_ticks)
    realized_carbon: float       # kg CO2 eliminated, priced at actual MCI
    forecast_carbon: float       # same hours priced at solve-time forecasts
    realized_baseline: float     # no-DR carbon of the committed hours
    total_inner_steps: int

    @property
    def realized_reduction_pct(self) -> float:
        return 100.0 * self.realized_carbon / max(self.realized_baseline,
                                                  1e-12)

    @property
    def forecast_error_pct(self) -> float:
        """|forecast − realized| carbon for committed hours, % of realized."""
        return 100.0 * abs(self.forecast_carbon - self.realized_carbon) \
            / max(abs(self.realized_carbon), 1e-12)


class RollingHorizonSolver:
    """Online DR controller: warm-started re-solves over a sliding window.

    Args:
      problem: fleet template; `usage`/`jobs` are treated as periodic
        traces that slide with the window (`np.roll` along time; in the
        solve's program where the lane can, see `_window`). A
        multi-region problem (`problem.is_multiregion`) is supported:
        pass one `ForecastStream` per region (see `stream`).
      stream: revised-forecast source; `stream.horizon` must equal
        `problem.T`. For a multi-region problem pass a sequence of
        `problem.R` streams (one per region, e.g. from
        `ForecastStream.regional`); each tick then installs the
        stacked `(R, T)` forecast. The per-tick migration post-stage
        is *not* applied inside the loop (the committed hours are the
        streaming deliverable; run `fleet_migration` on the committed
        matrix offline to add the spatial lever).
      policy: a `DRPolicy` object (`CR1(lam=...)`, `CR2(...)`,
        `CR3(...)`, ...) or a `POLICY_REGISTRY` name. Unknown names raise
        `ValueError` (naming the registered choices) here at
        construction, not at the first `step()`.
      legacy policy knobs: `lam` (CR1), `cap_frac` (CR2),
        `rho`/`tax_frac` (CR3) and `outer` configure the policy object
        when `policy` is given by name; they are ignored when a policy
        object is passed.
      cold_steps: inner Adam steps for the tick-0 cold solve.
      warm_steps: inner steps for warm-started re-solves — the streaming
        speedup is `cold_steps / warm_steps` per multiplier round.
      adaptive_warm: scale each warm tick's budget by the forecast
        revision magnitude instead of spending `warm_steps` flat. The
        tick's relative revision `‖mci_t[:-1] − mci_{t−1}[1:]‖₂ /
        ‖mci_{t−1}[1:]‖₂` (the re-forecast hours both horizons cover) is
        mapped linearly onto `[warm_steps_min, warm_steps]` (quantized
        to 4 levels — the budget is a static jit argument, so this
        bounds the trace cache), saturating at `revision_ref`: a quiet
        tick (the forecast barely moved, the shifted warm start is
        already near-optimal) re-solves with `warm_steps_min` inner
        steps, a heavily revised tick gets the full warm budget.
      warm_steps_min: floor for adaptive budgets (default
        `warm_steps // 4`).
      revision_ref: relative revision magnitude that earns the full
        `warm_steps` (default 0.05 — about the day-ahead error of the
        default `ForecastStream` sigma).
      mesh: optional device mesh — every tick's re-solve runs sharded over
        its fleet axis (workloads padded to the device count once; the
        engine state stays padded between ticks).
      donate: donate each tick's incoming `EngineState` to the re-solve
        (in-place buffers, one XLA call per tick). Prior ticks'
        `plan.state` objects become invalid once the next tick runs, so
        leave False when capturing states from `on_tick` callbacks.
      guard_recompiles: enforce the one-trace claim at runtime. The
        first solve of each static configuration — a (steps, shift,
        reset_mu) tick combo, or a day-scan shape — may compile; every
        later solve of the same configuration runs inside
        `repro.analysis.recompile_guard(0)` and raises
        `RecompileError` if the jit cache missed (a drifting static
        argument, shape, or dtype silently turning "one trace per
        tick" into "a compile per tick"). Debug/CI knob; off by
        default because the guard swaps jax-internal counters in and
        out around every solve.
      events: JSONL tick ledger — a path or an open
        `repro.obs.EventWriter`. Every `step()`/`run_scanned()` tick
        appends a typed `TickEvent` (forecast-revision magnitude, warm
        budget spent, tick latency, per-region committed/realized
        carbon, migration credit, recompile + dispatch counts, the self
        time of each phase span and the bytes uploaded and pulled —
        read from the tick's `dr.tick` span), and any
        in-solve convergence samples append as `TelemetryEvent`s. All
        emission is host-side AFTER the solve returns, so the
        one-dispatch contracts (warm tick, scanned day) are untouched;
        render with `python -m repro.obs.report <path>`.
      telemetry: `repro.obs.TelemetryConfig` — capture in-solve
        convergence traces inside each tick's jitted solve (CR1/CR2,
        no fused kernel; see `SolveContext.telemetry`). Pairs with
        `events` to land the samples in the ledger; without `events`
        the trace is still on `tick.plan.extras["telemetry"]`.

    CR3 note: the policy object's `rho` is the *configured* price, so
    every window re-clears from it — clearing only ever lowers ρ, and
    carrying a lowered price forward would ratchet the fleet onto a
    permanently depressed carbon price after one transient tick.
    `last_rho` exposes the most recent cleared price
    (`plan.extras["rho"]`).
    """

    def __init__(self, problem: FleetProblem,
                 stream: ForecastStream | Sequence[ForecastStream], *,
                 policy: str | DRPolicy = "cr1", lam: float = 1.45,
                 cap_frac: float = 0.78, rho: float = 0.02,
                 tax_frac: float = 0.2, cold_steps: int = 600,
                 warm_steps: int = 150, outer: int = 4,
                 use_kernel: bool | None = None,
                 mesh=None, donate: bool = False,
                 adaptive_warm: bool = False,
                 warm_steps_min: int | None = None,
                 revision_ref: float = 0.05,
                 guard_recompiles: bool = False,
                 events: EventWriter | str | None = None,
                 telemetry: TelemetryConfig | None = None):
        streams = (tuple(stream) if isinstance(stream, (list, tuple))
                   else (stream,))
        # Degenerate R=1 regional problems canonicalize up front so the
        # whole streaming path (accounting included) is bitwise the
        # single-region engine, matching `api.solve`'s contract.
        problem = _single_region_view(problem)
        want = problem.R if problem.is_multiregion else 1
        if len(streams) != want:
            raise ValueError(
                f"need {want} forecast stream(s) for this problem "
                f"(R={problem.R}), got {len(streams)}")
        for s in streams:
            if s.horizon != problem.T:
                raise ValueError(
                    f"stream horizon {s.horizon} != problem.T {problem.T}")
        self.problem = problem
        self.streams = streams
        self.stream = streams[0]
        # Registry names become policy objects configured with the legacy
        # knobs; unknown names fail HERE with the registered choices (an
        # opaque mid-run failure at the first step() otherwise).
        self.policy = configured_policy(policy, lam=lam, cap_frac=cap_frac,
                                        rho=rho, tax_frac=tax_frac,
                                        outer=outer)
        self.last_rho = getattr(self.policy, "rho", None)
        self.cold_steps = cold_steps
        self.warm_steps = warm_steps
        self.adaptive_warm = adaptive_warm
        self.warm_steps_min = max(1, warm_steps // 4) \
            if warm_steps_min is None else warm_steps_min
        if not 0 < self.warm_steps_min <= warm_steps:
            raise ValueError(
                f"warm_steps_min must be in (0, warm_steps={warm_steps}]; "
                f"got {self.warm_steps_min}")
        if revision_ref <= 0:
            raise ValueError(f"revision_ref must be > 0, got {revision_ref}")
        self.revision_ref = revision_ref
        self.use_kernel = use_kernel
        self.mesh = mesh
        self.donate = donate
        self.guard_recompiles = guard_recompiles
        if events is None or isinstance(events, EventWriter):
            self.events = events
        else:
            self.events = EventWriter(
                events, tags={"policy": self.policy.name,
                              "cold_steps": cold_steps,
                              "warm_steps": warm_steps})
        self.telemetry = telemetry
        # A lane that slides the window in its own program keeps the
        # fleet on the device (sent once, by the first tick) and takes
        # the tick's hour offset; the others get the window slid here.
        self._resident = Resident(problem) if (
            mesh is None and getattr(self.policy, "slides", False)) else None
        self._seen_traces: set[tuple] = set()
        self._state: EngineState | None = None
        self._prev_forecast: np.ndarray | None = None
        self._tick = 0
        self._history: list[TickResult] = []

    # -- per-tick plumbing --------------------------------------------------
    @property
    def _n_ticks(self) -> int:
        return min(s.n_ticks for s in self.streams)

    def _forecast(self, tick: int) -> np.ndarray:
        """This tick's revised horizon: `(T,)`, or `(R, T)` stacked over
        the per-region streams for a multi-region problem."""
        if not self.problem.is_multiregion:
            return self.streams[0].forecast(tick)
        return np.stack([s.forecast(tick) for s in self.streams])

    def _realized(self, tick: int) -> float | np.ndarray:
        if not self.problem.is_multiregion:
            return self.streams[0].realized(tick)
        return np.array([s.realized(tick) for s in self.streams])

    def _by_region(self, committed: np.ndarray) -> np.ndarray | None:
        if not self.problem.is_multiregion:
            return None
        return region_totals(self.problem.region, committed,
                             self.problem.R)

    def _window_problem(self, tick: int, mci: np.ndarray) -> FleetProblem:
        """Slide usage/jobs (and any operational cap) to hours
        [tick, tick+T) on the host and install `mci`. The migration
        topology is stripped: only hour 0 of each plan is committed, so
        the per-tick spatial post-stage would price hours that never
        run."""
        p = self.problem
        return dataclasses.replace(
            p, mci=np.asarray(mci), topology=None,
            usage=np.roll(p.usage, -tick, axis=1),
            jobs=np.roll(p.jobs, -tick, axis=1),
            upper=None if p.upper is None
            else np.roll(p.upper, -tick, axis=1))

    def _window(self, tick: int,
                mci: np.ndarray) -> tuple[FleetProblem, int | None]:
        """What tick `tick` solves: (problem, hour offset). A resident
        lane gets the fleet as it is with `mci` installed and the offset
        `tick mod T`, which its program applies (`SolveContext.window`);
        any other lane the window slid here (`_window_problem`) and
        None."""
        if self._resident is None:
            return self._window_problem(tick, mci), None
        return (dataclasses.replace(self.problem, mci=np.asarray(mci),
                                    topology=None),
                tick % self.problem.T)

    def _solve(self, window: tuple[FleetProblem, int | None],
               warm: EngineState | None, steps: int, shift: int,
               reset_mu: bool) -> FleetSolveResult:
        p, offset = window
        ctx = SolveContext(mesh=self.mesh, donate=self.donate, shift=shift,
                           window=offset, reset_mu=reset_mu, warm=warm,
                           use_kernel=self.use_kernel, steps=steps,
                           telemetry=self.telemetry,
                           fleet=None if offset is None else self._resident)
        plan = solve(p, self.policy, ctx=ctx)
        if "rho" in plan.extras:
            self.last_rho = plan.extras["rho"]
        return plan

    def _traceguard(self, key: tuple):
        """`recompile_guard(0)` for re-solves of an already-compiled
        static configuration (`guard_recompiles=True`); the first solve
        of each `key` — and everything when the knob is off — runs
        unguarded."""
        if not self.guard_recompiles or key not in self._seen_traces:
            self._seen_traces.add(key)
            return contextlib.nullcontext()
        from repro.analysis.recompile import recompile_guard
        return recompile_guard(0, label=f"tick {self._tick} {key[0]}")

    def _warm_budget(self, rel: float) -> int:
        """Inner steps for this warm tick: `warm_steps` flat, or scaled by
        the forecast revision magnitude `rel` under `adaptive_warm` (the
        hours both horizons forecast — hour k of this tick vs hour k+1
        of the previous one; see `_rel_revision`)."""
        if not self.adaptive_warm or self._prev_forecast is None:
            return self.warm_steps
        frac = min(1.0, rel / self.revision_ref)
        # Quantize to 4 budget levels: the step count is a static jit
        # argument, so a continuum of budgets would compile a fresh trace
        # per tick; 4 levels bound the cache at 4 warm traces.
        frac = round(3 * frac) / 3
        return int(round(self.warm_steps_min
                         + (self.warm_steps - self.warm_steps_min) * frac))

    # -- tick ledger --------------------------------------------------------
    def _measure(self):
        """Compile counters (pure measurement) while the ledger is on —
        attributes jit traces to ticks. Nestable inside `_traceguard`'s
        failing-mode guard (hook swap is save/restore)."""
        if self.events is None:
            return contextlib.nullcontext(None)
        from repro.analysis.recompile import recompile_guard
        return recompile_guard(None, label="tick ledger")

    def _emit_tick(self, out: TickResult, *, revision: float,
                   scope: SpanScope | None, recompiles: int, cold: bool,
                   objective_proxy: float | None) -> None:
        """Append one `TickEvent` (host-side, after the solve returned —
        never inside the dispatch). `scope` is the tick's open `dr.tick`
        span, which gives the latency so far, the phases closed under
        it, and the dispatches and bytes they counted; None for a tick
        that rode an earlier tick's dispatch."""
        if self.events is None:
            return
        if out.committed_by_region is not None:
            per = np.asarray(out.committed_by_region, float)
            committed = (per * np.asarray(out.forecast_mci,
                                          float)).tolist()
            realized = (per * np.asarray(out.realized_mci, float)).tolist()
        else:
            tot = float(out.committed.sum())
            committed = [tot * float(out.forecast_mci)]
            realized = [tot * float(out.realized_mci)]
        plan = out.plan
        credit = 0.0
        if plan is not None and "migration" in plan.extras:
            credit = float(plan.extras["migration"].net_saved)
        if scope is None:
            latency_s, phases, counts = 0.0, None, {}
        else:
            latency_s, phases = scope.elapsed_s, scope.phases_ms
            counts = scope.totals
        self.events.write(TickEvent(
            tick=out.tick, revision=float(revision),
            warm_steps=int(out.inner_steps), cold=bool(cold),
            objective_proxy=objective_proxy, latency_s=float(latency_s),
            committed_carbon=committed, realized_carbon=realized,
            migration_credit=credit, recompiles=int(recompiles),
            dispatches=int(counts.get("dispatches", 0)), phases_ms=phases,
            h2d_bytes=int(counts.get("h2d_bytes", 0)),
            d2h_bytes=int(counts.get("d2h_bytes", 0))))
        if plan is not None and self.telemetry is not None:
            trace = plan.extras.get("telemetry")
            if trace is not None and not isinstance(trace, tuple):
                self._emit_trace(out.tick, trace)

    def _emit_trace(self, tick: int, trace) -> None:
        """Append one solve's convergence samples as `TelemetryEvent`s."""
        if self.events is None or trace is None:
            return
        for s in trace.samples():
            self.events.write(TelemetryEvent(tick=tick, **s))

    def step(self) -> TickResult:
        """Ingest the next forecast revision, re-solve, commit hour 0.

        The tick runs under a `dr.tick` span (`repro.obs.spans`) whose
        phases are `dr.tick.forecast`, `dr.tick.window`, `dr.solve`
        (opened by `api.solve`), `dr.tick.commit` and, with `events`
        set, `dr.tick.ledger`."""
        tick = self._tick
        warm = self._state
        with span("dr.tick", tick=tick,
                  meta={"cold": warm is None}) as tk:
            with span("dr.tick.forecast"):
                mci_hat = self._forecast(tick)
                rev = _rel_revision(self._prev_forecast, mci_hat)
            with span("dr.tick.window"):
                window = self._window(tick, mci_hat)
            # Warm ticks shift the plan one hour and restart the mu
            # schedule at the policy's mu0 — without the reset, mu
            # compounds by mu_growth^outer per tick and CR2/CR3's walls
            # turn stiff within a handful of ticks (multipliers still
            # carry the constraint prices). Both happen *inside* the
            # solve's jitted call, so a tick is one XLA dispatch (donated
            # when self.donate).
            steps = self.cold_steps if warm is None \
                else self._warm_budget(rev)
            tk.meta["steps"] = steps
            with self._traceguard(("tick", steps, warm is not None)), \
                    self._measure() as stats:
                plan = self._solve(window, warm, steps,
                                   shift=0 if warm is None else 1,
                                   reset_mu=warm is not None)
            with span("dr.tick.commit"):
                self._state = plan.state
                self._prev_forecast = mci_hat
                self._tick = tick + 1
                # hour 0 came back with the solve; the full plan stays on
                # the device until a caller reads `plan.D`
                committed = plan.hour0
                out = TickResult(
                    tick=tick, committed=committed,
                    forecast_mci=(float(mci_hat[0]) if mci_hat.ndim == 1
                                  else mci_hat[:, 0].copy()),
                    realized_mci=self._realized(tick),
                    inner_steps=plan.iters, plan=plan,
                    committed_by_region=self._by_region(committed))
                if self._history:   # bound memory: full plans live on
                    self._history[-1] = dataclasses.replace(  # the latest
                        self._history[-1], plan=None)        # tick only
                self._history.append(out)
            if self.events is not None:
                with span("dr.tick.ledger"):
                    self._emit_tick(
                        out, revision=rev, scope=tk,
                        recompiles=stats.traces if stats else 0,
                        cold=warm is None,
                        objective_proxy=float(plan.carbon_reduction_pct))
        return out

    def run(self, n_ticks: int | None = None,
            on_tick: Callable[[TickResult], None] | None = None,
            ) -> StreamingReport:
        """Run `n_ticks` hours (default: all the stream(s) support)."""
        n = self._n_ticks - self._tick if n_ticks is None else n_ticks
        for _ in range(n):
            out = self.step()
            if on_tick is not None:
                on_tick(out)
        return self.report()

    def run_scanned(self, n_ticks: int | None = None) -> StreamingReport:
        """Run `n_ticks` hours as ONE XLA dispatch (`api.solve_day`).

        Precomputes the (n_ticks, T) forecast-revision stack from the
        stream, then folds every tick's window-roll + plan shift +
        mu-reset + warm re-solve into a single `lax.scan` — a 24-tick
        day is one donated-buffer XLA call instead of 24. Matches the
        per-tick `run()` loop to <0.01 pp realized carbon (CR1/CR2
        only; CR3/B1/B3 need host-side per-tick control flow and raise
        `NotImplementedError`). `mesh=` is honoured: the whole day scan
        runs inside the fleet shard_map, including multi-region fleets
        (per-region norms ride the scan as row-sharded stacks).
        Warm-continues from and updates the solver state, so
        `run_scanned(24)` per day and mixed `step()`/`run_scanned()`
        schedules both work.

        `adaptive_warm` is incompatible: the per-tick budget is a
        static jit argument chosen from the revision magnitude at run
        time, which a fixed scan cannot express — use flat
        `warm_steps` here.

        The whole day runs under one `dr.tick` span (tagged with its
        first tick), with the same phases as `step`.
        """
        if self.adaptive_warm:
            raise ValueError(
                "run_scanned needs a flat warm budget: adaptive_warm "
                "picks each tick's (static) step count from the forecast "
                "revision at run time, which one fixed scan trace cannot "
                "express — construct with adaptive_warm=False or use run()")
        t0 = self._tick
        n = self._n_ticks - t0 if n_ticks is None else n_ticks
        if n <= 0:
            raise ValueError(f"n_ticks must be >= 1, got {n}")
        from repro.core.api import solve_day
        was_cold = self._state is None
        with span("dr.tick", tick=t0,
                  meta={"cold": was_cold, "steps": self.warm_steps,
                        "ticks": n}) as tk:
            with span("dr.tick.forecast"):
                mci_stack = np.stack([self._forecast(t0 + i)
                                      for i in range(n)])
                # Per-tick revision magnitudes, walked over the stack
                # before _prev_forecast advances to the final horizon.
                prev = self._prev_forecast
                revs = []
                for i in range(n):
                    revs.append(_rel_revision(prev, mci_stack[i]))
                    prev = mci_stack[i]
            with span("dr.tick.window"):
                p_win = self._window_problem(t0, mci_stack[0])
            ctx = SolveContext(mesh=self.mesh, donate=self.donate,
                               warm=self._state,
                               use_kernel=self.use_kernel, shift=1,
                               reset_mu=self._state is not None,
                               telemetry=self.telemetry)
            with self._traceguard(("day", n, self._state is not None)), \
                    self._measure() as stats:
                day = solve_day(p_win, self.policy, mci_stack, ctx=ctx,
                                cold_steps=self.cold_steps,
                                warm_steps=self.warm_steps)
            with span("dr.tick.commit"):
                self._state = day.last.state
                self._prev_forecast = mci_stack[-1]
                self._tick = t0 + n
                outs = [TickResult(
                    tick=t0 + i, committed=day.committed[i],
                    forecast_mci=(float(mci_stack[i][0])
                                  if mci_stack[i].ndim == 1
                                  else mci_stack[i][:, 0].copy()),
                    realized_mci=self._realized(t0 + i),
                    inner_steps=day.inner_steps[i],
                    plan=day.last if i == n - 1 else None,
                    committed_by_region=self._by_region(day.committed[i]))
                    for i in range(n)]
                if self._history:   # same memory bound as step()
                    self._history[-1] = dataclasses.replace(
                        self._history[-1], plan=None)
                self._history.extend(outs)
            if self.events is not None:
                with span("dr.tick.ledger"):
                    # One dispatch covered the whole day: latency, phases,
                    # traces and the counted dispatches and bytes land on
                    # tick 0, the objective proxy on the last tick (the
                    # only per-plan metric the scan keeps).
                    traces = day.last.extras.get("telemetry", ())
                    for i, out in enumerate(outs):
                        self._emit_tick(
                            out, revision=revs[i],
                            scope=tk if i == 0 else None,
                            recompiles=stats.traces if stats and i == 0
                            else 0,
                            cold=i == 0 and was_cold,
                            objective_proxy=(
                                float(day.last.carbon_reduction_pct)
                                if i == n - 1 else None))
                        if i < len(traces):
                            self._emit_trace(out.tick, traces[i])
        return self.report()

    def report(self) -> StreamingReport:
        ticks = tuple(self._history)
        if not ticks:
            raise RuntimeError("no ticks committed yet — call step()/run()")
        committed = np.stack([t.committed for t in ticks], axis=1)
        base_usage = np.asarray(self.problem.usage)
        Tn = base_usage.shape[1]
        if self.problem.is_multiregion:
            region = self.problem.region
            baseline = sum(
                float((np.asarray(t.realized_mci)
                       * region_totals(region,
                                       base_usage[:, t.tick % Tn],
                                       self.problem.R)).sum())
                for t in ticks)
        else:
            baseline = sum(
                t.realized_mci * float(base_usage[:, t.tick % Tn].sum())
                for t in ticks)
        return StreamingReport(
            ticks=ticks, committed=committed,
            realized_carbon=sum(t.realized_carbon for t in ticks),
            forecast_carbon=sum(t.forecast_carbon for t in ticks),
            realized_baseline=float(baseline),
            total_inner_steps=sum(t.inner_steps for t in ticks))
