"""`repro.obs` tests: the in-solve telemetry contract (telemetry-off
bitwise-identical to pre-PR, telemetry-on plans bitwise-identical to
telemetry-off), the trace surfaces through `solve`/`sweep`/`ensemble`/
`solve_day`, the streaming tick ledger (one-dispatch contract intact
with the ledger enabled), the JSONL schema pin, span timing, the span
recorder (nesting, the bounded ring, profiler trace names) and the
program's tick spans and byte counters, and the report CLI round
trip."""
import dataclasses
import glob
import json
import os
import pathlib
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.core.api import (CR1, CR2, CR3, SolveContext, solve, solve_day,
                            sweep)
from repro.core.fleet_solver import synthetic_fleet
from repro.obs import (SCHEMA_VERSION, ConvergenceTrace, EventWriter,
                       SpanEvent, TelemetryConfig, TickEvent, host_meta,
                       read_events, span, spans)
from repro.obs.report import main as report_main

from conftest import run_in_subprocess


@pytest.fixture(scope="module")
def fp():
    return synthetic_fleet(6, seed=3)


# ---------------------------------------------------------------------------
# In-solve telemetry: bitwise parity + trace content
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", [CR1(lam=1.4), CR2(cap_frac=0.12)],
                         ids=["cr1", "cr2"])
def test_telemetry_on_is_bitwise_off(fp, policy):
    """The ISSUE acceptance bar: telemetry-on plans/states are bitwise
    identical to telemetry-off — the trace rides the scan as extra aux
    outputs, it never perturbs the solve."""
    off = solve(fp, policy, ctx=SolveContext(steps=120))
    on = solve(fp, policy,
               ctx=SolveContext(steps=120,
                                telemetry=TelemetryConfig(every=10)))
    np.testing.assert_array_equal(off.D, on.D)
    np.testing.assert_array_equal(np.asarray(off.state.x),
                                  np.asarray(on.state.x))
    assert off.carbon_reduction_pct == on.carbon_reduction_pct
    assert off.extras.get("telemetry") is None


@pytest.mark.parametrize("policy", [CR1(lam=1.4), CR2(cap_frac=0.12)],
                         ids=["cr1", "cr2"])
def test_telemetry_trace_content(fp, policy):
    r = solve(fp, policy,
              ctx=SolveContext(steps=120,
                               telemetry=TelemetryConfig(every=10)))
    trace = r.extras["telemetry"]
    assert isinstance(trace, ConvergenceTrace)
    # every=10 over (outer * inner) total steps: steps 10, 20, ...
    assert trace.n_samples == trace.step.shape[0] > 0
    assert trace.step[0] == 10 and np.all(np.diff(trace.step) == 10)
    assert np.all(np.isfinite(trace.objective))
    assert np.all(trace.grad_norm >= 0)
    if policy.name == "cr1":
        # unconstrained lane: no residuals, violation pinned at 0
        assert np.all(trace.violation == 0.0)
    else:
        assert np.all(trace.violation >= 0.0)
        assert trace.mu[-1] >= trace.mu[0]   # mu schedule grows
    d = next(trace.samples())
    assert set(d) == {"step", "objective", "grad_norm", "violation",
                      "dx", "mu"}
    json.dumps(d)   # samples are ledger-ready


def test_telemetry_mesh_parity_subprocess():
    """Sharded telemetry all-reduces to the solo trace (objective psum,
    violation pmax), and the sharded plan stays bitwise the solo plan."""
    run_in_subprocess("""
import numpy as np
from repro.core.api import CR1, SolveContext, solve
from repro.core.fleet_solver import synthetic_fleet
from repro.launch.mesh import make_fleet_mesh
from repro.obs import TelemetryConfig

p = synthetic_fleet(8, seed=3)
tel = TelemetryConfig(every=15)
solo = solve(p, CR1(lam=1.4), ctx=SolveContext(steps=60, telemetry=tel))
mesh = make_fleet_mesh()
assert len(mesh.devices.ravel()) == 2
sh = solve(p, CR1(lam=1.4),
           ctx=SolveContext(steps=60, telemetry=tel, mesh=mesh))
np.testing.assert_array_equal(solo.D, sh.D)
t0, t1 = solo.extras["telemetry"], sh.extras["telemetry"]
np.testing.assert_array_equal(t0.step, t1.step)
np.testing.assert_allclose(t0.objective, t1.objective, rtol=1e-6)
np.testing.assert_allclose(t0.violation, t1.violation, rtol=1e-6,
                           atol=1e-12)
print("mesh telemetry OK")
""", devices=2)


def test_telemetry_refuses_fused_kernel(fp):
    with pytest.raises(NotImplementedError, match="telemetry"):
        solve(fp, CR1(lam=1.4),
              ctx=SolveContext(steps=40, use_kernel=True,
                               telemetry=TelemetryConfig(every=10)))


def test_telemetry_config_validates():
    with pytest.raises(ValueError):
        TelemetryConfig(every=0)


def test_sweep_loop_lane_carries_traces(fp):
    """Telemetry forces the per-policy loop (the vmapped lane has no
    trace plumbing); each result carries its own trace."""
    rs = sweep(fp, [CR1(lam=1.2), CR1(lam=1.6)],
               ctx=SolveContext(steps=60,
                                telemetry=TelemetryConfig(every=10)))
    assert len(rs) == 2
    for r in rs:
        assert r.extras["telemetry"].n_samples > 0
    # traces differ across lambdas — they are per-solve, not shared
    assert not np.array_equal(rs[0].extras["telemetry"].objective,
                              rs[1].extras["telemetry"].objective)


def test_ensemble_telemetry_forces_loop(fp):
    from repro.core.ensemble import evaluate_ensemble
    from repro.core.scenario import DuckPerturb, resolve_scenarios

    stack = resolve_scenarios([DuckPerturb(n_scenarios=2, seed=0)], fp)
    got = evaluate_ensemble(
        fp, CR1(lam=1.4), stack,
        ctx=SolveContext(steps=60, telemetry=TelemetryConfig(every=10)))
    assert not got.batched
    assert all(e["telemetry"].n_samples > 0 for e in got.extras)
    with pytest.raises(ValueError, match="telemetry"):
        evaluate_ensemble(
            fp, CR1(lam=1.4), stack, batched=True,
            ctx=SolveContext(steps=60, telemetry=TelemetryConfig(every=10)))


@pytest.mark.parametrize("policy", [CR1(lam=1.4), CR2(cap_frac=0.12)],
                         ids=["cr1", "cr2"])
def test_solve_day_traces_per_tick(fp, policy):
    rng = np.random.default_rng((7, 2))
    base = np.asarray(fp.mci, float)
    stack = np.stack([np.roll(base, -i) * (1 + 0.01 * rng.standard_normal(
        base.shape)) for i in range(4)])
    off = solve_day(fp, policy, stack, cold_steps=60, warm_steps=20)
    on = solve_day(fp, policy, stack, cold_steps=60, warm_steps=20,
                   ctx=SolveContext(telemetry=TelemetryConfig(every=10)))
    np.testing.assert_array_equal(off.committed, on.committed)
    traces = on.last.extras["telemetry"]
    assert len(traces) == 4            # tick 0 + 3 warm ticks
    # cold budget is 3x the warm budget, so (whatever the policy's outer
    # multiplier) tick 0 carries 3x the samples of each warm tick
    warm_n = traces[1].n_samples
    assert warm_n > 0
    assert traces[0].n_samples == 3 * warm_n
    assert all(t.n_samples == warm_n for t in traces[1:])
    assert "telemetry" not in off.last.extras


# ---------------------------------------------------------------------------
# Streaming ledger: events + one-dispatch contract
# ---------------------------------------------------------------------------
def test_streaming_ledger_round_trip(fp, tmp_path, capsys):
    from repro.core.carbon import ForecastStream
    from repro.core.streaming import RollingHorizonSolver

    path = tmp_path / "run.jsonl"
    stream = ForecastStream.caiso(n_ticks=3, horizon=fp.T, seed=1)
    solver = RollingHorizonSolver(fp, stream, policy="cr1", cold_steps=60,
                                  warm_steps=15, events=str(path),
                                  telemetry=TelemetryConfig(every=15))
    solver.run(3)
    recs = read_events(path)
    assert recs[0]["kind"] == "header"
    assert recs[0]["schema"] == SCHEMA_VERSION
    assert recs[0]["tags"]["policy"] == "cr1"
    ticks = [r for r in recs if r["kind"] == "tick"]
    assert [t["tick"] for t in ticks] == [0, 1, 2]
    assert ticks[0]["cold"] and not ticks[1]["cold"]
    assert ticks[0]["warm_steps"] == 60 and ticks[1]["warm_steps"] == 15
    assert ticks[0]["revision"] == 0.0 and ticks[1]["revision"] > 0
    assert all(t["latency_s"] > 0 and t["dispatches"] == 1 for t in ticks)
    assert ticks[0]["recompiles"] > 0     # cold tick compiles
    assert ticks[2]["recompiles"] == 0    # second warm tick: cache hit
    tel = [r for r in recs if r["kind"] == "telemetry"]
    assert sorted({t["tick"] for t in tel}) == [0, 1, 2]
    # the schema-pinned round trip: report CLI renders it and exits 0
    assert report_main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "tick ledger (3 ticks)" in out and "convergence" in out


def test_run_scanned_one_dispatch_with_ledger(fp, tmp_path, monkeypatch):
    """The ledger must not cost dispatches: a scanned day with events +
    telemetry on still funnels through ONE day-scan call, and a second
    same-shape day is provably compile-free (recompile_guard(0)) —
    emission is host-side after the solve."""
    import repro.core.api as api
    from repro.analysis import recompile_guard
    from repro.core.carbon import ForecastStream
    from repro.core.streaming import RollingHorizonSolver

    path = tmp_path / "day.jsonl"
    stream = ForecastStream.caiso(n_ticks=12, horizon=fp.T, seed=2)
    solver = RollingHorizonSolver(fp, stream, policy="cr1", cold_steps=60,
                                  warm_steps=15, events=str(path),
                                  telemetry=TelemetryConfig(every=15))
    solver.run_scanned(4)   # day 1: cold scan compiles
    solver.run_scanned(4)   # day 2: warm continuation compiles (new
    #                         static combo: first_shift=1, reset_mu)
    calls = []
    orig = api._DAY.plain
    monkeypatch.setattr(api, "_DAY", api._DAY._replace(
        plain=lambda *a, **k: (calls.append(1), orig(*a, **k))[1]))
    with recompile_guard(0, label="scanned day with ledger"):
        solver.run_scanned(4)   # day 3: provably compile-free
    assert len(calls) == 1
    recs = read_events(path)
    ticks = [r for r in recs if r["kind"] == "tick"]
    assert len(ticks) == 12
    # the one dispatch lands on each day's first tick, 0 elsewhere
    assert [t["dispatches"] for t in ticks] == [1, 0, 0, 0] * 3
    assert sum(t["recompiles"] for t in ticks[8:]) == 0
    # in-solve traces landed for every scanned tick
    tel_ticks = {r["tick"] for r in recs if r["kind"] == "telemetry"}
    assert tel_ticks == set(range(12))


# ---------------------------------------------------------------------------
# Events: schema pin, atomic append, host metadata
# ---------------------------------------------------------------------------
def test_event_writer_appends_without_second_header(tmp_path):
    path = tmp_path / "ev.jsonl"
    with EventWriter(str(path), tags={"a": 1}) as w:
        w.write(SpanEvent(name="x", elapsed_s=0.5))
    with EventWriter(str(path)) as w:   # reopen: header already present
        w.write(SpanEvent(name="y", elapsed_s=0.25))
    recs = read_events(path)
    assert [r["kind"] for r in recs] == ["header", "span", "span"]
    assert recs[0]["tags"] == {"a": 1}


def test_read_events_schema_pin(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "header", "schema": 999,
                               "host": {}}) + "\n")
    with pytest.raises(ValueError, match="schema"):
        read_events(bad)
    headerless = tmp_path / "nohdr.jsonl"
    headerless.write_text(json.dumps({"kind": "span", "name": "x",
                                      "elapsed_s": 1.0}) + "\n")
    with pytest.raises(ValueError, match="header"):
        read_events(headerless)
    assert report_main([str(bad)]) == 1   # CLI surfaces it as exit 1


def test_host_meta_fields():
    meta = host_meta()
    assert {"platform", "n_devices", "device_kind", "jax", "jaxlib",
            "pallas_interpret"} <= set(meta)
    assert meta["n_devices"] >= 1


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------
def test_span_times_device_work(tmp_path):
    import jax.numpy as jnp

    path = tmp_path / "spans.jsonl"
    with EventWriter(str(path)) as w:
        with span("mul", writer=w, meta={"n": 64}) as sp:
            y = sp.bind(jnp.ones(64) * 3)
        assert sp.elapsed_s > 0
        np.testing.assert_array_equal(np.asarray(y), 3 * np.ones(64))
        # the event is written even when the body raises
        with pytest.raises(RuntimeError, match="boom"):
            with span("fails", writer=w):
                raise RuntimeError("boom")
    recs = read_events(path)
    assert [r["name"] for r in recs[1:]] == ["mul", "fails"]
    assert recs[1]["meta"] == {"n": 64}


def test_tick_event_dataclass_round_trip(tmp_path):
    path = tmp_path / "t.jsonl"
    ev = TickEvent(tick=3, revision=0.02, warm_steps=40, cold=False,
                   objective_proxy=11.5, latency_s=0.2,
                   committed_carbon=[1.0, 2.0], realized_carbon=[1.1, 1.9],
                   migration_credit=0.3, recompiles=0, dispatches=1)
    with EventWriter(str(path)) as w:
        w.write(ev)
    rec = read_events(path)[1]
    assert rec["kind"] == "tick" and rec["tick"] == 3
    assert rec["committed_carbon"] == [1.0, 2.0]


# ---------------------------------------------------------------------------
# Span recorder: nesting, the ring, the profiler trace
# ---------------------------------------------------------------------------
def test_spans_nest_with_parent_and_tick_ids():
    spans.clear()
    with span("outer", tick=7) as outer:
        with span("mid") as mid:
            mid.count("h2d_bytes", 100)
            with span("leaf") as leaf:
                leaf.count("h2d_bytes", 20)
                leaf.count("dispatches", 1)
        with span("other", tick=9):
            pass
    recs = {r.name: r for r in spans.recorded()}
    # appended at exit: children before their parent
    assert [r.name for r in spans.recorded()] == ["leaf", "mid", "other",
                                                  "outer"]
    assert recs["outer"].parent is None and recs["outer"].tick == 7
    assert recs["mid"].parent == outer.id == recs["outer"].id
    assert recs["leaf"].parent == mid.id and recs["leaf"].tick == 7
    assert recs["other"].tick == 9          # an explicit tick wins
    assert len({r.id for r in recs.values()}) == 4
    for r in recs.values():
        assert r.end_ns >= r.start_ns
    assert recs["outer"].start_ns <= recs["mid"].start_ns \
        <= recs["leaf"].start_ns <= recs["leaf"].end_ns \
        <= recs["mid"].end_ns <= recs["outer"].end_ns
    # own counts on the record; the rollup on the live parent
    assert recs["mid"].counts == {"h2d_bytes": 100}
    assert recs["leaf"].counts == {"h2d_bytes": 20, "dispatches": 1}
    assert outer.totals == {"h2d_bytes": 120, "dispatches": 1}
    assert outer.counts == {}
    # self times by name; together with the parent's own time they make
    # up its whole span
    ph = outer.phases_ns
    assert set(ph) == {"mid", "leaf", "other"}
    assert ph["mid"] == recs["mid"].elapsed_ns - recs["leaf"].elapsed_ns
    own = outer.elapsed_s * 1e9 - recs["mid"].elapsed_ns \
        - recs["other"].elapsed_ns
    assert sum(ph.values()) + own == pytest.approx(
        recs["outer"].elapsed_ns, abs=1)
    spans.clear()
    assert spans.recorded() == []


def test_span_ring_is_bounded():
    spans.clear()
    n = spans.RING_SIZE + 25
    for i in range(n):
        with span("s", tick=i):
            pass
    recs = spans.recorded()
    assert len(recs) == spans.RING_SIZE >= 400 * 12
    assert recs[0].tick == 25 and recs[-1].tick == n - 1   # oldest gone
    spans.clear()


def test_span_records_on_exception_and_pops_the_stack():
    spans.clear()
    with pytest.raises(RuntimeError):
        with span("fails"):
            raise RuntimeError("boom")
    with span("after"):
        pass
    a, b = spans.recorded()
    assert a.name == "fails" and b.name == "after"
    assert b.parent is None                  # "fails" left the stack


def test_span_names_reach_the_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with span("dr.test.outer"):
            with span("dr.test.inner"):
                jnp.ones(8).block_until_ready()
    files = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    assert files
    pd = ProfileData.from_file(files[-1])
    names = {e.name for plane in pd.planes for line in plane.lines
             for e in line.events}
    assert {"dr.test.outer", "dr.test.inner"} <= names


# ---------------------------------------------------------------------------
# The program's spans on the tick path
# ---------------------------------------------------------------------------
TICK_PHASES = ["dr.tick.forecast", "dr.tick.window", "dr.solve",
               "dr.tick.commit"]
SOLVE_PHASES = ["dr.solve.dispatch", "dr.solve.wait", "dr.solve.pull",
                "dr.solve.report"]


def _children(recs, parent):
    return [r for r in recs if r.parent == parent.id]


def _sums_bytes(R: int, T: int) -> int:
    """What a solve's report sums pull (`fleet_solver.ReportSums`): the
    plan's and the usage's (R, T) hour totals and two scalars, each a
    float32 pair."""
    return (2 * R * T + 2) * 2 * 4


def _host_bytes(p) -> int:
    """What a solve uploads: every array of the fleet in float32 (x64
    off), the bool column as bytes, and the float λ."""
    arrays = [p.usage, p.jobs, p.entitlement, p.k, p.rts_coeffs, p.betas,
              p.x2_kind, p.mci]
    return (sum(np.asarray(a).size * 4 for a in arrays)
            + np.asarray(p.is_batch).size + 4)


def _tick_bytes(p, tick: int) -> int:
    """What a streaming CR1 tick uploads: the fleet goes up once, on tick
    0 (`_host_bytes`); every tick sends its forecast and λ, and the hour
    offset as an int32."""
    sent = _host_bytes(p) if tick == 0 else np.asarray(p.mci).size * 4 + 4
    return sent + 4


@pytest.fixture(scope="module")
def cr1_stream(fp, tmp_path_factory):
    """A 3-tick CR1 stream with the ledger on, and the spans it left."""
    from repro.core.carbon import ForecastStream
    from repro.core.streaming import RollingHorizonSolver

    path = tmp_path_factory.mktemp("ledger") / "run.jsonl"
    stream = ForecastStream.caiso(n_ticks=3, horizon=fp.T, seed=1)
    solver = RollingHorizonSolver(fp, stream, policy="cr1", cold_steps=60,
                                  warm_steps=15, events=str(path))
    spans.clear()
    report = solver.run(3)
    return report, spans.recorded(), read_events(path)


def test_cr1_stream_opens_one_tick_span_per_tick(cr1_stream):
    _, recs, _ = cr1_stream
    ticks = [r for r in recs if r.name == "dr.tick"]
    assert [t.tick for t in ticks] == [0, 1, 2]
    assert all(t.parent is None for t in ticks)
    assert [t.meta["cold"] for t in ticks] == [True, False, False]
    assert [t.meta["steps"] for t in ticks] == [60, 15, 15]
    for t in ticks:
        kids = _children(recs, t)
        assert [k.name for k in kids] == TICK_PHASES + ["dr.tick.ledger"]
        assert all(k.tick == t.tick for k in kids)
        (solve_span,) = [k for k in kids if k.name == "dr.solve"]
        assert solve_span.meta == {"policy": "cr1", "lane": "solo"}
        assert [k.name for k in _children(recs, solve_span)] == \
            SOLVE_PHASES


def test_cr1_stream_counts_bytes_as_the_arrays_nbytes(fp, cr1_stream):
    report, recs, _ = cr1_stream
    sends = [r for r in recs if r.name == "dr.solve.dispatch"]
    # the fleet goes up with tick 0 and stays on the device, where each
    # tick's program slides it; the warm state is already there
    assert [r.counts["h2d_bytes"] for r in sends] == [
        _tick_bytes(fp, t) for t in range(3)]
    assert [r.counts.get("fleet_uploads", 0) for r in sends] == [1, 0, 0]
    pulls = [r.counts["d2h_bytes"] for r in recs
             if r.name == "dr.solve.pull"]
    committed = report.ticks[-1].committed
    assert committed.dtype == np.float32
    # hour 0 and the report sums; the plan stays on the device
    assert pulls == [committed.nbytes + _sums_bytes(1, fp.T)] * 3
    assert [r.counts["dispatches"] for r in recs
            if r.name == "dr.solve.dispatch"] == [1, 1, 1]


def test_cr1_stream_tick_leaves_the_plan_on_the_device(fp, cr1_stream):
    """A tick is one dispatch and pulls no plan (`plan_pulls` 0); reading
    the last tick's `plan.D` afterwards is one `dr.solve.pull` of the
    whole plan, outside any tick, and the second read pulls nothing."""
    report, recs, _ = cr1_stream
    for t in (r for r in recs if r.name == "dr.tick"):
        kids = [r for r in recs if r.tick == t.tick and r is not t]
        assert sum(k.counts.get("dispatches", 0) for k in kids) == 1
        assert sum(k.counts.get("plan_pulls", 0) for k in kids) == 0
    plan = report.ticks[-1].plan
    n0 = len(spans.recorded())
    D = plan.D
    assert plan.D is D
    (pull,) = spans.recorded()[n0:]
    assert pull.name == "dr.solve.pull" and pull.tick is None
    assert pull.counts == {"d2h_bytes": D.nbytes, "plan_pulls": 1}
    assert D.shape == (fp.W, fp.T) and D.dtype == np.float32
    np.testing.assert_array_equal(D[:, 0], report.ticks[-1].committed)


def test_phases_cover_the_tick(cr1_stream):
    _, recs, _ = cr1_stream
    for t in (r for r in recs if r.name == "dr.tick"):
        covered = sum(k.elapsed_ns for k in _children(recs, t))
        assert covered >= 0.9 * t.elapsed_ns, (t.tick, covered,
                                               t.elapsed_ns)


def test_tick_event_carries_counted_dispatches_phases_and_bytes(
        fp, cr1_stream):
    _, recs, events = cr1_stream
    ticks = [e for e in events if e["kind"] == "tick"]
    spans_by_tick = {r.tick: r for r in recs if r.name == "dr.tick"}
    for ev in ticks:
        assert ev["dispatches"] == 1
        assert ev["h2d_bytes"] == _tick_bytes(fp, ev["tick"])
        # hour 0 and the report sums, not the plan
        assert ev["d2h_bytes"] == fp.W * 4 + _sums_bytes(1, fp.T)
        assert set(ev["phases_ms"]) == set(TICK_PHASES + SOLVE_PHASES)
        # the latency is the tick span up to the ledger write
        span_s = spans_by_tick[ev["tick"]].elapsed_ns * 1e-9
        assert 0 < ev["latency_s"] <= span_s
        assert sum(ev["phases_ms"].values()) * 1e-3 <= ev["latency_s"]


def test_report_renders_phases_and_bytes(cr1_stream, tmp_path, capsys):
    _, _, events = cr1_stream
    path = tmp_path / "copy.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in events))
    assert report_main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "up_MB" in out and "pull_MB" in out
    assert "phase self time per tick (ms)" in out
    for name in TICK_PHASES + SOLVE_PHASES:
        assert name in out


def test_cr3_tick_counts_every_clearing_round(tmp_path):
    """A CR3 tick that clears in several rounds runs them all in one
    dispatch, wait and pull under `dr.solve`, which counts the rounds
    (`clear_rounds`) and the regions left in deficit
    (`unbalanced_regions`); the tick ledger carries both counts."""
    from repro.core.carbon import ForecastStream
    from repro.core.fleet_solver import synthetic_fleet
    from repro.core.streaming import RollingHorizonSolver

    p = synthetic_fleet(4)
    # entitlements under peak usage and a huge ρ: rebates outrun taxes,
    # so every clearing iteration runs (the clearing stays unbalanced)
    tight = dataclasses.replace(p, entitlement=0.6 * p.usage.max(axis=1))
    stream = ForecastStream.caiso(n_ticks=1, horizon=tight.T, seed=1)
    policy = CR3(rho=1e4, tax_frac=0.1, outer=2, clearing_iters=2)
    path = tmp_path / "cr3.jsonl"
    spans.clear()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        solver = RollingHorizonSolver(tight, stream, policy=policy,
                                      cold_steps=40, warm_steps=10,
                                      events=str(path))
        out = solver.step()
    (ev,) = [e for e in read_events(path) if e["kind"] == "tick"]
    rounds = out.inner_steps // (40 * policy.outer)
    assert rounds == 3 == 1 + policy.clearing_iters
    recs = spans.recorded()
    assert not [r for r in recs if r.name == "dr.solve.round"]
    (root,) = [r for r in recs if r.name == "dr.solve"]
    assert [k.name for k in _children(recs, root)] == SOLVE_PHASES
    assert root.counts == {"clear_rounds": rounds, "unbalanced_regions": 1}
    assert ev["dispatches"] == 1
    assert ev["h2d_bytes"] == sum(r.counts.get("h2d_bytes", 0)
                                  for r in recs)
    # the plan (the float64 balance reads it), ρ as a float32 pair (one
    # region), the round count and the report sums
    assert ev["d2h_bytes"] == ((tight.W * tight.T + 2 + 1) * 4
                               + _sums_bytes(1, tight.T))


def test_solve_day_spans_one_dispatch(fp):
    spans.clear()
    base = np.asarray(fp.mci, float)
    stack = np.stack([np.roll(base, -i) for i in range(3)])
    solve_day(fp, CR1(lam=1.4), stack, cold_steps=30, warm_steps=10)
    recs = spans.recorded()
    (root,) = [r for r in recs if r.name == "dr.solve"]
    assert root.meta == {"policy": "cr1", "lane": "day"}
    assert [k.name for k in _children(recs, root)] == SOLVE_PHASES
    (pull,) = [r for r in recs if r.name == "dr.solve.pull"]
    # the (n, W) commitments and the last plan's report sums; the plan
    # stays on the device
    assert pull.counts["d2h_bytes"] == 3 * fp.W * 4 + _sums_bytes(1, fp.T)


def test_importing_obs_loads_neither_jax_nor_core():
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    code = ("import sys, repro.obs, repro.obs.report; "
            "bad = [m for m in sys.modules if m == 'jax' "
            "or m.startswith('repro.core')]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**os.environ, "PYTHONPATH": str(src)})
