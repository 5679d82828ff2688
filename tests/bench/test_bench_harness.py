"""CPU tests of the benchmark harness under `bench/`.

They cover the yardstick's arithmetic (percentiles, stall record, trace
reduction, work counts), finding cells and their files by name, the
shape of a run's last line, and the correctness check: its control (the
reference in bfloat16) and the faults it must catch, each planted under
the timed path of a tiny run driven without the chip. Nothing here
loads a TPU library at import.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import re
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import check, spec, stats, trace, traffic, work  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# -- statistics --------------------------------------------------------------
@pytest.mark.parametrize("q", [0.0, 10.0, 50.0, 90.0, 95.0, 100.0])
def test_percentile_over_all_ticks_matches_linear_interpolation(q):
    vals = np.random.default_rng(0).gamma(4.0, 0.1, size=107)
    assert stats.percentile(vals, q) == pytest.approx(
        float(np.percentile(vals, q)), rel=1e-12)


def test_percentile_takes_every_tick_and_a_stall_moves_only_the_tail():
    walls = [0.47] * 99 + [4.1]
    assert stats.percentile(walls, 50.0) == pytest.approx(0.47)
    assert stats.percentile(walls, 90.0) == pytest.approx(0.47)
    assert stats.percentile(walls, 100.0) == pytest.approx(4.1)
    with pytest.raises(ValueError):
        stats.percentile([], 50.0)


def test_stall_record_lists_ticks_over_twice_the_median():
    walls = [0.5, 0.51, 1.2, 0.49, 0.5]
    cpus = [0.4, 0.4, 0.41, 0.4, 0.4]
    assert stats.stalls(walls, cpus) == [
        {"tick": 2, "wall_s": 1.2, "cpu_s": 0.41}]


# -- trace reduction on a small recorded trace ---------------------------------
KERNELS = {"al_step": {"outputs": 3, "operands": 10},
           "dr_features": {"outputs": 1, "operands": 3}}
DEV = "/device:TPU:0"
CALL = ('%closed_call.16 = (f32[1024,48]{1,0}, f32[1024,48]{1,0}, '
        'f32[1024,48]{1,0}) custom-call(' + ", ".join(
            f"f32[1024,48]{{1,0}} %pad.{i}" for i in range(10))
        + '), custom_call_target="tpu_custom_call"')


def _recorded_trace():
    """Window [0, 1000) ns with two ticks; the device runs one module per
    tick, whose ops nest (a while loop around a kernel call); the host's
    Python tracer saw the window roll in the idle stretch of each tick."""
    ev = [("/host:CPU", "python", trace.WINDOW, 0.0, 1000.0),
          ("/host:CPU", "python", trace.TICK, 100.0, 400.0),
          ("/host:CPU", "python", trace.TICK, 500.0, 400.0),
          ("/host:CPU", "python", "$streaming.py:302 _window_problem",
           100.0, 90.0),
          ("/host:CPU", "python", "$numeric.py:1172 roll", 80.0, 10.0),
          ("/host:CPU", "main/1", "unrelated", 10.0, 5.0)]
    for t0 in (200.0, 600.0):
        ev += [(DEV, "XLA Modules", "jit__cr1_impl(42)", t0, 250.0),
               (DEV, "XLA Ops", "%while.26 = (f32[]) while(...)", t0, 200.0),
               (DEV, "XLA Ops", CALL, t0 + 10, 150.0),
               (DEV, "XLA Ops", "%fusion.3 = f32[] fusion()", t0 + 200,
                50.0)]
    return ev


def _records():
    info = {"W": 1000, "W_batch": 500, "T": 48, "R": 1, "warm_steps": 150,
            "device_kind": "TPU v5 lite", "solve_modules": ["jit__cr1_impl"]}
    return trace.reduce(_recorded_trace(), KERNELS, 0, info)


def test_trace_busy_union_and_idle_share():
    rec = _records()
    assert rec.busy == [[(200.0, 450.0), (600.0, 850.0)]]
    assert rec.window_s == pytest.approx(1e-6)
    assert rec.busy_s == pytest.approx(500e-9)
    idle = spec.metric_reader("device_idle_share")(rec)
    assert idle == pytest.approx(50.0)


def test_trace_per_module_device_time_dispatches_and_host_time():
    rec = _records()
    assert spec.metric_reader("solve_device_ms_per_tick")(rec) == \
        pytest.approx(250e-6)
    assert spec.metric_reader("dispatches_per_tick")(rec) == 1.0
    # each 400 ns tick has 250 ns of device work under it
    assert spec.metric_reader("host_ms_per_tick")(rec) == \
        pytest.approx(150e-6)
    assert spec.metric_reader("recompiles_in_window")(rec) == 0.0


def test_trace_breakdown_names_by_module_and_kernel():
    rec = _records()
    bd = trace.breakdown(rec)
    names = dict(bd["device_ops"])
    # self time: the while loop's 200 ns less its 150 ns kernel call
    assert names == {"jit__cr1_impl/while": pytest.approx(100e-9),
                     "jit__cr1_impl/al_step": pytest.approx(300e-9),
                     "jit__cr1_impl/fusion": pytest.approx(100e-9)}
    gaps = bd["idle_gaps"]
    assert sum(g for _w, g in gaps) == pytest.approx(500e-9)
    # [0, 200): at its midpoint the host was in the window roll
    assert gaps[0] == ["streaming.py _window_problem",
                       pytest.approx(200e-9)]
    assert {w for w, _g in gaps} == {"streaming.py _window_problem",
                                     "tick", "between_ticks"}


def test_kernel_roofline_is_a_share_and_silent_without_the_kernel():
    rec = _records()
    share = spec.metric_reader("al_step_roofline")(rec)
    # two calls over two ticks: 150 steps each; 150 ns of kernel time each
    flops, nbytes = work.al_step(500, 500, 48, 150, per_row_carbon=False)
    least, bound = work.least_time(flops, nbytes, work.peaks("TPU v5 lite"))
    assert bound == "hbm"
    assert share == pytest.approx(100.0 * 2 * least / 300e-9)
    assert spec.metric_reader("dr_features_roofline")(rec) is None


def test_trace_needs_its_window_span():
    with pytest.raises(ValueError):
        trace.reduce([e for e in _recorded_trace() if e[2] != trace.WINDOW],
                     KERNELS, 0, {})


# -- work counts and peaks -----------------------------------------------------
def test_work_counts_prefix_sums_as_linear_in_T():
    f48, _ = work.al_step(10, 10, 48, 1, per_row_carbon=False)
    f96, _ = work.al_step(10, 10, 96, 1, per_row_carbon=False)
    assert f96 == 2 * f48
    _, b1 = work.al_step(10, 10, 48, 1, per_row_carbon=False)
    _, b2 = work.al_step(10, 10, 48, 8, per_row_carbon=False)
    assert b1 == b2          # bytes are per call, steps stay in VMEM


def test_peaks_table_refuses_an_unknown_device():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
    assert work.least_time(1.0, 1e9, work.peaks("TPU v5 lite"))[1] == "hbm"


# -- finding everything by name ------------------------------------------------
def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [c["name"] for c in SPEC["configs"]] + CELLS
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert e2e == {"tick_ms_p50", "tick_ms_p90", "setup_s"}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == \
            c["name"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_with_its_config_traffic_limits_and_metrics(name):
    cell = spec.find_cell(name)
    assert cell.config["name"] == name.split(".", 1)[1]
    assert cell.traffic["name"] == name.split(".", 1)[0]
    assert set(cell.limits) <= {"commit_rel", "commit_mean_p50",
                                "commit_mean_rel",
                                "commit_total_rel", "energy_total_rel",
                                "committed_carbon_pp", "carbon_pp",
                                "penalty_pp", "plan_rel"}
    assert cell.limits
    assert {m["name"] for m in cell.end_to_end} >= {"tick_ms_p50",
                                                    "setup_s"}
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    assert hasattr(spec.reference(cell.traffic["reference"]), "Replay")


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        spec.find_cell("no_such.cell")
    with pytest.raises(ValueError):
        spec.metric_reader("../harness/runner")


# -- the check: control and planted faults at a tiny size ----------------------
TINY_W = 128


def _tiny(name: str):
    cell = spec.find_cell(name)
    cfg = json.loads(json.dumps(cell.config))
    cfg["fleet"]["args"]["num"] = TINY_W
    return dataclasses.replace(cell, config=cfg)


def _run(name, trace_on=False, seconds=0.5):
    from harness import runner
    return runner.run(_tiny(name), 2**31 + 11, seconds, trace_on,
                      require_tpu=False, cache=False)


@pytest.fixture(scope="module")
def tiny_run():
    return _run(CELLS[0])


def test_last_line_schema(tiny_run):
    r = tiny_run
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tick_ms_p50", "tick_ms_p90", "setup_s"}
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    for c in r["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(r)


def test_traced_last_line_schema():
    r = _run(CELLS[0], trace_on=True)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks" and "breakdown" in r
    assert r["device"]["window_s"] > 0
    per_layer = {m["name"] for m in SPEC["per_layer"]}
    assert set(r["metrics"]) <= per_layer
    assert "recompiles_in_window" in r["metrics"]


@pytest.mark.parametrize("name", CELLS)
def test_control_in_bfloat16_fails_the_limits(name):
    cell = _tiny(name)
    tr = cell.traffic
    p = traffic.build_fleet(cell.config, 5)
    actual, fc = traffic.make_forecasts(cell.config, tr, p.T, 5)
    fleet = traffic.fleet_arrays(p)
    rows = traffic.sample_rows(p.W, TINY_W, 5)
    ref = spec.reference(tr["reference"])
    n, skip = 8, 1 + tr["warm_ticks"]
    f32 = ref.Replay(fleet, rows, tr["algorithm"], "float32")
    c32, p32 = f32.run(fc, n, tr["solver"]["cold_steps"],
                       tr["solver"]["warm_steps"])
    c16, p16 = ref.Replay(fleet, rows, tr["algorithm"], "bfloat16").run(
        fc, n, tr["solver"]["cold_steps"], tr["solver"]["warm_steps"])
    nums = check.numbers({"commits": c16[skip:], "plan": p16},
                         {"commits": c32[skip:], "plan": p32},
                         f32, skip, fc[skip:n])
    assert not check.verdict(nums, cell.limits), nums


def _fault(kind):
    """Break the timed path under `RollingHorizonSolver.step`."""
    from repro.core.streaming import RollingHorizonSolver
    real = RollingHorizonSolver._solve
    held = {}

    def solve(self, p, warm, steps, shift, reset_mu):
        if kind == "unchanged" and warm is not None:
            return held["plan"]      # the state comes back unchanged
        plan = real(self, p, warm, steps, shift, reset_mu)
        held["plan"] = plan
        D = np.array(plan.D)
        if kind == "half":
            D[1::2] = 0.0            # half of the fleet left out
        elif kind == "altered":
            D = np.roll(D, -1, axis=1)   # hour 1 committed as hour 0
        else:
            return plan
        return dataclasses.replace(plan, D=D)
    return solve


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_planted_fault_makes_correct_false(kind, monkeypatch):
    from repro.core.streaming import RollingHorizonSolver
    monkeypatch.setattr(RollingHorizonSolver, "_solve", _fault(kind))
    r = _run(CELLS[0])
    assert r["correct"] is False
    assert any(not c["value"] <= c["limit"] for c in r["checks"].values())


def test_limits_separate_program_from_control_in_the_files():
    for name in CELLS:
        lim = spec.find_cell(name).limits
        assert all(0 < v < 1 for v in lim.values())
        assert all(math.isfinite(v) for v in lim.values())
