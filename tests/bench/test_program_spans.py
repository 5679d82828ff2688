"""CPU tests of the per-layer metrics that read the program's own spans.

A small recorded trace (two ticks) and the spans the program would have
recorded for it, on a clock that is offset from the trace's and slews
between the two ticks. The readers must pair each tick with its own
`bench_tick`, recover the idle time planted where no host phase runs,
and go silent where the program recorded no `dr.tick` spans.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import program_spans, spec, trace  # noqa: E402
from repro.obs import spans as obs_spans  # noqa: E402
from repro.obs.spans import SpanRecord  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
READERS = ["roll_ms_per_tick", "dispatch_ms_per_tick", "pull_ms_per_tick",
           "report_ms_per_tick", "upload_mb_per_tick", "pull_mb_per_tick",
           "idle_unattributed_ms_per_tick"]
DEV = "/device:TPU:0"
OP = "%fusion.1 = f32[] fusion()"

# Trace clock (ns): ticks [100, 900) and [1000, 1800). The device runs
# [420, 800) and [1300, 1665): for 20 ns after the dispatch returned in
# the first tick and 35 ns before the wait ended in the second it sits
# idle while the host waits for it, the idle time no host phase
# explains.
TICKS = [(100.0, 900.0), (1000.0, 1800.0)]
BUSY = [(420.0, 380.0), (1300.0, 365.0)]
PLANTED_NS = [20.0, 35.0]


def _trace_events():
    ev = [("/host:CPU", "python", trace.WINDOW, 0.0, 2000.0)]
    ev += [("/host:CPU", "python", trace.TICK, s, e - s) for s, e in TICKS]
    ev += [(DEV, "XLA Ops", OP, s, d) for s, d in BUSY]
    return ev


def _records():
    return trace.reduce(_trace_events(), {}, 0, {})


def _program_spans():
    """The program's records for an earlier (set-up) tick and the two
    window ticks, on a perf_counter clock 10 ms ahead of the trace's,
    and 50 ns further ahead by the second window tick."""
    ids = iter(range(1, 1000))
    out = []

    def tick(index, t0, phases, counts):
        root = next(ids)
        recs = []
        for name, a, b, parent in phases:
            i = next(ids)
            recs.append((name, i, a, b, parent))
        by_name = {}
        for name, i, a, b, parent in recs:
            by_name.setdefault(name, i)
        for name, i, a, b, parent in recs:
            up = root if parent is None else by_name[parent]
            out.append(SpanRecord(name, i, up, index, t0 + a, t0 + b,
                                  counts.get(name, {})))
        end = max(b for _n, _i, _a, b, _p in recs)
        out.append(SpanRecord("dr.tick", root, None, index, t0, t0 + end,
                              {}))

    def phases(report_inner):
        ph = [("dr.tick.forecast", 0, 50, None),
              ("dr.tick.window", 50, 200, None),
              ("dr.solve", 200, 780, None),
              ("dr.solve.dispatch", 200, 300, "dr.solve"),
              ("dr.solve.wait", 300, 700, "dr.solve"),
              ("dr.solve.pull", 700, 740, "dr.solve"),
              ("dr.solve.report", 740, 780, "dr.solve"),
              ("dr.tick.commit", 780, 800, None)]
        if report_inner:
            ph.append(("inner", 750, 760, "dr.solve.report"))
        return ph

    counts = {"dr.solve.dispatch": {"dispatches": 1, "h2d_bytes": 1000},
              "dr.solve.pull": {"d2h_bytes": 400}}
    tick(0, 5_000_000, phases(False), counts)        # set-up tick
    tick(1, 10_000_000, phases(True), counts)
    tick(2, 10_000_950, phases(False),
         {**counts, "dr.solve.dispatch": {"dispatches": 1,
                                          "h2d_bytes": 3000}})
    return out


@pytest.fixture()
def recorded(monkeypatch):
    recs = _program_spans()
    monkeypatch.setattr(obs_spans, "recorded", lambda: list(recs))
    return recs


def _read(name):
    return spec.metric_reader(name)(_records())


def test_new_metrics_are_declared_for_both_cells():
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    for name in READERS:
        m = per_layer[name]
        assert m["moves"] == "tick_ms_p50"
        assert m["source"] in ("program_span", "program_counter")
        assert m["workloads"] == cells


def test_window_ticks_are_the_last_dr_tick_spans(recorded):
    ticks = program_spans.window_ticks(_records())
    assert [t.root.tick for t in ticks] == [1, 2]
    assert {s.name for s in ticks[1].spans} == {
        "dr.tick.forecast", "dr.tick.window", "dr.solve",
        "dr.solve.dispatch", "dr.solve.wait", "dr.solve.pull",
        "dr.solve.report", "dr.tick.commit"}


@pytest.mark.parametrize("name,want", [
    ("roll_ms_per_tick", 150e-6),
    ("dispatch_ms_per_tick", 100e-6),
    ("pull_ms_per_tick", 40e-6),
    # self time: 30 ns in tick 1 (a 10-ns span inside), 40 ns in tick 2
    ("report_ms_per_tick", 35e-6),
    ("upload_mb_per_tick", 2000e-6),
    ("pull_mb_per_tick", 400e-6),
])
def test_span_readers(recorded, name, want):
    assert _read(name) == pytest.approx(want)


def test_alignment_recovers_the_planted_idle_time(recorded):
    per_tick = program_spans.idle_unattributed_ns(_records())
    assert per_tick == pytest.approx(PLANTED_NS)
    assert _read("idle_unattributed_ms_per_tick") == pytest.approx(
        1e-6 * sum(PLANTED_NS) / 2)


def test_one_alignment_for_the_window_would_misplace_the_phases(recorded):
    """The second tick's spans, shifted by the first tick's offset, land
    50 ns late: the check that the readers pair tick by tick."""
    ticks = program_spans.window_ticks(_records())
    shift = TICKS[0][0] - ticks[0].root.start_ns
    assert ticks[1].root.start_ns + shift != TICKS[1][0]
    assert ticks[1].root.start_ns + shift - TICKS[1][0] == 50


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_without_tick_spans(monkeypatch, name):
    monkeypatch.setattr(obs_spans, "recorded", lambda: [
        r for r in _program_spans() if r.name != "dr.tick"])
    assert _read(name) is None
    monkeypatch.setattr(obs_spans, "recorded", lambda: [])
    assert _read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_on_a_program_without_a_recorder(monkeypatch,
                                                            name):
    monkeypatch.delattr(obs_spans, "recorded")
    assert _read(name) is None


def test_readers_are_silent_with_fewer_spans_than_ticks(monkeypatch):
    one = [r for r in _program_spans() if r.tick == 2]
    monkeypatch.setattr(obs_spans, "recorded", lambda: one)
    assert all(_read(n) is None for n in READERS)


def test_idle_reader_is_silent_without_a_device_trace(recorded):
    rec = dataclasses.replace(_records(), busy=[])
    assert program_spans.idle_unattributed_ns(rec) is None
    assert spec.metric_reader("roll_ms_per_tick")(rec) == \
        pytest.approx(150e-6)


def test_tiny_traced_run_reports_the_arrays_bytes():
    """Without the chip, at W = 128: the span metrics are on the traced
    line, and the bytes are the fleet's arrays as the device holds them
    (float32, a byte per bool) and the plan and penalties pulled back."""
    from harness import runner
    cell = spec.find_cell(SPEC["workloads"][0]["name"])
    cfg = json.loads(json.dumps(cell.config))
    W = cfg["fleet"]["args"]["num"] = 128
    cell = dataclasses.replace(cell, config=cfg)
    r = runner.run(cell, 2**31 + 7, 0.5, True, require_tpu=False,
                   cache=False)
    got = r["metrics"]
    assert set(READERS[:6]) <= set(got)
    T = 48
    # usage, jobs; entitlement, k, x2_kind; rts_coeffs, betas (W, 3);
    # is_batch; mci; λ
    up = 4 * (2 * W * T + 3 * W + 6 * W) + W + 4 * T + 4
    assert got["upload_mb_per_tick"]["value"] == pytest.approx(up * 1e-6)
    assert got["pull_mb_per_tick"]["value"] == pytest.approx(
        4 * (W * T + W) * 1e-6)
    for name in READERS[:4]:
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms"


def test_stall_script_runs_without_the_chip(tmp_path, capsys):
    """`scripts/tick_stalls.py` at a tiny size: one short window per
    cell, every tick's parts read from the spans, the record written."""
    import importlib.util
    path = ROOT / "scripts" / "tick_stalls.py"
    mod_spec = importlib.util.spec_from_file_location("tick_stalls", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    out = tmp_path / "stalls.json"
    cells = [w["name"] for w in SPEC["workloads"]]
    args = ["--windows", "1", "--seconds", "0.3", "--cpu", "--num", "64",
            "--out", str(out)]
    for name in cells:
        args += ["--workload", name]
    assert mod.main(args) == 0
    got = json.loads(out.read_text())
    assert set(got) == set(cells)
    for rec in got.values():
        (w,) = rec["windows"]
        assert w["ticks"] > 0 and w["failed"] == 0
        parts = w["part_median_ms"]
        assert {"dr.tick.window", "dr.solve.wait", "dr.solve.report",
                mod.OWN, mod.OUTSIDE} <= set(parts)
        assert sum(parts.values()) == pytest.approx(w["median_ms"],
                                                    rel=0.5)
        assert rec["summary"]["windows"] == 1
