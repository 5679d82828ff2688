"""CPU tests of the `cr3_ticks` cell: a small run of CR3's streaming
market through the benchmark harness reports `correct`, each fault the
CR1 cells' tests plant under the timed path makes it false, and so does
a market that never clears (every tick answers the configured price).

The run keeps the cell's traffic and cuts the fleet to 2,048 workloads,
256 a region. A market couples a region's rows through its Eq.-6 sums:
at 16 workloads a region (the CR1 tests' size) one workload's rounding
difference moves its region's balance by percents, the program and the
reference clear in different rounds, and the check's numbers read as
far apart as the bfloat16 control's do.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import warnings

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "bench"))

from test_bench_harness import _fault  # noqa: E402

from harness import runner, spec  # noqa: E402

CELL = "cr3_ticks.cambium_r8_w100k_market"
SMALL_W = 2048
#: Most ticks a run may take: a planted fault that returns at once would
#: otherwise fill the window with ticks for the reference to replay.
MAX_TICKS = 5
#: Ticks of the sound run, its window the last five: `energy_total_rel`
#: limits a window's committed energy (the cell's window holds 10-13
#: ticks), and a one-tick window would hold a single tick's gap to it.
#: Over a five-tick window three seeds read 0.42-0.91 of that limit here.
SOUND_TICKS = 8


def _run(max_ticks=MAX_TICKS, seconds=0.5):
    cell = spec.find_cell(CELL)
    cfg = json.loads(json.dumps(cell.config))
    cfg["fleet"]["args"]["num"] = SMALL_W
    tr = {**cell.traffic, "max_ticks": max_ticks}
    return runner.run(dataclasses.replace(cell, config=cfg, traffic=tr),
                      2**31 + 11, seconds, False, require_tpu=False,
                      cache=False)


def test_small_run_is_correct():
    r = _run(SOUND_TICKS, seconds=1e6)
    assert r["correct"] is True and r["failed"] == 0
    setup = 1 + int(spec.find_cell(CELL).traffic["warm_ticks"])
    assert r["attempted"] == SOUND_TICKS - setup
    assert set(r["metrics"]) == {"tick_ms_p50", "setup_s"}
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_planted_fault_makes_correct_false(kind, monkeypatch):
    from repro.core.streaming import RollingHorizonSolver
    monkeypatch.setattr(RollingHorizonSolver, "_solve", _fault(kind))
    r = _run()
    assert r["correct"] is False
    assert any(not c["value"] <= c["limit"] for c in r["checks"].values())


def test_market_that_never_clears_makes_correct_false(monkeypatch):
    """The best responses run, but no price is ever cut: every tick's
    plan answers the configured ρ, as with `clearing_iters` 0. Only
    `energy_total_rel` sees it: 1.9 times its limit over one window
    tick, and at the limit over the sound run's five."""
    from repro.core import api

    def no_clearing(real):
        def run(p, rho, tax, reg_scale, state, _iters, *args, **kw):
            return real(p, rho, tax, reg_scale, state, 0, *args, **kw)
        return run

    for name in ("_cr3_cleared", "_cr3_cleared_donated"):
        monkeypatch.setattr(api, name, no_clearing(getattr(api, name)))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        r = _run()
    assert r["correct"] is False
    assert any(not c["value"] <= c["limit"] for c in r["checks"].values())
