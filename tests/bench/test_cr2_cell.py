"""CPU tests of the `cr2_ticks` cell: a small run of CR2's streaming
ticks through the benchmark harness reports `correct`, and each fault
planted under the timed path makes it false: the CR1 cells' state handed
back unchanged and half the plan left out, fairness references made
from the unslid fleet, and hour 1 committed as hour 0.

The run keeps the cell's traffic and cuts the fleet to 2,048 workloads,
256 a region, as the `cr3_ticks` tests do: each region's residual scale
is the mean of its rows' references, so a region of a few workloads
would read far from the cell.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "bench"))

from test_bench_harness import _fault  # noqa: E402

from harness import runner, spec  # noqa: E402

CELL = "cr2_ticks.cambium_r8_w100k_fair"
SMALL_W = 2048
#: Ticks of a run with hour 1 committed, the state handed back unchanged
#: or half the plan left out: two in the window.
SHORT_TICKS = 5
#: Ticks of the sound run, its window the last five, as the `cr3_ticks`
#: tests run theirs.
SOUND_TICKS = 8
#: Ticks of the run with the unslid references: the real-time rows'
#: references do not depend on the window's first hour (a sum over its
#: hours), the batch rows' move by a median 4-9% over the first 3-11
#: hours, so the fault shows once the window has slid far from hour 0.
#: The set-up's three ticks and a window of T = 48: every hour offset.
UNSLID_TICKS = 51


def _run(max_ticks):
    cell = spec.find_cell(CELL)
    cfg = json.loads(json.dumps(cell.config))
    cfg["fleet"]["args"]["num"] = SMALL_W
    tr = {**cell.traffic, "max_ticks": max_ticks}
    return runner.run(dataclasses.replace(cell, config=cfg, traffic=tr),
                      2**31 + 11, 1e6, False, require_tpu=False,
                      cache=False)


def test_small_run_is_correct():
    r = _run(SOUND_TICKS)
    assert r["correct"] is True and r["failed"] == 0
    setup = 1 + int(spec.find_cell(CELL).traffic["warm_ticks"])
    assert r["attempted"] == SOUND_TICKS - setup
    assert set(r["metrics"]) == {"tick_ms_p50", "tick_ms_p90", "setup_s"}
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


def _unslid_references(monkeypatch):
    """A sliding tick makes its fairness references from the fleet as it
    lies at offset 0, whatever hour the window starts at."""
    from repro.core import api
    eng = api._ENGINES["cr2"]

    def refs(p, knob, use_kernel, window=None):
        return eng.window(api._slide(p, -window), knob, use_kernel, 0)

    def impl(*args, **kw):      # a new function: a new trace
        return api._cr2_impl(*args, **kw)

    monkeypatch.setitem(api._ENGINES, "cr2", eng._replace(window=refs))
    monkeypatch.setitem(api._TICK, "cr2", api._jits(
        impl, api._TICK_STATIC, donate=2, checked=True))


def _hour_one_committed(monkeypatch):
    """Every tick commits hour 1 of its plan as hour 0, and the plan
    reads one hour early."""
    from repro.core.streaming import RollingHorizonSolver
    real = RollingHorizonSolver._solve

    def solve(self, p, warm, steps, shift, reset_mu):
        plan = real(self, p, warm, steps, shift, reset_mu)
        D = np.roll(np.array(plan.D), -1, axis=1)
        return dataclasses.replace(plan, D=D, hour0=D[:, 0].copy())

    monkeypatch.setattr(RollingHorizonSolver, "_solve", solve)


def _harness_fault(kind):
    """Plants `test_bench_harness._fault(kind)` under the timed path."""
    def plant(monkeypatch):
        from repro.core.streaming import RollingHorizonSolver
        monkeypatch.setattr(RollingHorizonSolver, "_solve", _fault(kind))
    return plant


@pytest.mark.parametrize("plant, ticks", [
    (_unslid_references, UNSLID_TICKS),
    (_hour_one_committed, SHORT_TICKS),
    (_harness_fault("unchanged"), SHORT_TICKS),
    (_harness_fault("half"), SHORT_TICKS)],
    ids=["unslid_references", "hour_one_committed", "unchanged", "half"])
def test_planted_fault_makes_correct_false(plant, ticks, monkeypatch):
    plant(monkeypatch)
    r = _run(ticks)
    assert r["correct"] is False
    assert any(not c["value"] <= c["limit"] for c in r["checks"].values())
