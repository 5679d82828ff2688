"""Every CR1/CR2 engine lane against results pinned before its lanes were
rebuilt around one tick body: `tests/lane_pins.py` solved each case on
the CPU and wrote `tests/lane_pins.npz`. Plans, hour-0 commitments and
report numbers must match bit for bit: the rebuilt lanes trace the same
arithmetic.

Also pins the names of the compiled modules that the benchmark finds
device time by (`bench/traffic/*.json`, `solve_modules`): JAX names a
module after the jitted function."""
import numpy as np
import pytest

import lane_pins
from repro.core import api
from repro.core.api import CR1, CR2, CR3, SolveContext, solve
from repro.core.fleet_solver import synthetic_fleet

PINS = np.load(lane_pins.PATH)


@pytest.mark.parametrize("case", lane_pins.cases())
def test_lane_matches_its_pin(case):
    got = lane_pins.run_case(case)
    assert sorted(got) == ["D", "hour0", "numbers"]
    for key, value in got.items():
        np.testing.assert_array_equal(value, PINS[f"{case}/{key}"],
                                      err_msg=f"{case}/{key}")


@pytest.mark.parametrize("policy, module", [
    (CR1(), "jit__cr1_impl"), (CR2(outer=1), "jit__cr2_impl"),
    (CR3(outer=1, clearing_iters=1), "jit__cr3_market")])
def test_tick_modules_keep_their_names(policy, module, monkeypatch):
    """A cold solve and a donated warm tick each lower one module, named
    for its policy's tick."""
    names = []
    dispatch = api._dispatch

    def spy(run, *args, **kw):
        names.append(run.lower(*args, **kw).as_text().split()[1])
        return dispatch(run, *args, **kw)

    monkeypatch.setattr(api, "_dispatch", spy)
    p = synthetic_fleet(8, seed=0)
    cold = solve(p, policy, ctx=SolveContext(steps=4, use_kernel=False))
    solve(p, policy, ctx=SolveContext(steps=4, use_kernel=False,
                                      warm=cold.state, donate=True, shift=1,
                                      reset_mu=True))
    assert names == [f"@{module}"] * 2
