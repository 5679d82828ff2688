#!/usr/bin/env python3
"""The two readings each correctness limit is set from, for one cell.

    python3 bench/tools/readings.py --workload cr1_ticks.caiso_w100k \
        --seeds 1101-1112 --control-seeds 1201-1203 --ticks 105 \
        --out chiprun_out/readings.json

Lower reading: the program at the cell's own size and load, one process,
on each seed of `--seeds`: set-up as a run does, `--ticks` window ticks,
then the same comparison as a run. Upper reading: the control, the
plain reference computed in bfloat16 in the program's place, compared
with the float32 reference over as many ticks, on each `--control-seeds`.
Prints one line per seed and the largest program and smallest control
reading of each number; writes all of it to `--out`.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--ticks", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import numpy as np
    from harness import check, runner, spec
    cell = spec.find_cell(args.workload)
    runner.devices(cell.chips, require_tpu=True)
    runner.compile_cache()
    res = {"workload": cell.name, "ticks": args.ticks, "program": {},
           "control": {}}
    arrays = {}     # per seed: window-mean commitments, program/control
                    # first and the float32 reference second

    for seed in args.seeds:
        t0 = time.perf_counter()
        st = runner.set_up(cell, seed)
        win = runner.measure(st.solver, float("inf"), args.ticks, st.rows)
        got = {"commits": np.stack(win.commits),
               "plan": np.asarray(win.last.plan.D)[st.rows]
               .astype(np.float64)}
        st.solver = win.last = None
        n = st.n_setup + len(win.walls)
        ref, commits, plan = runner.replay(cell, st, n, "float32")
        want = {"commits": commits[st.n_setup:], "plan": plan}
        nums = check.numbers(got, want, ref, st.n_setup,
                             st.forecasts[st.n_setup:n])
        arrays[f"program_{seed}"] = np.stack([got["commits"].mean(0),
                                              want["commits"].mean(0)])
        nums["failed"] = win.failed
        nums["tick_ms_p50"] = 1e3 * float(np.median(win.walls))
        res["program"][seed] = nums
        print(f"program {seed}: {json.dumps(nums)} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)

    for seed in args.control_seeds:
        t0 = time.perf_counter()
        st = runner.set_up(cell, seed)
        st.solver = None
        n = st.n_setup + args.ticks
        ref, c32, p32 = runner.replay(cell, st, n, "float32")
        _, c16, p16 = runner.replay(cell, st, n, "bfloat16")
        nums = check.numbers({"commits": c16[st.n_setup:], "plan": p16},
                             {"commits": c32[st.n_setup:], "plan": p32},
                             ref, st.n_setup, st.forecasts[st.n_setup:n])
        res["control"][seed] = nums
        arrays[f"control_{seed}"] = np.stack([c16[st.n_setup:].mean(0),
                                              c32[st.n_setup:].mean(0)])
        print(f"control {seed}: {json.dumps(nums)} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)

    keys = [k for k in next(iter(res["control"].values()), {})]
    for side, pick in (("program", max), ("control", min)):
        if res[side]:
            res[f"{side}_{pick.__name__}"] = {
                k: pick(v[k] for v in res[side].values()) for k in keys}
            print(f"{side} {pick.__name__}: "
                  f"{json.dumps(res[f'{side}_{pick.__name__}'])}")
    res["limits_in_use"] = cell.limits
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(res, indent=1))
    np.savez_compressed(str(args.out).rsplit(".", 1)[0] + ".npz", **arrays)
    return 0


if __name__ == "__main__":
    sys.exit(main())
