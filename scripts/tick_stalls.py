#!/usr/bin/env python3
"""Where a stalled tick spends its time: untraced windows of benchmark
cells, read through the program's own spans.

    python3 scripts/tick_stalls.py --workload cr1_ticks.caiso_w100k \
        --workload cr1_ticks.cambium_r8_w100k --windows 12 --seconds 51

For each cell the fleet is built once (from `--seed`); each window then
gets forecasts from a seed of its own, a new `RollingHorizonSolver`
warmed as the benchmark warms it (the cold tick and the warm-up ticks),
and a closed loop of ticks for `--seconds` with no profiler, as in the
benchmark's untraced run. After each window the script reads
`repro.obs.spans.recorded()`: the window's ticks are its last `dr.tick`
spans.

A tick is stalled when its wall time is over twice the window's median.
For each stalled tick the script names the phase — a span under
`dr.tick` (self time, by name), the tick's own time outside them, or
the time outside `dr.tick` altogether — that holds most of the extra
time over that part's median in the window, and whether it holds at
least half of it (else: no phase does). It also gives the tick's CPU
time and the garbage collector's time inside it. One JSON line per
window goes to standard output; the whole record to `--out`.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

STALL = 2.0
OWN = "dr.tick (own)"
OUTSIDE = "outside dr.tick"


class GcClock:
    """Start and stop times (perf_counter ns) of every collection."""

    def __init__(self):
        self.spans, self._t0 = [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter_ns()
        elif self._t0 is not None:
            self.spans.append((self._t0, time.perf_counter_ns()))
            self._t0 = None

    def inside(self, a: int, b: int) -> float:
        return sum(max(0, min(e, b) - max(s, a)) for s, e in self.spans)

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


def tick_parts(records, n: int) -> list[dict]:
    """Self time (ns) of each part of the last `n` `dr.tick` spans."""
    roots = [r for r in records if r.name == "dr.tick"][-n:]
    below = {}
    for r in records:
        below.setdefault(r.parent, []).append(r)
    out = []
    for root in roots:
        parts, todo = {}, [root]
        while todo:
            sp = todo.pop()
            kids = below.get(sp.id, [])
            own = (sp.end_ns - sp.start_ns) - sum(k.end_ns - k.start_ns
                                                  for k in kids)
            name = OWN if sp is root else sp.name
            parts[name] = parts.get(name, 0) + own
            todo += kids
        out.append({"root": root, "parts": parts})
    return out


def window(cell, problem, seed: int, seconds: float, gclock: GcClock):
    from harness import runner, traffic
    from repro.obs import spans

    tr = cell.traffic
    actual, fc = traffic.make_forecasts(cell.config, tr, problem.T, seed)
    solver = traffic.make_solver(problem, tr, actual, fc)
    for _ in range(1 + int(tr["warm_ticks"])):
        solver.step()
    n_setup = 1 + int(tr["warm_ticks"])
    spans.clear()
    gclock.spans.clear()
    win = runner.measure(solver, seconds, fc.shape[0] - n_setup,
                         np.arange(8))
    ticks = tick_parts(spans.recorded(), len(win.walls) + win.failed)
    walls = [1e3 * w for w in win.walls]
    med = float(np.median(walls))
    for t, wall in zip(ticks, walls):
        r = t["root"]
        t["parts"][OUTSIDE] = max(0.0, wall * 1e6 - (r.end_ns - r.start_ns))
    names = sorted({k for t in ticks for k in t["parts"]})
    part_med = {k: float(np.median([t["parts"].get(k, 0) for t in ticks]))
                for k in names}
    stalls = []
    for i, (t, wall) in enumerate(zip(ticks, walls)):
        if wall <= STALL * med:
            continue
        extra = {k: 1e-6 * (t["parts"].get(k, 0) - part_med[k])
                 for k in names}
        top = max(extra, key=extra.get)
        r = t["root"]
        stalls.append({
            "tick": i, "wall_ms": wall, "cpu_ms": 1e3 * win.cpus[i],
            "extra_ms": wall - med, "part": top,
            "part_extra_ms": extra[top],
            "holds_it": extra[top] >= 0.5 * (wall - med),
            "gc_ms": 1e-6 * gclock.inside(r.start_ns, r.end_ns),
            "parts_ms": {k: 1e-6 * v for k, v in t["parts"].items()}})
    return {"seed": seed, "ticks": len(walls), "failed": win.failed,
            "median_ms": med,
            "part_median_ms": {k: 1e-6 * v for k, v in part_med.items()},
            "stalls": stalls}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--windows", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=2_700_000_001)
    ap.add_argument("--out", default=str(ROOT / ".bench_cache"
                                         / "tick_stalls.json"))
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU and without the "
                         "persistent compile cache (a check of the script)")
    ap.add_argument("--num", type=int, default=None,
                    help="workloads in the fleet, in place of the "
                         "configuration's (a check at a small size)")
    args = ap.parse_args(argv)
    from harness import runner

    runner.devices(1, require_tpu=not args.cpu)
    if not args.cpu:
        runner.compile_cache()
    gclock = GcClock()
    try:
        report = windows(args, gclock)
    finally:
        gclock.close()
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    return 0


def windows(args, gclock: GcClock) -> dict:
    """Every window of every cell, with a summary per cell."""
    from harness import spec, traffic

    report = {}
    for name in args.workload:
        cell = spec.find_cell(name)
        if args.num is not None:
            cell.config["fleet"]["args"]["num"] = args.num
        problem = traffic.build_fleet(cell.config, args.seed)
        runs = []
        for k in range(args.windows):
            w = window(cell, problem, args.seed + 1 + k, args.seconds,
                       gclock)
            print(json.dumps({"cell": name, "window": k, **w}), flush=True)
            runs.append(w)
        stalled = [s for w in runs for s in w["stalls"]]
        report[name] = {
            "windows": runs,
            "summary": {
                "windows": len(runs),
                "windows_with_stall": sum(bool(w["stalls"]) for w in runs),
                "stalled_ticks": len(stalled),
                "by_part": {p: sum(s["part"] == p and s["holds_it"]
                                   for s in stalled)
                            for p in sorted({s["part"] for s in stalled})},
                "held_by_no_part": sum(not s["holds_it"] for s in stalled),
            }}
        print(json.dumps({"cell": name, **report[name]["summary"]}),
              flush=True)
        del problem
    return report


if __name__ == "__main__":
    sys.exit(main())
