"""One run of one cell: set-up, a measured window of ticks, the check.

The loop is closed with one client, as an hourly coordinator runs: the
next forecast is handed to `RollingHorizonSolver.step` when the previous
tick has committed its hour-0 curtailment on the host. A tick's time is
the host clock around that call. Set-up (fleet build, the cold tick
that compiles or loads the programs, the warm-up ticks) ends when the
window's first tick starts; nothing compiles inside the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

from harness import check, spec, stats, trace as tracing, traffic

STALL_FACTOR = 2.0


class NoChip(RuntimeError):
    """The process holds no TPU, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (Linux `/proc`), so set-up
    counts the interpreter and imports too."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        btime = next(int(line.split()[1]) for line in f
                     if line.startswith("btime"))
    return time.time() - (btime + start_ticks / os.sysconf("SC_CLK_TCK"))


def devices(chips: int, require_tpu: bool):
    """The cell's chips; `NoChip` when JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def compile_cache() -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR`
    where it is set, else a fixed directory inside the checkout. Every
    program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.ROOT / ".bench_cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts the traces and backend compiles that JAX reports between
    `start()` and `stop()`."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _event(self, name, _secs, **_kw):
        if name in self.EVENTS:
            self.count += 1

    def start(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def stop(self) -> int:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._event)
        return self.count


@dataclasses.dataclass
class Window:
    walls: list
    cpus: list
    commits: list
    failed: int
    last: object = None


def measure(solver, seconds: float, max_ticks: int, rows,
            annotate=contextlib.nullcontext):
    """Issue ticks back to back for `seconds` (closed loop, one client).
    Keeps each tick's wall and CPU time and the sampled rows' commits."""
    w = Window([], [], [], 0)
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline and len(w.walls) + w.failed \
            < max_ticks:
        t0, c0 = time.perf_counter(), time.process_time()
        try:
            with annotate():
                out = solver.step()
        except Exception as e:      # a tick that raises counts as failed
            print(f"tick failed: {e!r}", file=sys.stderr)
            w.failed += 1
            continue
        w.walls.append(time.perf_counter() - t0)
        w.cpus.append(time.process_time() - c0)
        w.commits.append(np.asarray(out.committed)[rows])
        w.last = out
    return w


def _line(tag: str, obj) -> None:
    print(f"{tag} {json.dumps(obj)}", flush=True)


@dataclasses.dataclass
class Setup:
    """A cell's inputs and its solver, warmed up."""
    problem: object
    solver: object
    forecasts: np.ndarray
    fleet: dict
    rows: np.ndarray
    n_setup: int
    parts: dict


def set_up(cell: spec.Cell, seed: int) -> Setup:
    """Build the fleet and the forecasts from the seed, then run the cold
    tick (which compiles or loads the programs) and the warm-up ticks."""
    tr = cell.traffic
    t0 = time.perf_counter()
    problem = traffic.build_fleet(cell.config, seed)
    fleet_s = time.perf_counter() - t0
    actual, fc = traffic.make_forecasts(cell.config, tr, problem.T, seed)
    solver = traffic.make_solver(problem, tr, actual, fc)
    rows = traffic.sample_rows(problem.W, int(tr["check"]["rows"]), seed)
    t0 = time.perf_counter()
    solver.step()
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(int(tr["warm_ticks"])):
        solver.step()
    warm_s = time.perf_counter() - t0
    return Setup(problem, solver, fc, traffic.fleet_arrays(problem),
                 rows, 1 + int(tr["warm_ticks"]),
                 {"fleet_build_s": fleet_s, "cold_tick_s": cold_s,
                  "warm_ticks_s": warm_s, "warm_ticks": int(tr["warm_ticks"])})


def replay(cell: spec.Cell, st: Setup, n_ticks: int, dtype: str):
    """The plain reference over ticks 0..n_ticks-1 for the sampled rows:
    (its evaluator, commits (n_ticks, S), last plan (S, T))."""
    tr = cell.traffic
    ref = spec.reference(tr["reference"]).Replay(st.fleet, st.rows,
                                                  tr["algorithm"], dtype)
    commits, plan = ref.run(st.forecasts, n_ticks,
                            tr["solver"]["cold_steps"],
                            tr["solver"]["warm_steps"])
    return ref, commits, plan


def compare(cell: spec.Cell, st: Setup, got: dict, n_win: int) -> dict:
    """The numbers of `check` for the window's `got` commits and last plan
    against the float32 reference."""
    n_total = st.n_setup + n_win
    ref, commits, plan = replay(cell, st, n_total, "float32")
    return check.numbers(got, {"commits": commits[st.n_setup:],
                               "plan": plan},
                         ref, st.n_setup, st.forecasts[st.n_setup:n_total])


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, cache: bool = True) -> dict:
    """One run of `cell`; returns the result object (the last line).
    `require_tpu=False` and `cache=False` let the CPU tests drive the
    rest of a run without the chip and without a persistent cache."""
    age0 = process_age_s()
    t_start = time.perf_counter()
    devs = devices(cell.chips, require_tpu)
    if cache:
        compile_cache()
    tr = cell.traffic
    st = set_up(cell, seed)
    counter = CompileCounter()
    tracer = None
    annotate = contextlib.nullcontext
    if trace:
        tracer = tracing.Tracer(spec.ROOT / ".bench_cache" / "trace")
        annotate = tracer.tick
        tracer.start()
    setup_s = age0 + (time.perf_counter() - t_start)
    counter.start()
    win = measure(st.solver, seconds, st.forecasts.shape[0] - st.n_setup,
                  st.rows, annotate)
    recompiles = counter.stop()
    events = tracer.stop() if tracer else None
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)

    parts = st.parts
    _line("setup", {"setup_s": setup_s, "start_imports_inputs_s":
                    setup_s - parts["fleet_build_s"] - parts["cold_tick_s"]
                    - parts["warm_ticks_s"], **parts})
    _line("ticks", {"wall_s": win.walls, "cpu_s": win.cpus})
    _line("stalls", {"median_s": stats.percentile(win.walls, 50.0)
                     if win.walls else None, "factor": STALL_FACTOR,
                     "ticks": len(win.walls),
                     "stalls": stats.stalls(win.walls, win.cpus,
                                            STALL_FACTOR)})

    # The check: the program's state goes first, then the reference runs.
    n_win = len(win.walls)
    nums = {}
    if n_win:
        got = {"commits": np.stack(win.commits),
               "plan": np.asarray(win.last.plan.D)[st.rows]
               .astype(np.float64)}
        st.solver = win.last = None
        gc.collect()
        nums = compare(cell, st, got, n_win)
    correct = bool(n_win and win.failed == 0
                   and check.verdict(nums, cell.limits))
    checks = {k: {"value": nums.get(k, float("nan")), "limit": lim}
              for k, lim in cell.limits.items()}
    print(f"readings {json.dumps(nums)}", file=sys.stderr)

    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(mem)}
    metrics = {}
    result = {"correct": correct, "attempted": n_win + win.failed,
              "failed": win.failed, "metrics": metrics, "device": dev}
    if trace:
        info = {"W": st.problem.W, "T": st.problem.T,
                "W_batch": int(np.asarray(st.problem.is_batch).sum()),
                "R": st.forecasts.shape[1],
                "warm_steps": int(tr["solver"]["warm_steps"]),
                "device_kind": devs[0].device_kind,
                "solve_modules": tr["solve_modules"]}
        rec = tracing.reduce(events, tr["kernels"], recompiles, info)
        dev["busy_s"], dev["window_s"] = rec.busy_s, rec.window_s
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["breakdown"] = tracing.breakdown(rec)
    else:
        for m in cell.end_to_end:
            v = end_to_end(m["name"], win.walls, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    return result


def end_to_end(name: str, walls: list, setup_s: float):
    """The end-to-end metrics, from the host clock."""
    if name == "setup_s":
        return setup_s
    if not walls:
        return None
    if name == "tick_ms_p50":
        return 1e3 * stats.percentile(walls, 50.0)
    if name == "tick_ms_p90":
        return 1e3 * stats.percentile(walls, 90.0)
    raise KeyError(f"no end-to-end metric {name!r}")
