"""From a profiler trace of the window to per-layer records.

The traced run wraps the window in a `bench_window` span and each tick
in a `bench_tick` span (host spans written by the benchmark itself, on
the trace's clock). From the device planes it keeps every operation
with its XLA module and, for a Pallas call, its kernel. The reduction
(`reduce`) takes plain tuples, so a small recorded trace checks it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import pathlib
import re
import shutil

WINDOW, TICK = "bench_window", "bench_tick"
_SUFFIX = re.compile(r"[.\d]+$")


@dataclasses.dataclass(frozen=True)
class Op:
    start: float        # ns, trace clock
    end: float
    name: str           # HLO name as the trace gives it
    module: str         # XLA module ('' where the trace gives none)
    kernel: str         # Pallas kernel ('' for other operations)
    device: int


@dataclasses.dataclass
class Records:
    """What the metric readers read. Times in ns on the trace clock."""
    window: tuple
    ticks: list
    ops: list
    modules: list       # (start, end, name, device) module executions
    busy: list          # merged busy intervals of one device, per device
    recompiles: int
    info: dict          # shapes, steps, device kind, traffic
    host: list = dataclasses.field(default_factory=list)
    # (start, end, name) Python function spans of the host, from the
    # profiler's Python tracer

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over devices."""
        tot = sum(overlap(b, self.window) for b in self.busy)
        return tot * 1e-9 / max(len(self.busy), 1)


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def overlap(merged: list, span: tuple) -> float:
    """Length of `span` covered by the disjoint sorted `merged`."""
    s0, e0 = span
    i = max(bisect.bisect_right([m[0] for m in merged], s0) - 1, 0)
    tot = 0.0
    for s, e in merged[i:]:
        if s >= e0:
            break
        tot += max(0.0, min(e, e0) - max(s, s0))
    return tot


def gaps(merged: list, span: tuple) -> list:
    """Idle (start, end) stretches of `span` outside `merged`."""
    out, cur = [], span[0]
    for s, e in merged:
        if e <= cur:
            continue
        if s >= span[1]:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < span[1]:
        out.append((cur, span[1]))
    return out


def _custom_call(name: str):
    """(outputs, operands) of a Pallas TPU custom call, from the HLO text
    the trace names the operation by; None for any other operation."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    head, _, rest = name.partition(" custom-call(")
    out = head.split(" = ", 1)[-1]
    n_out = out.count("[") if out.startswith("(") else 1
    depth, end = 1, 0
    while depth and end < len(rest):     # layouts nest parentheses
        depth += {"(": 1, ")": -1}.get(rest[end], 0)
        end += 1
    return n_out, rest[:end].count("%")


def kernel_of(name: str, kernels: dict) -> str:
    """The kernel (a key of `kernels`) whose call signature, `{"outputs":
    n, "operands": m}`, the operation has; '' if none. The trace gives no
    kernel names, and HLO names renumber with every program change."""
    sig = _custom_call(name)
    if sig is None:
        return ""
    for key, want in kernels.items():
        if sig == (want["outputs"], want["operands"]):
            return key
    return ""


def module_name(name: str) -> str:
    """`jit__cr1_impl(1099...)` -> `jit__cr1_impl`."""
    return name.split("(", 1)[0]


def reduce(events, kernels: dict, recompiles: int, info: dict) -> Records:
    """`events`: (plane, line, name, start_ns, dur_ns) tuples of the
    whole trace. An operation belongs to the XLA module whose execution
    on its device contains it."""
    window, ticks, raw_ops, modules, host = None, [], [], [], []
    devices = {}
    for plane, line, name, start, dur in events:
        end = start + dur
        if plane.startswith("/host"):
            if name == WINDOW:
                window = (start, end)
            elif name == TICK:
                ticks.append((start, end))
            elif name.startswith("$"):    # the Python tracer's events
                host.append((start, end, name))
            continue
        if not plane.startswith("/device:"):
            continue
        dev = devices.setdefault(plane, len(devices))
        if line == "XLA Modules":
            modules.append((start, end, module_name(name), dev))
        elif line == "XLA Ops":
            raw_ops.append((start, end, name, dev))
    if window is None:
        raise ValueError(f"trace has no {WINDOW} span")
    modules.sort()
    starts = [m[0] for m in modules]
    ops = []
    for start, end, name, dev in raw_ops:
        i = bisect.bisect_right(starts, start)
        mod = next((m[2] for m in reversed(modules[max(0, i - 8):i])
                    if m[3] == dev and end <= m[1]), "")
        ops.append(Op(start, end, name, mod, kernel_of(name, kernels), dev))
    ticks = sorted(t for t in ticks if t[0] >= window[0]
                   and t[1] <= window[1])
    busy = [merge((o.start, o.end) for o in ops if o.device == d)
            for d in range(len(devices))]
    return Records(window, ticks, ops, modules, busy, recompiles,
                   info, sorted(host))


def load_events(path) -> list:
    """Every event of the `.xplane.pb` under `path` as a plain tuple."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {path}")
    pd = ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name, float(e.start_ns),
                            float(e.duration_ns)))
    return out


class Tracer:
    """Profiler trace of the window into a fixed directory, removed once
    read. `tick()` is the span around one tick."""

    def __init__(self, path: pathlib.Path):
        self.path = path
        self._window = None

    def start(self):
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(str(self.path))     # Python tracer on
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()

    def tick(self):
        import jax
        return jax.profiler.TraceAnnotation(TICK)

    def stop(self) -> list:
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            return load_events(self.path)
        finally:
            shutil.rmtree(self.path, ignore_errors=True)


def op_label(op: Op) -> str:
    """A name that survives renumbering: module and kernel where known,
    else module and the HLO name without its number."""
    what = op.kernel or _SUFFIX.sub("", op.name.split(" = ", 1)[0]
                                    .lstrip("%"))
    return f"{op.module}/{what}" if op.module else what


def self_times(ops: list) -> list:
    """Each operation's time not covered by operations nested in it (a
    while loop's body runs as operations of their own)."""
    out, stack = [], []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= o.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(o.end, stack[-1][0].end) - o.start
        stack.append([o, o.end - o.start])
    out += [tuple(x) for x in reversed(stack)]
    return out


def host_activity(rec: Records, t: float) -> str:
    """What the host was doing at time t: the innermost Python function
    the profiler saw (file and function, without the line), else whether
    a tick was running."""
    best = None
    for s, e, name in rec.host:
        if s > t:
            break
        if t <= e and (best is None or s >= best[0]):
            best = (s, name)
    if best is not None:
        parts = best[1].lstrip("$").split(" ", 1)
        return f"{parts[0].split(':', 1)[0]} {parts[-1]}".strip()
    return "tick" if any(a <= t <= b for a, b in rec.ticks) \
        else "between_ticks"


def breakdown(rec: Records, top: int = 10) -> dict:
    """The device operations that took most (self) time, and the longest
    idle gaps by what the host was doing in them."""
    per = {}
    for o, own in self_times([o for o in rec.ops if o.device == 0]):
        lab = op_label(o)
        per[lab] = per.get(lab, 0.0) + own * 1e-9
    device_ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    busy = rec.busy[0] if rec.busy else []
    longest = sorted(gaps(busy, rec.window), key=lambda g: g[0] - g[1])
    idle = [[host_activity(rec, 0.5 * (s + e)), (e - s) * 1e-9]
            for s, e in longest[:top]]
    return {"device_ops": [list(kv) for kv in device_ops],
            "idle_gaps": idle}
