"""The program's own spans of the window's ticks, on the trace's clock.

The program records a `dr.tick` span for every tick, with its phases
under it (`repro.obs.spans`: perf_counter times, parent ids, byte and
dispatch counts), in a ring that lives in this process. The window's
ticks are the last `len(rec.ticks)` `dr.tick` spans: nothing of the
program runs after the window (the reference imports none of it).

A tick's spans are put on the trace's clock one tick at a time: the
`dr.tick` span is shifted so that its start meets the start of the
benchmark's own `bench_tick` span around the same tick, and its
descendants move with it. Pairing per tick keeps the slew between the
two clocks over a whole window out of the alignment.

A program that records no such spans (an older one) gives `None`, so a
metric that reads them is left out of the line, and nothing raises.
"""
from __future__ import annotations

import dataclasses

from harness import stats
from harness.trace import gaps, merge, overlap

TICK = "dr.tick"
#: Host phases of a tick: while one runs the host works and the device
#: waits for it. `dr.solve.wait` is left out: there the host waits for
#: the device.
HOST_PHASES = ("dr.tick.forecast", "dr.tick.window", "dr.solve.dispatch",
               "dr.solve.pull", "dr.solve.report", "dr.tick.commit")


@dataclasses.dataclass(frozen=True)
class Tick:
    root: object          # the `dr.tick` record
    spans: tuple          # every record below it

    def total_ns(self, name: str) -> int:
        """Summed length of the spans called `name`."""
        return sum(s.end_ns - s.start_ns for s in self.spans
                   if s.name == name)

    def self_ns(self, name: str) -> int:
        """Summed self time (less the spans directly inside) of the spans
        called `name`."""
        ids = {s.id for s in self.spans if s.name == name}
        inner = sum(s.end_ns - s.start_ns for s in self.spans
                    if s.parent in ids)
        return self.total_ns(name) - inner

    def count(self, key: str) -> int:
        """Summed count `key` over the tick's spans."""
        return sum(s.counts.get(key, 0) for s in (self.root,) + self.spans)


def recorded():
    """The program's span records, or None where it keeps none."""
    try:
        from repro.obs import spans
        return spans.recorded()
    except (ImportError, AttributeError):
        return None


def window_ticks(rec, records=None):
    """The window's ticks (one `Tick` per `bench_tick` of `rec`, in
    order), or None when the program recorded too few `dr.tick` spans."""
    records = recorded() if records is None else records
    if not records or not rec.ticks:
        return None
    roots = sorted((s for s in records if s.name == TICK),
                   key=lambda s: s.start_ns)
    if len(roots) < len(rec.ticks):
        return None
    roots = roots[-len(rec.ticks):]
    below = {}
    for s in records:
        below.setdefault(s.parent, []).append(s)
    out = []
    for root in roots:
        found, todo = [], [root.id]
        while todo:
            kids = below.get(todo.pop(), [])
            found += kids
            todo += [k.id for k in kids]
        out.append(Tick(root, tuple(found)))
    return out


def median_per_tick(rec, of, records=None):
    """Median over the window's ticks of `of(tick)`, or None."""
    ticks = window_ticks(rec, records)
    if ticks is None:
        return None
    return stats.percentile([of(t) for t in ticks], 50.0)


def idle_unattributed_ns(rec, records=None):
    """Per window tick: device idle inside its `bench_tick` span that no
    host phase covers, in ns (None without spans or a device trace)."""
    ticks = window_ticks(rec, records)
    if ticks is None or not rec.busy:
        return None
    out = []
    for (start, end), tick in zip(rec.ticks, ticks):
        shift = start - tick.root.start_ns
        host = merge((s.start_ns + shift, s.end_ns + shift)
                     for s in tick.spans if s.name in HOST_PHASES)
        idle = gaps(rec.busy[0], (start, end))
        out.append(sum((e - s) - overlap(host, (s, e)) for s, e in idle))
    return out
