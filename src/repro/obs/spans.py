"""Program spans: where the host path spends each tick, on one clock.

`span(name)` times a block of host code and records it three ways:

  * a `jax.profiler.TraceAnnotation` of the same name, so the span lands
    in any profiler trace on the device trace's clock (TensorBoard,
    Perfetto) — opened only once jax is imported, so `repro.obs` itself
    stays jax-free;
  * `start_ns` / `end_ns` from `time.perf_counter_ns()`, the id of its
    parent span, the tick it belongs to (inherited from the enclosing
    span unless given), and any counts attached with `sp.count(...)`;
  * on exit, one `SpanRecord` appended to a bounded module-level ring
    (`RING_SIZE` records, the oldest dropped first): `recorded()` reads
    it, `clear()` empties it. The ring is always on; with the profiler
    off a span costs a few microseconds.

The program opens these spans on its tick path (`dr.tick`, its phases,
`dr.solve` and the dispatch / wait / pull / report under it; a CR3
solve also counts `clear_rounds` and `unbalanced_regions` on
`dr.solve` with `count`), so a caller — a benchmark, an operator's
dashboard — can read where a tick's time went without a profiler::

    with obs.span("upload") as sp:
        sp.count("h2d_bytes", x.nbytes)
        y = f(x)
    rec = obs.spans.recorded()[-1]      # name, start_ns, end_ns, counts

Each open span also rolls its time and counts up into its parent: a
parent's `phases_ms` holds the self time of every span below it, by
name, and `totals` its own plus its descendants' counts.

JAX dispatch is asynchronous, so a span around `fn(x)` times the
enqueue, not the compute. `bind` keeps its meaning: values bound to the
span get `jax.block_until_ready` before the closing timestamp::

    with obs.span("cr1-solve", writer=w) as sp:
        sp.bind(solve(problem, CR1(lam=1.45)).D)
    print(sp.elapsed_s)

No program span binds, so the spans add no sync point of their own.
These are HOST-side tools. Never open a span (or anything else that
blocks on device work) inside jit-traced code — the drlint rule
`host-sync-in-jit` fires on exactly that; in-solve telemetry rides the
dispatch as stacked aux outputs instead (`repro.obs.telemetry`).
"""
from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import Any, NamedTuple, Optional

from repro.obs.events import EventWriter, SpanEvent

__all__ = ["RING_SIZE", "SpanRecord", "SpanScope", "clear", "count",
           "recorded", "span"]

#: Records the ring keeps: over 1,000 ticks of a dozen spans each.
RING_SIZE = 16384


class SpanRecord(NamedTuple):
    """One closed span, as the ring keeps it (a tuple: cheap to make)."""
    name: str
    id: int
    parent: Optional[int]     # id of the enclosing span (None at a root)
    tick: Optional[int]       # tick index, inherited from the enclosing span
    start_ns: int             # time.perf_counter_ns() at entry
    end_ns: int               # ... and at exit
    counts: dict              # this span's own counts (not its children's)
    meta: Optional[dict] = None

    @property
    def elapsed_ns(self) -> int:
        return self.end_ns - self.start_ns


_RING: collections.deque = collections.deque(maxlen=RING_SIZE)
_IDS = itertools.count(1)
_LOCAL = threading.local()
_ANNOTATION = None


def recorded() -> list[SpanRecord]:
    """The ring's records, oldest first (a span is appended at its exit,
    so children come before their parent)."""
    return list(_RING)


def clear() -> None:
    """Empty the ring."""
    _RING.clear()


def _stack() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def _annotation():
    """`jax.profiler.TraceAnnotation` once jax is imported, else None."""
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class SpanScope:
    """Live handle of an open span, yielded by `span`.

    Read `elapsed_s` after the block (inside it, the time so far), and
    `phases_ms` and `totals` for what the spans below it recorded.
    """

    __slots__ = ("name", "id", "parent", "tick", "meta", "start_ns",
                 "end_ns", "counts", "totals", "phases_ns", "_child_ns",
                 "_bound", "_writer", "_ann")

    def __init__(self, name: str, *, tick: int | None = None,
                 writer: Optional[EventWriter] = None,
                 meta: dict | None = None):
        self.name = name
        self.tick = tick
        self.meta = meta
        self.id = 0
        self.parent = None
        self.start_ns = self.end_ns = 0
        self.counts: dict = {}
        self.totals: dict = {}
        self.phases_ns: dict = {}
        self._child_ns = 0
        self._bound: tuple = ()
        self._writer = writer
        self._ann = None

    def bind(self, *values: Any) -> Any:
        """Attach outputs to synchronize on at span exit.

        Returns the single value (or the tuple) unchanged, so call
        sites can wrap an expression in-line::

            result = sp.bind(solve(...))
        """
        self._bound = self._bound + values
        return values[0] if len(values) == 1 else values

    def count(self, key: str, n: int) -> None:
        """Add `n` to this span's count `key` (bytes moved, dispatches)."""
        self.counts[key] = self.counts.get(key, 0) + n
        self.totals[key] = self.totals.get(key, 0) + n

    @property
    def elapsed_s(self) -> float:
        end = self.end_ns or time.perf_counter_ns()
        return (end - self.start_ns) * 1e-9

    @property
    def phases_ms(self) -> dict:
        """Self time, in ms, of every span closed below this one, summed
        by name."""
        return {k: v * 1e-6 for k, v in self.phases_ns.items()}

    def __enter__(self) -> "SpanScope":
        stack = _stack()
        if stack:
            up = stack[-1]
            self.parent = up.id
            if self.tick is None:
                self.tick = up.tick
        self.id = next(_IDS)
        stack.append(self)
        ann = _annotation()
        if ann is not None:
            self._ann = ann(self.name)
            self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        try:
            if self._bound:
                import jax
                jax.block_until_ready(self._bound)
        finally:
            self.end_ns = time.perf_counter_ns()
            if self._ann is not None:
                self._ann.__exit__(*exc)
                self._ann = None
            stack = _stack()
            stack.pop()
            self._close(stack[-1] if stack else None)

    def _close(self, up: "SpanScope | None") -> None:
        dur = self.end_ns - self.start_ns
        if up is not None:
            up._child_ns += dur
            ph = up.phases_ns
            ph[self.name] = ph.get(self.name, 0) + dur - self._child_ns
            for k, v in self.phases_ns.items():
                ph[k] = ph.get(k, 0) + v
            tot = up.totals
            for k, v in self.totals.items():
                tot[k] = tot.get(k, 0) + v
        _RING.append(SpanRecord(self.name, self.id, self.parent, self.tick,
                                self.start_ns, self.end_ns, self.counts,
                                self.meta))
        if self._writer is not None:
            self._writer.write(SpanEvent(
                name=self.name, elapsed_s=dur * 1e-9, meta=self.meta,
                start_ns=self.start_ns, parent=self.parent, tick=self.tick,
                counts=dict(self.counts)))


def count(**counts: int) -> None:
    """Add `counts` to the innermost span open in this thread (nothing
    when none is open): numbers a block learns only once its results are
    on the host, such as CR3's `clear_rounds` under `dr.solve`."""
    stack = _stack()
    if stack:
        for key, n in counts.items():
            stack[-1].count(key, n)


def span(name: str, *, tick: int | None = None,
         writer: Optional[EventWriter] = None,
         meta: dict | None = None) -> SpanScope:
    """Open a span named `name` (use as `with span(...) as sp:`).

    `tick` tags the span and everything opened inside it with a tick
    index (default: the enclosing span's). Any values passed to the
    scope's `.bind(...)` get `jax.block_until_ready` before the closing
    timestamp, so the span covers the device compute those values
    depend on — not just the time to enqueue it.

    When `writer` is given, a `SpanEvent` is appended to the ledger on
    exit (including on exception — the partial timing is still real).
    """
    return SpanScope(name, tick=tick, writer=writer, meta=meta)
