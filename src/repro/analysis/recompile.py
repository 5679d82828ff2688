"""`recompile_guard` — assert jit trace-cache stability at runtime.

The engine stack's performance story rests on claims the test suite
historically could not check directly: a warm streaming re-solve is
*the same compiled trace* as the cold solve (PR 2), and a scanned
streaming day is *one* XLA dispatch (PR 6). Both break silently — a
stray weak type, a drifting static argument, or an accidentally
non-hashable static turns "one trace" into "a fresh compile per tick"
with no error, just a 100x slowdown.

`recompile_guard` makes the claim executable::

    with recompile_guard() as stats:          # max_compiles=0
        solver.step()                          # must hit the jit cache
    # raises RecompileError on exit if anything was traced/lowered

It counts two signals while active:

  * ``stats.traces``    — fresh jaxpr traces (`pjit` trace-cache
    misses). A cold jit call counts several (one per nested pjit);
    a warm call counts zero.
  * ``stats.lowerings`` — jaxpr→MLIR module lowerings, i.e. actual
    compilations handed to XLA.

The guard fires when either count exceeds ``max_compiles`` on normal
exit (an exception inside the body propagates unchanged). Because a
single cold compile produces an implementation-defined number of
nested traces, the useful contract is ``max_compiles=0`` — "this
region must be compile-free" — which is exactly the warm/one-dispatch
claim. For diagnostics, read the counts off the yielded stats object.

Implementation note: the counters listen to jax's internal test
events (`jax._src.util.test_event_listener`): ``trace_to_jaxpr`` fires
on every jit trace-cache miss and ``lower_jaxpr_to_module`` on every
lowering — the events `jax._src.test_util`'s counting helpers read. A
listener already installed keeps receiving every event. If a jax
upgrade removes the hook, the guard raises at entry rather than
silently counting nothing.
"""
from __future__ import annotations

import contextlib
import dataclasses

__all__ = ["RecompileError", "RecompileStats", "recompile_guard"]


class RecompileError(RuntimeError):
    """A `recompile_guard` region compiled more than it promised."""


@dataclasses.dataclass
class RecompileStats:
    """Counters for one guard region (also usable purely for reporting
    with ``max_compiles=None``)."""
    traces: int = 0        # fresh pjit jaxpr traces (cache misses)
    lowerings: int = 0     # jaxpr->MLIR lowerings (XLA compiles)

    @property
    def compiled(self) -> bool:
        return self.traces > 0 or self.lowerings > 0


@contextlib.contextmanager
def recompile_guard(max_compiles: int | None = 0, *, label: str = ""):
    """Count jit traces/lowerings in the region; raise if over budget.

    Args:
      max_compiles: fail on exit when `traces` or `lowerings` exceeds
        this. 0 (default) asserts the region is compile-free — the
        warm-path/one-dispatch contract. None disables the check
        (pure measurement).
      label: prefix for the error message (e.g. the tick being run).

    Yields a `RecompileStats` whose counters update live.
    """
    from jax._src import util as _util

    if not hasattr(_util, "test_event_listener"):
        raise RecompileError(
            "recompile_guard found no jax test-event hook to count with "
            "(jax internals moved?) — refusing to guard nothing")
    stats = RecompileStats()
    prev = _util.test_event_listener

    def count(name, *args):
        if name == "trace_to_jaxpr":
            stats.traces += 1
        elif name == "lower_jaxpr_to_module":
            stats.lowerings += 1
        if prev is not None:
            prev(name, *args)

    _util.test_event_listener = count
    try:
        yield stats
    finally:
        _util.test_event_listener = prev
    if max_compiles is not None and (stats.traces > max_compiles
                                     or stats.lowerings > max_compiles):
        where = f"{label}: " if label else ""
        raise RecompileError(
            f"{where}guarded region compiled: {stats.traces} fresh "
            f"trace(s), {stats.lowerings} lowering(s) "
            f"(allowed {max_compiles}) — a static argument, shape, or "
            f"dtype drifted and the jit cache missed")
