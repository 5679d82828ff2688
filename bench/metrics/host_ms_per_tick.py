"""Median over the window's ticks of the tick's wall time that no device
operation covers, in ms: the host's own part of a tick. A median, so
that one stalled tick does not move it."""
from harness import stats
from harness.trace import overlap


def read(rec):
    if not rec.ticks or not rec.busy:
        return None
    busy = rec.busy[0]
    return 1e-6 * stats.percentile(
        [(e - s) - overlap(busy, (s, e)) for s, e in rec.ticks], 50.0)
