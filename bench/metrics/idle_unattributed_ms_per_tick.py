"""Median over the window's ticks of the device's idle time inside the
tick that no host phase of the program covers (forecast, window roll,
dispatch, pull, report, commit), in ms: the program's spans, shifted
per tick onto the trace's clock, against the device trace."""
from harness import program_spans, stats


def read(rec):
    per_tick = program_spans.idle_unattributed_ns(rec)
    if not per_tick:
        return None
    return 1e-6 * stats.percentile(per_tick, 50.0)
