"""Median over the window's ticks of the pull of the plan and penalties
to the host once the device is done (`dr.solve.pull`), in ms, from the
program's own spans."""
from harness import program_spans


def read(rec):
    v = program_spans.median_per_tick(
        rec, lambda t: t.total_ns("dr.solve.pull"))
    return None if v is None else 1e-6 * v
