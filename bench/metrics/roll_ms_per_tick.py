"""Median over the window's ticks of the window roll (`dr.tick.window`:
`RollingHorizonSolver._window_problem` sliding usage and jobs one hour),
in ms, from the program's own spans."""
from harness import program_spans


def read(rec):
    v = program_spans.median_per_tick(
        rec, lambda t: t.total_ns("dr.tick.window"))
    return None if v is None else 1e-6 * v
