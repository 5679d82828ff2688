"""Median over the window's ticks of the time spent calling into the
jitted solve until it returns (`dr.solve.dispatch`: argument conversion,
upload, enqueue), in ms, from the program's own spans."""
from harness import program_spans


def read(rec):
    v = program_spans.median_per_tick(
        rec, lambda t: t.total_ns("dr.solve.dispatch"))
    return None if v is None else 1e-6 * v
