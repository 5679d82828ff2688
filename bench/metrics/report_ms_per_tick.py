"""Median over the window's ticks of the host sums over the pulled plan
(`dr.solve.report`, `fleet_solver._report`: carbon, with the per-row
region gather of a multi-region fleet, penalty and preservation), self
time in ms, from the program's own spans."""
from harness import program_spans


def read(rec):
    v = program_spans.median_per_tick(
        rec, lambda t: t.self_ns("dr.solve.report"))
    return None if v is None else 1e-6 * v
