"""Median over the window's ticks of the bytes pulled back to the host
(`d2h_bytes`), in MB (1e6 bytes), from the program's own counters."""
from harness import program_spans


def read(rec):
    v = program_spans.median_per_tick(rec, lambda t: t.count("d2h_bytes"))
    return None if v is None else 1e-6 * v
