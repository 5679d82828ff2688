"""Median over the window's ticks of the host bytes passed into the
tick's dispatches (`h2d_bytes`, as the device holds them), in MB
(1e6 bytes), from the program's own counters."""
from harness import program_spans


def read(rec):
    v = program_spans.median_per_tick(rec, lambda t: t.count("h2d_bytes"))
    return None if v is None else 1e-6 * v
