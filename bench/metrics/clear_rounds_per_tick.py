"""Median over the window's ticks of the best responses CR3's market ran
in the tick (`clear_rounds`, counted on `dr.solve`), from the program's
own counters. A program that counts no rounds gives nothing."""
from harness import program_spans, stats


def read(rec):
    ticks = program_spans.window_ticks(rec)
    rounds = [t.count("clear_rounds") for t in ticks or ()]
    if not any(rounds):
        return None
    return stats.percentile(rounds, 50.0)
