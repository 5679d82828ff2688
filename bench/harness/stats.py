"""Statistics over every tick of a window."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of all `values`, interpolated
    linearly between the two nearest order statistics (numpy's
    default). Every value counts: no chunking, no trimming."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def stalls(walls, cpus, factor: float = 2.0) -> list[dict]:
    """Every tick longer than `factor` times the window's median: its
    index in the window, wall time and CPU time (seconds)."""
    if not walls:
        return []
    med = percentile(walls, 50.0)
    return [{"tick": i, "wall_s": w, "cpu_s": c}
            for i, (w, c) in enumerate(zip(walls, cpus))
            if w > factor * med]
