"""Traces and backend compiles that JAX reported inside the window."""


def read(rec):
    return float(rec.recompiles)
