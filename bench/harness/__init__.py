"""Benchmark harness for the fleet DR decision path on one TPU chip.

`run.py` at the top of `bench/` is the command; this package holds the
yardstick: the spec loader (`spec`), the traffic generator (`traffic`,
`signals`), the run loop (`runner`), the statistics (`stats`), the
trace reduction (`trace`), the peaks table and work counts (`peaks`,
`work`) and the correctness comparison (`check`). Configurations,
traffic mixes, references, limits and per-layer metrics are data or
small modules under `bench/`, found by the names in `BENCHMARK.json`.
"""
