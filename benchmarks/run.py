"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (stdout) and, per benchmark,
writes a machine-readable ``var/BENCH_<name>.json`` record (wall times,
problem sizes and objective/parity numbers parsed from the CSV rows,
plus host metadata) so the performance trajectory is tracked across PRs
— diff two checkouts' ``var/BENCH_*.json`` instead of eyeballing
stdout. Usage:

  PYTHONPATH=src python -m benchmarks.run [--only fig8] [--no-json]
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
import traceback

#: value with an optional unit suffix the benchmarks emit (%, x, pp, ms,
#: us, s, ...): group 1 is the numeric part.
_NUM = re.compile(r"^([+-]?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)([a-zA-Z%/]{0,3})$")


def _parse_rows(rows) -> list[dict]:
    """CSV rows `name,us_per_call,derived` -> JSON records. The derived
    column's `key=value` tokens (objective, parity, speedup, latencies,
    problem sizes) are lifted into a dict — numeric wherever the value is
    a number with at most a short unit suffix — so trajectories diff
    structurally."""
    out = []
    for line in rows or []:
        name, us, derived = str(line).split(",", 2)
        numbers = {}
        for tok in derived.replace(";", " ").split():
            if "=" in tok:
                k, v = tok.split("=", 1)
                m = _NUM.match(v)
                numbers[k] = float(m.group(1)) if m else v
        out.append({"name": name, "us_per_call": float(us),
                    "derived": derived, "numbers": numbers})
    return out


def _write_json(bench: str, status: str, rows, elapsed_s: float) -> None:
    import os
    import tempfile

    from benchmarks.common import VAR
    from repro.obs.events import host_meta
    VAR.mkdir(exist_ok=True)
    record = {
        "benchmark": bench,
        "status": status,
        "elapsed_s": round(elapsed_s, 3),
        "rows": _parse_rows(rows),
        # the shared obs fingerprint: platform/devices/jax+jaxlib
        # versions/pallas-interpret flag — diffable across checkouts
        "host": host_meta(),
        "unix_time": int(time.time()),
    }
    # temp-file + os.replace (the fleetcache pattern): an interrupted run
    # must never leave a truncated record for report.py --bench to choke on
    fd, tmp = tempfile.mkstemp(dir=VAR, prefix=f"BENCH_{bench}.",
                               suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        f.write(json.dumps(record, indent=1))
    os.replace(tmp, VAR / f"BENCH_{bench}.json")


def main() -> None:
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing var/BENCH_<name>.json records")
    args = ap.parse_args()

    from benchmarks import paper_figs, perf_micro, scenario_ensemble
    benches = [
        ("fig1_carbon_series", paper_figs.fig1_carbon_series),
        ("table5_lasso", paper_figs.table5_lasso),
        ("fig6_penalty_curves", paper_figs.fig6_penalty_curves),
        ("fig7_day_dynamics", paper_figs.fig7_day_dynamics),
        ("fig8_pareto", paper_figs.fig8_pareto),
        ("fig9_breakdown", paper_figs.fig9_breakdown),
        ("fig10_entropy", paper_figs.fig10_entropy),
        ("fig11_future", paper_figs.fig11_future),
        ("solver_scale", perf_micro.solver_scale),
        ("fleet_cr3_scale", perf_micro.fleet_cr3_scale),
        ("fleet_shard_scale", perf_micro.fleet_shard_scale),
        ("fleet_region_scale", perf_micro.fleet_region_scale),
        ("streaming_resolve", perf_micro.streaming_resolve),
        ("streaming_day", perf_micro.streaming_day),
        ("scenario_ensemble", scenario_ensemble.scenario_ensemble),
        ("kernel_micro", perf_micro.kernel_micro),
        ("al_step_micro", perf_micro.al_step_micro),
        ("train_throughput", perf_micro.train_throughput),
    ]
    # One span event per benchmark lands in var/BENCH_events.jsonl so a
    # whole harness run renders with `python -m repro.obs.report` (same
    # reporting side-channel rules as the JSON records: never fail a
    # benchmark over it).
    writer = None
    if not args.no_json:
        try:
            from benchmarks.common import VAR
            from repro.obs.events import EventWriter
            VAR.mkdir(exist_ok=True)
            writer = EventWriter(str(VAR / "BENCH_events.jsonl"),
                                 tags={"harness": "benchmarks.run"})
        except Exception as e:  # noqa: BLE001 — reporting side-channel
            print(f"# BENCH_events.jsonl not opened: {e}", file=sys.stderr)

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            rows = fn()
            status = "ok"
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{name},0,FAILED", flush=True)
            rows, status = [], "failed"
        elapsed = time.perf_counter() - t0
        if not args.no_json:
            # a JSON-record failure (read-only var/, disk full) must not
            # fail a benchmark that ran, nor abort the remaining ones
            try:
                _write_json(name, status, rows, elapsed)
            except Exception as e:  # noqa: BLE001 — reporting side-channel
                print(f"# BENCH_{name}.json not written: {e}",
                      file=sys.stderr)
        if writer is not None:
            try:
                from repro.obs.events import SpanEvent
                writer.write(SpanEvent(name=f"bench.{name}",
                                       elapsed_s=elapsed,
                                       meta={"status": status}))
            except Exception as e:  # noqa: BLE001 — reporting side-channel
                print(f"# BENCH_events.jsonl append failed: {e}",
                      file=sys.stderr)
    if writer is not None:
        writer.close()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
