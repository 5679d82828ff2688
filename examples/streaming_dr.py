"""Streaming DR demo: rolling-horizon re-solves under forecast revision.

Runs a Carbon Responder fleet *online*: every simulated hour a revised
day-ahead MCI forecast arrives, the coordinator warm-starts the fleet
engine from the previous plan (shifted one hour), re-solves the full
horizon with a fraction of the cold inner-step budget, and commits only
the first hour. Prints per-tick commitments and the realized-vs-forecast
carbon ledger.

  PYTHONPATH=src python examples/streaming_dr.py [--ticks 12] [--policy cr1]

Fleet scale: `--shard` runs every tick's re-solve sharded over all local
devices as one donated-buffer XLA call (workloads padded to the device
count, engine state re-solved in place). On CPU, expose virtual devices
first, e.g.:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python examples/streaming_dr.py --shard --workloads 10000

One-dispatch day: `--scan` folds the whole run into a single XLA call
(`RollingHorizonSolver.run_scanned` -> `api.solve_day`) — the tick loop
(window roll + plan shift + mu reset + warm re-solve) runs inside
`lax.scan` instead of Python, so a 24-tick day is one donated-buffer
dispatch instead of 24. CR1/CR2 only; parity with the per-tick loop is
<0.01 pp realized carbon:

  PYTHONPATH=src python examples/streaming_dr.py --scan --ticks 24

Observability: `--telemetry out.jsonl` writes the run's structured
event ledger — per-tick `TickEvent`s (forecast revision, warm budget,
latency, committed/realized carbon, recompile counts) plus in-solve
convergence samples captured inside the jitted AL loop — and prints
the report command to render it:

  PYTHONPATH=src python examples/streaming_dr.py --telemetry out.jsonl
  PYTHONPATH=src python -m repro.obs.report out.jsonl
"""
import argparse

from repro.core.api import POLICY_REGISTRY
from repro.core.carbon import ForecastStream
from repro.core.fleet_solver import synthetic_fleet
from repro.core.streaming import RollingHorizonSolver
from repro.obs import TelemetryConfig


def main() -> None:
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=12)
    ap.add_argument("--workloads", type=int, default=16)
    ap.add_argument("--policy", default="cr1",
                    choices=sorted(POLICY_REGISTRY),
                    help="POLICY_REGISTRY name; the controller resolves it "
                         "to a repro.core.api policy object")
    ap.add_argument("--cold-steps", type=int, default=600)
    ap.add_argument("--warm-steps", type=int, default=150)
    ap.add_argument("--shard", action="store_true",
                    help="shard the W axis over all devices and donate the "
                         "engine state each tick (in-place re-solves)")
    ap.add_argument("--scan", action="store_true",
                    help="whole run as ONE XLA dispatch: the tick loop "
                         "runs inside lax.scan (run_scanned/solve_day; "
                         "CR1/CR2 only)")
    ap.add_argument("--telemetry", metavar="PATH", default=None,
                    help="write the JSONL event ledger (tick events + "
                         "in-solve convergence telemetry) to PATH; render "
                         "with `python -m repro.obs.report PATH`")
    args = ap.parse_args()

    print("== Carbon Responder: rolling-horizon streaming DR ==")
    fleet = synthetic_fleet(args.workloads)
    stream = ForecastStream.caiso(n_ticks=args.ticks, horizon=fleet.T)
    print(f"fleet: {fleet.W} workloads x {fleet.T} h horizon, "
          f"policy {args.policy.upper()}")
    print(f"stream: {args.ticks} hourly forecast revisions "
          f"(sigma={stream.revision_sigma}/sqrt-hour lead error)")
    mesh = None
    if args.shard:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh()
        n = len(mesh.devices.ravel())
        print(f"sharding: {n} devices, "
              f"{-(-fleet.W // n)} workload rows/device, donated ticks")
    print()

    telemetry = None
    if args.telemetry:
        # In-solve convergence traces are CR1/CR2 lanes only; other
        # policies still get the tick ledger.
        telemetry = (TelemetryConfig(every=10)
                     if args.policy in ("cr1", "cr2") else None)
        state = ("on" if telemetry
                 else f"off — {args.policy.upper()} has no traced lane")
        print(f"ledger: {args.telemetry} (telemetry {state})")
    solver = RollingHorizonSolver(
        fleet, stream, policy=args.policy,
        cold_steps=args.cold_steps, warm_steps=args.warm_steps,
        mesh=mesh, donate=args.shard,
        events=args.telemetry, telemetry=telemetry)

    print("tick  start  steps  curtail[NP]  mci fc->act   CO2 fc/act [kg]")

    def show(tk):
        start = "cold" if tk.tick == 0 else "warm"
        print(f"{tk.tick:4d}  {start}  {tk.inner_steps:5d}  "
              f"{tk.committed.sum():11.2f}  "
              f"{tk.forecast_mci:5.0f}->{tk.realized_mci:3.0f}   "
              f"{tk.forecast_carbon:7.1f}/{tk.realized_carbon:7.1f}")

    if args.scan:
        if args.shard:
            raise SystemExit("--scan under --shard is a ROADMAP follow-up "
                             "(the day scan must nest inside the fleet "
                             "shard_map); drop one of the flags")
        report = solver.run_scanned(args.ticks)
        for tk in report.ticks:
            show(tk)
        print(f"\n(one XLA dispatch for all {args.ticks} ticks)")
    else:
        report = solver.run(args.ticks, on_tick=show)

    cold_total = args.cold_steps * args.ticks
    print(f"\ncommitted hours      : {len(report.ticks)}")
    print(f"realized carbon cut  : {report.realized_carbon:.1f} kg "
          f"({report.realized_reduction_pct:.2f}% of baseline)")
    print(f"forecast carbon cut  : {report.forecast_carbon:.1f} kg "
          f"(tracking error {report.forecast_error_pct:.2f}%)")
    print(f"inner steps spent    : {report.total_inner_steps} "
          f"(all-cold would be ~{cold_total}; "
          f"{cold_total / report.total_inner_steps:.1f}x saved)")
    mat = report.committed
    print("\nper-tick committed curtailment (rows = first "
          f"{min(6, mat.shape[0])} workloads):")
    for i in range(min(6, mat.shape[0])):
        line = "".join("▼" if x > 0.05 else ("▲" if x < -0.05 else "·")
                       for x in mat[i])
        print(f"  w{i:02d}: {line}")

    if args.telemetry:
        print(f"\nledger written: {args.telemetry}")
        print(f"render it: PYTHONPATH=src python -m repro.obs.report "
              f"{args.telemetry}")


if __name__ == "__main__":
    main()
