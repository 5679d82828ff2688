"""End-to-end driver: train a ~100M-parameter model for a few hundred steps
under a Carbon Responder throttle schedule, with fault-tolerant
checkpointing. (CPU-sized here; the same driver scales to the assigned
configs on TPU pods via --arch/--no-reduced.)

  PYTHONPATH=src python examples/train_fleet_dr.py [--steps 200]
"""
import argparse

import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import ShapeCell
from repro.core.carbon import caiso_2021
from repro.core.fleet import FleetCoordinator, FleetJob
from repro.launch.train import train
from repro.power.model import JobPowerModel


def main() -> None:
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=384)
    ap.add_argument("--lam", type=float, default=1.45)
    args = ap.parse_args()

    # ~100M params: 4 layers, d=384, vocab 32k  (embed 2*12.3M + blocks).
    cfg = reduced(get_config(args.arch), layers=args.layers,
                  d_model=args.d_model, vocab=32768)
    n_params = cfg.param_count()
    print(f"training {args.arch} (reduced): {n_params/1e6:.0f}M params")

    # 1. Fleet plan: this job + a serving neighbor share the pod's power.
    train_job = FleetJob(
        name="train", role="train",
        power=JobPowerModel("train", chips=256, t_compute_s=0.42,
                            t_step_s=0.55))
    serve_job = FleetJob(
        name="serve", role="serve",
        power=JobPowerModel("serve", chips=64, t_compute_s=0.008,
                            t_step_s=0.02))
    coord = FleetCoordinator([train_job, serve_job], caiso_2021(48),
                             lam=args.lam)
    schedules, plan = coord.plan()
    thr = schedules["train"].throttle
    print(f"CR plan: carbon ↓{plan.carbon_reduction_pct:.2f}%, "
          f"penalty {plan.total_penalty_pct:.2f}%; train throttle "
          f"min={thr.min():.2f} mean={thr.mean():.2f}")

    # 2. Train under the throttle schedule (steps-per-hour budgets).
    shape = ShapeCell("example", 256, 8, "train")
    report = train(cfg, shape, steps=args.steps, ckpt_dir="var/ckpt_example",
                   throttle=thr)
    losses = report["losses"]
    print(f"\nsteps={report['steps']}  wall={report['wall_s']:.1f}s  "
          f"{report['steps_per_s']:.2f} steps/s")
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(first->last; decreasing={losses[-1] < losses[0]})")
    if report["events"]:
        print(f"runtime events: {report['events'][:5]}")


if __name__ == "__main__":
    main()
