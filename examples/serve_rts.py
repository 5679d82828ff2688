"""Serve a small model with batched requests under Carbon Responder power
caps: shows the QoS ↔ power trade-off the RTS penalty models price.

  PYTHONPATH=src python examples/serve_rts.py
"""
import numpy as np

import jax

from repro.configs import get_config, reduced
from repro.launch.serve import Request, serve_requests
from repro.models import transformer as tf


def main() -> None:
    from repro.jaxcache import enable_compile_cache
    enable_compile_cache()
    cfg = reduced(get_config("qwen3-32b"), layers=2, d_model=128, vocab=2048)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def make_requests(n=12):
        return [Request(rid=i,
                        prompt=rng.integers(0, 2000, 12).astype(np.int32),
                        max_new=6) for i in range(n)]

    print("== RTS serving under power caps ==")
    print(f"{'power cap':>10s} {'batch':>6s} {'p50 (s)':>9s} {'p95 (s)':>9s}"
          f" {'tok/s':>8s}")
    for cap_frac, max_batch in ((0.0, 12), (0.2, 6), (0.4, 3)):
        stats = serve_requests(params, cfg, make_requests(),
                               max_batch=max_batch, max_len=32)
        print(f"{cap_frac:10.0%} {max_batch:6d} {stats.p(50):9.3f}"
              f" {stats.p(95):9.3f} {stats.throughput_tok_s:8.1f}")
    print("\n(deeper power caps -> smaller admitted batches -> longer queue"
          "\n delay: the latency degradation the Dynamo-fit cubic penalties"
          "\n price in Carbon Responder's RTS model)")


if __name__ == "__main__":
    main()
