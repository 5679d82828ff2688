"""Backend-aware Pallas dispatch shared by all kernel wrappers.

Pallas kernels compile for real on TPU and run in interpret mode
elsewhere (CPU containers, CI). `REPRO_PALLAS_INTERPRET` overrides the
auto-detection in both directions: truthy forces interpret mode even on
TPU (debugging), falsy forces the compiled path. A backend that fails to
initialize raises; it is never read as "not a TPU".
"""
from __future__ import annotations

import os

import jax

_FALSY = ("0", "false", "no", "off")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default(interpret: bool | None = None) -> bool:
    """Resolve an interpret flag: explicit > env override > backend."""
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() not in _FALSY
    return not on_tpu()
