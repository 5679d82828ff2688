"""Peaks of the chip, and the work each kernel's algorithm needs.

Counts come from shapes and from what the algorithm must do, not from
how the program implements it today: a running sum over T hours is T
additions per row (the program's kernels compute it as a T x T mask
matmul, which a later change may drop), and the bytes are the tiles a
call reads and writes once. So a change of implementation leaves the
counts as they are, and a share of the roofline can only rise by doing
the same work faster.
"""
from __future__ import annotations

#: Published peaks per `device_kind`. Source: Google Cloud documentation,
#: "TPU v5e": 197 TFLOP/s (bf16), 819 GB/s HBM bandwidth, 16 GB HBM.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}

F32 = 4

#: al_step, operations per (row, hour) element and projected-Adam step.
#: Real-time rows: delta and the cubic's gradient (6), the carbon term
#: and weight (2), Adam with bias correction (14), the box clip (2).
#: Batch rows: the queue-integral gradient with its two O(T) running
#: sums, two reversed running sums and hinge tests (18), the carbon term
#: (2), Adam (14), the box clip (2) and three rounds of day-mean removal
#: with a clip each (12).
AL_STEP_FLOPS_RTS = 24
AL_STEP_FLOPS_BATCH = 48
#: dr_features, operations per element: three rates (6), three O(T)
#: running sums (3), three hinges with their thresholds (9), the
#: delayed-jobs count (3).
DR_FEATURES_FLOPS = 21


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{', '.join(sorted(PEAKS))}") from None


def al_step(W_rts: int, W_batch: int, T: int, steps: int,
            per_row_carbon: bool) -> tuple[float, float]:
    """(flops, bytes) of one call running `steps` fused steps: reads x,
    both Adam moments, usage, jobs and the box bounds, the row parameters
    (12 per row) and the carbon term ((1, T), or (W, T) per row); writes
    x and both moments."""
    W = W_rts + W_batch
    flops = steps * T * (AL_STEP_FLOPS_RTS * W_rts
                         + AL_STEP_FLOPS_BATCH * W_batch)
    cvec = W * T if per_row_carbon else T
    nbytes = F32 * (7 * W * T + 12 * W + cvec + 3 * W * T)
    return float(flops), float(nbytes)


def dr_features(W: int, T: int) -> tuple[float, float]:
    """(flops, bytes) of one call: reads the plan, usage and jobs, writes
    four features per row."""
    return float(DR_FEATURES_FLOPS * W * T), float(F32 * (3 * W * T + 4 * W))


def least_time(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc, tm = flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "hbm")
