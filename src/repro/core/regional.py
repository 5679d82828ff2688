"""RegionReductions — per-region segment-summed reductions as one layer.

Multi-region fleets price every region on its own normalizers. Each
engine lane (solo solve, sharded solve, sweep, ensemble, day scan,
streaming, CR3 fiscal clearing, the migration stages) needs the same
small family of per-region reductions, and PR 7 grew them as ad-hoc
``mci.ndim`` branches scattered across api/ensemble/migration/streaming.
This module is their single home; every lane consumes it.

Two flavors live here, matching the two places reductions run:

  * **Traced, row-separable** (jnp; safe inside jit/vmap/shard_map
    bodies): ``region_rows`` (the :class:`RegionReductions` view of a
    fleet), ``region_sum`` (segment-sum a per-row quantity and gather
    the per-region total back to rows), the CR1/CR2 normalizer tuples
    ``cr1_norms``/``cr2_norms`` whose multi-region twins are per-row
    (W,)/(W, 1) vectors, and the pad/spec plumbing that lets those
    vectors ride device meshes (``pad_row_norms``, ``norm_specs``).
    Everything stays row-separable so the engine's sharding contract
    (no cross-device reductions inside the differentiated objective)
    holds — per-region totals are scattered back to rows *before* the
    solve and shard with their rows.

  * **Traced per-region totals** (``region_layout`` on the host,
    ``region_sums`` in the trace): CR3's Eq.-6 rebates paid per region,
    summed on the device inside the clearing loop in double-float
    (``dfloat``), so the loop's balance test agrees with a float64 one,
    and a solve's report sums (``fleet_solver.report_sums``). CR3 sums
    each region's rows by one fixed pairwise tree, so a region's total
    is the same, bit for bit, alone or inside a multi-region fleet.

  * **Host-side, exact numpy** (``region_totals``): per-region
    accumulation of (W,) or (W, T) row weights — CR3's taxes
    collected per region, the migration stage's movable/headroom
    aggregates and streaming's per-region carbon ledgers.

Single-region problems flow through the same functions and get the
fleet-global scalar forms, so callers never branch on region-ness
themselves; the R=1 path is bitwise-identical to the pre-regional
code (same expressions, same evaluation order).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import dfloat
from repro.core.fleet_solver import FleetProblem, _bounds

__all__ = ["CR1_NORM_FILLS", "CR2_NORM_FILLS", "RegionReductions",
           "cr1_norms", "cr2_norms", "cr3_reg_scale", "norm_specs",
           "pad_row_norms", "region_layout", "region_rows", "region_sum",
           "region_sums", "region_totals"]

#: Pad fills for `pad_row_norms` keeping device-pad rows inert:
#: weights 0 (pad rows contribute nothing), step/scale divisors 1
#: (nothing blows up). Order matches the norms tuples.
CR1_NORM_FILLS = (0.0, 0.0, 1.0)   # (pen_w, car_w, step_w)
CR2_NORM_FILLS = (0.0, 1.0, 1.0)   # (car_w, scale_w, step_w)


class RegionReductions(NamedTuple):
    """Per-row region view of a multi-region fleet (see `region_rows`)."""
    region: jax.Array   #: (W,) int — each row's region id
    wmci: jax.Array     #: (W, T) — each row's region MCI trace
    counts: jax.Array   #: (W,) — row count of each row's region


def region_rows(p: FleetProblem) -> RegionReductions:
    """Per-row region scatter helpers for a multi-region problem:
    `(region, wmci, counts)` with `wmci[w] = mci[region[w]]` (W, T) and
    `counts[w]` the row count of w's region. Segment sums over the
    region ids turn per-region reductions into per-row normalizer
    vectors — the multi-region twin of the fleet-global scalars, still
    row-separable so the sharding contract holds (pad rows carry
    region 0 but their norms are overridden by `pad_row_norms`)."""
    region = jnp.asarray(p.region)
    R = jnp.asarray(p.mci).shape[0]
    counts = jax.ops.segment_sum(jnp.ones(p.W), region, num_segments=R)
    return RegionReductions(region, jnp.asarray(p.mci)[region],
                            counts[region])


def region_sum(x, region, R: int):
    """Per-row view of a per-region sum: segment-sum then gather back."""
    return jax.ops.segment_sum(x, region, num_segments=R)[region]


def region_layout(region, R: int):
    """Host-side layout of a fleet's rows for `region_sums`: `(order,
    offsets)`, where `order` is the stable permutation that sorts the
    rows by region (None when they already are, as `regional_fleet`
    keeps them) and `offsets` the R + 1 bounds of each region's run of
    rows in that order. A single-region fleet of W rows is `(None, (0,
    W))`."""
    region = np.asarray(region)
    counts = np.bincount(region, minlength=R)
    offsets = (0,) + tuple(int(c) for c in np.cumsum(counts))
    if np.all(region[1:] >= region[:-1]):
        return None, offsets
    return np.argsort(region, kind="stable").astype(np.int32), offsets


def region_sums(x, order, offsets: tuple, *, total=dfloat.tree_sum):
    """Per-region totals of the per-row values `x` ((W,) or (W, T), or a
    `dfloat` pair of such arrays), traced, as a `dfloat` pair of (R,) or
    (R, T) arrays: each region's rows (in `order`, bounded by `offsets`;
    see `region_layout`) are summed by `total`, with float64 accuracy
    from float32 operations. Rows past `offsets[-1]` (device pad rows)
    count nowhere.

    By default one fixed pairwise tree (`dfloat.tree_sum`): padding a run
    with zeros leaves every sum of the tree as it was, so a region's
    total is the same reduction of the same values, bit for bit, whether
    the region is solved alone or inside a multi-region fleet (CR3's
    market relies on this). `dfloat.reduce_sum` reads the values once,
    in the compiler's order (a solve's report sums)."""
    width = max(b - a for a, b in zip(offsets[:-1], offsets[1:]))

    def runs(a):
        if order is not None:
            a = a[order]
        tail = ((0, 0),) * (a.ndim - 1)
        return jnp.stack([jnp.pad(a[lo:hi], ((0, width - (hi - lo)),) + tail)
                          for lo, hi in zip(offsets[:-1], offsets[1:])])

    if isinstance(x, tuple):
        return total(tuple(runs(a) for a in x), axis=1)
    x = runs(x)
    return total((x, jnp.zeros_like(x)), axis=1)


def region_totals(region, weights, R: int) -> np.ndarray:
    """Exact host-side per-region totals of per-row weights: (W,) weights
    give an (R,) total, (W, T) weights an (R, T) total. `region` may be
    a masked row subset as long as it is index-aligned with `weights`
    (e.g. `region[is_batch]` with `residual[is_batch]`). The one numpy
    accumulation primitive behind CR3's taxes collected, migration's
    movable/headroom aggregates and streaming's per-region ledgers."""
    region = np.asarray(region)
    w = np.asarray(weights, float)
    if w.ndim == 1:
        return np.bincount(region, weights=w, minlength=R)
    out = np.zeros((R,) + w.shape[1:])
    np.add.at(out, region, w)
    return out


def cr1_norms(p: FleetProblem):
    """Fleet-global CR1 reductions (normalizers + shared step scale) —
    computed from the TRUE fleet before any device padding, then passed
    into the sharded solve as replicated scalars.

    Multi-region problems get the per-REGION twin: each region is
    normalized on its own entitlement/carbon/step reductions (scattered
    back to per-row vectors), so with zero migration bandwidth the joint
    solve decomposes exactly into R independent single-region solves."""
    lo, hi = _bounds(p)
    mci = jnp.asarray(p.mci)
    if mci.ndim == 2:
        region, wmci, counts_w = region_rows(p)
        R = mci.shape[0]
        pen_w = 100.0 / region_sum(jnp.asarray(p.entitlement), region, R)
        car_w = 100.0 / region_sum((jnp.asarray(p.usage) * wmci).sum(1),
                                   region, R)
        rowmeans = jnp.maximum(hi - lo, 1e-6).mean(axis=1)
        step_w = (region_sum(rowmeans, region, R) / counts_w)[:, None]
        return pen_w, car_w, step_w
    return (100.0 / jnp.asarray(p.entitlement).sum(),
            100.0 / (jnp.asarray(p.usage).sum(0) * mci).sum(),
            jnp.maximum(hi - lo, 1e-6).mean())


def cr2_norms(p: FleetProblem, refs):
    """Fleet-global CR2 reductions (carbon normalizer, equality-residual
    scale, shared step scale) from the TRUE fleet before padding. Per-
    region twin for multi-region problems, as in `cr1_norms`."""
    lo, hi = _bounds(p)
    mci = jnp.asarray(p.mci)
    if mci.ndim == 2:
        region, wmci, counts_w = region_rows(p)
        R = mci.shape[0]
        car_w = 100.0 / region_sum((jnp.asarray(p.usage) * wmci).sum(1),
                                   region, R)
        scale_w = jnp.maximum(region_sum(refs, region, R) / counts_w, 1e-3)
        rowmeans = jnp.maximum(hi - lo, 1e-6).mean(axis=1)
        step_w = (region_sum(rowmeans, region, R) / counts_w)[:, None]
        return car_w, scale_w, step_w
    return (100.0 / (jnp.asarray(p.usage).sum(0) * mci).sum(),
            jnp.maximum(refs.mean(), 1e-3),
            jnp.maximum(hi - lo, 1e-6).mean())


def cr3_reg_scale(p: FleetProblem):
    """CR3's per-row regularizer normalizer for a multi-region fleet:
    1e-3/(W_region·T) scattered to rows, so each region's market
    regularizes exactly like its standalone single-region market."""
    region = np.asarray(p.region)
    counts = np.bincount(region, minlength=p.R)
    return jnp.asarray((1e-3 / (counts * p.T))[region][:, None])


def pad_row_norms(norms, W_pad: int, fills):
    """Pad per-row multi-region norm vectors to the device-padded W.
    Fill values (`CR1_NORM_FILLS`/`CR2_NORM_FILLS`) keep pad rows inert
    (0 for weights so they contribute nothing, 1 for step/scale divisors
    so nothing blows up)."""
    out = []
    for a, f in zip(norms, fills):
        a = jnp.asarray(a)
        pad = W_pad - a.shape[0]
        out.append(a if pad == 0 else jnp.concatenate(
            [a, jnp.full((pad,) + a.shape[1:], f, a.dtype)]))
    return tuple(out)


def norm_specs(p: FleetProblem, axis, n: int = 3, *, stacked: bool = False):
    """shard_map specs for a norms tuple: replicated scalars for the
    single-region path, row-sharded vectors for multi-region. With
    `stacked=True` the norms carry a leading replicated axis (per-tick
    day-scan stacks, per-lane sweep/ensemble stacks) ahead of the
    sharded row axis."""
    if np.ndim(p.mci) == 1:
        one = P()
    else:
        one = P(None, axis) if stacked else P(axis)
    return (one,) * n
