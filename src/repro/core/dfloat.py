"""Double-float arithmetic: a value carried as the unevaluated sum
`hi + lo` of two float32 arrays (about 48 significant bits), for the few
device sums whose decisions must agree with float64 ones on a chip that
has no float64 — CR3's Eq.-6 fiscal sums (`api._cr3_market`) and a solve's report sums (`fleet_solver.report_sums`).

A pair is a tuple `(hi, lo)` with |lo| at most half an ulp of hi. Sums
use Knuth's error-free TwoSum, which needs round-to-nearest float32
additions evaluated as written. A product of two float32s is split on
its bits (the low 12 mantissa bits masked off), so each partial product
is exact whatever the compiler fuses; the partials are then added as
pairs. Every operation is elementwise and broadcasts like jnp.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["add", "div", "gt", "mul", "pair", "reduce_sum", "split",
           "tree_sum", "value"]


def split(x) -> tuple[np.ndarray, np.ndarray]:
    """Host-side: the float32 pair nearest float64 `x`."""
    x = np.asarray(x, np.float64)
    hi = x.astype(np.float32)
    return hi, (x - hi).astype(np.float32)


def value(x) -> np.ndarray:
    """Host-side: the float64 value of the pair `x` (`split`'s inverse)."""
    return np.asarray(x[0], np.float64) + np.asarray(x[1], np.float64)


def pair(x):
    """`x` as a pair: a pair as it is, an array with a zero low part."""
    if isinstance(x, tuple):
        return x
    x = jnp.asarray(x)
    return x, jnp.zeros_like(x)


def _two_sum(a, b):
    s = a + b
    v = s - a
    return s, (a - (s - v)) + (b - v)


def _fast_two_sum(a, b):
    """TwoSum when |a| >= |b|."""
    s = a + b
    return s, b - (s - a)


def add(x, y):
    s, e = _two_sum(x[0], y[0])
    return _fast_two_sum(s, e + (x[1] + y[1]))


def _halves(a):
    """a = top + rest exactly, each with at most 12 significant bits."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    top = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFFF000),
                                       jnp.float32)
    return top, a - top


def _prod(a, b):
    """The float32 product a·b as a pair: four exact partial products."""
    a1, a2 = _halves(a)
    b1, b2 = _halves(b)
    p = (a1 * b1, jnp.zeros_like(a1 * b1))
    for t in (a1 * b2, a2 * b1, a2 * b2):
        p = add(p, (t, jnp.zeros_like(t)))
    return p


def mul(x, y):
    p = _prod(x[0], y[0])
    return add(p, (x[0] * y[1] + x[1] * y[0], jnp.zeros_like(p[0])))


def div(x, y):
    q = x[0] / y[0]
    qy = mul((q, jnp.zeros_like(q)), y)
    r = add(x, (-qy[0], -qy[1]))
    return _fast_two_sum(q, r[0] / y[0])


def gt(x, y):
    """x > y, elementwise."""
    return (x[0] - y[0]) + (x[1] - y[1]) > 0


def tree_sum(x, axis: int):
    """Sum of the pair `x` over `axis` by a fixed pairwise tree: adjacent
    pairs added, level by level, a level of odd length padded with one
    zero. Trailing zeros change no sum of the tree, so a run summed alone
    and the same run padded with zeros give the same pair, bit for bit."""
    hi, lo = x
    while hi.shape[axis] > 1:
        if hi.shape[axis] % 2:
            pad = [(0, 0)] * hi.ndim
            pad[axis] = (0, 1)
            hi, lo = jnp.pad(hi, pad), jnp.pad(lo, pad)
        ev = lambda a: jax.lax.slice_in_dim(a, 0, None, 2, axis)  # noqa: E731
        od = lambda a: jax.lax.slice_in_dim(a, 1, None, 2, axis)  # noqa: E731
        hi, lo = add((ev(hi), ev(lo)), (od(hi), od(lo)))
    return jnp.squeeze(hi, axis), jnp.squeeze(lo, axis)


def reduce_sum(x, axis: int):
    """Sum of the pair (or array) `x` over `axis` by the compiler's own
    reduction, each step an `add`: one pass over `x` in whatever order
    suits its layout, within about n·2^-48 of Σ|x| for n terms in any
    order. The order may differ between programs: use `tree_sum` where
    the bits must not depend on the program or the layout."""
    zero = jnp.zeros((), pair(x)[0].dtype)
    return jax.lax.reduce(pair(x), (zero, zero), add, (axis,))
