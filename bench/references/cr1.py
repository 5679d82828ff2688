"""Plain reference for streaming CR1 (Efficient DR) ticks.

Written from the policy's statement, not from the program: each tick
re-solves the window [t, t+T) of the fleet (usage and jobs rolled t
hours, the tick's forecast installed) by projected Adam on

    lam * pen_w * sum_w k_w * pen_w(D_w) - car_w * sum_w D_w . mci

with fresh moments, from zero on the first tick and from the previous
tick's plan shifted one hour (the vacated hour at zero) afterwards, and
commits hour 0. `pen` is the calibrated penalty: a cubic in D/usage for
real-time services; for batch services the positive part of
b0 + b1 * wait_power + b2 * x2 over queue-integral features, where a
running backlog within `queue_rtol` of its row's total |rate| counts as
empty. The projection clips to the box (curtail at most
min(frac * E, usage); batch may boost to E) and removes each day's mean
from batch rows, alternately, `projection_rounds` times.

The normalizers are fleet-wide (per region for a multi-region fleet), so
they are computed on the host in float64 from the whole fleet, and every
row then evolves on its own: the reference replays the ticks for a
sample of rows only. It imports nothing of the program and takes only
the inputs: the fleet's arrays and the forecasts.
"""
from __future__ import annotations

import numpy as np


def fleet_constants(fleet: dict, frac: float) -> dict:
    """Per-region sums of the whole fleet (float64, host)."""
    usage = np.asarray(fleet["usage"], np.float64)
    E = np.asarray(fleet["entitlement"], np.float64)
    isb = np.asarray(fleet["is_batch"], bool)
    region = np.asarray(fleet["region"])
    R = int(region.max()) + 1
    hi = np.minimum(frac * E[:, None], usage)
    lo = np.where(isb[:, None], -(E[:, None] - usage), 0.0)
    rowmean = np.maximum(hi - lo, 1e-6).mean(axis=1)
    counts = np.bincount(region, minlength=R)
    colsum = np.zeros((R, usage.shape[1]))
    np.add.at(colsum, region, usage)
    return {"pen_w": 100.0 / np.bincount(region, weights=E, minlength=R),
            "step_w": np.bincount(region, weights=rowmean, minlength=R)
            / counts,
            "colsum": colsum}


def carbon_weights(colsum: np.ndarray, mci: np.ndarray, t: int):
    """(R,) 100 / baseline carbon of the window at tick t, per region."""
    return 100.0 / (np.roll(colsum, -t, axis=1) * mci).sum(axis=1)


def _penalty(jnp, x, u, j, rows, rtol):
    """(S,) calibrated penalty of each row's plan (without k)."""
    a3, a2, a1 = (rows["rts"][:, i:i + 1] for i in range(3))
    b0, b1, b2 = (rows["betas"][:, i] for i in range(3))
    delta = x / u
    rts = (a3 * delta ** 3 + a2 * delta ** 2 + a1 * delta).sum(axis=1)

    def queue(rate):
        cum = jnp.cumsum(rate, axis=1)
        thr = rtol * jnp.abs(rate).sum(axis=1, keepdims=True)
        return jnp.where(cum > thr, cum, 0.0).sum(axis=1)

    wait_power = queue(x)
    wait_sq = queue(j * x * jnp.abs(x) / u)
    delayed = (j * jnp.where(x > 0, x, 0.0) / u).sum(axis=1)
    z = b0 + b1 * wait_power + b2 * jnp.where(rows["x2_kind"] > 0.5,
                                               wait_sq, delayed)
    batch = jnp.where(z > 0, z, 0.0)
    return jnp.where(rows["is_batch"], batch, rts)


class Replay:
    """Replays streaming CR1 ticks for a sample of rows in `dtype`."""

    def __init__(self, fleet: dict, rows_idx: np.ndarray, algo: dict,
                 dtype: str = "float32"):
        import jax
        import jax.numpy as jnp
        self.jax, self.jnp = jax, jnp
        self.algo = algo
        self.dt = jnp.dtype(dtype)
        frac = float(algo["max_curtail_frac"])
        self.const = fleet_constants(fleet, frac)
        take = {k: np.asarray(fleet[k])[rows_idx]
                for k in ("usage", "jobs", "entitlement", "k", "rts_coeffs",
                          "betas", "x2_kind", "is_batch", "region")}
        self.region = take["region"]
        self.rows = {
            "usage": jnp.asarray(take["usage"], self.dt),
            "jobs": jnp.asarray(take["jobs"], self.dt),
            "E": jnp.asarray(take["entitlement"], self.dt)[:, None],
            "k": jnp.asarray(take["k"], self.dt),
            "rts": jnp.asarray(take["rts_coeffs"], self.dt),
            "betas": jnp.asarray(take["betas"], self.dt),
            "x2_kind": jnp.asarray(take["x2_kind"], self.dt),
            "is_batch": jnp.asarray(take["is_batch"], bool),
            "pen_w": jnp.asarray(self.const["pen_w"][self.region], self.dt),
            "step_w": jnp.asarray(self.const["step_w"][self.region],
                                  self.dt)[:, None],
        }
        self._tick = jax.jit(self._tick_impl, static_argnames=("steps",))

    def _project(self, x, lo, hi):
        jnp = self.jnp
        a = self.algo
        S, T = x.shape
        day = int(a["day_hours"])
        span = max(1, T // day) * day
        x = jnp.clip(x, lo, hi)
        for _ in range(int(a["projection_rounds"])):
            d = x[:, :span].reshape(S, span // day, day)
            d = d - d.mean(axis=-1, keepdims=True)
            moved = jnp.concatenate([d.reshape(S, span), x[:, span:]], 1)
            x = jnp.clip(jnp.where(self.rows["is_batch"][:, None], moved, x),
                         lo, hi)
        return x

    def _tick_impl(self, x, t, wmci, car, *, steps: int):
        jax, jnp, a, r = self.jax, self.jnp, self.algo, self.rows
        dt = self.dt
        u = jnp.roll(r["usage"], -t, axis=1)
        j = jnp.roll(r["jobs"], -t, axis=1)
        hi = jnp.minimum(dt.type(a["max_curtail_frac"]) * r["E"], u)
        lo = jnp.where(r["is_batch"][:, None], -(r["E"] - u),
                       jnp.zeros_like(u))
        lam = dt.type(a["lam"])
        rtol = dt.type(a["queue_rtol"])

        def objective(x):
            pen = _penalty(jnp, x, u, j, r, rtol)
            return ((lam * r["pen_w"] * r["k"] * pen).sum()
                    - (car[:, None] * x * wmci).sum())

        grad = jax.grad(objective)
        lr = dt.type(a["lr"]) * r["step_w"]
        b1, b2, eps = (float(a[k]) for k in ("beta1", "beta2", "eps"))
        one_b1, one_b2 = dt.type(1.0 - b1), dt.type(1.0 - b2)

        def step(i, c):
            x, m, v = c
            g = grad(x)
            m = m + one_b1 * (g - m)
            v = v + one_b2 * (g * g - v)
            n = (i + 1).astype(jnp.float32)
            mhat = m / (1.0 - b1 ** n).astype(dt)
            vhat = v / (1.0 - b2 ** n).astype(dt)
            return self._project(x - lr * mhat / (jnp.sqrt(vhat)
                                                  + dt.type(eps)), lo, hi), m, v

        x = self._project(x, lo, hi)
        z = jnp.zeros_like(x)
        x, _, _ = jax.lax.fori_loop(0, steps, step, (x, z, z))
        return x

    def run(self, mci: np.ndarray, n_ticks: int, cold: int, warm: int):
        """Replay ticks 0..n_ticks-1 with forecasts `mci` (n, R, T).
        Returns (commits (n_ticks, S), last plan (S, T)) in float64."""
        jnp = self.jnp
        S, T = self.rows["usage"].shape
        x = jnp.zeros((S, T), self.dt)
        commits = np.empty((n_ticks, S))
        for t in range(n_ticks):
            wmci = jnp.asarray(mci[t][self.region], self.dt)
            car = jnp.asarray(carbon_weights(self.const["colsum"], mci[t], t)
                              [self.region], self.dt)
            if t:
                x = jnp.roll(x, -1, axis=1).at[:, -1].set(0)
            x = self._tick(x, jnp.int32(t), wmci, car,
                           steps=cold if t == 0 else warm)
            commits[t] = np.asarray(x[:, 0], np.float64)
        return commits, np.asarray(x, np.float64)

    def penalty_pct(self, plan: np.ndarray, t: int) -> float:
        """100 * sum k * pen(plan) / sum E over the sampled rows, for the
        window of tick t."""
        jnp, r = self.jnp, self.rows
        x = jnp.asarray(plan, self.dt)
        u = jnp.roll(r["usage"], -t, axis=1)
        j = jnp.roll(r["jobs"], -t, axis=1)
        pen = _penalty(jnp, x, u, j, r, self.dt.type(self.algo["queue_rtol"]))
        return float(100.0 * np.asarray((r["k"] * pen).astype(jnp.float32),
                                        np.float64).sum()
                     / np.asarray(r["E"], np.float64).sum())
