"""Plain reference for streaming CR2 (Fair and Centralized DR) ticks.

Written from the policy's statement (arXiv:2311.08589 §4, Eq. 4), not
from the program. Every tick re-solves the window [t, t+T) of the fleet
(usage and jobs rolled t hours, the tick's forecast installed):

    minimize  - sum_w car_w * D_w . mci_r(w)
    s.t.      h_w = (k_w pen_w(D_w) - ref_w) / scale_w = 0   for every w,

where ref_w = k_w pen_w(d_cap,w) is the workload's penalty under an
equal power cap, d_cap = max(usage - cap_frac * E, 0), over the same
hours as its plan. The equalities are priced by an augmented Lagrangian:
`outer` rounds of `steps` projected Adam steps from fresh moments on

    objective + sum_w lam_w h_w + mu / 2 * sum_w h_w^2,

each round followed by lam_w <- lam_w + mu h_w and mu <- min(mu_growth *
mu, mu_max). Every tick starts mu at mu0; the multipliers carry from tick
to tick, and the plan too, shifted one hour (the vacated hour at zero;
zero plans and multipliers on the first tick). Hour 0 is committed. The
step is lr times the region's mean box width. The projection clips to
the box (curtail at most min(frac * E, usage); batch may boost to E) and
removes each day's mean from batch rows, alternately,
`projection_rounds` times.

`pen` is the calibrated penalty: a cubic in D/usage for real-time
services; for batch services the positive part of b0 + b1 * wait_power +
b2 * x2 over queue-integral features, where a running backlog within
`queue_rtol` of its row's total |rate| counts as empty.

The normalizers are per region and fleet-wide, so they are computed on
the host in float64 from the whole fleet: car_w = 100 / the region's
baseline carbon of the window, scale_w = max(the mean of the region's
references, 1e-3), and the step scale. Usage is T-periodic, so the whole
fleet's references are computed once per hour offset. Given them, every
row evolves on its own, and the reference replays the ticks for a sample
of rows only.

Departures from the program's arithmetic: the references and the
normalizers are float64 here (the program takes the cap's curtailment
made on the host in float64, rounds it to float32 and evaluates the
penalties in float32); the gradient is autodiff of the
Lagrangian as written (the program folds 1 / scale_w into k and the
references of its fused step); Adam's moments are updated as
m + (1 - beta1)(g - m). It imports nothing of the program and takes only
the inputs: the fleet's arrays and the forecasts.
"""
from __future__ import annotations

import numpy as np


def _penalty(xp, x, u, j, rows, rtol):
    """(S,) calibrated penalty of each row's plan (without k), in the
    array module `xp` (numpy or jax.numpy)."""
    a3, a2, a1 = (rows["rts"][:, i:i + 1] for i in range(3))
    b0, b1, b2 = (rows["betas"][:, i] for i in range(3))
    delta = x / u
    rts = (a3 * delta ** 3 + a2 * delta ** 2 + a1 * delta).sum(axis=1)

    def queue(rate):
        cum = xp.cumsum(rate, axis=1)
        thr = rtol * xp.abs(rate).sum(axis=1, keepdims=True)
        return xp.where(cum > thr, cum, 0.0).sum(axis=1)

    wait_power = queue(x)
    wait_sq = queue(j * x * xp.abs(x) / u)
    delayed = (j * xp.where(x > 0, x, 0.0) / u).sum(axis=1)
    z = b0 + b1 * wait_power + b2 * xp.where(rows["x2_kind"] > 0.5,
                                             wait_sq, delayed)
    batch = xp.where(z > 0, z, 0.0)
    return xp.where(rows["is_batch"], batch, rts)


class Fleet:
    """The whole fleet in float64 on the host: each region's step scale,
    its hourly usage, and the references of every hour offset."""

    def __init__(self, fleet: dict, algo: dict):
        f64 = {k: np.asarray(fleet[k], np.float64)
               for k in ("usage", "jobs", "entitlement", "k")}
        self.usage, self.jobs = f64["usage"], f64["jobs"]
        self.E, self.k = f64["entitlement"], f64["k"]
        self.rows = {"rts": np.asarray(fleet["rts_coeffs"], np.float64),
                     "betas": np.asarray(fleet["betas"], np.float64),
                     "x2_kind": np.asarray(fleet["x2_kind"], np.float64),
                     "is_batch": np.asarray(fleet["is_batch"], bool)}
        self.region = np.asarray(fleet["region"])
        self.R = int(self.region.max()) + 1
        self.counts = np.bincount(self.region, minlength=self.R)
        self.algo = algo
        frac = float(algo["max_curtail_frac"])
        hi = np.minimum(frac * self.E[:, None], self.usage)
        lo = np.where(self.rows["is_batch"][:, None],
                      -(self.E[:, None] - self.usage), 0.0)
        rowmean = np.maximum(hi - lo, 1e-6).mean(axis=1)
        self.step_w = np.bincount(self.region, weights=rowmean,
                                  minlength=self.R) / self.counts
        self.colsum = np.zeros((self.R, self.usage.shape[1]))
        np.add.at(self.colsum, self.region, self.usage)
        self._refs = {}

    def refs(self, t: int) -> np.ndarray:
        """(W,) k * pen(d_cap) of every row over the window of tick t."""
        T = self.usage.shape[1]
        t %= T
        if t not in self._refs:
            u = np.roll(self.usage, -t, axis=1)
            j = np.roll(self.jobs, -t, axis=1)
            cap = float(self.algo["cap_frac"]) * self.E[:, None]
            d_cap = np.maximum(u - cap, 0.0)
            self._refs[t] = self.k * _penalty(
                np, d_cap, u, j, self.rows, float(self.algo["queue_rtol"]))
        return self._refs[t]

    def norms(self, mci: np.ndarray, t: int):
        """(R,) car_w and scale_w of tick t's window under forecast `mci`."""
        car = 100.0 / (np.roll(self.colsum, -t, axis=1) * mci).sum(axis=1)
        mean = np.bincount(self.region, weights=self.refs(t),
                           minlength=self.R) / self.counts
        return car, np.maximum(mean, 1e-3)


class Replay:
    """Replays streaming CR2 ticks for a sample of rows in `dtype`."""

    def __init__(self, fleet: dict, rows_idx: np.ndarray, algo: dict,
                 dtype: str = "float32"):
        import jax
        import jax.numpy as jnp
        self.jax, self.jnp = jax, jnp
        self.algo = algo
        self.dt = jnp.dtype(dtype)
        self.fleet = Fleet(fleet, algo)
        self.idx = np.asarray(rows_idx)
        take = {k: np.asarray(fleet[k])[self.idx]
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
            "step_w": jnp.asarray(self.fleet.step_w[self.region],
                                  self.dt)[:, None],
        }
        self._tick = jax.jit(self._tick_impl,
                             static_argnames=("steps", "outer"))

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

    def _tick_impl(self, x, lam, t, wmci, car, ref, scale, *, steps: int,
                   outer: int):
        jax, jnp, a, r = self.jax, self.jnp, self.algo, self.rows
        dt = self.dt
        u = jnp.roll(r["usage"], -t, axis=1)
        j = jnp.roll(r["jobs"], -t, axis=1)
        hi = jnp.minimum(dt.type(a["max_curtail_frac"]) * r["E"], u)
        lo = jnp.where(r["is_batch"][:, None], -(r["E"] - u),
                       jnp.zeros_like(u))
        rtol = dt.type(a["queue_rtol"])

        def residual(x):
            return (r["k"] * _penalty(jnp, x, u, j, r, rtol) - ref) / scale

        def lagrangian(x, lam, mu):
            h = residual(x)
            return (-(car[:, None] * x * wmci).sum() + (lam * h).sum()
                    + 0.5 * mu * (h * h).sum())

        grad = jax.grad(lagrangian)
        lr = dt.type(a["lr"]) * r["step_w"]
        b1, b2, eps = (float(a[k]) for k in ("beta1", "beta2", "eps"))
        one_b1, one_b2 = dt.type(1.0 - b1), dt.type(1.0 - b2)

        def one_round(_, c):
            x, lam, mu = c

            def step(i, s):
                x, m, v = s
                g = grad(x, lam, mu)
                m = m + one_b1 * (g - m)
                v = v + one_b2 * (g * g - v)
                n = (i + 1).astype(jnp.float32)
                mhat = m / (1.0 - b1 ** n).astype(dt)
                vhat = v / (1.0 - b2 ** n).astype(dt)
                return self._project(x - lr * mhat / (jnp.sqrt(vhat)
                                                      + dt.type(eps)),
                                     lo, hi), m, v

            z = jnp.zeros_like(x)
            x, _, _ = jax.lax.fori_loop(0, steps, step, (x, z, z))
            lam = lam + mu * residual(x)
            mu = jnp.minimum(mu * dt.type(a["mu_growth"]),
                             dt.type(a["mu_max"]))
            return x, lam, mu

        x = self._project(x, lo, hi)
        x, lam, _ = jax.lax.fori_loop(
            0, outer, one_round, (x, lam, jnp.asarray(a["mu0"], dt)))
        return x, lam

    def run(self, mci: np.ndarray, n_ticks: int, cold: int, warm: int):
        """Replay ticks 0..n_ticks-1 with forecasts `mci` (n, R, T).
        Returns (commits (n_ticks, S), last plan (S, T)) in float64."""
        jnp = self.jnp
        S, T = self.rows["usage"].shape
        x = jnp.zeros((S, T), self.dt)
        lam = jnp.zeros(S, self.dt)
        commits = np.empty((n_ticks, S))
        for t in range(n_ticks):
            car, scale = self.fleet.norms(mci[t], t)
            ref = self.fleet.refs(t)[self.idx]
            if t:
                x = jnp.roll(x, -1, axis=1).at[:, -1].set(0)
            x, lam = self._tick(
                x, lam, jnp.int32(t),
                jnp.asarray(mci[t][self.region], self.dt),
                jnp.asarray(car[self.region], self.dt),
                jnp.asarray(ref, self.dt),
                jnp.asarray(scale[self.region], self.dt),
                steps=cold if t == 0 else warm,
                outer=int(self.algo["outer"]))
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
