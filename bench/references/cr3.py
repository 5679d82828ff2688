"""Plain reference for streaming CR3 (Fair and Decentralized DR) ticks.

Written from the policy's statement (arXiv:2311.08589 §4, Eqs. 5–8),
not from the program. Each region runs its own market. Every tick, for
each region on its own and over all of its rows, the window [t, t+T) is
re-solved (usage and jobs rolled t hours, the region's forecast
installed):

  * Best responses (Eq. 5/8): every workload i minimizes

        k_i pen_i(d_i) / E_i + reg / (W_r T) * sum_t (d_it / E_i)^2

    subject to its peak allowance

        tau_i logsumexp((u_i - d_i) / tau_i) <= (1 - tax) E_i
                                                + rho_r <mci_r, d_i>,

    tau_i = tau_frac * E_i, by an augmented Lagrangian: `outer` rounds of
    `steps` projected Adam steps from fresh moments on

        objective + mu/2 * sum_i (max(0, lam_i/mu - g_i)^2 - (lam_i/mu)^2)

    (g_i the allowance's slack over E_i), each round followed by
    lam_i <- max(0, lam_i - mu g_i) and mu <- min(mu_growth * mu, mu_max),
    mu starting at mu0. A batch row's gradient has each day's mean
    removed before the Adam step; the step is lr times the row's mean box
    width. The projection clips to the box (curtail at most
    min(frac * E, usage); batch may boost to E) and removes each day's
    mean from batch rows, alternately, `projection_rounds` times.
  * Clearing (Eq. 6): with paid_r = rho_r * sum_i <d_i, mci_r> and
    collected_r = tax * sum_i E_i (float64, on the host), while paid_r >
    collected_r + 1e-9 and at most `clearing_iters` times, the
    coordinator lowers rho_r <- rho_r * max(0.5, 0.9 collected_r /
    paid_r) and the region's workloads answer again from their last plan
    and multipliers, mu back at mu0. Every tick starts from the
    configured rho: a lowered price is not carried forward.
  * Warm start: the first tick starts from zero plans and multipliers;
    each later tick from the previous tick's last plan shifted one hour
    (the vacated hour at zero) and its multipliers. Hour 0 is committed.

`pen` is the calibrated penalty: a cubic in d/usage for real-time
services; for batch services the positive part of b0 + b1 * wait_power
+ b2 * x2 over queue-integral features, where a running backlog within
`queue_rtol` of its row's total |rate| counts as empty.

Departures from the paper: the smooth maximum tau * logsumexp in place of
max_t (the paper's allowance is a hard peak, which gives no gradient);
the tiny quadratic regularizer, which picks the least adjustment among
equally cheap best responses (a batch plan that defers nothing costs
nothing, so without it any deep-feasible plan is a best response); the
multiplicative price rule and its cap on rounds, where the paper only
asks that the coordinator lower the price until taxes cover rebates.

A market couples all of a region's rows (rho_r depends on the sum over
them), so the reference clears each region over all its rows, one region
at a time, and reports the sampled rows. It imports nothing of the
program and takes only the inputs: the fleet's arrays and the forecasts.
"""
from __future__ import annotations

import numpy as np


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
    """Replays streaming CR3 ticks over the whole fleet in `dtype` and
    reports the rows `rows_idx`."""

    def __init__(self, fleet: dict, rows_idx: np.ndarray, algo: dict,
                 dtype: str = "float32"):
        import jax
        import jax.numpy as jnp
        self.jax, self.jnp = jax, jnp
        self.algo = algo
        self.dt = jnp.dtype(dtype)
        region = np.asarray(fleet["region"])
        self.members = [np.flatnonzero(region == r)
                        for r in range(int(region.max()) + 1)]
        self.blocks = [self._block(fleet, idx) for idx in self.members]
        self.E64 = [np.asarray(fleet["entitlement"], np.float64)[idx]
                    for idx in self.members]
        self.rows_idx = np.asarray(rows_idx)
        self.region = region[self.rows_idx]
        self.rows = self._block(fleet, self.rows_idx)
        self._round = jax.jit(self._round_impl, static_argnames=("steps",))

    def _block(self, fleet: dict, idx: np.ndarray) -> dict:
        jnp, dt = self.jnp, self.dt
        take = {k: np.asarray(fleet[k])[idx]
                for k in ("usage", "jobs", "entitlement", "k", "rts_coeffs",
                          "betas", "x2_kind", "is_batch")}
        return {
            "usage": jnp.asarray(take["usage"], dt),
            "jobs": jnp.asarray(take["jobs"], dt),
            "E": jnp.asarray(take["entitlement"], dt)[:, None],
            "k": jnp.asarray(take["k"], dt),
            "rts": jnp.asarray(take["rts_coeffs"], dt),
            "betas": jnp.asarray(take["betas"], dt),
            "x2_kind": jnp.asarray(take["x2_kind"], dt),
            "is_batch": jnp.asarray(take["is_batch"], bool),
        }

    def _day_split(self, x):
        day = int(self.algo["day_hours"])
        S, T = x.shape
        span = max(1, T // day) * day
        return x[:, :span].reshape(S, span // day, day), x[:, span:]

    def _less_day_mean(self, x, is_batch):
        """Batch rows with each whole day's mean removed."""
        jnp = self.jnp
        days, rest = self._day_split(x)
        days = days - days.mean(axis=-1, keepdims=True)
        moved = jnp.concatenate([days.reshape(x.shape[0], -1), rest], 1)
        return jnp.where(is_batch[:, None], moved, x)

    def _project(self, x, lo, hi, is_batch):
        x = self.jnp.clip(x, lo, hi)
        for _ in range(int(self.algo["projection_rounds"])):
            x = self.jnp.clip(self._less_day_mean(x, is_batch), lo, hi)
        return x

    def _round_impl(self, x, lam, rho, t, mci, blk, *, steps: int):
        """One best response of a region's rows at price `rho`, from plan
        `x` and multipliers `lam`: `outer` AL rounds of `steps` projected
        Adam steps, mu from mu0."""
        jax, jnp, a, dt = self.jax, self.jnp, self.algo, self.dt
        u = jnp.roll(blk["usage"], -t, axis=1)
        j = jnp.roll(blk["jobs"], -t, axis=1)
        E = blk["E"]
        S, T = u.shape
        hi = jnp.minimum(dt.type(a["max_curtail_frac"]) * E, u)
        lo = jnp.where(blk["is_batch"][:, None], -(E - u), jnp.zeros_like(u))
        scale = jnp.maximum(hi - lo, dt.type(1e-6)).mean(axis=1,
                                                         keepdims=True)
        tau = dt.type(a["tau_frac"]) * E
        keep = dt.type(1.0 - a["tax_frac"])
        reg = dt.type(a["reg"] / (S * T))
        rtol = dt.type(a["queue_rtol"])

        def slack(x):
            peak = tau[:, 0] * jax.nn.logsumexp((u - x) / tau, axis=1)
            return (keep * E[:, 0] + rho * (x * mci).sum(1) - peak) / E[:, 0]

        def lagrangian(x, lam, mu):
            pen = blk["k"] * _penalty(jnp, x, u, j, blk, rtol)
            obj = (pen / E[:, 0]).sum() + (reg * (x / E) ** 2).sum()
            s = jnp.maximum(lam / mu - slack(x), 0.0)
            return obj + 0.5 * mu * ((s * s).sum() - ((lam / mu) ** 2).sum())

        grad = jax.grad(lagrangian)
        lr = dt.type(a["lr"]) * scale
        b1, b2, eps = (float(a[k]) for k in ("beta1", "beta2", "eps"))
        one_b1, one_b2 = dt.type(1.0 - b1), dt.type(1.0 - b2)

        def step(i, c):
            x, m, v, lam, mu = c
            g = self._less_day_mean(grad(x, lam, mu), blk["is_batch"])
            m = dt.type(b1) * m + one_b1 * g
            v = dt.type(b2) * v + one_b2 * g * g
            n = (i + 1).astype(jnp.float32)
            mhat = m / (1.0 - b1 ** n).astype(dt)
            vhat = v / (1.0 - b2 ** n).astype(dt)
            x = self._project(x - lr * mhat / (jnp.sqrt(vhat) + dt.type(eps)),
                              lo, hi, blk["is_batch"])
            return x, m, v, lam, mu

        x = self._project(x, lo, hi, blk["is_batch"])
        mu = dt.type(a["mu0"])
        z = jnp.zeros_like(x)
        for _ in range(int(a["outer"])):
            x, _, _, _, _ = jax.lax.fori_loop(0, steps, step,
                                              (x, z, z, lam, mu))
            lam = jnp.maximum(lam - mu * slack(x), 0.0)
            mu = jnp.minimum(mu * dt.type(a["mu_growth"]),
                             dt.type(a["mu_max"]))
        return x, lam

    def market(self, r: int, x, lam, t: int, mci_r: np.ndarray,
               steps: int):
        """Region r's market at tick t from plan `x` (its rows, already
        shifted for the tick) and multipliers `lam`: best responses, then
        clearing. Returns its last plan and multipliers, and (rho, paid,
        collected, rounds) of its last round."""
        jnp, a, dt = self.jnp, self.algo, self.dt
        collected = float(a["tax_frac"]) * float(self.E64[r].sum())
        mci_d = jnp.asarray(mci_r, dt)
        mci64 = np.asarray(mci_r, np.float64)
        rho = float(a["rho"])

        def answer(x, lam):
            x, lam = self._round(x, lam, dt.type(rho), jnp.int32(t), mci_d,
                                 self.blocks[r], steps=steps)
            return x, lam, rho * float((np.asarray(x, np.float64)
                                        @ mci64).sum())

        x, lam, paid = answer(x, lam)
        rounds = 1
        for _ in range(int(a["clearing_iters"])):
            if paid <= collected + 1e-9:
                break
            rho *= max(0.5, 0.9 * collected / max(paid, 1e-9))
            x, lam, paid = answer(x, lam)
            rounds += 1
        return x, lam, (rho, paid, collected, rounds)

    def run(self, mci: np.ndarray, n_ticks: int, cold: int, warm: int):
        """Replay ticks 0..n_ticks-1 with forecasts `mci` (n, R, T).
        Returns (commits (n_ticks, S), last plan (S, T)) of the reported
        rows, in float64. `markets` then holds each tick's (R, 4) market
        outcomes: rho, paid, collected and rounds per region."""
        jnp = self.jnp
        T = mci.shape[2]
        W = sum(len(m) for m in self.members)
        xs = [jnp.zeros((len(m), T), self.dt) for m in self.members]
        lams = [jnp.zeros((len(m),), self.dt) for m in self.members]
        commits = np.empty((n_ticks, len(self.rows_idx)))
        plan = np.zeros((W, T))
        self.markets = np.empty((n_ticks, len(self.members), 4))
        with self.jax.default_matmul_precision("highest"):
            for t in range(n_ticks):
                for r, idx in enumerate(self.members):
                    x = xs[r]
                    if t:
                        x = jnp.roll(x, -1, axis=1).at[:, -1].set(0)
                    xs[r], lams[r], self.markets[t, r] = self.market(
                        r, x, lams[r], t, mci[t][r],
                        cold if t == 0 else warm)
                    plan[idx] = np.asarray(xs[r], np.float64)
                commits[t] = plan[self.rows_idx, 0]
        return commits, plan[self.rows_idx]

    def penalty_pct(self, plan: np.ndarray, t: int) -> float:
        """100 * sum k * pen(plan) / sum E over the reported rows, for the
        window of tick t."""
        jnp, r = self.jnp, self.rows
        x = jnp.asarray(plan, self.dt)
        u = jnp.roll(r["usage"], -t, axis=1)
        j = jnp.roll(r["jobs"], -t, axis=1)
        pen = _penalty(jnp, x, u, j, r, self.dt.type(self.algo["queue_rtol"]))
        return float(100.0 * np.asarray((r["k"] * pen).astype(jnp.float32),
                                        np.float64).sum()
                     / np.asarray(r["E"], np.float64).sum())
