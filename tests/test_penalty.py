"""Penalty model tests (paper §IV, Eq. 1–2)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import penalty as pen


def test_rts_polynomial_published_coefficients():
    """f_RTS1(δ) = 6.3δ³ − 13δ² + 51.6δ at δ=0.2 ⇒ ≈ 9.85 (% latency)."""
    m = pen.PenaltyModel(name="RTS1", kind="realtime",
                         usage=np.ones(1), entitlement=1.0, k=1.0,
                         params=pen.RTS_COEFFS["RTS1"])
    d = jnp.asarray([0.2])
    expected = 6.3 * 0.2**3 - 13 * 0.2**2 + 51.6 * 0.2
    assert float(m.raw_loss(d)) == pytest.approx(expected, rel=1e-6)


def test_rts2_monotone_on_curtailment_range():
    m = pen.PenaltyModel(name="RTS2", kind="realtime",
                         usage=np.ones(1), entitlement=1.0, k=1.0,
                         params=pen.RTS_COEFFS["RTS2"])
    deltas = np.linspace(0, 0.5, 20)
    losses = [float(m.raw_loss(jnp.asarray([x]))) for x in deltas]
    assert all(b >= a - 1e-9 for a, b in zip(losses, losses[1:]))


def test_k_calibration_property(paper_fleet):
    """C_i at the calibration curtailment equals the 15% entitlement loss
    (the defining property of k — §IV ¶4)."""
    for name in ("RTS1", "RTS2"):
        m = paper_fleet[name]
        d = m.calibration_curtailment()
        got = float(m.penalty(jnp.asarray(d)))
        want = pen.CALIBRATION_CAP * m.entitlement
        assert got == pytest.approx(want, rel=1e-3)


def test_batch_penalty_positive_part(paper_fleet):
    """Eq. 2: batch penalty is clamped at zero (boost can't earn credit)."""
    m = paper_fleet["AITraining"]
    d = -0.2 * m.usage          # pure boost
    assert float(m.penalty(jnp.asarray(d))) == pytest.approx(0.0, abs=1e-6)


def test_batch_penalty_increases_with_curtailment(paper_fleet):
    m = paper_fleet["DataPipeline"]
    c1 = float(m.penalty(jnp.asarray(0.2 * m.usage)))
    c2 = float(m.penalty(jnp.asarray(0.4 * m.usage)))
    assert c2 > c1 >= 0.0


def test_published_feature_selection(paper_fleet):
    assert paper_fleet["AITraining"].feature_names == (
        "waiting_time_power", "num_jobs_delayed")
    assert paper_fleet["DataPipeline"].feature_names == (
        "waiting_time_power", "waiting_time_squared")


def test_fleet_composition(paper_fleet):
    kinds = {m.kind for m in paper_fleet.values()}
    assert kinds == {"realtime", "batch_slo", "batch_noslo"}
    for m in paper_fleet.values():
        assert m.entitlement > float(np.max(m.usage))


def test_paper_fleet_build_independent_of_hash_seed():
    """The calibrated fleet is a function of its arguments alone: the
    per-service job-trace seeds must not come from Python's per-process
    string hash (a differently calibrated fleet per run moved every
    result built on it)."""
    import json
    import os
    import subprocess
    import sys

    code = ("import json\n"
            "from repro.core.penalty import build_paper_fleet\n"
            "f = build_paper_fleet(hours=24, num_samples=8, num_jobs=300)\n"
            "print(json.dumps({n: [float(m.k), *map(float, m.params)]"
            " for n, m in f.items()}))\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    outs = []
    for hash_seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=hash_seed, PYTHONPATH=src)
        res = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stderr[-2000:]
        outs.append(json.loads(res.stdout.strip().splitlines()[-1]))
    assert outs[0] == outs[1]
