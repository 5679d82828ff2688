"""`repro.analysis` tests: drlint rule detections + suppressions, the
CLI contract, the checkify sanitizer lane (parity, NaN injection,
unsupported-combo refusals), and `recompile_guard` one-trace claims
(warm vs cold `solve()`, `run_scanned` across consecutive days).

Every drlint rule gets at least one positive-detection test against a
synthetic bad snippet; the clean-tree test pins the invariant that the
shipped `src/repro` lints clean (CI runs the same check via
`scripts/ci.sh`)."""
import dataclasses
import pathlib
import textwrap

import numpy as np
import pytest

from repro.analysis import (RecompileError, SanitizeError, check_all_finite,
                            checked_jit, recompile_guard)
from repro.analysis.lint import lint_paths, main as lint_main
from repro.analysis.rules import RULES, lint_source
from repro.core.api import CR1, CR2, CR3, SolveContext, solve, sweep
from repro.core.fleet_solver import synthetic_fleet

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


def _lint(source: str, path: str = "src/repro/core/example.py"):
    return lint_source(path, textwrap.dedent(source))


def _rules(violations):
    return [v.rule for v in violations]


# ---------------------------------------------------------------------------
# drlint: one positive detection per rule
# ---------------------------------------------------------------------------
def test_rule_registry_complete():
    assert set(RULES) == {
        "jit-host-leak", "donation-twin", "check-vma-justification",
        "tuple-seed", "np-on-traced", "deprecated-shim",
        "adhoc-partition-spec", "host-sync-in-jit"}


def test_jit_host_leak_float_and_item():
    vs = _lint("""
        import jax

        @jax.jit
        def f(x):
            y = float(x)
            return y + x.sum().item()
    """)
    assert _rules(vs) == ["jit-host-leak", "jit-host-leak"]
    assert "float()" in vs[0].message and ".item()" in vs[1].message


def test_jit_host_leak_traced_branch():
    vs = _lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if jnp.any(x > 0):
                return x
            return -x
    """)
    assert _rules(vs) == ["jit-host-leak"]
    assert "lax.cond" in vs[0].message


def test_jit_host_leak_static_metadata_is_legal():
    vs = _lint("""
        import jax

        @jax.jit
        def f(x, n_eq):
            k = int(x.shape[0])
            if n_eq:
                return x[:k]
            return x
    """)
    assert vs == []


def test_jit_host_leak_only_in_reachable_functions():
    # Same float() call, but nothing jits `f` — host-side code is free
    # to concretize.
    vs = _lint("""
        def f(x):
            return float(x)
    """)
    assert vs == []


@pytest.mark.parametrize("wiring", ["factory", "table"])
def test_jit_host_leak_through_factory_and_table(wiring):
    # `impl` is jitted by a module factory that jits its parameter, and
    # reaches `leak` by a top-level table lookup: both are jit roots or
    # edges, so the float() inside `leak` is flagged either way.
    body = {
        "factory": """
            def jits(fn, static):
                return jax.jit(fn, static_argnames=static)

            def impl(x):
                return leak(x)

            RUN = jits(impl, ())
        """,
        "table": """
            PIECES = {"a": leak}

            def impl(x):
                return PIECES["a"](x)

            RUN = jax.jit(impl)
        """,
    }[wiring]
    vs = _lint("import jax\n\n\ndef leak(x):\n    return float(x)\n"
               + textwrap.dedent(body))
    assert _rules(vs) == ["jit-host-leak"]


def test_engine_lanes_are_jit_reachable():
    """The CR1/CR2 lanes are jitted through `api._jits` and reach their
    pieces through `api._ENGINES`: the lint walks the whole hot path."""
    from repro.analysis.rules import Module, _jit_reachable
    path = SRC / "core" / "api.py"
    reach = {f.name for f in _jit_reachable(
        Module.parse(str(path), path.read_text()))}
    want = {"_cr1_impl", "_cr2_impl", "_tick", "_cr1_pieces", "_cr2_pieces",
            "_al_fused_inner", "_tick_report", "_batch_lane", "_day_lane",
            "_day_impl", "_day_sums", "_coupled_impl", "_cr3_market"}
    assert want <= reach, sorted(want - reach)


def test_donation_twin_missing_sibling():
    vs = _lint("""
        import jax

        def impl(p, lam, warm, steps):
            return warm

        _run_donated = jax.jit(impl, static_argnames=("steps",),
                               donate_argnums=(2,))
    """)
    assert _rules(vs) == ["donation-twin"]
    assert "non-donated jit" in vs[0].message


def test_donation_twin_ok_and_static_donation_flagged():
    ok = _lint("""
        import jax

        def impl(p, lam, warm, steps):
            return warm

        _STATIC = ("steps",)
        _run = jax.jit(impl, static_argnames=_STATIC)
        _run_donated = jax.jit(impl, static_argnames=_STATIC,
                               donate_argnums=(2,))
    """)
    assert ok == []
    bad = _lint("""
        import jax

        def impl(p, lam, warm, steps):
            return warm

        _run = jax.jit(impl, static_argnames=("steps",))
        _run_donated = jax.jit(impl, static_argnames=("steps",),
                               donate_argnums=(3,))
    """)
    assert _rules(bad) == ["donation-twin"]
    assert "static" in bad[0].message


def test_check_rep_needs_pallas_comment():
    bad = _lint("""
        import jax

        def build(mesh, body, specs):
            return jax.shard_map(body, mesh=mesh, in_specs=specs,
                                 out_specs=specs, check_vma=False)
    """)
    assert _rules(bad) == ["check-vma-justification"]
    ok = _lint("""
        import jax

        def build(mesh, body, specs):
            # check_vma=False: body dispatches the al_step pallas_call,
            # which has no shard_map varying-axes rule.
            return jax.shard_map(body, mesh=mesh, in_specs=specs,
                                 out_specs=specs, check_vma=False)
    """)
    assert ok == []


def test_tuple_seed_arithmetic_flagged():
    bad = _lint("""
        import numpy as np

        def batch(seed, step, host):
            return np.random.default_rng(seed * 4093 + step)
    """)
    assert _rules(bad) == ["tuple-seed"]
    ok = _lint("""
        import numpy as np
        import jax

        def batch(seed, step, host):
            key = jax.random.PRNGKey(seed)
            return np.random.default_rng((seed, step, host))
    """)
    assert ok == []


def test_np_on_traced():
    bad = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)
    """)
    assert _rules(bad) == ["np-on-traced"]
    # Metadata queries stay legal on tracers.
    ok = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return x.reshape(np.shape(x)[0], -1)
    """)
    assert ok == []


def test_deprecated_shim():
    bad = _lint("""
        from repro.core.fleet_solver import solve_cr1_fleet

        def run(p):
            return solve_cr1_fleet(p, lam=1.4)
    """)
    assert _rules(bad) == ["deprecated-shim"]
    # The shims' own module is exempt (definitions + parity docs).
    ok = _lint("""
        def caller(p):
            return solve_cr1_fleet(p, lam=1.4)
    """, path="src/repro/core/fleet_solver.py")
    assert ok == []


def test_adhoc_partition_spec():
    bad = _lint("""
        from jax.sharding import PartitionSpec as P

        def specs():
            return P("fleet"), P(None, "region")
    """)
    assert _rules(bad) == ["adhoc-partition-spec", "adhoc-partition-spec"]
    # Named axis constants are the sanctioned spelling.
    ok = _lint("""
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import FLEET_AXIS

        def specs():
            return P(FLEET_AXIS)
    """)
    assert ok == []
    # Out of scope outside core/ (training scaffolding owns its axes).
    out_of_scope = _lint("""
        from jax.sharding import PartitionSpec as P
        SPEC = P("data", "model")
    """, path="src/repro/launch/sharding.py")
    assert out_of_scope == []


def test_host_sync_in_jit():
    bad = _lint("""
        import jax
        from repro import obs

        @jax.jit
        def f(x):
            y = x * 2
            jax.block_until_ready(y)
            with obs.span("inner"):
                z = jax.device_get(y)
            return z
    """)
    assert _rules(bad) == ["host-sync-in-jit"] * 3
    assert "block_until_ready" in bad[0].message
    # Host-side timing around (not inside) jitted code is the contract.
    ok = _lint("""
        import jax
        from repro import obs

        @jax.jit
        def f(x):
            return x * 2

        def bench(x):
            with obs.span("solve") as s:
                s.bind(f(x))
            return jax.device_get(f(x))
    """)
    assert ok == []


# ---------------------------------------------------------------------------
# drlint: suppression mechanics
# ---------------------------------------------------------------------------
def test_suppression_with_rationale_honored():
    vs = _lint("""
        import jax

        @jax.jit
        def f(flag):
            # drlint: disable=jit-host-leak -- static jit argument
            return bool(flag)
    """)
    assert vs == []


def test_suppression_same_line_and_multi_rule():
    vs = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return float(np.sum(x))  # drlint: disable=jit-host-leak,np-on-traced -- host-side debug helper
    """)
    assert vs == []


def test_suppression_without_rationale_is_a_violation():
    vs = _lint("""
        import jax

        @jax.jit
        def f(flag):
            # drlint: disable=jit-host-leak
            return bool(flag)
    """)
    assert _rules(vs) == ["suppression-rationale"]


def test_suppression_does_not_reach_two_lines_down():
    vs = _lint("""
        import jax

        @jax.jit
        def f(flag):
            # drlint: disable=jit-host-leak -- too far away
            y = 1
            return bool(flag)
    """)
    assert _rules(vs) == ["jit-host-leak"]


# ---------------------------------------------------------------------------
# drlint: tree + CLI contract
# ---------------------------------------------------------------------------
def test_shipped_tree_lints_clean():
    """The invariant CI enforces: src/repro has zero unsuppressed
    violations (and every suppression in it carries a rationale)."""
    vs = lint_paths([str(SRC)])
    assert vs == [], "\n".join(v.format() for v in vs)


def test_cli_exit_and_format(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import numpy as np
        rng = np.random.default_rng(7 * 1000 + 3)
    """))
    rc = lint_main([str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert f"{bad}:3:" in out and "tuple-seed" in out

    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert lint_main([str(good)]) == 0
    assert lint_main(["--list-rules"]) == 0


# ---------------------------------------------------------------------------
# Sanitizer lane
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def fp():
    return synthetic_fleet(6, seed=3)


@pytest.mark.parametrize("policy", [CR1(lam=1.4), CR2(cap_frac=0.12)],
                         ids=["cr1", "cr2"])
def test_sanitize_parity(fp, policy):
    """sanitize=True is the same solve with guards — bitwise plan/state
    parity with the unchecked lane."""
    plain = solve(fp, policy, ctx=SolveContext(steps=60))
    checked = solve(fp, policy, ctx=SolveContext(steps=60, sanitize=True))
    np.testing.assert_array_equal(plain.D, checked.D)
    np.testing.assert_array_equal(np.asarray(plain.state.x),
                                  np.asarray(checked.state.x))
    assert plain.carbon_reduction_pct == checked.carbon_reduction_pct


@pytest.mark.parametrize("policy", [CR1(lam=1.4), CR2(cap_frac=0.12)],
                         ids=["cr1", "cr2"])
def test_sanitize_catches_injected_nan(fp, policy):
    """A poisoned carbon trace must raise SanitizeError naming the AL
    check — the unchecked lane silently returns a NaN plan."""
    mci = np.asarray(fp.mci, float).copy()
    mci[3] = np.nan
    poisoned = dataclasses.replace(fp, mci=mci)
    silent = solve(poisoned, policy, ctx=SolveContext(steps=40))
    assert np.isnan(np.asarray(silent.D)).any()   # the failure mode
    with pytest.raises(SanitizeError, match="non-finite"):
        solve(poisoned, policy, ctx=SolveContext(steps=40, sanitize=True))


@pytest.mark.parametrize("policy", [CR1(lam=1.4), CR2(cap_frac=0.12)],
                         ids=["cr1", "cr2"])
def test_sanitize_day_scan_parity_and_nan(fp, policy):
    """The checkify lane extends to solo `solve_day` scans: bitwise
    committed-matrix parity, and a NaN in any tick's forecast row fires
    `SanitizeError` instead of poisoning the rest of the day."""
    from repro.core.api import solve_day

    rng = np.random.default_rng((11, 4))
    base = np.asarray(fp.mci, float)
    stack = np.stack([np.roll(base, -i) * (1 + 0.01 * rng.standard_normal(
        base.shape)) for i in range(3)])
    plain = solve_day(fp, policy, stack, cold_steps=40, warm_steps=10)
    checked = solve_day(fp, policy, stack, cold_steps=40, warm_steps=10,
                        ctx=SolveContext(sanitize=True))
    np.testing.assert_array_equal(plain.committed, checked.committed)
    poisoned = stack.copy()
    poisoned[1, 5] = np.nan   # warm tick 1's horizon
    with pytest.raises(SanitizeError, match="non-finite"):
        solve_day(fp, policy, poisoned, cold_steps=40, warm_steps=10,
                  ctx=SolveContext(sanitize=True))


@pytest.mark.parametrize("lane", ["tick", "day"])
@pytest.mark.parametrize("policy", [CR1(lam=1.4), CR2(cap_frac=0.12)],
                         ids=["cr1", "cr2"])
def test_lane_twins_donate_the_engine_state(fp, policy, lane):
    """The twins that `api._jits` builds, checked where drlint's
    `donation-twin` cannot see them: the donated twin consumes the warm
    engine state it is given (a traced argument), and its plain sibling
    leaves that state readable."""
    from repro.core.api import solve_day

    def run(warm, donate):
        ctx = SolveContext(steps=8, warm=warm, donate=donate)
        if lane == "tick":
            solve(fp, policy, ctx=dataclasses.replace(ctx, shift=1,
                                                      reset_mu=True))
        else:
            solve_day(fp, policy, np.stack([np.asarray(fp.mci)] * 2),
                      ctx=ctx)

    warm = solve(fp, policy, ctx=SolveContext(steps=8)).state
    run(warm, donate=False)
    assert not warm.x.is_deleted()
    run(warm, donate=True)
    assert warm.x.is_deleted()


def test_sanitize_refuses_unsupported_combos(fp):
    with pytest.raises(NotImplementedError, match="no sanitized lane"):
        solve(fp, CR3(), ctx=SolveContext(sanitize=True))
    with pytest.raises(NotImplementedError, match="solo debug lane"):
        solve(fp, CR1(lam=1.4), ctx=SolveContext(sanitize=True, donate=True))
    with pytest.raises(NotImplementedError, match="solo-solve debug lane"):
        sweep(fp, [CR1(lam=1.2), CR1(lam=1.6)],
              ctx=SolveContext(sanitize=True))


def test_check_all_finite_unit():
    import jax.numpy as jnp

    def f(x):
        y = x * 2
        check_all_finite("unit", y=y)
        return y

    g = checked_jit(f)
    err, out = g(jnp.ones(4))
    err.throw()   # clean input: no error
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones(4))
    err, _ = g(jnp.array([1.0, np.inf, 3.0, 4.0]))
    with pytest.raises(SanitizeError, match="non-finite values in y"):
        err.throw()


# ---------------------------------------------------------------------------
# recompile_guard: the one-trace claims
# ---------------------------------------------------------------------------
def test_recompile_guard_measures_and_fires():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x * 3

    with recompile_guard(None) as stats:
        f(jnp.ones(5))
    assert stats.traces > 0 and stats.lowerings > 0   # both hooks count

    with recompile_guard(0):
        f(jnp.ones(5))      # warm: cache hit, no trace

    with pytest.raises(RecompileError, match="jit cache missed"):
        with recompile_guard(0, label="forced retrace"):
            f(jnp.ones(7))  # new shape forces a retrace


def test_warm_and_cold_solve_share_one_trace(fp):
    """`solve()` cold passes `EngineState.cold(...)` — the same pytree
    shape a warm state has — so cold and warm re-solves hit one jit
    entry."""
    ctx = SolveContext(steps=40)
    first = solve(fp, CR1(lam=1.4), ctx=ctx)          # compiles once
    with recompile_guard(0, label="warm+cold solve"):
        solve(fp, CR1(lam=1.4), ctx=ctx)              # cold again
        solve(fp, CR1(lam=1.4),
              ctx=dataclasses.replace(ctx, warm=first.state))  # warm


def test_run_scanned_compiles_once_across_days(fp):
    """Consecutive same-length day scans reuse one trace; the solver's
    own `guard_recompiles` enforces it from day 2 on (and a bare
    guard(0) around day 3 re-checks it from the outside)."""
    from repro.core.streaming import ForecastStream, RollingHorizonSolver

    actual = np.tile(np.asarray(fp.mci), 3)[:fp.T + 16]
    stream = ForecastStream(actual=actual, horizon=fp.T, seed=0)
    solver = RollingHorizonSolver(fp, stream, policy=CR1(lam=1.4),
                                  cold_steps=60, warm_steps=20,
                                  guard_recompiles=True)
    solver.run_scanned(4)                  # day 1: compiles
    solver.run_scanned(4)                  # day 2: guarded by the solver
    with recompile_guard(0, label="day 3"):
        solver.run_scanned(4)              # day 3: provably compile-free


def test_run_guard_ticks(fp):
    """Per-tick warm re-solves after the first warm tick run under the
    solver's guard — a drifting static argument would raise."""
    from repro.core.streaming import ForecastStream, RollingHorizonSolver

    actual = np.tile(np.asarray(fp.mci), 3)[:fp.T + 16]
    stream = ForecastStream(actual=actual, horizon=fp.T, seed=1)
    solver = RollingHorizonSolver(fp, stream, policy=CR1(lam=1.4),
                                  cold_steps=60, warm_steps=20,
                                  adaptive_warm=False,
                                  guard_recompiles=True)
    report = solver.run(5)
    assert len(report.ticks) == 5
