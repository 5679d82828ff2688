"""`repro.analysis` — the correctness tooling tier.

Two halves:

  * **drlint** (`repro.analysis.lint` / `.rules`): an AST static-
    analysis pass encoding the repo's JAX invariants — jit twins,
    check_vma justifications, tuple seeding, host-leak bans — as a
    rule registry with per-rule suppression comments. Run it with
    ``python -m repro.analysis.lint``; `scripts/ci.sh` fails on
    violations.
  * **runtime sanitizers** (`.sanitize` / `.recompile`):
    `SolveContext(sanitize=True)` threads checkify non-finite guards
    through the CR1/CR2 lanes and the AL inner loop, and
    `recompile_guard()` asserts the warm-path one-trace and
    one-dispatch-per-day claims at runtime.

`analysis/README.md` documents every lint rule with its motivating
incident.
"""
from repro.analysis.recompile import (RecompileError, RecompileStats,
                                      recompile_guard)
from repro.analysis.sanitize import (SanitizeError, check_all_finite,
                                     checked_jit)

__all__ = ["RecompileError", "RecompileStats", "SanitizeError",
           "check_all_finite", "checked_jit", "recompile_guard"]
