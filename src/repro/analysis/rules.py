"""drlint rule registry: this repo's JAX invariants as AST checks.

Every rule encodes an invariant the engine stack actually relies on —
each one has a motivating incident (see `analysis/README.md` for the
full list with history):

  * ``jit-host-leak``        — no host-side Python on traced values in
                               jit-reachable functions.
  * ``donation-twin``        — every ``jit(donate_argnums=...)`` twin
                               mirrors a non-donated sibling (api.py's
                               twin pattern).
  * ``check-vma-justification`` — ``shard_map(..., check_vma=False)``
                               must carry a comment naming the
                               pallas_call that requires it.
  * ``tuple-seed``           — ``default_rng((seed, ...))`` tuple
                               seeding, never ``seed + idx`` arithmetic.
  * ``np-on-traced``         — no ``np.*`` value computation in
                               jit-reachable hot paths (shape/metadata
                               queries are whitelisted).
  * ``deprecated-shim``      — internal code must not call the legacy
                               ``solve_cr{1,2,3}_fleet`` shims.
  * ``adhoc-partition-spec`` — no string-literal axis names in
                               ``P(...)``; axis names flow from
                               `repro.launch.mesh` / `regional.norm_specs`.
  * ``host-sync-in-jit``     — no ``block_until_ready`` /
                               ``jax.device_get`` / ``obs.span`` inside
                               jit-reachable code; host syncs live
                               outside the trace (telemetry rides the
                               solve as stacked aux outputs instead).

Suppression: append ``# drlint: disable=<rule>[,<rule>] -- <rationale>``
to the flagged line, or put it on its own line directly above. The
rationale after ``--`` is mandatory — a suppression without one is
itself a violation (``suppression-rationale``).

Rules are module-local by design: the checker parses one file at a time
and never imports the code under analysis, so drlint runs in
milliseconds with no JAX (or any repo) import. Cross-module jit
reachability is approximated by `EXTRA_JIT_ROOTS` — the short table of
functions this repo documents as "jitted by their callers" (e.g.
`engine.al_minimize`, which adapters wrap in their own `jax.jit`).
"""
from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from typing import Callable, Iterable

__all__ = ["EXTRA_JIT_ROOTS", "Module", "RULES", "Violation", "lint_source"]


# ---------------------------------------------------------------------------
# Infrastructure: violations, modules, suppressions, registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Violation:
    """One rule hit at a source location."""
    rule: str
    path: str
    line: int
    col: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} " \
               f"{self.message}"


@dataclasses.dataclass(frozen=True)
class Suppression:
    line: int
    rules: frozenset[str]
    rationale: str


_SUPPRESS_RE = re.compile(
    r"#\s*drlint:\s*disable=([\w\-, ]+?)\s*(?:--\s*(.*\S))?\s*$")


@dataclasses.dataclass
class Module:
    """One parsed source file plus everything the rules need."""
    path: str
    source: str
    tree: ast.Module
    comments: dict[int, str]            # line -> comment text
    suppressions: dict[int, Suppression]  # line the suppression sits on

    @classmethod
    def parse(cls, path: str, source: str) -> "Module":
        tree = ast.parse(source, filename=path)
        comments: dict[int, str] = {}
        try:
            toks = tokenize.generate_tokens(io.StringIO(source).readline)
            for tok in toks:
                if tok.type == tokenize.COMMENT:
                    comments[tok.start[0]] = tok.string
        except tokenize.TokenError:
            pass
        sups = {}
        for line, text in comments.items():
            m = _SUPPRESS_RE.search(text)
            if m:
                names = frozenset(
                    s.strip() for s in m.group(1).split(",") if s.strip())
                sups[line] = Suppression(line, names, m.group(2) or "")
        return cls(path, source, tree, comments, sups)

    def suppressed(self, rule: str, line: int) -> bool:
        """A suppression covers its own line and the line below it."""
        for at in (line, line - 1):
            s = self.suppressions.get(at)
            if s is not None and rule in s.rules:
                return True
        return False


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    summary: str
    check: Callable[[Module], list[Violation]]


RULES: dict[str, Rule] = {}


def rule(name: str, summary: str):
    def deco(fn):
        RULES[name] = Rule(name, summary, fn)
        return fn
    return deco


def lint_source(path: str, source: str) -> list[Violation]:
    """Run every registered rule over one file; apply suppressions."""
    mod = Module.parse(path, source)
    out: list[Violation] = []
    for r in RULES.values():
        for v in r.check(mod):
            if not mod.suppressed(v.rule, v.line):
                out.append(v)
    # A suppression that hides a rule must say why: rationale-free
    # suppressions defeat the point of the pass (rule of the pass itself,
    # so it cannot be suppressed).
    for s in mod.suppressions.values():
        if not s.rationale:
            out.append(Violation(
                "suppression-rationale", path, s.line, 0,
                "suppression without rationale — append '-- <why>'"))
    return sorted(out, key=lambda v: (v.line, v.col, v.rule))


# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------
def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute chains / Names; '' for anything else."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    return ""


def _is_jax_jit(node: ast.AST) -> bool:
    return _dotted(node) in ("jax.jit", "jit")


def _jit_target(call: ast.Call) -> str | None:
    """The function name jitted by a `jax.jit(fn, ...)` call, if a Name."""
    if _is_jax_jit(call.func) and call.args \
            and isinstance(call.args[0], ast.Name):
        return call.args[0].id
    return None


def _partial_jit_decorator(dec: ast.AST) -> bool:
    """`@functools.partial(jax.jit, ...)` / `@partial(jit, ...)`."""
    return (isinstance(dec, ast.Call)
            and _dotted(dec.func) in ("functools.partial", "partial")
            and bool(dec.args) and _is_jax_jit(dec.args[0]))


#: path-suffix -> function names jitted by *callers* in other modules.
#: The engine is deliberately not jitted in its own module (adapters own
#: the jit so warm re-solves share one trace) — without this table the
#: reachability walk would never enter it.
EXTRA_JIT_ROOTS: dict[str, frozenset[str]] = {
    "core/engine.py": frozenset(
        {"al_minimize", "al_minimize_batched", "al_minimize_sharded"}),
    # fleet_solver helpers called from inside api.py's jitted impls.
    "core/fleet_solver.py": frozenset(
        {"fleet_penalties", "_projection", "_bounds", "_enter_tick"}),
    # regional norm builders ride inside the jitted lanes.
    # (`region_totals`/`cr3_reg_scale` are deliberately host-side numpy
    # — see their docstrings — so they are NOT roots.)
    "core/regional.py": frozenset(
        {"cr1_norms", "cr2_norms", "region_sum"}),
}


def _function_index(tree: ast.Module) -> dict[str, list[ast.FunctionDef]]:
    idx: dict[str, list[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            idx.setdefault(node.name, []).append(node)
    return idx


def _jit_factories(tree: ast.Module) -> dict[str, int]:
    """Module functions that jit one of their own parameters (`def
    f(impl, ...): ... jax.jit(impl, ...)`): name -> that parameter's
    position, so a call `f(fn, ...)` makes `fn` a root."""
    out: dict[str, int] = {}
    for fn in tree.body:
        if not isinstance(fn, ast.FunctionDef):
            continue
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        for node in ast.walk(fn):
            t = _jit_target(node) if isinstance(node, ast.Call) else None
            if t in params:
                out[fn.name] = params.index(t)
    return out


def _module_bindings(tree: ast.Module) -> dict[str, ast.AST]:
    """Top-level `NAME = value` bindings: name -> value."""
    out: dict[str, ast.AST] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign):
            out.update((t.id, node.value) for t in node.targets
                       if isinstance(t, ast.Name))
        elif isinstance(node, ast.AnnAssign) and node.value is not None \
                and isinstance(node.target, ast.Name):
            out[node.target.id] = node.value
    return out


def _jit_reachable(mod: Module) -> list[ast.FunctionDef]:
    """FunctionDefs reachable (same module) from a jit root.

    Roots: `X = jax.jit(fn, ...)` assignments, `@jax.jit` /
    `@functools.partial(jax.jit, ...)` decorators, functions handed to a
    jit factory of the module (`_jit_factories`), and EXTRA_JIT_ROOTS.
    Edges: any Name reference inside a reachable body that matches a
    module function (catches plain calls and functions handed to
    vmap/scan/shard_map alike — a deliberate over-approximation), or a
    top-level binding, whose value is then walked the same way (a table
    of functions that a traced body looks up)."""
    idx = _function_index(mod.tree)
    factories = _jit_factories(mod.tree)
    bindings = _module_bindings(mod.tree)
    roots: set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            t = _jit_target(node)
            if t:
                roots.add(t)
            pos = factories.get(getattr(node.func, "id", None))
            if pos is not None and len(node.args) > pos \
                    and isinstance(node.args[pos], ast.Name):
                roots.add(node.args[pos].id)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jax_jit(dec) or _partial_jit_decorator(dec):
                    roots.add(node.name)
    norm = mod.path.replace("\\", "/")
    for suffix, names in EXTRA_JIT_ROOTS.items():
        if norm.endswith(suffix):
            roots |= names
    seen: set[int] = set()
    out: list[ast.FunctionDef] = []
    work: list[ast.AST] = [fn for name in roots for fn in idx.get(name, [])]
    while work:
        fn = work.pop()
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.append(fn)
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn and id(node) not in seen:
                work.append(node)   # nested defs run under the same trace
            if isinstance(node, ast.Name):
                work.extend(f for f in idx.get(node.id, ())
                            if id(f) not in seen)
                if node.id in bindings:
                    work.append(bindings[node.id])
    return out


def _own_statements(fn: ast.FunctionDef) -> Iterable[ast.AST]:
    """Walk `fn` excluding nested function bodies (they are reported as
    their own reachable functions — avoids double counting)."""
    work: list[ast.AST] = list(ast.iter_child_nodes(fn))
    while work:
        node = work.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            work.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# Rule 1: jit-host-leak
# ---------------------------------------------------------------------------
_STATIC_METADATA_ATTRS = {"shape", "ndim", "size", "dtype"}


def _is_static_expr(node: ast.AST) -> bool:
    """Heuristic: expressions whose value is trace-time static even when
    built from a traced array — shape/metadata queries and literals."""
    if isinstance(node, ast.Constant):
        return True
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) \
                and sub.attr in _STATIC_METADATA_ATTRS:
            return True
        if isinstance(sub, ast.Call) and _dotted(sub.func) in (
                "len", "np.ndim", "np.shape", "jnp.ndim", "jnp.shape"):
            return True
    return False


def _traced_test(test: ast.AST) -> bool:
    """True when an `if` test computes on traced values: any jnp.* call,
    or a .any()/.all() reduction. Static metadata (`x.ndim == 2`,
    `if n_eq:`) stays legal."""
    for sub in ast.walk(test):
        if isinstance(sub, ast.Call):
            name = _dotted(sub.func)
            if name.startswith("jnp.") or name.startswith("jax.numpy."):
                return True
            if isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in ("any", "all", "item"):
                return True
    return False


@rule("jit-host-leak",
      "host-side Python on traced values inside jit-reachable code")
def _check_host_leak(mod: Module) -> list[Violation]:
    out = []
    for fn in _jit_reachable(mod):
        for node in _own_statements(fn):
            if isinstance(node, ast.Call):
                name = _dotted(node.func)
                if name in ("float", "int", "bool") and node.args \
                        and not _is_static_expr(node.args[0]):
                    out.append(Violation(
                        "jit-host-leak", mod.path, node.lineno,
                        node.col_offset,
                        f"`{name}()` on a (potentially traced) value in "
                        f"jit-reachable `{fn.name}` — concretizes the "
                        f"tracer; keep it an array or hoist to the host"))
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item":
                    out.append(Violation(
                        "jit-host-leak", mod.path, node.lineno,
                        node.col_offset,
                        f"`.item()` in jit-reachable `{fn.name}` — "
                        f"forces a device sync / fails under trace"))
            if isinstance(node, (ast.If, ast.While)) \
                    and _traced_test(node.test):
                out.append(Violation(
                    "jit-host-leak", mod.path, node.lineno,
                    node.col_offset,
                    f"Python branch on a traced condition in "
                    f"jit-reachable `{fn.name}` — use jnp.where/"
                    f"lax.cond instead"))
    return out


# ---------------------------------------------------------------------------
# Rule 2: donation-twin
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _JitEntry:
    var: str
    target: str
    call: ast.Call
    kwargs: dict[str, ast.AST]


def _top_level_constants(tree: ast.Module) -> dict[str, tuple]:
    """Resolve `_CR1_STATIC = ("steps", ...)`-style tuple constants."""
    consts: dict[str, tuple] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            try:
                consts[node.targets[0].id] = ast.literal_eval(node.value)
            except (ValueError, SyntaxError):
                pass
    return consts


def _resolve(node: ast.AST | None, consts: dict[str, tuple]):
    if node is None:
        return None
    if isinstance(node, ast.Name):
        return consts.get(node.id, ...)   # ... = unresolvable
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return ...


@rule("donation-twin",
      "jit(donate_argnums=...) must mirror a non-donated sibling")
def _check_donation_twin(mod: Module) -> list[Violation]:
    consts = _top_level_constants(mod.tree)
    fns = {n.name: n for n in mod.tree.body
           if isinstance(n, ast.FunctionDef)}
    entries: list[_JitEntry] = []
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call):
            target = _jit_target(node.value)
            if target:
                entries.append(_JitEntry(
                    node.targets[0].id, target, node.value,
                    {kw.arg: kw.value for kw in node.value.keywords
                     if kw.arg}))
    out = []
    for e in entries:
        if "donate_argnums" not in e.kwargs:
            continue
        static = _resolve(e.kwargs.get("static_argnames"), consts)
        siblings = [
            s for s in entries
            if s.target == e.target and "donate_argnums" not in s.kwargs
            and _resolve(s.kwargs.get("static_argnames"), consts) == static]
        if not siblings:
            out.append(Violation(
                "donation-twin", mod.path, e.call.lineno,
                e.call.col_offset,
                f"`{e.var}` donates `{e.target}` buffers but no "
                f"non-donated jit of `{e.target}` with matching "
                f"static_argnames exists — the twin pattern needs both"))
            continue
        donated = _resolve(e.kwargs["donate_argnums"], consts)
        fn = fns.get(e.target)
        if fn is None or donated is ...:
            continue
        if isinstance(donated, int):
            donated = (donated,)
        pos = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        static_names = set(static) if isinstance(static, tuple) else set()
        for i in donated:
            if not isinstance(i, int) or i >= len(pos):
                out.append(Violation(
                    "donation-twin", mod.path, e.call.lineno,
                    e.call.col_offset,
                    f"`{e.var}` donates position {i} but `{e.target}` "
                    f"has only {len(pos)} positional params"))
            elif pos[i] in static_names:
                out.append(Violation(
                    "donation-twin", mod.path, e.call.lineno,
                    e.call.col_offset,
                    f"`{e.var}` donates `{pos[i]}` (position {i}) which "
                    f"is static — donation applies to traced buffers"))
    return out


# ---------------------------------------------------------------------------
# Rule 3: check-vma-justification
# ---------------------------------------------------------------------------
@rule("check-vma-justification",
      "shard_map(check_vma=False) must name its pallas_call in a comment")
def _check_check_vma(mod: Module) -> list[Violation]:
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) \
                or "shard_map" not in _dotted(node.func):
            continue
        for kw in node.keywords:
            if kw.arg == "check_vma" and isinstance(kw.value, ast.Constant) \
                    and kw.value.value is False:
                lines = range(max(1, node.lineno - 6), kw.value.lineno + 1)
                justified = any(
                    "pallas" in mod.comments.get(ln, "").lower()
                    for ln in lines)
                if not justified:
                    out.append(Violation(
                        "check-vma-justification", mod.path,
                        kw.value.lineno, kw.value.col_offset,
                        "check_vma=False without a nearby comment naming "
                        "the pallas_call that requires it (pallas kernels "
                        "have no shard_map varying-axes rule — say which "
                        "one, or drop the flag)"))
    return out


# ---------------------------------------------------------------------------
# Rule 4: tuple-seed
# ---------------------------------------------------------------------------
_ARITH = (ast.Add, ast.Sub, ast.Mult, ast.FloorDiv, ast.Mod, ast.BitXor,
          ast.LShift, ast.RShift)


def _has_tuple_operand(node: ast.AST) -> bool:
    return any(isinstance(sub, ast.Tuple) for sub in ast.walk(node))


@rule("tuple-seed",
      "RNG seeds must be tuples, never seed arithmetic")
def _check_tuple_seed(mod: Module) -> list[Violation]:
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        name = _dotted(node.func)
        if not (name.endswith("default_rng") or name.endswith("PRNGKey")):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.BinOp) \
                and isinstance(arg.op, _ARITH) \
                and not _has_tuple_operand(arg):
            out.append(Violation(
                "tuple-seed", mod.path, node.lineno, node.col_offset,
                f"seed arithmetic in `{name}(...)` — streams collide "
                f"when index products overlap (the PR 5 incident class); "
                f"seed with a tuple: `{name}((seed, idx, ...))`"))
    return out


# ---------------------------------------------------------------------------
# Rule 5: np-on-traced
# ---------------------------------------------------------------------------
#: np.* calls that only read static metadata — safe on tracers.
_NP_METADATA_OK = frozenset(
    {"ndim", "shape", "dtype", "result_type", "issubdtype",
     "broadcast_shapes", "size"})


@rule("np-on-traced",
      "no numpy value computation in jit-reachable hot paths")
def _check_np_on_traced(mod: Module) -> list[Violation]:
    out = []
    for fn in _jit_reachable(mod):
        for node in _own_statements(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if name.startswith("np.") and not name.startswith("np.random."):
                attr = name.split(".", 1)[1]
                if attr not in _NP_METADATA_OK:
                    out.append(Violation(
                        "np-on-traced", mod.path, node.lineno,
                        node.col_offset,
                        f"`{name}(...)` in jit-reachable `{fn.name}` — "
                        f"numpy concretizes tracers (ConcretizationTypeError"
                        f" at best, silent host fallback at worst); use "
                        f"jnp, or hoist the computation out of the traced "
                        f"region"))
    return out


# ---------------------------------------------------------------------------
# Rule 6: deprecated-shim
# ---------------------------------------------------------------------------
_SHIMS = frozenset({"solve_cr1_fleet", "solve_cr1_fleet_sweep",
                    "solve_cr2_fleet", "solve_cr3_fleet"})


@rule("deprecated-shim",
      "internal code must not call the legacy solve_cr*_fleet shims")
def _check_deprecated_shim(mod: Module) -> list[Violation]:
    if mod.path.replace("\\", "/").endswith("core/fleet_solver.py"):
        return []   # the shims' own home (definitions + parity docs)
    out = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            base = name.rsplit(".", 1)[-1]
            if base in _SHIMS:
                out.append(Violation(
                    "deprecated-shim", mod.path, node.lineno,
                    node.col_offset,
                    f"`{base}` is a deprecated shim — call "
                    f"`api.solve(problem, policy, ctx=...)` instead"))
    return out


# ---------------------------------------------------------------------------
# Rule 7: adhoc-partition-spec
# ---------------------------------------------------------------------------
@rule("adhoc-partition-spec",
      "PartitionSpec axis names must come from launch.mesh, not literals")
def _check_adhoc_pspec(mod: Module) -> list[Violation]:
    # Scoped to the fleet engine (core/): that is where specs and the
    # fleet mesh must stay in sync through `fleet_axes`/`norm_specs`.
    # The generic training scaffolding (launch/sharding.py) has its own
    # ("data", "model") axis vocabulary and is out of scope.
    if "/core/" not in mod.path.replace("\\", "/"):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name not in ("P", "PartitionSpec") \
                and not name.endswith(".PartitionSpec"):
            continue
        for arg in node.args:
            bad = [s for s in ast.walk(arg)
                   if isinstance(s, ast.Constant) and isinstance(s.value,
                                                                 str)]
            if bad:
                out.append(Violation(
                    "adhoc-partition-spec", mod.path, node.lineno,
                    node.col_offset,
                    f"string-literal axis name {bad[0].value!r} in "
                    f"`P(...)` — axis names flow from "
                    f"`launch.mesh.fleet_axes`/`FLEET_AXIS`/"
                    f"`REGION_AXIS` (and norm specs from "
                    f"`regional.norm_specs`) so mesh refactors can't "
                    f"silently desync specs"))
                break
    return out


# ---------------------------------------------------------------------------
# Rule 8: host-sync-in-jit
# ---------------------------------------------------------------------------
#: dotted names that force a host<->device synchronization (or, for
#: obs.span, deliberately block on device work before reading a clock).
_HOST_SYNC = frozenset({"jax.block_until_ready", "block_until_ready",
                        "jax.device_get", "device_get",
                        "obs.span", "span"})


@rule("host-sync-in-jit",
      "no block_until_ready/device_get/obs.span in jit-reachable code")
def _check_host_sync(mod: Module) -> list[Violation]:
    out = []
    for fn in _jit_reachable(mod):
        for node in _own_statements(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if name in _HOST_SYNC or name.endswith(".block_until_ready") \
                    or name.endswith(".device_get"):
                base = name.rsplit(".", 1)[-1]
                out.append(Violation(
                    "host-sync-in-jit", mod.path, node.lineno,
                    node.col_offset,
                    f"`{name}(...)` in jit-reachable `{fn.name}` — a host "
                    f"sync has no meaning under trace ({base} on a tracer "
                    f"is a no-op at best, a concretization error at "
                    f"worst) and pins the dispatch pipeline if the "
                    f"function also runs eagerly; keep host syncs and "
                    f"`obs.span` timing OUTSIDE jitted code — in-solve "
                    f"observability rides the solve as stacked aux "
                    f"outputs (see `repro.obs.telemetry`)"))
    return out
