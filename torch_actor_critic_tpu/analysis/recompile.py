"""recompile-risk rules: call patterns that retrace or recompile.

The recompilation watchdog (diagnostics/watchdog.py) catches these at
runtime as ``recompile_anomaly`` events; this family catches the same
hazards before a single test runs (docs/OBSERVABILITY.md
"Recompilation-watchdog runbook" cross-references both directions):

* ``jit-cache-discard`` — ``jax.jit(f)(...)`` invoked immediately:
  the wrapper (and its compile cache) is thrown away after one call,
  so every execution pays a full retrace+compile.
* ``jit-in-loop`` — constructing ``jax.jit(...)`` inside a for/while
  body: a fresh wrapper (fresh cache) per iteration.
* ``varying-shape-arg`` — passing a dynamically-bounded slice
  (``x[:n]`` with non-constant ``n``) to a known-jitted callable:
  every distinct length is a new shape, a new trace, a new compile.
* ``donated-reuse`` — reading a buffer after passing it at a donated
  position (``donate_argnums``): the callee may have aliased its
  memory; on TPU the read returns garbage, on CPU it silently works
  (donation is a no-op) and the bug ships.
* ``shard-map-hot-path`` — the PR-8 invariant, promoted from the
  retired source-regex pin in tests/test_mesh_gspmd.py: ``shard_map``
  belongs only in ``parallel/context.py`` (the ring-attention home).
  Every other
  reference must sit in :data:`SHARD_MAP_ALLOWLIST`, and every
  allowlist entry must still match a real reference
  (``stale-allowlist``) — the allowlist is checked, never trusted.
"""

from __future__ import annotations

import ast
import typing as t

from torch_actor_critic_tpu.analysis.reachability import Project, _is_wrapper
from torch_actor_critic_tpu.analysis.walker import (
    FileContext,
    Finding,
    dotted_name,
)

__all__ = ["check", "SHARD_MAP_ALLOWLIST"]

FAMILY = "recompile-risk"

_JIT_MAKERS = frozenset({"jax.jit", "jit", "pjit", "jax.pmap", "pmap"})

# Files where shard_map lives by definition (the rule text itself).
SHARD_MAP_HOME = ("parallel/context.py",)

# (path suffix, scope qualname) pairs allowed to reference shard_map
# outside its home. Scope "<module>" means module level. Every entry
# must match at least one live reference or the run fails with
# stale-allowlist. Justifications live in docs/ANALYSIS.md.
SHARD_MAP_ALLOWLIST: t.FrozenSet[t.Tuple[str, str]] = frozenset({
    # The sp ring-attention burst is manual by nature (a real named
    # axis for the K/V rotation) — the one sanctioned hot-path use.
    ("parallel/dp.py", "DataParallelSAC._build_ring_burst"),
})

_SHARD_NAMES = frozenset({"shard_map"})


def _is_jit_maker(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _is_wrapper(
        dotted_name(node.func), _JIT_MAKERS
    )


def _donated_positions(call: ast.Call) -> t.Tuple[int, ...]:
    for kw in call.keywords:
        if kw.arg not in ("donate_argnums", "donate_argnames"):
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for elt in v.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                    out.append(elt.value)
            return tuple(out)
        # Conditional donation (e.g. `(1,) if donate else ()`) can't be
        # resolved statically; skip rather than guess.
    return ()


def _scope_qualname(ctx: FileContext, node: ast.AST) -> str:
    fn = ctx.enclosing_function(node)
    if fn is None:
        return "<module>"
    for info in ctx.functions:
        if info.node is fn:
            return info.qualname
    return fn.name  # pragma: no cover - every function is indexed


def _target_key(node: ast.AST) -> str | None:
    """'name' or 'self.attr' for jitted-callable tracking."""
    if isinstance(node, ast.Name):
        return node.id
    name = dotted_name(node)
    if name and name.startswith("self.") and name.count(".") == 1:
        return name
    return None


def check(project: Project) -> t.List[Finding]:
    findings: t.List[Finding] = []
    allow_hits: t.Set[t.Tuple[str, str]] = set()

    for ctx in project.files:
        _check_jit_construction(ctx, findings)
        jitted = _collect_jitted(ctx, findings)
        _check_call_sites(ctx, jitted, findings)
        _check_shard_map(ctx, findings, allow_hits)

    for entry in sorted(SHARD_MAP_ALLOWLIST - allow_hits):
        # Only report staleness when the allowlisted file was actually
        # part of this run (linting a single unrelated file must not
        # fail on the whole-package allowlist).
        if any(f.path.endswith(entry[0]) for f in project.files):
            findings.append(Finding(
                "stale-allowlist", entry[0], 1, 0,
                f"shard-map allowlist entry {entry!r} matches no "
                "reference; the code it excused is gone",
                "remove the entry from analysis/recompile.py "
                "SHARD_MAP_ALLOWLIST",
            ))
    return findings


# ------------------------------------------------------ jit construction


def _check_jit_construction(ctx: FileContext, findings: t.List[Finding]):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if _is_jit_maker(node.func):
            findings.append(Finding(
                "jit-cache-discard", ctx.path, node.lineno, node.col_offset,
                "jax.jit(...) invoked immediately: the wrapper and its "
                "compile cache are discarded after this one call, so every "
                "execution retraces and recompiles",
                "bind the jitted callable once (module/attr) and call the "
                "binding",
            ))
        if not _is_jit_maker(node):
            continue
        for anc in ctx.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            if isinstance(anc, (ast.For, ast.While)):
                findings.append(Finding(
                    "jit-in-loop", ctx.path, node.lineno, node.col_offset,
                    "jax.jit(...) constructed inside a loop body: a fresh "
                    "wrapper (and fresh compile cache) per iteration",
                    "hoist the jit construction out of the loop",
                ))
                break


# -------------------------------------------------- call-site analysis


def _collect_jitted(
    ctx: FileContext, findings: t.List[Finding]
) -> t.Dict[str, t.Tuple[int, ...]]:
    """'name' / 'self.attr' -> donated positions, for every
    ``x = jax.jit(...)`` assignment in the file (positions are () when
    nothing is donated — the name is still a known-jitted callable for
    varying-shape-arg)."""
    jitted: t.Dict[str, t.Tuple[int, ...]] = {}
    for node in ast.walk(ctx.tree):
        value: ast.AST | None = None
        targets: t.List[ast.AST] = []
        if isinstance(node, ast.Assign):
            value, targets = node.value, list(node.targets)
        elif isinstance(node, ast.Return) and node.value is not None:
            # `return jax.jit(f, donate_argnums=...)` from a builder:
            # track the builder itself as producing a donating callable
            # is out of scope (the binding happens elsewhere); skip.
            continue
        if value is None or not _is_jit_maker(value):
            continue
        donated = _donated_positions(t.cast(ast.Call, value))
        for target in targets:
            key = _target_key(target)
            if key is not None:
                jitted[key] = donated
    return jitted


def _statement_of(ctx: FileContext, node: ast.AST) -> ast.stmt | None:
    cur: ast.AST | None = node
    while cur is not None and not isinstance(cur, ast.stmt):
        cur = ctx.parent(cur)
    return t.cast("ast.stmt | None", cur)


def _check_call_sites(
    ctx: FileContext,
    jitted: t.Dict[str, t.Tuple[int, ...]],
    findings: t.List[Finding],
):
    if not jitted:
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        key = _target_key(node.func)
        if key is None or key not in jitted:
            continue
        _check_varying_shape(ctx, node, findings)
        donated = jitted[key]
        if donated:
            _check_donated_reuse(ctx, node, donated, findings)


def _check_varying_shape(
    ctx: FileContext, call: ast.Call, findings: t.List[Finding]
):
    for arg in call.args:
        for sub in ast.walk(arg):
            if not (
                isinstance(sub, ast.Subscript)
                and isinstance(sub.slice, ast.Slice)
            ):
                continue
            bounds = [
                b for b in (sub.slice.lower, sub.slice.upper)
                if b is not None and not isinstance(b, ast.Constant)
            ]
            if bounds:
                findings.append(Finding(
                    "varying-shape-arg", ctx.path,
                    sub.lineno, sub.col_offset,
                    "dynamically-bounded slice passed to a jitted "
                    "callable: every distinct length is a new shape and "
                    "a full recompile",
                    "pad to a fixed (bucketed) shape, or mark the bound "
                    "static if it takes few values",
                ))


def _check_donated_reuse(
    ctx: FileContext,
    call: ast.Call,
    donated: t.Tuple[int, ...],
    findings: t.List[Finding],
):
    fn = ctx.enclosing_function(call)
    if fn is None:
        return
    stmt = _statement_of(ctx, call)
    if stmt is None:
        return
    for pos in donated:
        if pos >= len(call.args):
            continue
        arg = call.args[pos]
        if not isinstance(arg, ast.Name):
            continue
        name = arg.id
        # The statement holding the call often rebinds the donated
        # name (`state, buf, m = burst(state, buf, chunk)`): collect
        # names stored by that statement — reads of those afterwards
        # see the NEW buffer, which is fine.
        rebound = {
            n.id for n in ast.walk(stmt)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store)
        }
        if name in rebound:
            continue
        end = stmt.end_lineno or stmt.lineno
        next_store = min(
            (
                n.lineno for n in ast.walk(fn)
                if isinstance(n, ast.Name) and n.id == name
                and isinstance(n.ctx, ast.Store) and n.lineno > end
            ),
            default=None,
        )
        for n in ast.walk(fn):
            if not (
                isinstance(n, ast.Name) and n.id == name
                and isinstance(n.ctx, ast.Load) and n.lineno > end
            ):
                continue
            if next_store is not None and n.lineno >= next_store:
                continue
            findings.append(Finding(
                "donated-reuse", ctx.path, n.lineno, n.col_offset,
                f"{name!r} is read after being passed at a donated "
                f"position (arg {pos}) on line {call.lineno}: its buffer "
                "may already be aliased by the callee (garbage on TPU; "
                "silently fine on CPU where donation is a no-op)",
                "use the callee's returned value, or stop donating this "
                "argument",
            ))
            break  # one finding per donated arg per call site


# ------------------------------------------------------------ shard_map


def _check_shard_map(
    ctx: FileContext,
    findings: t.List[Finding],
    allow_hits: t.Set[t.Tuple[str, str]],
):
    if any(ctx.path.endswith(home) for home in SHARD_MAP_HOME):
        return
    for node in ast.walk(ctx.tree):
        name: str | None = None
        if isinstance(node, ast.Name) and node.id in _SHARD_NAMES:
            name = node.id
        elif isinstance(node, ast.Attribute) and node.attr in _SHARD_NAMES:
            name = node.attr
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            hit = next(
                (
                    a for a in node.names
                    if (a.asname or a.name).split(".")[-1] in _SHARD_NAMES
                    or a.name.split(".")[-1] in _SHARD_NAMES
                ),
                None,
            )
            if hit is not None:
                name = hit.name
        if name is None:
            continue
        scope = _scope_qualname(ctx, node)
        entry = next(
            (
                e for e in SHARD_MAP_ALLOWLIST
                if ctx.path.endswith(e[0]) and e[1] in (scope, "*")
            ),
            None,
        )
        if entry is not None:
            allow_hits.add(entry)
            continue
        findings.append(Finding(
            "shard-map-hot-path", ctx.path, node.lineno, node.col_offset,
            f"{name!r} referenced outside parallel/context.py "
            "(PR-8 invariant: hot paths are plain GSPMD "
            "jit-with-sharding)",
            "keep manual mapping in an allowlisted scope, or add a "
            "justified entry to "
            "SHARD_MAP_ALLOWLIST (analysis/recompile.py) and "
            "docs/ANALYSIS.md",
        ))
