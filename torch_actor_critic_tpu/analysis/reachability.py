"""Traced-code reachability: which functions run under a jax trace.

The jit-hygiene family only makes sense inside code that is traced —
host code is free to call ``time.perf_counter`` or ``.item()``. This
module computes the traced set:

* **Roots** — functions passed to a jit/scan/vmap-style wrapper
  (``jax.jit(f)``, ``jax.lax.scan(body, ...)``, ``@jax.jit``,
  ``functools.partial(jax.jit, ...)`` decorators, Pallas kernels), in
  any file. Lambdas passed to wrappers are roots too.
* **Seeds** — the walk is anchored on the CostRegistry/watchdog source
  names (``train/update_burst``, ``train/ondevice_epoch``,
  ``train/population_epoch``, ``train/scenario_epoch``,
  ``serve/forward``): the builders that
  register those programs are listed in :data:`ENTRY_POINTS`, and the
  pass verifies each one still exists and still constructs a jit root
  — a renamed builder raises ``stale-entry-point`` instead of the walk
  silently going blind (the table is checked, never trusted).
* **Closure** — call edges out of traced functions: plain local calls,
  ``self.method``, package-internal ``module.func`` via the import
  table, and a bounded last-resort heuristic for ``obj.method`` calls
  (every package class defining that method, when at most 3 do and the
  candidate contains no overt host-side constructs — low-confidence
  edges buy recall into the algorithm layer without tainting host
  drivers).

Functions passed to host-callback escapes (``jax.pure_callback``,
``jax.debug.callback``, ``io_callback``) are explicitly *host* code
and excluded from the traced set.
"""

from __future__ import annotations

import ast
import typing as t

from torch_actor_critic_tpu.analysis.walker import (
    FileContext,
    Finding,
    FunctionInfo,
    dotted_name,
)

__all__ = ["Project", "ENTRY_POINTS", "JIT_WRAPPERS"]

PACKAGE = "torch_actor_critic_tpu"

# Wrapper call names whose function-valued arguments are traced.
# Matched against the full dotted callee name and its last two
# segments (``jax.lax.scan`` and ``lax.scan`` both count).
JIT_WRAPPERS: t.FrozenSet[str] = frozenset({
    "jax.jit", "jit", "pjit", "jax.pmap", "pmap", "jax.vmap", "vmap",
    "jax.lax.scan", "lax.scan", "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.cond", "lax.cond",
    "jax.lax.switch", "lax.switch", "jax.lax.map", "lax.map",
    "jax.lax.associative_scan", "lax.associative_scan",
    "jax.checkpoint", "jax.remat", "jax.grad", "jax.value_and_grad",
    "jax.custom_vjp", "jax.custom_jvp",
    "shard_map", "jax.shard_map",
    "pl.pallas_call", "pallas_call", "pltpu.pallas_call",
})

# Host-callback escapes: their function argument runs on the HOST even
# though the call site is traced code.
CALLBACK_WRAPPERS: t.FrozenSet[str] = frozenset({
    "jax.pure_callback", "pure_callback",
    "jax.debug.callback", "debug.callback",
    "jax.experimental.io_callback", "io_callback",
})

# CostRegistry/watchdog source name -> (path suffix, builder qualname).
# The builder is the host function whose body constructs the jit
# program registered under that name; the nested functions it hands to
# a wrapper are the walk's seeds. Verified every run (stale-entry-point).
ENTRY_POINTS: t.Dict[str, t.Tuple[str, str]] = {
    "train/update_burst": ("parallel/dp.py", "DataParallelSAC._build_burst"),
    "train/ondevice_epoch": ("sac/ondevice.py", "OnDeviceLoop._build_epoch"),
    "train/population_epoch": (
        "sac/ondevice.py", "PopulationOnDeviceLoop._build_epoch",
    ),
    "train/scenario_epoch": (
        "scenarios/loop.py", "ScenarioOnDeviceLoop._build_epoch",
    ),
    # The population burst builds its jit inline in the dispatch
    # method (no separate _build_*): the method IS the builder.
    "train/population_burst": (
        "parallel/population.py", "PopulationLearner._build_burst",
    ),
    "replay/prefetch_push": (
        "replay/prefetch.py", "RefillPrefetcher._build_push",
    ),
    "train/offline_burst": (
        "replay/offline.py", "OfflineLearner._build_burst",
    ),
    "serve/forward": ("serve/engine.py", "PolicyEngine._build_forwards"),
    "serve/sharded_forward": (
        "serve/sharded.py", "ShardedPolicyEngine._build_forwards",
    ),
}

# Method names too generic for the cross-class fallback resolution.
_NOISE_METHODS = frozenset({
    "append", "extend", "get", "pop", "popleft", "items", "keys",
    "values", "update", "copy", "clear", "add", "remove", "join",
    "read", "write", "close", "record", "result", "put", "send",
    "recv", "start", "stop", "item", "mean", "max", "min", "sum",
    "reshape", "astype", "replace", "apply", "init", "split", "view",
    "snapshot", "format",
})

# Calls that mark a function as overtly host-side; a low-confidence
# (heuristic) edge into such a function is dropped.
_HOST_MARKERS = frozenset({
    "jax.jit", "jit", "time.perf_counter", "time.time",
    "time.monotonic", "time.sleep", "print", "open", "get_watchdog",
    "jax.device_put", "logger.info", "logger.warning", "logger.debug",
    "logger.error",
})


def _call_names(node: ast.AST) -> t.Set[str]:
    out: t.Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            name = dotted_name(sub.func)
            if name:
                out.add(name)
    return out


def _is_wrapper(name: str | None, table: t.FrozenSet[str]) -> bool:
    if not name:
        return False
    if name in table:
        return True
    parts = name.split(".")
    return len(parts) >= 2 and ".".join(parts[-2:]) in table


class _ModuleIndex:
    """Per-file resolution tables."""

    def __init__(self, ctx: FileContext):
        self.ctx = ctx
        self.by_qualname: t.Dict[str, FunctionInfo] = {
            f.qualname: f for f in ctx.functions
        }
        self.by_last: t.Dict[str, t.List[FunctionInfo]] = {}
        for f in ctx.functions:
            self.by_last.setdefault(f.qualname.rsplit(".", 1)[-1], []).append(f)
        self.qual_of: t.Dict[ast.AST, str] = {
            f.node: f.qualname for f in ctx.functions
        }
        # alias -> package-internal module path ("a/b.py"), and
        # imported symbol -> (module path, symbol name).
        self.module_aliases: t.Dict[str, str] = {}
        self.symbol_imports: t.Dict[str, t.Tuple[str, str]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith(PACKAGE):
                        bound = alias.asname or alias.name.split(".")[0]
                        self.module_aliases[bound] = alias.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                if not node.module.startswith(PACKAGE):
                    continue
                for alias in node.names:
                    full = f"{node.module}.{alias.name}"
                    bound = alias.asname or alias.name
                    # `from pkg.x import y` binds y as either module
                    # pkg/x/y.py or symbol y in pkg/x.py; record both
                    # candidates, resolution tries module first.
                    self.module_aliases.setdefault(bound, full)
                    self.symbol_imports[bound] = (node.module, alias.name)


class Project:
    """All parsed files plus the project-level traced-set analysis."""

    def __init__(self, files: t.Sequence[FileContext]):
        self.files = list(files)
        self.by_path: t.Dict[str, FileContext] = {f.path: f for f in self.files}
        self.indexes: t.Dict[str, _ModuleIndex] = {
            f.path: _ModuleIndex(f) for f in self.files
        }
        # module dotted name -> path, for import resolution.
        self.module_paths: t.Dict[str, str] = {}
        for path in self.by_path:
            mod = path[:-3].replace("/", ".")
            if mod.endswith(".__init__"):
                mod = mod[: -len(".__init__")]
            self.module_paths[mod] = path
        self.method_index: t.Dict[str, t.List[t.Tuple[str, FunctionInfo]]] = {}
        for path, ctx in self.by_path.items():
            for f in ctx.functions:
                if f.class_name and f.qualname == f"{f.class_name}.{f.node.name}":
                    self.method_index.setdefault(f.node.name, []).append(
                        (path, f)
                    )
        self._traced: t.Dict[t.Tuple[str, str], FunctionInfo] | None = None
        self._host_callbacks: t.Set[t.Tuple[str, str]] = set()

    # --------------------------------------------------------------- roots

    def _resolve_plain(
        self, path: str, site: ast.AST, name: str
    ) -> t.List[t.Tuple[str, FunctionInfo]]:
        """Scope-aware resolution of a bare-name function reference:
        a sibling/enclosing-scope nested def wins over module level;
        class methods never match a bare name (they need ``self.``);
        a ``from pkg.x import f`` symbol resolves cross-module."""
        idx = self.indexes[path]
        ctx = self.by_path[path]
        cands = idx.by_last.get(name, [])
        enclosing: t.List[str] = []
        for anc in ctx.ancestors(site):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = idx.qual_of.get(anc)
                if q:
                    enclosing.append(q)
        for q in enclosing:
            hits = [f for f in cands if f.qualname == f"{q}.{name}"]
            if hits:
                return [(path, f) for f in hits]
        hits = [f for f in cands if f.qualname == name]
        if hits:
            return [(path, f) for f in hits]
        sym = idx.symbol_imports.get(name)
        if sym is not None:
            mod, symbol = sym
            target = self.module_paths.get(f"{mod}.{symbol}")
            if target is None:
                target = self.module_paths.get(mod)
                if target is not None:
                    tf = self.indexes[target].by_qualname.get(symbol)
                    if tf is not None:
                        return [(target, tf)]
            return []
        return []

    def _function_for_arg(
        self, path: str, arg: ast.AST, site: ast.AST | None = None
    ) -> t.List[t.Tuple[str, FunctionInfo]]:
        """Resolve a wrapper's function-valued argument."""
        if isinstance(arg, ast.Call):
            name = dotted_name(arg.func)
            if name and name.rsplit(".", 1)[-1] in ("partial", "wraps"):
                if arg.args:
                    return self._function_for_arg(path, arg.args[0], site)
            if _is_wrapper(name, JIT_WRAPPERS) and arg.args:
                # nested wrappers: jax.jit(jax.vmap(f))
                return self._function_for_arg(path, arg.args[0], site)
            return []
        name = dotted_name(arg)
        if name is None:
            return []
        if "." not in name:
            return self._resolve_plain(path, site if site is not None else arg, name)
        if name.startswith("self."):
            meth = name.split(".", 1)[1]
            return self._resolve_self(path, arg, meth)
        return self._resolve_dotted(path, name)

    def _resolve_dotted(
        self, path: str, name: str
    ) -> t.List[t.Tuple[str, FunctionInfo]]:
        """``alias.f`` / ``alias.sub.f`` / ``ClassName.m`` through the
        file's import table and class index."""
        idx = self.indexes[path]
        parts = name.split(".")
        hit = idx.by_qualname.get(name)
        if hit is not None:
            return [(path, hit)]
        head, meth = parts[0], parts[-1]
        mod = idx.module_aliases.get(head)
        if mod is not None:
            dotted = ".".join([mod] + parts[1:-1])
            target = self.module_paths.get(dotted)
            if target is not None:
                tf = self.indexes[target].by_qualname.get(meth)
                if tf is not None:
                    return [(target, tf)]
        if head in idx.symbol_imports and len(parts) == 2:
            mod_name, sym = idx.symbol_imports[head]
            target = self.module_paths.get(f"{mod_name}.{sym}")
            if target is not None:
                tf = self.indexes[target].by_qualname.get(meth)
                if tf is not None:
                    return [(target, tf)]
        return []

    def _roots_in_file(self, path: str) -> t.List[t.Tuple[str, FunctionInfo]]:
        ctx = self.by_path[path]
        roots: t.List[t.Tuple[str, FunctionInfo]] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    name = dotted_name(dec)
                    if isinstance(dec, ast.Call):
                        name = dotted_name(dec.func)
                        if name and name.rsplit(".", 1)[-1] == "partial":
                            name = dotted_name(dec.args[0]) if dec.args else None
                    if _is_wrapper(name, JIT_WRAPPERS):
                        info = next(
                            (f for f in ctx.functions if f.node is node), None
                        )
                        if info:
                            roots.append((path, info))
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            if _is_wrapper(callee, CALLBACK_WRAPPERS):
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    for loc in self._function_for_arg(path, arg, node):
                        self._host_callbacks.add((loc[0], loc[1].qualname))
                continue
            if not _is_wrapper(callee, JIT_WRAPPERS):
                continue
            cands = list(node.args) + [
                k.value for k in node.keywords
                if k.arg in ("f", "fun", "body_fun", "cond_fun", "kernel")
            ]
            for arg in cands:
                if isinstance(arg, ast.Lambda):
                    # Treat the lambda body as traced by attaching a
                    # synthetic FunctionInfo; rules walk `.node`.
                    roots.append((path, FunctionInfo(
                        f"<lambda:{arg.lineno}>", arg, None
                    )))
                    continue
                roots.extend(self._function_for_arg(path, arg, node))
        return roots

    # ------------------------------------------------------------ resolve

    def _resolve_self(
        self, path: str, node: ast.AST, meth: str
    ) -> t.List[t.Tuple[str, FunctionInfo]]:
        return self._resolve_self2(path, node, meth)[0]

    def _resolve_self2(
        self, path: str, node: ast.AST, meth: str
    ) -> t.Tuple[t.List[t.Tuple[str, FunctionInfo]], bool]:
        """Resolve ``self.meth``; the bool says whether the hit is
        exact (own class) or a cross-class heuristic fallback."""
        ctx = self.by_path[path]
        cls = None
        for anc in ctx.ancestors(node):
            if isinstance(anc, ast.ClassDef):
                cls = anc.name
                break
        if cls is not None:
            hit = self.indexes[path].by_qualname.get(f"{cls}.{meth}")
            if hit is not None:
                return [(path, hit)], True
        return self._resolve_heuristic(meth), False

    @staticmethod
    def _looks_host_side(fn: FunctionInfo) -> bool:
        """Overtly host-side: constructs jits (directly or via a
        ``_build*`` helper), takes wall-clock readings, places
        buffers, logs. Used to prune LOW-CONFIDENCE (heuristic)
        reachability — exact edges are never pruned."""
        names = _call_names(fn.node)
        if names & _HOST_MARKERS:
            return True
        return any(
            n.rsplit(".", 1)[-1].startswith("_build") for n in names
        )

    def _resolve_heuristic(
        self, meth: str
    ) -> t.List[t.Tuple[str, FunctionInfo]]:
        if meth.startswith("__") or meth in _NOISE_METHODS:
            return []
        cands = self.method_index.get(meth, [])
        if not 1 <= len(cands) <= 5:
            return []
        return [
            (path, f) for path, f in cands if not self._looks_host_side(f)
        ]

    def _callees(
        self, path: str, fn: FunctionInfo
    ) -> t.Tuple[
        t.List[t.Tuple[str, FunctionInfo]],
        t.List[t.Tuple[str, FunctionInfo]],
    ]:
        """(exact_edges, heuristic_edges) out of ``fn``."""
        exact: t.List[t.Tuple[str, FunctionInfo]] = []
        heur: t.List[t.Tuple[str, FunctionInfo]] = []
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None or _is_wrapper(name, JIT_WRAPPERS):
                continue
            if "." not in name:
                exact.extend(self._resolve_plain(path, node, name))
                continue
            parts = name.split(".")
            if parts[0] == "self" and len(parts) == 2:
                hits, confident = self._resolve_self2(path, node, parts[1])
                (exact if confident else heur).extend(hits)
                continue
            resolved = self._resolve_dotted(path, name)
            if resolved:
                exact.extend(resolved)
                continue
            heur.extend(self._resolve_heuristic(parts[-1]))
        return exact, heur

    # -------------------------------------------------------------- traced

    def traced(self) -> t.Dict[t.Tuple[str, str], FunctionInfo]:
        """(path, qualname) -> FunctionInfo for every traced function.

        Two-tier closure: exact edges (same-scope names, own-class
        ``self.method``, import-resolved ``module.func``) propagate
        unconditionally from the jit roots; heuristic (cross-class
        method-name) edges only admit functions that don't look
        host-side, and everything downstream of a heuristic edge stays
        under that filter — one low-confidence hop must not taint a
        whole host subsystem as traced."""
        if self._traced is not None:
            return self._traced
        seen: t.Dict[t.Tuple[str, str], FunctionInfo] = {}
        confident: t.Set[t.Tuple[str, str]] = set()
        work: t.List[t.Tuple[str, FunctionInfo, bool]] = []
        for path in self.by_path:
            work.extend((p, f, True) for p, f in self._roots_in_file(path))
        while work:
            path, fn, exact = work.pop()
            key = (path, fn.qualname)
            if key in self._host_callbacks:
                continue
            if key in seen and (not exact or key in confident):
                continue
            if not exact and self._looks_host_side(fn):
                continue
            seen[key] = fn
            if exact:
                confident.add(key)
            if isinstance(fn.node, ast.Lambda):
                continue
            exact_edges, heur_edges = self._callees(path, fn)
            work.extend((p, f, exact) for p, f in exact_edges)
            work.extend((p, f, False) for p, f in heur_edges)
        self._traced = seen
        return seen

    def is_traced_file(self, path: str) -> bool:
        return any(p == path for p, _ in self.traced())

    # --------------------------------------------------------- entry seeds

    def entry_point_findings(self) -> t.List[Finding]:
        """Verify the checked seed table: every CostRegistry source
        name must still map to an existing builder that constructs at
        least one jit root."""
        out: t.List[Finding] = []
        if not any(
            p.endswith(f"{PACKAGE}/__init__.py") for p in self.by_path
        ):
            # The seed table only applies to whole-package runs; a
            # partial run (fixtures, a single file) can't tell a
            # renamed builder from an un-linted one.
            return out
        traced = self.traced()
        for cost_name, (suffix, builder) in ENTRY_POINTS.items():
            path = next(
                (p for p in self.by_path if p.endswith(suffix)), None
            )
            if path is None:
                out.append(Finding(
                    "stale-entry-point", suffix, 1, 0,
                    f"entry point {cost_name!r}: file {suffix!r} not found",
                    "update analysis/reachability.py ENTRY_POINTS",
                ))
                continue
            ctx = self.by_path[path]
            fn = next(
                (f for f in ctx.functions if f.qualname == builder), None
            )
            if fn is None:
                out.append(Finding(
                    "stale-entry-point", path, 1, 0,
                    f"entry point {cost_name!r}: builder {builder!r} "
                    "no longer exists",
                    "update analysis/reachability.py ENTRY_POINTS to the "
                    "renamed builder",
                ))
                continue
            lo = fn.node.lineno
            hi = max(
                (n.end_lineno or lo) for n in ast.walk(fn.node)
                if hasattr(n, "end_lineno") and n.end_lineno
            )
            seeded = any(
                p == path and lo <= info.node.lineno <= hi
                for (p, _), info in traced.items()
            )
            if not seeded:
                out.append(Finding(
                    "stale-entry-point", path, fn.node.lineno, 0,
                    f"entry point {cost_name!r}: builder {builder!r} no "
                    "longer constructs a jit program the walk can seed from",
                    "check that the builder still passes a function to a "
                    "jit/scan wrapper, or update ENTRY_POINTS",
                ))
        return out
