"""donation-safety rules: dataflow over donated buffers.

PR 1's very first review bug — the serving engine donating the PRNG
key buffer warmup then reused across buckets — is the canonical member
of this family: a value passed at a ``donate_argnums`` position whose
buffer the callee aliases, then read again by the caller. On TPU the
read returns garbage (or XLA raises a deleted-buffer error); on CPU
donation is a no-op and the bug ships silently, which is why only a
static check catches it before chip time. PR 11's ``donated-reuse``
already covers the syntactic core (a locally-bound ``x = jax.jit(f,
donate_argnums=...)`` called and then a straight-line read); this
module adds the *dataflow* tier over ``analysis/dataflow.py``:

* ``use-after-donation`` — a value passed at a donated position of a
  donating callable and then read, returned, or captured afterwards in
  the caller. Donating callables resolve three ways the syntactic rule
  cannot: the checked :data:`DONATING_ENTRY_POINTS` table (host
  dispatch methods of the jit entry points — ``update_burst``,
  ``push_chunk``, ``epoch``), dict-of-jit bindings
  (``self._fwd = {True: jax.jit(...), ...}`` called through a
  subscript), and **conditionally** donating constructions
  (``donate_argnums=(1,) if donate else ()`` — donation happens on
  accelerators exactly where the bug bites, so the union of both
  branches is what must be safe). Loop-carried reuse is included: a
  donated value bound outside a loop and never rebound inside it is
  re-donated dead on the second iteration.
* ``undonated-push`` — ``buffer/replay.py``'s ``push`` docstring is a
  contract ("callers should jit push with ``donate_argnums=(0,)``"):
  a 1e6-slot HBM ring copied per store because one call site forgot
  the donation is a silent 2x-residency, 0.5x-throughput tax. Every
  ``jax.jit`` construction over the replay ``push`` must donate the
  ring argument.
* ``stale-donation-table`` — :data:`DONATING_ENTRY_POINTS` is checked,
  never trusted (the shard-map-allowlist precedent): every row's
  builder must still exist and still construct jits donating exactly
  the positions the row claims, so the table cannot drift from the
  code it describes.
"""

from __future__ import annotations

import ast
import typing as t

from torch_actor_critic_tpu.analysis.dataflow import (
    FlowScope,
    function_events,
    tracked_key,
)
from torch_actor_critic_tpu.analysis.reachability import Project, _is_wrapper
from torch_actor_critic_tpu.analysis.walker import (
    FileContext,
    Finding,
    dotted_name,
)

__all__ = ["check", "DONATING_ENTRY_POINTS"]

FAMILY = "donation-safety"

_JIT_MAKERS = frozenset({"jax.jit", "jit", "pjit", "jax.pmap", "pmap"})
_UNWRAP = frozenset({
    "jax.vmap", "vmap", "jax.pmap", "pmap", "partial", "functools.partial",
})


class DonationRow(t.NamedTuple):
    """One checked entry: where the donating program is built and how
    host code dispatches into it."""

    file: str                       # path suffix of the builder's file
    builder: str                    # builder qualname in that file
    method: str | None              # host dispatch method name (None =
    #                                 dispatched through a local jit
    #                                 binding the local collector sees)
    donated: t.Tuple[int, ...]      # positions the builder must donate


# Derived from reachability.ENTRY_POINTS: the donate_argnums contract
# of every jit entry point, plus the warmup-path push wrappers that
# share the same rings. `method` names how the host trainer/driver
# dispatches into the program — any `<recv>.<method>(...)` call site in
# the package is held to the donated positions. Verified every
# whole-package run (stale-donation-table).
DONATING_ENTRY_POINTS: t.Dict[str, DonationRow] = {
    "train/update_burst": DonationRow(
        "parallel/dp.py", "DataParallelSAC._build_burst",
        "update_burst", (0, 1),
    ),
    "train/population_burst": DonationRow(
        "parallel/population.py", "PopulationLearner._build_burst",
        "update_burst", (0, 1),
    ),
    "train/ondevice_epoch": DonationRow(
        "sac/ondevice.py", "OnDeviceLoop._build_epoch", "epoch", (0, 1),
    ),
    "train/population_epoch": DonationRow(
        "sac/ondevice.py", "PopulationOnDeviceLoop._build_epoch",
        "epoch", (0, 1),
    ),
    "train/scenario_epoch": DonationRow(
        "scenarios/loop.py", "ScenarioOnDeviceLoop._build_epoch",
        "epoch", (0, 1),
    ),
    "train/push_chunk": DonationRow(
        "parallel/dp.py", "DataParallelSAC.push_chunk",
        "push_chunk", (0,),
    ),
    "train/population_push_chunk": DonationRow(
        "parallel/population.py", "PopulationLearner.push_chunk",
        "push_chunk", (0,),
    ),
    "replay/prefetch_push": DonationRow(
        "replay/prefetch.py", "RefillPrefetcher._build_push",
        "push_into", (0,),
    ),
    "train/offline_burst": DonationRow(
        "replay/offline.py", "OfflineLearner._build_burst",
        "burst", (0,),
    ),
    "serve/forward": DonationRow(
        "serve/engine.py", "PolicyEngine._build_forwards", None, (1,),
    ),
    "serve/sharded_forward": DonationRow(
        "serve/sharded.py", "ShardedPolicyEngine._build_forwards",
        None, (1,),
    ),
}

# method name -> donated positions, for call-site matching.
_METHOD_DONATIONS: t.Dict[str, t.Tuple[int, ...]] = {
    row.method: row.donated
    for row in DONATING_ENTRY_POINTS.values()
    if row.method is not None
}


def _is_jit_maker(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _is_wrapper(
        dotted_name(node.func), _JIT_MAKERS
    )


def _positions_of(value: ast.AST) -> t.Tuple[int, ...]:
    """Donated positions of a donate_argnums value expression, with
    IfExp branches UNIONED: `(1,) if donate else ()` donates on
    accelerators, which is exactly where use-after-donation bites."""
    if isinstance(value, ast.IfExp):
        return tuple(sorted(
            set(_positions_of(value.body)) | set(_positions_of(value.orelse))
        ))
    if isinstance(value, ast.Constant) and isinstance(value.value, int):
        return (value.value,)
    if isinstance(value, (ast.Tuple, ast.List)):
        out = []
        for elt in value.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
        return tuple(out)
    return ()


def _donations_of_call(call: ast.Call) -> t.Tuple[t.Tuple[int, ...], bool]:
    """(positions, static): positions donated by a jit construction;
    ``static`` True when the spelling is an unconditional literal (the
    recompile-risk family's domain — skipped here to avoid flagging
    one hazard under two rule ids)."""
    for kw in call.keywords:
        if kw.arg not in ("donate_argnums", "donate_argnames"):
            continue
        positions = _positions_of(kw.value)
        static = not isinstance(kw.value, ast.IfExp) and bool(positions)
        return positions, static
    return (), True


# --------------------------------------------------------- local sources


def _collect_donating_bindings(
    ctx: FileContext,
) -> t.Dict[str, t.Tuple[int, ...]]:
    """Bindings of donating callables the syntactic ``donated-reuse``
    rule cannot see: conditional donate spellings and dict-of-jit
    values (both keyed by the bound name / ``self.attr``)."""
    out: t.Dict[str, t.Tuple[int, ...]] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        donated: t.Tuple[int, ...] = ()
        if _is_jit_maker(value):
            positions, static = _donations_of_call(t.cast(ast.Call, value))
            if positions and not static:
                donated = positions
        elif isinstance(value, ast.Dict) and value.values and all(
            _is_jit_maker(v) for v in value.values
        ):
            acc: t.Set[int] = set()
            for v in value.values:
                positions, _ = _donations_of_call(t.cast(ast.Call, v))
                acc.update(positions)
            donated = tuple(sorted(acc))
        if not donated:
            continue
        for target in node.targets:
            key = tracked_key(target)
            if key is not None:
                out[key] = donated
    return out


def _donated_call_sites(
    ctx: FileContext, bindings: t.Dict[str, t.Tuple[int, ...]]
) -> t.Iterator[t.Tuple[ast.Call, t.Tuple[int, ...], str]]:
    """(call, donated positions, why) for every donating call site in
    the file: table-matched dispatch methods, local conditional/dict
    jit bindings (incl. subscripted dict-jit calls)."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        # Local bindings: direct call or dict-jit subscript call.
        base = func.value if isinstance(func, ast.Subscript) else func
        key = tracked_key(base)
        if key is not None and key in bindings:
            yield node, bindings[key], f"jitted callable {key!r}"
            continue
        if isinstance(func, ast.Attribute):
            positions = _METHOD_DONATIONS.get(func.attr)
            if positions is not None:
                yield node, positions, (
                    f"donating entry point .{func.attr}() "
                    "(analysis/donation.py DONATING_ENTRY_POINTS)"
                )


# ----------------------------------------------------------- the checks


def _check_use_after_donation(
    ctx: FileContext, findings: t.List[Finding]
) -> None:
    bindings = _collect_donating_bindings(ctx)
    scopes: t.Dict[ast.AST, FlowScope] = {}
    for info in ctx.functions:
        fn = info.node
        scope = scopes.setdefault(fn, FlowScope(ctx, fn))
        for call, positions, why in _donated_call_sites(ctx, bindings):
            if ctx.enclosing_function(call) is not fn:
                continue
            stmt = scope.statement_of(call)
            if stmt is None:
                continue
            for pos in positions:
                if pos >= len(call.args):
                    continue
                key = tracked_key(call.args[pos])
                if key is None or key == "self":
                    continue
                _check_one_donation(
                    ctx, scope, call, stmt, pos, key, why, findings
                )


def _check_one_donation(
    ctx: FileContext,
    scope: FlowScope,
    call: ast.Call,
    stmt: ast.stmt,
    pos: int,
    key: str,
    why: str,
    findings: t.List[Finding],
) -> None:
    events = function_events(scope, {key})
    end = stmt.end_lineno or stmt.lineno
    # Rebound by the donating statement itself (`state, buf, m =
    # burst(state, buf, chunk)`) — the sound pattern: later reads see
    # the callee's fresh output buffer.
    rebound_same_stmt = any(
        e.kind == "store" and scope.statement_of(e.node) is stmt
        for e in events
    )
    loops = scope.loops_enclosing(call)
    if loops and not rebound_same_stmt:
        loop = loops[0]
        stored_in_loop = any(
            e.kind == "store"
            and any(l2 is loop for l2 in scope.loops_enclosing(e.node))
            for e in events
        )
        if not stored_in_loop:
            findings.append(Finding(
                "use-after-donation", ctx.path, call.lineno, call.col_offset,
                f"{key!r} is donated (arg {pos}) to {why} inside a loop "
                "without being rebound in the loop body: the second "
                "iteration passes an already-donated buffer (garbage or "
                "a deleted-buffer error on TPU; silently fine on CPU "
                "where donation is a no-op)",
                "rebind the donated value from the callee's return "
                "inside the loop, or move the value's construction into "
                "the loop body",
            ))
            return
    if rebound_same_stmt:
        return
    for e in events:
        if e.kind != "load":
            continue
        line = getattr(e.node, "lineno", 0)
        if line <= end:
            continue
        if not scope.reaches(call, e.node):
            continue
        # A store between the call and this read (on a compatible
        # path) kills the donated value first.
        killed = any(
            s.kind == "store"
            and end < getattr(s.node, "lineno", 0) <= line
            and scope.reaches(s.node, e.node)
            for s in events
        )
        if killed:
            continue
        what = "captured by a closure" if e.closure else "read"
        findings.append(Finding(
            "use-after-donation", ctx.path, line,
            getattr(e.node, "col_offset", 0),
            f"{key!r} is {what} after being donated (arg {pos}, line "
            f"{call.lineno}) to {why}: its buffer may already be "
            "aliased by the callee (garbage on TPU; silently fine on "
            "CPU where donation is a no-op)",
            "use the callee's returned value, rebind the name from it, "
            "or stop donating this argument",
        ))
        return  # one finding per donated arg per call site


def _resolves_to_replay_push(ctx: FileContext, idx, arg: ast.AST) -> bool:
    """Does a jit-wrapped target resolve to buffer/replay.py's push
    (unwrapping vmap/partial layers)?"""
    if isinstance(arg, ast.Call):
        name = dotted_name(arg.func)
        if name and (
            name in _UNWRAP or name.rsplit(".", 1)[-1] in ("partial",)
        ):
            return bool(arg.args) and _resolves_to_replay_push(
                ctx, idx, arg.args[0]
            )
        return False
    name = dotted_name(arg)
    if name is None:
        return False
    last = name.rsplit(".", 1)[-1]
    if last != "push":
        return False
    if ctx.path.endswith("buffer/replay.py"):
        return True
    sym = idx.symbol_imports.get(name)
    if sym is not None:
        return sym[0].endswith("buffer.replay") and sym[1] == "push"
    if "." in name:
        head = name.split(".")[0]
        mod = idx.module_aliases.get(head)
        return mod is not None and mod.endswith("buffer.replay")
    return False


def _check_undonated_push(
    project: Project, ctx: FileContext, findings: t.List[Finding]
) -> None:
    idx = project.indexes[ctx.path]
    for node in ast.walk(ctx.tree):
        if not _is_jit_maker(node):
            continue
        call = t.cast(ast.Call, node)
        if not call.args:
            continue
        if not _resolves_to_replay_push(ctx, idx, call.args[0]):
            continue
        positions, _ = _donations_of_call(call)
        if 0 in positions:
            continue
        findings.append(Finding(
            "undonated-push", ctx.path, call.lineno, call.col_offset,
            "replay push jitted WITHOUT donating the ring argument: "
            "XLA copies the full ring every store (2x HBM residency "
            "on a 1e6-slot buffer) — buffer/replay.py's docstring "
            "makes donation the contract",
            "jit with donate_argnums=(0,) and rebind the buffer from "
            "the return value",
        ))


def _check_table(project: Project, findings: t.List[Finding]) -> None:
    """stale-donation-table: every row's builder still exists and
    still donates exactly what the row claims."""
    if not any(
        p.endswith("torch_actor_critic_tpu/__init__.py")
        for p in project.by_path
    ):
        return  # partial runs can't tell a moved builder from un-linted
    for cost_name, row in DONATING_ENTRY_POINTS.items():
        path = next(
            (p for p in project.by_path if p.endswith(row.file)), None
        )
        ctx = project.by_path.get(path) if path else None
        fn = None
        if ctx is not None:
            fn = next(
                (f for f in ctx.functions if f.qualname == row.builder),
                None,
            )
        if fn is None:
            findings.append(Finding(
                "stale-donation-table", row.file, 1, 0,
                f"donation table row {cost_name!r}: builder "
                f"{row.builder!r} not found in {row.file!r}",
                "update analysis/donation.py DONATING_ENTRY_POINTS to "
                "the moved/renamed builder",
            ))
            continue
        donated: t.Set[int] = set()
        for node in ast.walk(fn.node):
            if _is_jit_maker(node):
                positions, _ = _donations_of_call(t.cast(ast.Call, node))
                donated.update(positions)
        if tuple(sorted(donated)) != tuple(sorted(row.donated)):
            findings.append(Finding(
                "stale-donation-table", t.cast(str, path),
                fn.node.lineno, 0,
                f"donation table row {cost_name!r} claims donated "
                f"positions {tuple(sorted(row.donated))} but builder "
                f"{row.builder!r} constructs jits donating "
                f"{tuple(sorted(donated))}",
                "fix the builder's donate_argnums or update "
                "DONATING_ENTRY_POINTS (analysis/donation.py) — and "
                "re-audit every dispatch call site",
            ))


def check(project: Project) -> t.List[Finding]:
    findings: t.List[Finding] = []
    _check_table(project, findings)
    for ctx in project.files:
        _check_use_after_donation(ctx, findings)
        _check_undonated_push(project, ctx, findings)
    return findings
