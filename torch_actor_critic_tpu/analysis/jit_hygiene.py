"""jit-hygiene rules: host-side constructs inside traced code.

Inside a function reachable from a jit/scan entry point
(:mod:`~torch_actor_critic_tpu.analysis.reachability`), host-device
sync points and host-state reads are silent performance/correctness
hazards:

* ``host-sync-in-jit`` — ``.item()`` / ``.tolist()`` /
  ``.block_until_ready()`` / ``jax.device_get`` anywhere in traced
  code, and ``float()``/``int()``/``bool()`` casts or ``np.*`` calls
  applied to *traced values* (approximated as values derived from the
  traced function's parameters — closure variables are typically
  trace-time constants and stay exempt). Each of these either forces a
  device->host transfer per step or raises a ``TracerArrayConversion``
  at trace time; on the fused Podracer-style loops one stray sync
  serializes the device's queue with the host (PAPERS.md).
* ``wallclock-in-jit`` — ``time.*`` / ``datetime.now`` in traced code
  reads the clock ONCE at trace time and bakes the value into the
  compiled program: the metric it feeds goes silently constant.
* ``host-random-in-jit`` — stdlib ``random.*`` / ``np.random.*`` in
  traced code is the same bug for randomness (``jax.random`` with
  explicit keys is the traced-safe spelling and is never flagged).
* ``frame-f32-materialize`` — ``astype(float32)`` or division by 255
  applied to a frame-derived value outside the fused pixel pipeline
  (``ops/pixels.py``, the decode's home) or the checked
  :data:`FRAME_DECODE_ALLOWLIST`. Frames live in HBM as uint8 by
  design (4x smaller replay, ``buffer/replay.py``); decoding them to
  f32 anywhere but the fused gather re-creates the 4x-width frame
  batch the pixel-pipeline work removed — the silent regression this
  rule exists to stop. Like the shard-map allowlist, every entry must
  still match a real decode (``stale-allowlist``).
"""

from __future__ import annotations

import ast
import typing as t

from torch_actor_critic_tpu.analysis.reachability import (
    CALLBACK_WRAPPERS,
    Project,
    _is_wrapper,
)
from torch_actor_critic_tpu.analysis.walker import (
    Finding,
    FunctionInfo,
    dotted_name,
)

__all__ = ["check", "FRAME_DECODE_ALLOWLIST"]

FAMILY = "jit-hygiene"

# The fused pixel pipeline: uint8 frame decode lives here by
# definition (both the Pallas kernel and its jnp reference path).
FRAME_DECODE_HOME = ("ops/pixels.py",)

# (path suffix, scope qualname) pairs allowed to decode uint8 frames
# to f32 outside the pipeline home. Scope "*" means anywhere in the
# file. Every entry must match at least one live decode or the run
# fails with stale-allowlist. Justifications live in docs/ANALYSIS.md.
FRAME_DECODE_ALLOWLIST: t.FrozenSet[t.Tuple[str, str]] = frozenset({
    # The legacy in-model decode — pixel_pipeline="reference"'s
    # bit-pinned parity path (uint8 frames cast + normalized inside
    # SimpleCNN). It must keep existing verbatim: precision=f32 on the
    # reference pipeline is graph- and bit-identical to the pre-fusion
    # builds by contract.
    ("models/visual.py", "SimpleCNN.__call__"),
})

# Attribute-call syncs flagged on ANY receiver inside traced code.
_SYNC_METHODS = frozenset({"item", "tolist", "block_until_ready"})
_SYNC_CALLS = frozenset({"jax.device_get", "device_get"})
_CAST_BUILTINS = frozenset({"float", "int", "bool", "complex"})
_WALLCLOCK = frozenset({
    "time.time", "time.perf_counter", "time.monotonic", "time.sleep",
    "time.process_time", "time.time_ns", "time.perf_counter_ns",
    "datetime.now", "datetime.utcnow", "datetime.datetime.now",
    "datetime.datetime.utcnow",
})
_NP_ALIASES = ("np", "numpy")


def _is_host_random(name: str) -> bool:
    parts = name.split(".")
    if parts[0] == "random" and len(parts) > 1:
        return True
    return len(parts) >= 3 and parts[-3] in _NP_ALIASES and parts[-2] == "random"


def _param_names(node: ast.AST) -> t.Set[str]:
    if isinstance(node, ast.Lambda):
        args = node.args
    elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        args = node.args
    else:  # pragma: no cover - defensive
        return set()
    names = {
        a.arg
        for a in (
            list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        )
    }
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)
    names.discard("self")
    names.discard("cls")
    return names


def _tainted_names(fn_node: ast.AST) -> t.Set[str]:
    """Parameters plus names assigned from param-derived expressions
    (two fixed-point passes — enough for the straight-line bodies jit
    functions have)."""
    tainted = _param_names(fn_node)
    body = getattr(fn_node, "body", None)
    if body is None or isinstance(body, ast.AST):  # Lambda
        return tainted
    for _ in range(2):
        for node in ast.walk(fn_node):
            if not isinstance(node, ast.Assign):
                continue
            if _derives_from(node.value, tainted):
                for target in node.targets:
                    for n in ast.walk(target):
                        if isinstance(n, ast.Name):
                            tainted.add(n.id)
    return tainted


# Attribute reads that are static under trace: a tracer's .shape /
# .dtype / .ndim are Python values at trace time, so host math over
# them is fine (and idiomatic — bucket ladders, fsdp spec planning).
_STATIC_ATTRS = frozenset({
    "shape", "dtype", "ndim", "size", "nbytes", "itemsize", "sharding",
})


def _derives_from(node: ast.AST, tainted: t.Set[str]) -> bool:
    """Does the expression read a tainted name through a non-static
    path? ``x`` and ``x[0]`` taint; ``x.shape`` / ``np.prod(x.shape)``
    do not."""
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False
    if isinstance(node, ast.Name):
        return node.id in tainted and isinstance(node.ctx, ast.Load)
    return any(
        _derives_from(child, tainted)
        for child in ast.iter_child_nodes(node)
    )


def _callback_subtrees(fn_node: ast.AST) -> t.Set[ast.AST]:
    """Function/lambda nodes inside ``fn_node`` that are host-callback
    bodies (their code runs on the host; hygiene rules skip them)."""
    out: t.Set[ast.AST] = set()
    local_defs = {
        n.name: n for n in ast.walk(fn_node)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call):
            continue
        if not _is_wrapper(dotted_name(node.func), CALLBACK_WRAPPERS):
            continue
        for arg in list(node.args) + [k.value for k in node.keywords]:
            if isinstance(arg, ast.Lambda):
                out.add(arg)
            name = dotted_name(arg)
            if name in local_defs:
                out.add(local_defs[name])
    return out


def _walk_skipping(root: ast.AST, skip: t.Set[ast.AST]) -> t.Iterator[ast.AST]:
    stack = [root]
    while stack:
        node = stack.pop()
        if node in skip:
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# -------------------------------------------------- frame decode rule

_F32_NAMES = frozenset({
    "jnp.float32", "np.float32", "jax.numpy.float32", "numpy.float32",
    "float32",
})


def _is_f32_spelling(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value == "float32":
        return True
    name = dotted_name(node)
    return name is not None and name in _F32_NAMES


def _is_255(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) and node.value in (255, 255.0)


def _mentions_frame(node: ast.AST) -> bool:
    """Does the expression DIRECTLY read a frame value — a name or
    attribute spelled with 'frame' (``frames``, ``batch.states.frame``,
    ``frame_batch``)? Deliberately no dataflow propagation: once frames
    enter the network, everything downstream derives from them, and
    casting *activations* to f32 is the mixed-precision policy (the
    heads do exactly that), not a frame materialization."""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and "frame" in n.id.lower():
            return True
        if isinstance(n, ast.Attribute) and "frame" in n.attr.lower():
            return True
    return False


def _frame_scope_qualname(ctx, node: ast.AST) -> str:
    fn = ctx.enclosing_function(node)
    if fn is None:
        return "<module>"
    for info in ctx.functions:
        if info.node is fn:
            return info.qualname
    return fn.name  # pragma: no cover - every function is indexed


def _check_frame_decode(
    project: Project,
    findings: t.List[Finding],
    emit: t.Callable,
) -> None:
    allow_hits: t.Set[t.Tuple[str, str]] = set()
    for ctx in project.files:
        if any(ctx.path.endswith(home) for home in FRAME_DECODE_HOME):
            continue
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                decoded = (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"
                    and len(node.args) == 1
                    and _is_f32_spelling(node.args[0])
                    and _mentions_frame(node.func.value)
                )
                what = "astype(float32)"
            elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Div):
                decoded = _is_255(node.right) and _mentions_frame(node.left)
                what = "division by 255"
            else:
                continue
            if not decoded:
                continue
            scope = _frame_scope_qualname(ctx, node)
            entry = next(
                (
                    e for e in FRAME_DECODE_ALLOWLIST
                    if ctx.path.endswith(e[0]) and e[1] in (scope, "*")
                ),
                None,
            )
            if entry is not None:
                allow_hits.add(entry)
                continue
            emit(
                "frame-f32-materialize", ctx.path, node,
                f"{what} on a frame-derived value materializes the "
                "4x-width f32 frame batch the fused pixel pipeline "
                "exists to avoid (frames are uint8 in HBM by design)",
                "route sampling through pixel_pipeline='fused' "
                "(ops/pixels.py decodes in-kernel), or add a justified "
                "entry to FRAME_DECODE_ALLOWLIST (analysis/"
                "jit_hygiene.py) and docs/ANALYSIS.md",
            )
    for entry in sorted(FRAME_DECODE_ALLOWLIST - allow_hits):
        if any(f.path.endswith(entry[0]) for f in project.files):
            findings.append(Finding(
                "stale-allowlist", entry[0], 1, 0,
                f"frame-decode allowlist entry {entry!r} matches no "
                "decode; the code it excused is gone",
                "remove the entry from analysis/jit_hygiene.py "
                "FRAME_DECODE_ALLOWLIST",
            ))


def check(project: Project) -> t.List[Finding]:
    findings: t.List[Finding] = []
    seen: t.Set[t.Tuple[str, int, int, str]] = set()

    def emit(rule, path, node, message, hint):
        key = (path, node.lineno, node.col_offset, rule)
        if key in seen:
            return
        seen.add(key)
        findings.append(
            Finding(rule, path, node.lineno, node.col_offset, message, hint)
        )

    findings.extend(project.entry_point_findings())
    _check_frame_decode(project, findings, emit)

    for (path, _), fn in sorted(
        project.traced().items(), key=lambda kv: (kv[0][0], kv[0][1])
    ):
        fn_node: ast.AST = fn.node
        tainted = _tainted_names(fn_node)
        skip = _callback_subtrees(fn_node)
        where = f"traced function {fn.qualname!r}"
        for node in _walk_skipping(fn_node, skip):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if isinstance(node.func, ast.Attribute):
                if node.func.attr in _SYNC_METHODS and not node.args:
                    emit(
                        "host-sync-in-jit", path, node,
                        f".{node.func.attr}() inside {where} forces a "
                        "device->host sync every trace execution",
                        "keep the value on device (jnp reductions) or move "
                        "the read outside the jit boundary",
                    )
                    continue
            if name is None:
                continue
            if name in _SYNC_CALLS:
                emit(
                    "host-sync-in-jit", path, node,
                    f"{name}() inside {where} is a host transfer",
                    "return the array and read it outside the trace",
                )
            elif name in _CAST_BUILTINS and len(node.args) == 1 and (
                _derives_from(node.args[0], tainted)
            ):
                emit(
                    "host-sync-in-jit", path, node,
                    f"{name}() on a traced value inside {where} "
                    "(concretization error or silent host sync)",
                    "use jnp casts (.astype) on device, or mark the "
                    "argument static at the jit boundary",
                )
            elif name.split(".")[0] in _NP_ALIASES and (
                not _is_host_random(name)
                and len(node.args) >= 1
                and _derives_from(node.args[0], tainted)
            ):
                emit(
                    "host-sync-in-jit", path, node,
                    f"{name}() on a traced value inside {where} "
                    "materializes on host",
                    "use the jnp equivalent so the op stays in the trace",
                )
            if name in _WALLCLOCK:
                emit(
                    "wallclock-in-jit", path, node,
                    f"{name}() inside {where} is evaluated ONCE at trace "
                    "time; the compiled program sees a constant",
                    "take timings on the host around the jit call "
                    "(telemetry phase spans), not inside it",
                )
            elif _is_host_random(name):
                emit(
                    "host-random-in-jit", path, node,
                    f"{name}() inside {where} draws host randomness at "
                    "trace time (constant in the compiled program)",
                    "thread a jax.random key through the trace instead",
                )
    return findings
