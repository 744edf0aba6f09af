"""prng-discipline rules: key hygiene over the def-use chains.

``jax.random`` guarantees independent streams only for DISTINCT keys;
every hazard in this family produces correlated (often identical)
randomness that no test asserting "is finite / has the right shape"
will ever catch — replay rows sampled twice, exploration noise
repeating per epoch, population members collapsing onto one stream.
The engine-key bug PR 1's review caught (warmup reusing one key across
buckets after donation deleted it) sat in exactly this class.

Three rules over :mod:`~torch_actor_critic_tpu.analysis.dataflow`'s
per-function event streams (branch-exclusivity aware — arms of one
``if`` never execute in sequence):

* ``key-reuse`` — a key consumed by two sinks without an intervening
  rebind. A *sink* is any use that derives randomness or hands the key
  on (a ``jax.random.<dist>`` draw, an ``apply(..., key, ...)`` call,
  a capture into a carry/return). The sound idiom is destructive:
  ``key, sub = jax.random.split(key)`` — the rebind kills the old
  value in the same statement.
* ``key-split-nondestructive`` — ``sub = jax.random.split(key)``
  spelling that silently keeps ``key`` live, followed by another
  consumption of ``key``: ``split`` is deterministic, so the children
  overlap with any later use of the parent (and a second
  ``split(key)`` yields the SAME children). Splitting without
  rebinding is fine only when the parent is never touched again.
* ``key-loop-reuse`` — a key consumed inside a loop while bound
  outside it and never rebound in the body: every iteration draws from
  the identical key (the warmup-across-buckets shape of the PR-1 bug).

``jax.random.fold_in(key, data)`` is exempt as a consumer: deriving
per-step/per-device subkeys from one parent with distinct fold data is
the sanctioned decorrelation idiom on every fused loop (``fold_in(rng,
dev)``), and whether the data differs per call is not statically
decidable. Reads of key *metadata* (``key.shape``) and subscripted
reads of key ARRAYS (``keys[i]`` — distinct rows are distinct keys)
are not consumption either.

A name is a key if it is spelled like one (``key``, ``rng``,
``*_key``/``*_keys``, ``k_*``) or assigned from a key-producing call
(``jax.random.key/PRNGKey/split/fold_in/wrap_key_data``) — both
checked per function, no interprocedural guessing.
"""

from __future__ import annotations

import ast
import typing as t

from torch_actor_critic_tpu.analysis.dataflow import (
    FlowScope,
    NameEvent,
    function_events,
    tracked_key,
)
from torch_actor_critic_tpu.analysis.reachability import Project
from torch_actor_critic_tpu.analysis.walker import (
    FileContext,
    Finding,
    dotted_name,
)

__all__ = ["check"]

FAMILY = "prng-discipline"

# Key producers: assignment from these marks the target as a key.
_KEY_PRODUCERS = frozenset({
    "key", "PRNGKey", "split", "fold_in", "wrap_key_data", "clone",
})
# Spelling-based key detection (exact names / affixes). Deliberately
# excludes bare `k` (ubiquitous dict-iteration name); spelling only
# counts in functions that touch jax.random at all — `for key in
# metrics:` in a pure-host module is a dict key, not a PRNG key.
_KEY_NAMES = frozenset({"key", "rng", "subkey", "act_key"})
_KEY_SUFFIXES = ("_key", "_keys", "_rng")
_KEY_PREFIXES = ("k_",)

_RANDOM_HEADS = frozenset({"jax", "random", "jrandom", "jr"})

# Callees that read key METADATA or raw bytes without consuming the
# stream: `key_data`/`key_impl` (serialization, utils/checkpoint.py),
# the repo's `_is_prng_key` predicate, and `abstract_of` (the
# ShapeDtypeStruct capture the cost registry and the scope table lower
# with — shapes only, docs/ANALYSIS.md). Passing a key to these is not
# a sink.
_METADATA_SINKS = frozenset({
    "key_data", "key_impl", "_is_prng_key", "abstract_of",
})


def _random_call_kind(name: str | None) -> str | None:
    """'split' / 'fold_in' for jax.random.{split,fold_in} spellings,
    None for anything else."""
    if not name:
        return None
    parts = name.split(".")
    last = parts[-1]
    if last not in ("split", "fold_in"):
        return None
    if len(parts) == 1:
        return None  # bare split() is almost always str.split
    if parts[0] in _RANDOM_HEADS or parts[-2] == "random":
        return last
    return None


def _is_key_name(key: str) -> bool:
    last = key.rsplit(".", 1)[-1].lower()
    if last in _KEY_NAMES:
        return True
    return last.endswith(_KEY_SUFFIXES) or last.startswith(_KEY_PREFIXES)


def _assigned_keys(fn_node: ast.AST) -> t.Set[str]:
    """Names assigned from key-producing jax.random calls."""
    out: t.Set[str] = set()
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if isinstance(value, ast.Subscript):
            value = value.value  # split(k, n)[0]
        if not isinstance(value, ast.Call):
            continue
        name = dotted_name(value.func)
        if not name:
            continue
        parts = name.split(".")
        if parts[-1] not in _KEY_PRODUCERS:
            continue
        if len(parts) >= 2 and not (
            parts[0] in _RANDOM_HEADS or parts[-2] == "random"
        ):
            continue
        if len(parts) == 1 and parts[-1] not in ("PRNGKey",):
            continue
        for target in node.targets:
            if isinstance(target, (ast.Tuple, ast.List)):
                for elt in target.elts:
                    k = tracked_key(elt)
                    if k:
                        out.add(k)
            else:
                k = tracked_key(target)
                if k:
                    out.add(k)
    return out


def _classify_load(
    scope: FlowScope, event: NameEvent
) -> t.Tuple[str, ast.Call | None]:
    """('exempt'|'split'|'sink', enclosing call) for one key read.

    Only CALL ARGUMENTS consume a key: comparisons (``key is None``),
    metadata reads (``key.dtype``), key-array indexing (``keys[i]`` —
    distinct rows are distinct keys) and plain captures are not
    consumption (precision over recall: the sound split idiom rebinds,
    so an unsound capture resurfaces at its eventual call site)."""
    node = event.node
    parent = scope._parents.get(node)
    if isinstance(parent, ast.Attribute) and parent.value is node:
        return "exempt", None
    if isinstance(parent, ast.Subscript) and parent.value is node:
        return "exempt", None
    # A keyword argument under a key-spelled name (`EnvState(rng=...)`,
    # `replace(rng=...)`) is a carry — the key rides a struct onward,
    # it is not drawn from here.
    if isinstance(parent, ast.keyword) and parent.arg is not None and (
        _is_key_name(parent.arg)
    ):
        return "exempt", None
    # Innermost call whose ARGUMENT list carries the read.
    cur: ast.AST | None = node
    while cur is not None and not isinstance(cur, ast.stmt):
        up = scope._parents.get(cur)
        if isinstance(up, ast.Call) and cur is not up.func:
            name = dotted_name(up.func)
            kind = _random_call_kind(name)
            if kind == "fold_in":
                return "exempt", up
            if kind == "split":
                return "split", up
            if name and name.rsplit(".", 1)[-1] in _METADATA_SINKS:
                return "exempt", up
            return "sink", up
        if isinstance(up, ast.Call):  # the read IS the callee
            return "exempt", None
        cur = up
    return "exempt", None


def _check_function(
    ctx: FileContext,
    scope: FlowScope,
    keys: t.Set[str],
    findings: t.List[Finding],
) -> None:
    for key in sorted(keys):
        events = [
            e for e in function_events(scope, {key}) if not e.closure
        ]
        if not events:
            continue
        consumes: t.List[t.Tuple[NameEvent, str]] = []
        flagged = False
        for e in events:
            if e.kind == "store":
                # Destructive rebind: earlier consumes are dead on
                # every path through this store. Conservative: any
                # store clears the slate for reads it reaches; reads
                # on incompatible paths are handled by `reaches`.
                consumes = [
                    (c, k) for c, k in consumes
                    if not scope.reaches(e.node, c.node)
                    and not scope.reaches(c.node, e.node)
                ]
                continue
            kind, _call = _classify_load(scope, e)
            if kind == "exempt":
                continue
            # ---- loop rule: consumed each iteration, never rebound
            loops = scope.loops_enclosing(e.node)
            if loops and not flagged:
                loop = loops[0]
                stored_in_loop = any(
                    s.kind == "store"
                    and any(
                        l2 is loop
                        for l2 in scope.loops_enclosing(s.node)
                    )
                    for s in events
                )
                if not stored_in_loop:
                    findings.append(Finding(
                        "key-loop-reuse", ctx.path,
                        getattr(e.node, "lineno", 0),
                        getattr(e.node, "col_offset", 0),
                        f"PRNG key {key!r} is consumed inside a loop "
                        "but bound outside it and never rebound in the "
                        "body: every iteration draws from the "
                        "IDENTICAL key (identical randomness)",
                        "split per iteration — `key, sub = "
                        "jax.random.split(key)` inside the loop, or "
                        "fold_in the loop index",
                    ))
                    flagged = True
                    continue
            # ---- pair rule
            if not flagged:
                for prev, prev_kind in consumes:
                    if not scope.reaches(prev.node, e.node):
                        continue
                    if prev_kind == "split":
                        findings.append(Finding(
                            "key-split-nondestructive", ctx.path,
                            getattr(e.node, "lineno", 0),
                            getattr(e.node, "col_offset", 0),
                            f"PRNG key {key!r} was split "
                            f"non-destructively on line "
                            f"{getattr(prev.node, 'lineno', 0)} (the "
                            "split did not rebind it) and is consumed "
                            "again here: the parent's later use "
                            "overlaps the children's streams",
                            "rebind at the split — `key, sub = "
                            "jax.random.split(key)` — so the stale "
                            "parent cannot leak forward",
                        ))
                    else:
                        findings.append(Finding(
                            "key-reuse", ctx.path,
                            getattr(e.node, "lineno", 0),
                            getattr(e.node, "col_offset", 0),
                            f"PRNG key {key!r} is consumed a second "
                            "time without an intervening split "
                            f"(first consumed on line "
                            f"{getattr(prev.node, 'lineno', 0)}): both "
                            "sinks draw IDENTICAL randomness",
                            "split before each sink — `key, sub = "
                            "jax.random.split(key)` — and hand each "
                            "consumer its own subkey",
                        ))
                    flagged = True
                    break
            consumes.append((e, kind))


def _fn_key_facts(
    fn: ast.AST,
) -> t.Tuple[bool, t.Set[str], t.Set[str]]:
    """(mentions jax.random, names passed to jax.random.* calls,
    names ever used as a callee) — the provenance evidence key-ness
    gating needs."""
    mentions_random = False
    random_args: t.Set[str] = set()
    called: t.Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        ck = tracked_key(node.func)
        if ck is not None:
            called.add(ck)
        if not name:
            continue
        parts = name.split(".")
        if "random" not in parts or parts[0] not in ("jax", "jrandom", "jr"):
            continue  # np.random/stdlib random are host-random, not keys
        mentions_random = True
        # Only the KEY argument position marks key-ness: arg 0 of a
        # jax.random consumer (split/fold_in/normal/...), or `key=`.
        # Producers take seeds/raw data there, not keys.
        if parts[-1] in ("key", "PRNGKey", "wrap_key_data"):
            continue
        if parts[-1] in _METADATA_SINKS:
            continue  # metadata reads neither consume nor confer key-ness
        if node.args:
            k = tracked_key(node.args[0])
            if k is not None:
                random_args.add(k)
        for kw in node.keywords:
            if kw.arg == "key":
                k = tracked_key(kw.value)
                if k is not None:
                    random_args.add(k)
    return mentions_random, random_args, called


def check(project: Project) -> t.List[Finding]:
    findings: t.List[Finding] = []
    for ctx in project.files:
        for info in ctx.functions:
            fn = info.node
            scope = FlowScope(ctx, fn)
            mentions_random, random_args, called = _fn_key_facts(fn)
            # Key-ness needs provenance: produced by jax.random, fed to
            # jax.random, or key-spelled in a function that uses
            # jax.random at all. Names the function CALLS are
            # callables, never keys (`self._next_key()`).
            keys = _assigned_keys(fn) | random_args
            if mentions_random:
                for node in ast.walk(fn):
                    k = tracked_key(node)
                    if k is not None and _is_key_name(k):
                        keys.add(k)
            keys -= called
            keys.discard("self")
            if keys:
                _check_function(ctx, scope, keys, findings)
    return findings
