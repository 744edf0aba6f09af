"""Device scopes: the names the update and epoch programs give their
parts, and the table that joins those names to a profiler trace.

The programs wrap their parts in ``jax.named_scope`` under one prefix
(metadata only: no operation is added, no switch turns it off). A
scope reaches the compiled program as a substring of each
instruction's ``metadata={op_name="..."}`` and survives the
``jvp``/``transpose``/``vmap`` wrapping of the name stack. It does not
reach a device trace taken without the HLO proto (the benchmark's, and
``--profile-epochs``'): an event on the ``XLA Ops`` line is named by
its instruction's HLO text without metadata. :func:`scope_table` joins
the two by instruction name, from the compiled program's own text.

The persistent compilation cache leaves metadata out of its key, so an
executable loaded from it may carry the scopes of whichever commit
compiled it, and jax hands that same executable to every later
``lower().compile()`` of the program in the process.
:func:`scope_table_for` therefore compiles once more, past both;
instruction names do not depend on metadata, so the table of that
compile fits the executable that ran.
"""

from __future__ import annotations

import re
import typing as t

import jax
from jax.experimental.compilation_cache import compilation_cache

PUSH = "tac/push"
SAMPLE = "tac/sample"
DECODE = "tac/sample/decode"
CRITIC = "tac/critic"
ACTOR = "tac/actor"
ALPHA = "tac/alpha"
OPTIMIZER = "tac/optimizer"
POLYAK = "tac/polyak"
ALLREDUCE = "tac/allreduce"
COLLECT_ACT = "tac/collect/act"
COLLECT_ENV = "tac/collect/env_step"
# The parts of a history trunk (models/sequence.py), nested inside
# tac/critic and tac/actor: the innermost name is the one a reader gets.
TRUNK_EMBED = "tac/trunk/embed"  # observation projection, final norm
TRUNK_ATTENTION = "tac/trunk/attention"
# A stack that mixes attention kinds names each sublayer's kind inside it
# (norm, projections, rotary, kernels, output projection), and the per-head
# output gate apart; a reader of tac/trunk/attention still sums them all.
TRUNK_ATTENTION_FULL = "tac/trunk/attention/full"
TRUNK_ATTENTION_SLIDING = "tac/trunk/attention/sliding"
TRUNK_ATTENTION_GATE = "tac/trunk/attention/gate"
TRUNK_DENSE_FFN = "tac/trunk/dense_ffn"  # a block's dense gated feed-forward and its norm
TRUNK_MOE_ROUTE = "tac/trunk/moe/route"
TRUNK_MOE_EXPERTS = "tac/trunk/moe/experts"
TRUNK_MOE_PRODUCTS = "tac/trunk/moe/experts/products"  # the grouped products alone
TRUNK_MOE_PLAN = "tac/trunk/moe/experts/plan"  # ops/moe.py::plan_assignments: the held rows' order
TRUNK_MOE_LATENT = "tac/trunk/moe/latent"  # projections into and out of the experts' width
TRUNK_MOE_SHARED = "tac/trunk/moe/shared"  # the expert every token passes
# A state-space mixer: its two projections, the short convolution, the
# recurrence over the history, the gated norm.
TRUNK_SSM_PROJ = "tac/trunk/ssm/proj"
TRUNK_SSM_CONV = "tac/trunk/ssm/conv"
TRUNK_SSM_SCAN = "tac/trunk/ssm/scan"
TRUNK_SSM_GATE_NORM = "tac/trunk/ssm/gate_norm"
SCOPES = (
    PUSH, SAMPLE, DECODE, CRITIC, ACTOR, ALPHA, OPTIMIZER, POLYAK,
    ALLREDUCE, COLLECT_ACT, COLLECT_ENV, TRUNK_EMBED, TRUNK_ATTENTION,
    TRUNK_ATTENTION_FULL, TRUNK_ATTENTION_SLIDING, TRUNK_ATTENTION_GATE,
    TRUNK_DENSE_FFN, TRUNK_MOE_ROUTE, TRUNK_MOE_EXPERTS, TRUNK_MOE_PRODUCTS, TRUNK_MOE_PLAN,
    TRUNK_MOE_LATENT, TRUNK_MOE_SHARED, TRUNK_SSM_PROJ, TRUNK_SSM_CONV,
    TRUNK_SSM_SCAN, TRUNK_SSM_GATE_NORM,
)
HOST_PREFIX = "tac/host/"  # the recorder's phase annotations

INHERITED = "~"  # suffix of a scope an instruction got from its neighbours

# Longest name first, so that tac/sample/decode is not read as tac/sample.
_SCOPE = re.compile("|".join(sorted(map(re.escape, SCOPES), key=len, reverse=True)))
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")
_NAME = re.compile(r"%([\w.\-]+)")
_ATTR = re.compile(r"\b(calls|body|condition)=%([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_PLUMBING = ("tuple", "get-tuple-element", "parameter")

Table = t.Dict[str, t.Dict[str, int]]
Value = t.Tuple[str, str, t.Optional[int]]  # computation, instruction, tuple index


def scope_of(op_name: str) -> str:
    """The innermost scope in an instruction's ``op_name``; ``""`` where
    it has none."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


def module_name(hlo_text: str) -> str:
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else ""


class _Instruction(t.NamedTuple):
    name: str
    opcode: str
    operands: t.Tuple[str, ...]
    attrs: t.Dict[str, str]  # calls / body / condition -> computation
    index: int | None  # of a get-tuple-element
    scope: str | None  # None: no op_name at all; "": an op_name, none of ours
    root: bool


def _parse(hlo_text: str) -> t.Dict[str, t.List[_Instruction]]:
    """Every computation's instructions, in the text's order."""
    computations: t.Dict[str, t.List[_Instruction]] = {}
    body = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            body = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            body = None
            continue
        m = _INSTRUCTION.match(line) if body is not None else None
        if not m:
            continue
        rest = m.group(3)
        op = _OPCODE.search(rest)
        operands: t.Tuple[str, ...] = ()
        if op:  # the operand list runs to the parenthesis that closes it
            depth, i = 1, op.end()
            while i < len(rest) and depth:
                depth += {"(": 1, ")": -1}.get(rest[i], 0)
                i += 1
            operands = tuple(_NAME.findall(rest[op.end():i]))
        op_name = _OP_NAME.search(rest)
        index = _INDEX.search(rest)
        body.append(_Instruction(
            m.group(2), op.group(1) if op else "", operands,
            dict(_ATTR.findall(rest)), int(index.group(1)) if index else None,
            scope_of(op_name.group(1)) if op_name else None, bool(m.group(1)),
        ))
    return computations


def scope_table(hlo_text: str) -> Table:
    """``{instruction name: {scope: instruction count}}`` for every
    instruction of every computation no fusion calls. A fusion's entry
    counts the instructions of the computation it calls (through nested
    fusions) that carry an ``op_name``; any other instruction counts
    itself. The scope ``""`` stands for "none of ours".

    The compiler makes instructions of its own (layout copies, the loops
    it expands a scatter or a relayout into) and gives them no ``op_name``.
    Such an entry, instead of ``""``, holds the scopes of its nearest
    scoped neighbours along the data flow (the instructions that produce
    what it reads and that read what it produces, through other such
    instructions), each marked ``~``; a loop the compiler made hands its
    entry down to the instructions of its body. Whether the neighbours
    agree is for the reader to judge."""
    computations = _parse(hlo_text)
    fused = {i.attrs["calls"] for body in computations.values() for i in body if "calls" in i.attrs}

    def fused_counts(name: str, into: t.Dict[str, int]) -> None:
        for i in computations.get(name, ()):
            if "calls" in i.attrs:
                fused_counts(i.attrs["calls"], into)
            elif i.scope is not None:
                into[i.scope] = into.get(i.scope, 0) + 1

    table: Table = {}
    for name, body in computations.items():
        if name in fused:
            continue
        for i in body:
            counts: t.Dict[str, int] = {}
            if "calls" in i.attrs:
                fused_counts(i.attrs["calls"], counts)
            else:
                counts[i.scope or ""] = 1
            table[i.name] = counts
    _inherit(table, {c: b for c, b in computations.items() if c not in fused})
    return table


def _scoped(counts: t.Mapping[str, int]) -> t.Dict[str, int]:
    return {s: n for s, n in counts.items() if s}


def _inherit(table: Table, computations: t.Dict[str, t.List[_Instruction]]) -> None:
    """Give every entry of ``table`` without a scope of ours the scopes of its
    nearest scoped neighbours (see :func:`scope_table`). Values flow through
    tuples, ``get-tuple-element`` and a loop's carried state by index."""
    by_name = {c: {i.name: i for i in body} for c, body in computations.items()}
    reads: t.Dict[Value, t.List[t.Tuple[str, str]]] = {}  # value -> instructions
    flows: t.Dict[Value, t.List[Value]] = {}  # value -> the values it becomes
    made_by: t.Dict[Value, t.List[Value]] = {}  # the reverse of flows

    def flow(src: Value, dst: Value) -> None:
        flows.setdefault(src, []).append(dst)
        made_by.setdefault(dst, []).append(src)

    for c, body in computations.items():
        for i in body:
            if i.opcode == "tuple":
                for k, o in enumerate(i.operands):
                    flow((c, o, None), (c, i.name, k))
            elif i.opcode == "get-tuple-element" and i.operands:
                flow((c, i.operands[0], i.index), (c, i.name, None))
            else:
                for o in i.operands:
                    reads.setdefault((c, o, None), []).append((c, i.name))
            if i.opcode == "while" and i.operands and i.attrs.get("body") in computations:
                # element k of the state: into the body (and the condition),
                # round the loop, and out again
                b = i.attrs["body"]
                param = next((x for x in computations[b] if x.opcode == "parameter"), None)
                root = next((x for x in computations[b] if x.root), None)
                if param is None or root is None or root.opcode != "tuple":
                    continue
                cond = i.attrs.get("condition")
                cond_param = next(
                    (x for x in computations.get(cond, ()) if x.opcode == "parameter"), None
                )
                for k in range(len(root.operands)):
                    flow((c, i.operands[0], k), (b, param.name, k))
                    flow((b, root.name, k), (b, param.name, k))
                    flow((b, root.name, k), (c, i.name, k))
                    if cond_param is not None:
                        flow((c, i.operands[0], k), (cond, cond_param.name, k))

    index_of: t.Dict[t.Tuple[str, str], t.List[Value]] = {}
    for v in list(flows) + list(made_by):
        if v[2] is not None:
            index_of.setdefault((v[0], v[1]), []).append(v)

    def closure(values: t.List[Value], edges: t.Dict[Value, t.List[Value]]) -> t.Set[Value]:
        """``values`` and every value they become (or came from) by plumbing."""
        done, stack = set(values), list(values)
        while stack:
            for nxt in edges.get(stack.pop(), ()):
                if nxt not in done:
                    done.add(nxt)
                    stack.append(nxt)
        return done

    def nearest(c: str, name: str, forward: bool) -> t.Dict[str, int]:
        """Scopes of the first scoped instructions reached from ``name``:
        forward its readers, backward its producers, each step through
        instructions that have no scope themselves."""
        hits: t.Dict[str, int] = {}
        frontier, seen = [(c, name)], {(c, name)}
        while frontier and not hits:
            reached: t.List[t.Tuple[str, str]] = []
            for fc, fname in frontier:
                if forward:
                    made = [(fc, fname, None)] + index_of.get((fc, fname), [])
                    for v in closure(made, flows):
                        reached += reads.get(v, [])
                else:
                    read = [(fc, o, None) for o in by_name[fc][fname].operands]
                    for vc, vname, k in closure(read, made_by):
                        src = by_name[vc].get(vname)
                        # element k of a loop's result is made in its body
                        if src is not None and (k is None or src.opcode != "while"):
                            reached.append((vc, vname))
            frontier = []
            for rc, rname in reached:
                if (rc, rname) in seen:
                    continue
                seen.add((rc, rname))
                if by_name[rc][rname].opcode in _PLUMBING:
                    continue
                found = _scoped(table.get(rname, {}))
                if found:
                    for s_, n in found.items():
                        s_ = s_.rstrip(INHERITED) + INHERITED
                        hits[s_] = hits.get(s_, 0) + n
                else:
                    frontier.append((rc, rname))
        return hits

    callers = {
        i.attrs[role]: i.name
        for body in computations.values() for i in body
        for role in ("body", "condition") if role in i.attrs
    }
    for _ in range(3):  # an inherited scope is a neighbour's scope in the next round
        changed = False
        for c, body in computations.items():
            for i in body:
                if i.opcode in _PLUMBING or _scoped(table[i.name]):
                    continue
                hits = nearest(c, i.name, True)
                for s_, n in nearest(c, i.name, False).items():
                    hits[s_] = hits.get(s_, 0) + n
                if hits:
                    table[i.name] = hits
                    changed = True
        for c, body in computations.items():  # a loop the compiler made
            handed = _scoped(table.get(callers.get(c, ""), {}))
            if handed and all(k.endswith(INHERITED) for k in handed):
                for i in body:
                    if i.opcode not in _PLUMBING and not _scoped(table[i.name]):
                        table[i.name] = dict(handed)
                        changed = True
        if not changed:
            break


def abstract_of(*trees) -> tuple:
    """Shape, dtype and sharding of every array leaf: what a program was
    built for, kept so that it can be lowered again after its donated
    arguments are gone."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None),
            weak_type=getattr(x, "weak_type", False),
        ),
        trees,
    )


def scope_table_for(jit_fn, *abstract) -> dict:
    """``{"module": name, "table": scope_table}`` of ``jit_fn`` compiled
    for ``abstract``: one real compile (see the module docstring). Off the
    training path: seconds, once, on request."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # jax asks once whether a cache is in use
    try:
        # An option set to its default changes nothing the compiler does; it
        # is part of the key of jax's in-memory cache of executables.
        text = jit_fn.lower(*abstract).compile(
            compiler_options={"xla_detailed_logging": True}
        ).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
    return {"module": module_name(text), "table": scope_table(text)}
