"""What the files that ask the chip's compiler share
(``test_chip_compile.py``, ``test_chip_compile_population.py``,
``test_chip_compile_trunk.py``, ``test_chip_compile_hybrid.py``): the
described ``v5e:2x2`` host and the compiler's settings as fixtures, abstract
arrays placed on a described chip, and the readers of a compiled program's
text. A plain module, imported by name."""

import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from torch_actor_critic_tpu.telemetry import scopes

OBS_DIM, ACT_DIM = 17, 6  # HalfCheetah-v5, the reference flagship


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"the v5e:2x2 topology cannot be described here: {e!r}")
    return topo.devices


@pytest.fixture(autouse=True)
def chip_compiler(monkeypatch):
    """Cache off (see module docstring), and the trace-time kernel
    guards told the target is a TPU: the code under test asks
    ``jax.default_backend()``, which here still says ``cpu``."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, device):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(device)
    )


def _on(device, tree):
    return jax.tree_util.tree_map(
        lambda x: _shape(x.shape, x.dtype, device), tree
    )


def _chunk_of(ring, rows):
    """``rows`` rows a member of the shapes a member-stacked ring holds."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            (x.shape[0], rows) + x.shape[2:], x.dtype
        ),
        ring.data,
    )


def _ring_scatters(hlo_text, rows):
    """The ``scatter`` instructions, fused ones too, whose result has a
    dimension of at least the ring's row count."""
    found = []
    for shape in re.findall(r"= (\S+?\[[\d,]*\])\S* scatter\(", hlo_text):
        dims = [int(d) for d in re.findall(r"\d+", shape.split("[", 1)[1])]
        if any(d >= rows for d in dims):
            found.append(shape)
    return found


def _as_large_as(hlo_text, elements, op):
    """``op`` instructions (fused ones too) whose result has at least
    ``elements`` elements."""
    found = []
    for shape in re.findall(rf"= (\S+?\[[\d,]*\])\S* {op}\(", hlo_text):
        dims = [int(d) for d in re.findall(r"\d+", shape.split("[", 1)[1])]
        if int(np.prod(dims)) >= elements:
            found.append(shape)
    return found


def _expert_layer_rows(hlo_text, op):
    """The rows that every gather (its result; XLA:TPU lowers one to a fusion
    that keeps its name) or scatter (its updates) of the expert layer moves."""
    shape_of = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", hlo_text))
    rows = []
    for line in hlo_text.splitlines():
        if scopes.TRUNK_MOE_EXPERTS not in line:
            continue
        if op == "gather":
            m = re.search(r"%[\w.\-]+ = (\w+\[[\d,]*\])\S* (?:gather|fusion)\(.*/gather\"", line)
            moved = m.group(1) if m else None
        else:
            m = re.search(r" scatter\(([^)]*)\)", line)
            moved = shape_of[re.findall(r"%([\w.\-]+)", m.group(1))[2]] if m else None
        if moved:
            rows.append(int(re.findall(r"\d+", moved.split("[", 1)[1])[0]))
    return rows


def _weight_gradient_fusions(hlo_text):
    """``[(dimensions of a convolution's result, ones left out and sorted,
    whether an instruction under the optimizer's scope shares its fusion)]``
    for every convolution inside a fusion of an optimized program, a nested
    fusion counted with the fusion that calls it."""
    bodies = {
        m.group(1): m.group(2) for m in re.finditer(
            r"^(?:ENTRY )?%([\w.\-]+) \(.*?\{\n(.*?)^\}", hlo_text, re.S | re.M
        )
    }

    def whole(name, seen):
        body = bodies.get(name, "")
        for callee in re.findall(r"calls=%([\w.\-]+)", body):
            if callee not in seen:
                seen.add(callee)
                body += whole(callee, seen)
        return body

    found = []
    for name, body in bodies.items():
        if "fused_computation" in name:
            continue
        for callee in re.findall(r" fusion\(.*?calls=%([\w.\-]+)", body):
            inside = whole(callee, {callee})
            for dims in re.findall(r"= \w+\[([\d,]+)\]\S* convolution\(", inside):
                shape = tuple(sorted(int(d) for d in dims.split(",") if d != "1"))
                found.append((shape, scopes.OPTIMIZER in inside))
    return found


def _weight_gradients_stand_alone(hlo_text, taken, left):
    """ISSUE 46: no fusion holds both a convolution whose result is a taken
    kernel's shape and an instruction under ``tac/optimizer`` (the product is
    XLA's plain fusion between its two barriers, Adam and polyak a pass of
    their own), while a kernel the rule leaves still has Adam fused behind
    its gradient (what the reading looks for is there to be found)."""
    fusions = _weight_gradient_fusions(hlo_text)
    taken, left = ({tuple(sorted(shape)) for shape in shapes} for shapes in (taken, left))
    assert taken <= {shape for shape, _ in fusions}, (taken, fusions)
    assert [f for f in fusions if f[0] in taken and f[1]] == []
    assert [f for f in fusions if f[0] in left and f[1]], fusions


def _selection_is_a_pass(hlo_text, tokens, top_k, experts):
    """ISSUE 41: the router takes its ``top_k`` of ``experts`` by the
    selection's kernels, forward and backward; no ``sort`` stands under the
    router's scope (``lax.top_k`` lowered to whole sorts of a token's
    scores), and nothing of the size tokens x top_k x experts is formed,
    in memory or inside a fusion."""
    under_route = [line for line in hlo_text.splitlines() if scopes.TRUNK_MOE_ROUTE in line]
    assert under_route and not [line for line in under_route if " sort(" in line]
    kinds = [_kernel_kind(name) for name in _kernels(hlo_text)]
    assert kinds.count("router-top-k") >= 2 and kinds.count("router-top-k-bwd") >= 1, kinds
    assert not re.search(rf"\[(?:1,)?{tokens},{top_k},{experts}\]", hlo_text)


def _plan_sorts_the_held_candidates(hlo_text, tokens, top_k, n_held):
    """ISSUE 43: every ``sort`` under the expert layer's scope is the plan's
    (``moe.plan_assignments``), of one operand (the packed word: no index
    beside the key, no comparator over two) and of no more elements than a
    call's candidates, tokens x the fewer of ``top_k`` and the held experts;
    the parent sorted tokens x ``top_k`` keys with an iota."""
    sorts = [
        line for line in hlo_text.splitlines()
        if scopes.TRUNK_MOE_EXPERTS in line and " sort(" in line
    ]
    assert sorts and all(scopes.TRUNK_MOE_PLAN in line for line in sorts), sorts
    for line in sorts:
        result, operands = re.search(r"= (.*?) sort\(([^)]*)\)", line).groups()
        assert operands.count("%") == 1 and not result.startswith("("), line
        sorted_shape = re.match(r"\w+\[[\d,]*\]", result).group(0)
        assert _elements(sorted_shape) <= tokens * min(top_k, n_held), line


def _kernels(hlo_text):
    """Names of the Mosaic kernels' instructions."""
    return re.findall(
        r"%([\w.\-]+) = [^=]*? custom-call\(.*custom_call_target=\"tpu_custom_call\"",
        hlo_text,
    )


def _kernel_kind(name):
    from benchmark.harness import trace

    return trace.op_kind(name)


def _reads_as_attention(kind):
    """``benchmark/harness/trace.py::kind_seconds``'s rule for the tag that
    ``trunk.flash_roofline`` reads (``harness/trunk_read.py::FLASH``)."""
    from benchmark.harness import trace, trunk_read

    return trace.kind_seconds({"by_kind": {kind: 1.0}}, trunk_read.FLASH) == 1.0


def _relayouts_round_the_kernels(hlo_text, batch, history, heads, d):
    """Instructions under the attention scope (fused ones too) that write a
    float32 array of q's size in the projections' layout ``[batch, history,
    heads, d]`` or the kernels' ``[batch, heads, history, d]`` /
    ``[batch * heads, history, d]`` by a ``transpose`` or a ``copy``, or a
    lane-wide copy of the row statistics ``[batch * heads, history, 128]`` by
    a ``broadcast``: what ISSUE 39 took out of the program, as ``(what, the
    instruction's line)``. (Under the burst's ``vmap`` the shapes carry a
    leading 1.)"""
    q_forms = {
        f"{batch},{history},{heads},{d}", f"{batch},{heads},{history},{d}",
        f"{batch * heads},{history},{d}",
    }
    found = []
    for line in hlo_text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%([\w.\-]+) = f32\[(?:1,)?([\d,]*)\]\S* (transpose|copy|broadcast)\(",
            line,
        )
        if not m or scopes.TRUNK_ATTENTION not in line:
            continue
        name, dims, op = m.groups()
        if (op == "broadcast" and dims == f"{batch * heads},{history},128") or (
            op != "broadcast" and dims in q_forms
        ):
            found.append((f"{op} {name} f32[{dims}]", line))
    return found


def _entry(hlo_text):
    """``{name: (result shapes, op, operand names, callee)}`` of the entry
    computation, and ``{computation: its instructions' ops}``."""
    ops_of, body, entry = {}, None, {}
    for line in hlo_text.splitlines():
        header = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if header:
            body, in_entry = header.group(2), bool(header.group(1))
            ops_of[body] = set()
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z0-9\-]*)\((.*)", line)
        if not m or body is None:
            continue
        name, result, op, rest = m.groups()
        ops_of[body].add(op)
        if in_entry:
            callee = re.search(r"calls=%?([\w.\-]+)", rest)
            entry[name] = (
                re.findall(r"\w+\[[\d,]*\]", result), op,
                re.findall(r"%([\w.\-]+)", rest.split("),")[0]),
                callee.group(1) if callee else None,
            )
    return entry, ops_of


def _elements(shape):
    return int(np.prod([int(d) for d in re.findall(r"\d+", shape.split("[", 1)[1])] or [1]))
