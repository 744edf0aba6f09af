"""The chip's compiler, asked in the sandbox (on-chip-measurement §2.3).

libtpu compiles for a TPU that is described and not attached, so the
kernels and the step programs of the main path are compiled here for a
``v5e:2x2`` host at their real shapes: what Mosaic or XLA:TPU would
refuse on the chip is refused here, at no chip time. Nothing runs —
a compile that passes says nothing about results or speed;
``chip_smoke.py`` is where these programs execute and are compared.

One parametrised test, skipped where the topology cannot be described.
The persistent compilation cache is off around it: such a compile can
be written to the cache but not read back without a chip.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from torch_actor_critic_tpu.buffer.replay import (
    init_replay_buffer,
    init_visual_replay_buffer,
    nbytes,
    push,
)
from torch_actor_critic_tpu.core.types import Batch, BufferState
from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.ops import moe, pixels
from torch_actor_critic_tpu.ops.attention import flash_attention
from torch_actor_critic_tpu.parallel import DataParallelSAC, make_mesh
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.telemetry import scopes
from torch_actor_critic_tpu.utils.config import SACConfig

OBS_DIM, ACT_DIM = 17, 6  # HalfCheetah-v5, the reference flagship
WALL_RUNNER_RING = (20000, 64, 64, 3)


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


def _flash(devices, shape, dtype, pad_lanes=128):
    """Forward and ``jax.grad`` of the flash kernels: 1 + 3 custom calls
    (fwd; fwd-with-lse + dQ + dK/dV)."""
    x = _shape(shape, dtype, devices[0])

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, None, None, False, pad_lanes)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    for fn, n_kernels in ((fwd, 1), (jax.grad(loss, (0, 1, 2)), 3)):
        text = jax.jit(fn).lower(x, x, x).compile().as_text()
        assert text.count("tpu_custom_call") == n_kernels


def _pixel(devices, batch, dtype, shift, frame_stack=1):
    dev = devices[0]
    offsets = _shape((batch, 2), jnp.int32, dev)

    def gather(ring, idx, offs):
        return pixels.fused_frame_gather(
            ring, idx, offs if shift else None, normalize=True,
            out_dtype=dtype, frame_stack=frame_stack, impl="pallas",
        )

    compiled = jax.jit(gather).lower(
        _shape(WALL_RUNNER_RING, jnp.uint8, dev),
        _shape((batch,), jnp.int32, dev), offsets,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


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


def _flash_grouped(devices, block_length):
    """The three flash kernels at the SDAR trunk's shapes: 32 query heads
    over 4 shared key/value heads of 128 (read through the index maps),
    the block-causal mask, float32 tiles with bfloat16 products."""
    q = _shape((8, 32, 1024, 128), jnp.float32, devices[0])
    kv = _shape((8, 4, 1024, 128), jnp.float32, devices[0])

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, None, None, False, 128, block_length, True)

    grads = jax.grad(lambda q, k, v: fwd(q, k, v).sum(), (0, 1, 2))
    for fn, n_kernels in ((fwd, 1), (grads, 3)):
        compiled = jax.jit(fn).lower(q, kv, kv).compile()
        assert compiled.as_text().count("tpu_custom_call") == n_kernels
    assert [x.shape for x in jax.eval_shape(grads, q, kv, kv)] == [
        q.shape, kv.shape, kv.shape
    ]


def _flash_window(devices):
    """The three flash kernels at a sliding layer's shapes in
    ``laguna_s21_trunk_burst``: 18 query heads over 2 shared key/value heads
    of 128, histories of 4,096, a window of 512: the grids run over the two
    key blocks (three query blocks, for dK/dV) a row of 512-wide blocks can
    see where the causal sweep runs over eight, and the forward kernel keeps
    the kind the benchmark's flash readers find it by (inside the cell's
    burst all 25 kernels of the five layers read so: the sandbox compile at
    size, PERF.md section 4)."""
    q = _shape((2, 18, 4096, 128), jnp.float32, devices[0])
    kv = _shape((2, 2, 4096, 128), jnp.float32, devices[0])

    def fwd(q, k, v):
        # a kernel is named for the scope that calls it: the layer's module, `attention`
        with jax.named_scope("attention"):
            return flash_attention(q, k, v, True, None, None, False, 128, 1, True, 512)

    grads = jax.grad(lambda q, k, v: fwd(q, k, v).sum(), (0, 1, 2))
    for fn, n_kernels in ((fwd, 1), (grads, 3)):
        text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
        kinds = [_kernel_kind(name) for name in _kernels(text)]
        assert len(kinds) == n_kernels and (fn is grads or _reads_as_attention(kinds[0])), kinds
    assert [x.shape for x in jax.eval_shape(grads, q, kv, kv)] == [
        q.shape, kv.shape, kv.shape
    ]


def _compile_trunk_burst(devices):
    """The shared-trunk burst over the cell's ring of histories (8,192 rows
    of 1024 x 17, the trunk itself at a cut width so that this compiles in
    seconds), compiled for the described v5e: ``(configuration, rows,
    history, compiled burst)``."""
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    rows, history = 8192, 1024
    cfg = SACConfig(
        trunk_block="sdar_moe", history_len=history, batch_size=8, update_every=10,
        buffer_size=rows, burst_unroll=1, trunk_hidden=256, trunk_q_heads=8,
        trunk_kv_heads=2, trunk_head_dim=128, trunk_layers=1, trunk_experts=128,
        trunk_experts_held=(0, 4), trunk_experts_per_tok=4, trunk_expert_width=128,
    )
    spec = jax.ShapeDtypeStruct((history, OBS_DIM), jnp.float32)
    env = type("Env", (), dict(act_dim=ACT_DIM, act_limit=1.0, obs_spec=spec))
    sac = make_learner(cfg, *build_models(cfg, env), ACT_DIM)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=devices[:1]))
    state = jax.eval_shape(sac.init_state, jax.random.key(0), jnp.zeros(spec.shape))

    def ring_of(n):
        one = jax.eval_shape(lambda: init_replay_buffer(n, spec, ACT_DIM).data)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), one
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(data=ring_of(rows), ptr=index, size=index)
    chunk = ring_of(cfg.update_every)
    compiled = learner._build_burst(cfg.update_every, state, ring, chunk).lower(
        state, ring, chunk
    ).compile()
    return cfg, rows, history, compiled


def _trunk_burst(devices):
    """The cut burst (:func:`_compile_trunk_burst`): the grouped products and
    the kernels lower under the burst's ``vmap`` over its device axis, and no
    gather or scatter of the ring's size is among its instructions."""
    cfg, rows, history, compiled = _compile_trunk_burst(devices)
    text = compiled.as_text()
    assert "ragged-dot" in text and text.count("tpu_custom_call") >= 3
    # ISSUE 39: q's one pass ahead of the flash kernels is taken under the
    # burst's ``vmap`` (the target's and the online trunk's forward, and one
    # back), and under the attention scope nothing else transposes or copies
    # a float32 activation of q's size between the projections' layout and
    # the kernels', nor broadcasts the row statistics across lanes, but XLA's
    # two relayouts of ``o_proj``'s cotangent in the one backward pass (the
    # parent's burst at the cell's widths holds 29 of these, this one 8).
    kinds = [_kernel_kind(name) for name in _kernels(text)]
    assert kinds.count("qk-rope") >= 2 and kinds.count("qk-rope-bwd") >= 1, kinds
    left = _relayouts_round_the_kernels(
        text, cfg.batch_size, history, cfg.trunk_q_heads, cfg.trunk_head_dim
    )
    assert len(left) <= 2 and all(what.startswith("copy") for what, _ in left), left
    # The compiler's account of this cut program's peak: with 16 experts
    # 2,177,838,592 B at PR 39's parent and 2,170,855,424 with q's pass; with
    # the cell's 128 (PR 41) 2,198,296,576 at the parent and 2,198,294,528
    # with the selection's kernels. A layout carried into the burst's state,
    # or a kernel that moves what XLA keeps, shows here.
    assert compiled.memory_analysis().peak_memory_in_bytes < 2.2025e9
    # Neither a scatter nor a gather whose result is as large as a ring leaf:
    # the sample's gather is batch-sized.
    assert _as_large_as(text, rows * history * OBS_DIM, "scatter") == []
    assert _as_large_as(text, rows * history * OBS_DIM, "gather") == []
    # The expert layer gathers and scatter-adds a piece at a time, forward
    # and backward, and runs only the pieces that hold a held row: a chunk is
    # 2 * tokens rows here (16,384), and none of it moves in one operation.
    for op in ("gather", "scatter"):
        moved = _expert_layer_rows(text, op)
        assert moved and max(moved) <= moe.PIECE_ROWS < 2 * 8 * history, (op, moved)
    # The burst's state keeps the expert kernels as they rest: the transposed
    # layout the input-gradient products want stays inside the layer (without
    # ``moe._if_any`` XLA carries it up into the scan's state, a relayout of
    # every kernel's gradient every step and half as much scratch again).
    kernels = set(re.findall(r"f32\[(?:1,)?4,(?:256,128|128,256)\]\{([\d,]+)", text))
    assert kernels and kernels <= {"2,1,0", "3,2,1,0"}, kernels
    # ISSUE 41: the selection's kernels under the burst's ``vmap`` (128
    # experts: the cell's; with 16 the rounds are XLA's), and no sort or mask
    _selection_is_a_pass(text, cfg.batch_size * history, cfg.trunk_experts_per_tok, 128)
    _plan_sorts_the_held_candidates(text, cfg.batch_size * history, cfg.trunk_experts_per_tok, 4)


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


def _trunk_burst_weight_gradients(devices):
    """The cut burst with the shape rule lowered to its widths (``q_proj`` and
    ``o_proj``, 256 x 1024, taken; ``k_proj`` and ``v_proj``, 256 x 256,
    left): the mechanism engages in the compiled program, at 4.5 MB more of
    the compiler's account of the step's peak."""
    from torch_actor_critic_tpu.models import sequence

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sequence, "OWN_WEIGHT_GRAD_MIN_ELEMENTS", 256 * 1024)
        cfg, _, _, compiled = _compile_trunk_burst(devices)
    hidden, d = cfg.trunk_hidden, cfg.trunk_head_dim
    _weight_gradients_stand_alone(
        compiled.as_text(),
        taken=[(hidden, cfg.trunk_q_heads * d)], left=[(hidden, cfg.trunk_kv_heads * d)],
    )
    # 2,202,822,144 B with the two kernels' gradients and their operands
    # written out (PR 46), against the cut burst's 2,198,294,528 without
    assert compiled.memory_analysis().peak_memory_in_bytes < 2.2035e9


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


def _compile_hybrid_trunk_burst(devices):
    """The ``nemotron_h`` trunk's burst as the benchmark's cell builds it, at
    the cell's own sizes, compiled for the described v5e: ``(cell's
    configuration, abstract state, compiled burst)``."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.drivers import trunkburst
    from benchmark.harness import registry, spans
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    _, cell, config = registry.resolve("nemotron3_super_trunk_burst")
    driver = registry.load_driver(cell["driver"])(
        cell, config, 1, spans.Spans(), {"rehearsal": False}
    )
    cfg, env = driver.sac_config(), trunkburst.Spec(driver.model)
    sac = make_learner(cfg, *build_models(cfg, env), env.act_dim)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=devices[:1]))
    state = jax.eval_shape(sac.init_state, jax.random.key(0), env.example_obs())

    def ring_of(n):
        one = jax.eval_shape(lambda: init_replay_buffer(n, env.obs_spec, env.act_dim).data)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), one
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(data=ring_of(cell["traffic"]["ring_rows"]), ptr=index, size=index)
    chunk = ring_of(cfg.update_every)
    compiled = learner._build_burst(cfg.update_every, state, ring, chunk).lower(
        state, ring, chunk
    ).compile()
    return cfg, state, compiled


def _hybrid_trunk_burst(devices):
    """The ``nemotron_h`` trunk's burst as the benchmark's cell builds it, at
    the cell's own sizes (one period of eleven layers at the published widths,
    this chip's share of heads and experts, 1024 x batch 4, every block
    recomputed): the chunked scan, the flash kernels without q's pass, the
    two-kernel grouped products and the router's top 22 of 512 lower for the
    v5e, and the compiler's account of the step fits the chip."""
    cfg, state, compiled = _compile_hybrid_trunk_burst(devices)
    assert (cfg.trunk_pattern, cfg.trunk_hidden, cfg.trunk_remat) == ("EMEMEMEMEM*", 4096, 11)
    mem = compiled.memory_analysis()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.critic_params))
    assert 566e6 < n_params < 570e6
    # trunk, target and Adam's moments rest in the arguments and are updated in
    # place; the whole step fits the chip's 15.75 GiB as it did before the
    # selection's kernels (read at PR 40: 13.42 GB; at PR 41: see PERF.md)
    assert mem.alias_size_in_bytes >= 16 * n_params
    assert mem.peak_memory_in_bytes < 13.43e9, mem.peak_memory_in_bytes
    text = compiled.as_text()
    kinds = [_kernel_kind(name) for name in _kernels(text)]
    assert "ragged-dot" in text and "qk-rope" not in kinds and len(kinds) >= 3, kinds
    # the expert layer still moves a piece at a time
    for op in ("gather", "scatter"):
        moved = _expert_layer_rows(text, op)
        assert moved and max(moved) <= moe.PIECE_ROWS, (op, moved)
    _selection_is_a_pass(
        text, cfg.batch_size * cfg.history_len, cfg.trunk_experts_per_tok, cfg.trunk_experts
    )
    lo, hi = cfg.trunk_experts_held
    _plan_sorts_the_held_candidates(
        text, cfg.batch_size * cfg.history_len, cfg.trunk_experts_per_tok, hi - lo
    )
    # ISSUE 46, by the rule as it stands: the shared expert's and the
    # state-space projections' weight gradients are products of their own;
    # ``k_proj`` / ``v_proj`` (4096 x 128) keep Adam fused behind theirs
    inner = cfg.trunk_ssm_heads * cfg.trunk_ssm_head_dim
    _weight_gradients_stand_alone(
        text,
        taken=[(cfg.trunk_hidden, cfg.trunk_shared_expert_width), (inner, cfg.trunk_hidden)],
        left=[(cfg.trunk_hidden, cfg.trunk_kv_heads * cfg.trunk_head_dim)],
    )


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


def _trunk_attention_passes(devices):
    """ISSUE 39: one attention layer of ``sdar30b_a3b_trunk_burst``,
    ``x + GroupedQueryAttention(RMSNorm(x))`` at the cell's shapes (8 x 1024
    tokens of 2048, 32 query over 4 key/value heads of 128, ``bf16_dots``),
    forward and gradient. Between ``q_proj`` and the forward kernel q is read
    once and written once (at the parent the norm's reduce, its multiply,
    rotary and the transposing copy: four instructions over 33,554,432
    elements); the row statistics reach the backward kernels as the forward
    kernel and ``delta``'s reduce wrote them (before: two broadcasts to
    ``f32[256,1024,128]`` and a slice out of one); none of the parent's three
    relayouts of a q-sized float32 activation is left in this program (inside
    the burst XLA still relays ``o_proj``'s cotangent twice: ``trunk-burst-ring``);
    and the new pass is no operation of the kind ``trunk.flash_roofline``
    divides by."""
    from flax import linen as nn

    from torch_actor_critic_tpu.models import sequence

    spec = sequence.TrunkSpec()
    batch, history = 8, 1024
    q_elements = batch * history * spec.q_heads * spec.head_dim

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, pos):
            with jax.named_scope(scopes.TRUNK_ATTENTION):
                u = sequence.RMSNorm(spec.rms_eps, name="input_norm")(x)
                return x + sequence.GroupedQueryAttention(spec, name="attention")(u, pos)

    layer, pos = Layer(), jnp.arange(history)
    x = _shape((batch, history, spec.hidden), jnp.float32, devices[0])
    params = _on(devices[0], jax.eval_shape(layer.init, jax.random.key(0), x, pos))

    def forward(params, x):
        return layer.apply(params, x, pos)

    def compile_(fn):
        return jax.jit(fn).lower(params, x).compile()

    forward_text = compile_(forward).as_text()
    gradient = compile_(
        jax.grad(lambda params, x: jnp.sum(forward(params, x) ** 2), (0, 1))
    )
    gradient_text = gradient.as_text()

    # the reader's kind is the flash kernels', and theirs alone
    for text, flash, passes in (
        (forward_text, 1, ["qk-rope"]), (gradient_text, 3, ["qk-rope", "qk-rope-bwd"]),
    ):
        kinds = [_kernel_kind(name) for name in _kernels(text)]
        assert [k for k in kinds if _reads_as_attention(k)] == ["attention"] * flash, kinds
        assert sorted(k for k in kinds if not _reads_as_attention(k)) == passes, kinds
        assert _relayouts_round_the_kernels(
            text, batch, history, spec.q_heads, spec.head_dim
        ) == []

    # what touches an array of q's size ahead of the forward kernel: the pass
    entry, ops_of = _entry(forward_text)
    kernel = next(
        n for n in entry
        if _reads_as_attention(_kernel_kind(n)) and entry[n][1] == "custom-call"
    )
    ahead, stack = set(), [kernel]
    while stack:
        for operand in entry.get(stack.pop(), ((), "", (), None))[2]:
            if operand in entry and operand not in ahead:
                ahead.add(operand)
                stack.append(operand)
    passes_over_q = []
    for name in ahead:
        result, op, operands, callee = entry[name]
        shapes = result + [s for o in operands if o in entry for s in entry[o][0]]
        product = callee is not None and "convolution" in ops_of.get(callee, ())
        if (
            op not in ("bitcast", "get-tuple-element", "parameter", "tuple")
            and not product
            and any(_elements(s) == q_elements for s in shapes)
        ):
            passes_over_q.append(name)
    assert [_kernel_kind(n) for n in passes_over_q] == ["qk-rope"], passes_over_q

    # no slice out of a lane-wide copy of the row statistics either
    lane_wide = f"f32[{batch * spec.q_heads},{history},128]"
    shape_of = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", gradient_text))
    slices = [
        m for m in re.findall(r" slice\(%([\w.\-]+)", gradient_text)
        if shape_of.get(m) == lane_wide
    ]
    assert slices == [], slices
    # the gradient's program: 1,214,467,072 B at the parent by the compiler's
    # account, 941,444,608 with the pass
    assert gradient.memory_analysis().peak_memory_in_bytes < 1.0e9


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


# What may carry a whole ring leaf through a program without passing over
# it: names for a buffer, and an update in place.
_NO_PASS = {
    "parameter", "get-tuple-element", "tuple", "while", "bitcast",
    "dynamic-update-slice",
}


def _whole_leaf_passes(hlo_text, rows):
    """The instructions of an optimized program (fused ones too) whose
    result has a dimension of at least ``rows``, a ring's row count, and
    that read or write all of it: everything but parameters, tuples and
    their elements, loops, bitcasts and in-place updates (a
    ``dynamic-update-slice``, alone or as the root of a fusion)."""
    root_of, name = {}, None
    for line in hlo_text.splitlines():
        header = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if header:
            name = header.group(1)
        root = re.match(r"\s*ROOT .*? = .*? ([a-z][a-z0-9\-]*)\(", line)
        if root and name is not None:
            root_of[name] = root.group(1)
    found = []
    for line in hlo_text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][a-z0-9\-]*)\((.*)", line
        )
        if not m:
            continue
        result, op, rest = m.groups()
        shapes = [
            [int(d) for d in dims.split(",") if d]
            for dims in re.findall(r"\[([\d,]*)\]", result)
        ]
        largest = max(shapes, key=lambda dims: np.prod(dims), default=[])
        if not largest or max(largest) < rows or op in _NO_PASS:
            continue
        if op.endswith("-start"):  # holds its operand too; its -done is judged
            continue
        callee = re.search(r"calls=%?([\w.\-]+)", rest)
        if op == "fusion" and callee and (
            root_of.get(callee.group(1)) == "dynamic-update-slice"
        ):
            continue
        found.append((f"{op} {result.split('{')[0]}", int(np.prod(largest))))
    return found


def _visual_burst_passes_over_no_frame_leaf(devices):
    """ISSUE 30: ``wallrunner_cnn_burst``'s burst at the cell's sizes. The
    frame ring rests tile by tile (``buffer/replay.py::stored_row_shape``):
    push and gather work on it as it rests and nothing passes over a frame
    leaf (two copies a window before: 98% of the ring's elements). What
    still passes over a leaf, as it did: the feature and action leaves on
    their way to the gather (bfloat16, row-major) and the prefetch of the
    scalar ones, 2% of the ring (PERF.md section 7)."""
    from benchmark.drivers import _common
    from benchmark.harness import registry
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    _, cell, config = registry.resolve("wallrunner_cnn_burst")
    rows = cell["traffic"]["ring_rows"]
    cfg = _common.sac_config(config, cell)
    env = _common.EnvSpec(config["model"])
    sac = make_learner(cfg, *build_models(cfg, env), env.act_dim)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=devices[:1]))
    state = jax.eval_shape(sac.init_state, jax.random.key(0), env.example_obs())

    def on_dp(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), tree
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(
        data=on_dp(jax.eval_shape(
            lambda: init_replay_buffer(rows, env.obs_spec, env.act_dim).data
        )),
        ptr=index, size=index,
    )
    frame = ring.data.states.frame
    assert frame.shape == (1, rows, 96, 128)
    # The chunk as the Trainer stages it: rows in a transition's shape.
    # (One in the stored shape hides the fault: the compiler carries a
    # picture's own layout through push's reshape onto the ring.)
    n = cfg.update_every
    obs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((n,) + tuple(x.shape), x.dtype),
        env.obs_spec,
    )
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    chunk = on_dp(Batch(
        states=obs, actions=f32((n, env.act_dim)), rewards=f32((n,)),
        next_states=obs, done=f32((n,)),
    ))
    compiled = learner._build_burst(n, state, ring, chunk).lower(
        state, ring, chunk
    ).compile()
    passes = _whole_leaf_passes(compiled.as_text(), rows)
    assert [name for name, size in passes if size >= frame.size // 4] == []
    ring_elements = sum(x.size for x in jax.tree_util.tree_leaves(ring.data))
    assert sum(size for _, size in passes) < 0.025 * ring_elements
    # the two padded frame copies were 9.4 GiB of scratch
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def _population_epoch_keeps_its_three_passes(devices):
    """ISSUE 30: ``cheetah_pop32_fused``'s epoch at the cell's sizes. The
    population's rings rest as before: each of the three leaves wider than
    a scalar is still converted to bfloat16 and relaid once a window; no
    other pass may join those (PERF.md section 7 says what was tried)."""
    from benchmark.drivers import _common
    from benchmark.harness import registry
    from torch_actor_critic_tpu.envs.ondevice import get_on_device_env
    from torch_actor_critic_tpu.sac.ondevice import (
        PopulationOnDeviceLoop,
        _wrap_and_build,
    )

    _, cell, config = registry.resolve("cheetah_pop32_fused")
    traffic = cell["traffic"]
    cfg = _common.sac_config(config, cell)
    env_cls, sac = _wrap_and_build(get_on_device_env(traffic["env"]), cfg)
    loop = PopulationOnDeviceLoop(
        sac, env_cls, n_members=cfg.population, n_envs=traffic["n_envs"]
    )
    state, ring, envs, keys, _ = jax.eval_shape(
        lambda k: loop.init(k, buffer_capacity=traffic["ring_rows"]),
        jax.random.key(0),
    )
    compiled = loop._build_epoch(
        traffic["steps_per_dispatch"], cfg.update_every, False
    ).lower(*_on(devices[0], (state, ring, envs, keys))).compile()
    passes = _whole_leaf_passes(compiled.as_text(), traffic["ring_rows"])
    assert sorted(name for name, _ in passes) == [
        "copy bf16[32,1000000,17]", "copy bf16[32,1000000,17]",
        "copy bf16[32,1000000,6]",
    ]


def _push_in_place(devices, make_ring, members, rows):
    """``jit(vmap(push), donate_argnums=0)`` alone passes over no ring
    leaf: a relayout of one reads and writes 200% of its bytes (the
    scatter this replaced: 500-700% accessed, 94-143% temp, PERF.md PR
    25), the contiguous write touches the two windows' tiles, whatever
    the ring's size."""
    ring = jax.eval_shape(
        lambda: jax.vmap(lambda _: make_ring())(jnp.arange(members))
    )
    compiled = jax.jit(jax.vmap(push), donate_argnums=0).lower(
        *_on(devices[0], (ring, _chunk_of(ring, rows)))
    ).compile()
    ring_bytes = nbytes(ring.data)
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= ring_bytes, "updated in place"
    assert mem.temp_size_in_bytes < 0.01 * ring_bytes
    assert cost["bytes accessed"] < 0.05 * ring_bytes
    text = compiled.as_text()
    assert " scatter(" not in text and " gather(" not in text


def _population_programs(devices, members):
    """The population burst and the fused population epoch at the
    reference configuration: neither holds a scatter over a ring."""
    from torch_actor_critic_tpu.envs.ondevice import get_on_device_env
    from torch_actor_critic_tpu.parallel.population import PopulationLearner
    from torch_actor_critic_tpu.sac.ondevice import (
        PopulationOnDeviceLoop,
        _wrap_and_build,
    )

    cfg = SACConfig()
    env_cls, sac = _wrap_and_build(get_on_device_env("cheetah-run-jax"), cfg)
    loop = PopulationOnDeviceLoop(sac, env_cls, n_members=members, n_envs=16)
    state, ring, env_states, act_keys, _ = jax.eval_shape(
        lambda: loop.init(jax.random.key(0), cfg.buffer_size)
    )
    chunk = _chunk_of(ring, cfg.update_every)
    burst = PopulationLearner(sac, members)._build_burst(cfg.update_every)
    epoch = loop._build_epoch(2 * cfg.update_every, cfg.update_every, False)
    for program, args in (
        (burst, (state, ring, chunk)),
        (epoch, (state, ring, env_states, act_keys)),
    ):
        compiled = program.lower(*_on(devices[0], args)).compile()
        assert compiled.memory_analysis().alias_size_in_bytes >= nbytes(ring)
        assert _ring_scatters(compiled.as_text(), cfg.buffer_size) == []


def _reference_burst(devices, dp):
    """The main path's program at the reference configuration: 50
    update steps, batch 64, (256, 256), a 1,000,000-slot ring, state
    and ring donated — through the same ``DataParallelSAC`` builder the
    trainer uses, on a mesh of described chips."""
    cfg = SACConfig()
    assert (cfg.hidden_sizes, cfg.batch_size, cfg.update_every) == (
        (256, 256), 64, 50
    )
    assert cfg.buffer_size == 1_000_000
    sac = SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=cfg.hidden_sizes),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        ACT_DIM,
    )
    learner = DataParallelSAC(sac, make_mesh(dp=dp, devices=devices[:dp]))
    state = jax.eval_shape(
        sac.init_state, jax.random.key(0), jnp.zeros((OBS_DIM,))
    )

    def rows(n):
        f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
        return Batch(
            states=f32((dp, n, OBS_DIM)), actions=f32((dp, n, ACT_DIM)),
            rewards=f32((dp, n)), next_states=f32((dp, n, OBS_DIM)),
            done=f32((dp, n)),
        )

    ring = BufferState(
        data=rows(cfg.buffer_size // dp),
        ptr=jax.ShapeDtypeStruct((dp,), jnp.int32),
        size=jax.ShapeDtypeStruct((dp,), jnp.int32),
    )
    chunk = rows(cfg.update_every)
    compiled = learner._build_burst(
        cfg.update_every, state, ring, chunk
    ).lower(state, ring, chunk).compile()
    mem = compiled.memory_analysis()
    ring_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(ring)
    )
    # Donation holds: the ring (and the state) come back in the buffers
    # they arrived in, so a device keeps one copy of its shard.
    assert mem.alias_size_in_bytes >= ring_bytes // dp
    text = compiled.as_text()
    assert ("all-reduce" in text) == (dp > 1), "pmean over dp"
    assert _ring_scatters(text, cfg.buffer_size // dp) == []


CASES = [
    pytest.param(_flash, (shape, dtype), id=f"flash-{name}-{dtype.__name__}")
    for name, shape in (("2k", (4, 8, 2048, 64)), ("seq", (64, 4, 8, 16)))
    for dtype in (jnp.float32, jnp.bfloat16)
] + [
    pytest.param(
        _flash, ((4, 8, 2048, 64), jnp.bfloat16, 64), id="flash-2k-lanes64"
    ),
] + [
    pytest.param(
        _pixel, (batch, dtype, shift),
        id=f"pixel-b{batch}-{dtype.__name__}-{'shift' if shift else 'plain'}",
    )
    for batch in (32, 512)
    for dtype in (jnp.float32, jnp.bfloat16)
    for shift in (False, True)
] + [
    pytest.param(_pixel, (32, jnp.bfloat16, True, 3), id="pixel-stack3"),
    pytest.param(_reference_burst, (1,), id="update-burst"),
    pytest.param(_reference_burst, (4,), id="dp4-burst"),
    pytest.param(
        _push_in_place,
        (
            functools.partial(
                init_replay_buffer, 1_000_000,
                jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM,
            ),
            32, 800,
        ),
        id="push-pop32-1M-rows",
    ),
    pytest.param(
        _push_in_place,
        (
            functools.partial(
                init_visual_replay_buffer, 200_000, 168,
                WALL_RUNNER_RING[1:], 56,
            ),
            1, 50,
        ),
        id="push-frames-200k-rows",
    ),
    pytest.param(_population_programs, (8,), id="population-burst-and-epoch"),
    pytest.param(_flash_grouped, (1,), id="flash-grouped-causal"),
    pytest.param(_flash_grouped, (4,), id="flash-grouped-block4"),
    pytest.param(_flash_window, (), id="flash-window-512-of-4096"),
    pytest.param(_trunk_burst, (), id="trunk-burst-ring"),
    pytest.param(_trunk_burst_weight_gradients, (), id="trunk-burst-own-weight-gradients"),
    pytest.param(_trunk_attention_passes, (), id="trunk-attention-passes"),
    pytest.param(_hybrid_trunk_burst, (), id="hybrid-trunk-burst-at-size"),
    pytest.param(
        _visual_burst_passes_over_no_frame_leaf, (),
        id="no-whole-leaf-pass-visual-burst",
    ),
    pytest.param(
        _population_epoch_keeps_its_three_passes, (),
        id="no-whole-leaf-pass-population-epoch",
    ),
]


@pytest.mark.parametrize("compile_case, args", CASES)
def test_compiles_for_v5e(v5e, compile_case, args):
    compile_case(v5e, *args)
