"""The chip's compiler, asked in the sandbox (``test_chip_compile.py`` says
how): the SDAR trunk's burst over the cell's ring, the trunk itself at a cut
width, and one attention layer at the cell's shapes, compiled for the
described v5e; what their compiled text holds of kernels, relayouts, sorts
and weight-gradient fusions."""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile_helpers import (  # noqa: F401  (``v5e`` and ``chip_compiler`` are fixtures)
    ACT_DIM,
    OBS_DIM,
    _as_large_as,
    _elements,
    _entry,
    _expert_layer_rows,
    _kernel_kind,
    _kernels,
    _on,
    _plan_sorts_the_held_candidates,
    _reads_as_attention,
    _relayouts_round_the_kernels,
    _selection_is_a_pass,
    _shape,
    _weight_gradients_stand_alone,
    chip_compiler,
    v5e,
)

from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
from torch_actor_critic_tpu.core.types import BufferState
from torch_actor_critic_tpu.ops import moe
from torch_actor_critic_tpu.parallel import DataParallelSAC, make_mesh
from torch_actor_critic_tpu.telemetry import scopes
from torch_actor_critic_tpu.utils.config import SACConfig

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


CASES = [
    pytest.param(_trunk_burst, (), id="trunk-burst-ring"),
    pytest.param(_trunk_burst_weight_gradients, (), id="trunk-burst-own-weight-gradients"),
    pytest.param(_trunk_attention_passes, (), id="trunk-attention-passes"),
]


@pytest.mark.parametrize("compile_case, args", CASES)
def test_compiles_for_v5e(v5e, compile_case, args):
    compile_case(v5e, *args)
