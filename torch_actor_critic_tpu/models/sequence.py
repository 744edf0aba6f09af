"""Sequence (transformer) policies and critics over observation histories.

A capability **extension** — the reference's models are feedforward over
fixed-width observation vectors with no sequence axis anywhere
(SURVEY.md §5 "Long-context: absent by construction"). These modules
give the framework a long-context policy class for partially-observable
tasks: a causal transformer encoder over the last ``T`` observations,
with the same squashed-Gaussian head as the MLP actor (ref
``networks/linear.py:39-51`` math, shared via
:mod:`torch_actor_critic_tpu.ops.distributions`), so a
``SequenceActor`` drops into the SAC losses wherever ``Actor`` does.

Designed for the distributed path from the start: the trunk takes a
``pos_offset`` (global position of this device's local chunk) and an
injectable ``attention_fn``, which is exactly the surface
:mod:`torch_actor_critic_tpu.parallel.context` needs to run the same
module under ``shard_map`` with ring attention over an ``sp`` mesh axis.
"""

from __future__ import annotations

import dataclasses
import math
import typing as t

import jax
import jax.numpy as jnp
from flax import linen as nn

from torch_actor_critic_tpu.models.mlp import Dense, torch_linear_kernel_init
from torch_actor_critic_tpu.ops import moe, ssm
from torch_actor_critic_tpu.ops.attention import (
    Rope,
    attention as sdpa,
    qk_norm_rope,
    rms_norm,
    rotary,
)
from torch_actor_critic_tpu.ops.distributions import squashed_gaussian_sample
from torch_actor_critic_tpu.telemetry import scopes

# attention_fn(q, k, v, causal) -> out, all (batch, heads, seq, head_dim)
AttentionFn = t.Callable[..., jax.Array]


def _auto_batch(obs_seq: jax.Array, *rest: jax.Array):
    """Add a leading batch axis to an unbatched ``(T, D)`` history (and
    companion arrays), like the visual stack's auto-reshape (ref
    ``convolutional.py:91-96``). Returns ``(unbatched, obs_seq, *rest)``."""
    unbatched = obs_seq.ndim == 2
    if unbatched:
        obs_seq = obs_seq[None]
        rest = tuple(x[None] for x in rest)
    return (unbatched, obs_seq, *rest)


def default_attention(q, k, v, causal=True, **mask):
    """``mask``: ``block_length`` / ``bf16_dots`` / ``window`` of
    :func:`ops.attention.attention` (a decoder block passes them, ``window`` on
    a sliding layer alone; the transformer block passes none)."""
    return sdpa(q, k, v, causal=causal, **mask)


def _sp_pos_offset(obs_seq: jax.Array, sp_axis: str | None):
    """Global position of this device's chunk start: 0 single-device;
    ``axis_index(sp) * T_local`` when the sequence axis is sharded."""
    if sp_axis is None:
        return 0
    return jax.lax.axis_index(sp_axis) * obs_seq.shape[1]


def _sp_last_token(h: jax.Array, sp_axis: str | None, sp_size: int):
    """The representation of the *global* last timestep.

    Single-device: ``h[:, -1]``. Under sequence sharding the global last
    token lives on the final ``sp`` device; a masked ``psum`` broadcasts
    it to every device so downstream heads/losses are replicated over
    ``sp`` (same gather the acting path uses,
    ``parallel/context.py``)."""
    last = h[:, -1]
    if sp_axis is None:
        return last
    idx = jax.lax.axis_index(sp_axis)
    masked = jnp.where(idx == sp_size - 1, last, jnp.zeros_like(last))
    return jax.lax.psum(masked, sp_axis)


def xla_attention(q, k, v, causal=True, **mask):
    """Backend-portable attention (no Pallas): for modules that must
    compile on the host CPU while TPU is the default backend, e.g. the
    trainer's host actor mirror."""
    return sdpa(q, k, v, causal=causal, impl="xla", **mask)


class MultiHeadAttention(nn.Module):
    """Causal MHA with a pluggable attention kernel."""

    num_heads: int
    attention_fn: AttentionFn = default_attention
    dtype: t.Any = jnp.float32  # projection compute dtype; params stay f32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dtype = self.dtype
        b, s, d_model = x.shape
        assert d_model % self.num_heads == 0, (d_model, self.num_heads)
        head_dim = d_model // self.num_heads

        def split(y):  # (B, T, D) -> (B, H, T, d)
            return y.reshape(b, s, self.num_heads, head_dim).transpose(0, 2, 1, 3)

        # Megatron attention pairing: q/k/v projections column-parallel
        # (equivalently: heads sharded over tp), output projection
        # row-parallel — one psum per attention block under tp.
        # The attention kernels accumulate in f32 regardless of input
        # dtype (see ops/attention.py), so bf16 q/k/v is safe.
        q = split(Dense(d_model, tp_role="col", dtype=dtype)(x))
        k = split(Dense(d_model, tp_role="col", dtype=dtype)(x))
        v = split(Dense(d_model, tp_role="col", dtype=dtype)(x))
        out = self.attention_fn(q, k, v, causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, d_model)
        return Dense(d_model, tp_role="row", dtype=dtype)(out)


class TransformerBlock(nn.Module):
    """Pre-LN block: LN → MHA → residual, LN → GELU MLP → residual."""

    num_heads: int
    mlp_ratio: int = 4
    attention_fn: AttentionFn = default_attention
    dtype: t.Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dtype = self.dtype
        d_model = x.shape[-1]
        # LayerNorm statistics stay float32 (flax upcasts internally);
        # its output is cast to the compute dtype by the next Dense.
        x = x + MultiHeadAttention(
            self.num_heads, self.attention_fn, dtype=dtype
        )(nn.LayerNorm()(x))
        h = nn.LayerNorm()(x)
        h = Dense(self.mlp_ratio * d_model, tp_role="col", dtype=dtype)(h)
        h = nn.gelu(h)
        h = Dense(d_model, tp_role="row", dtype=dtype)(h)
        return x + h


# A layer's kind, one letter of ``TrunkSpec.pattern``
# (``utils/config.py::TRUNK_LAYER_KINDS`` says each in words).
SDAR_BLOCK = "S"  # two sublayers a block: attention, then sparse experts
STATE_SPACE = "M"  # one Mamba-2 mixer
ATTENTION = "*"  # one attention mixer
EXPERTS = "E"  # one expert mixer
# Blocks of two sublayers in a stack that mixes attention kinds (``laguna``):
# full or sliding-window attention, then sparse experts (capital) or a dense
# gated feed-forward (small).
FULL_EXPERTS, WINDOW_EXPERTS = "F", "W"
FULL_DENSE, WINDOW_DENSE = "f", "w"
# (sets: the empty kind of a layer that names none is in no set, as it is in every string)
TWO_SUBLAYERS = frozenset((SDAR_BLOCK, FULL_EXPERTS, WINDOW_EXPERTS, FULL_DENSE, WINDOW_DENSE))
WINDOWED = frozenset((WINDOW_EXPERTS, WINDOW_DENSE))
DENSE_FFN = frozenset((FULL_DENSE, WINDOW_DENSE))


@dataclasses.dataclass(frozen=True)
class TrunkSpec:
    """A published decoder stack as the shared history trunk: its layers as a
    string of kinds (``pattern``), one set of widths for them, and what this
    chip holds of each (``SACConfig.trunk_*``).

    ``pattern`` is one letter a layer (the kinds above); left empty it is ``layers``
    SDAR blocks. Which family sets what: SDAR-30B-A3B (``sdar_moe``) is
    ``"S" * layers`` with the defaults below; ``nemotron_h`` is a string of
    ``M``, ``*`` and ``E`` with ``qk_norm_rope`` off, sigmoid routing, plain
    ``relu2`` experts in a latent width beside a shared expert, and the
    ``ssm_*`` sizes; ``laguna`` is a string of ``F``, ``W``, ``f`` and ``w``
    with ``qk_norm`` off (rotary without the per-head norm), ``head_gate``,
    ``dense_width``, softmax routing times ``routed_scale`` beside a shared
    expert, and what differs by attention kind: a sliding layer's ``window``,
    its ``window_q_heads`` and ``window_rope_theta`` over the whole head, a
    full layer's ``q_heads`` and ``rope_theta`` over ``rope_share`` of the
    head under YaRN (``rope_yarn_factor``, ``rope_yarn_positions``). A chip's
    share of a layer is in the counts: ``experts_held`` of ``experts`` (the
    router looks at all), ``q_heads`` / ``window_q_heads`` / ``kv_heads`` and
    ``ssm_heads`` / ``ssm_groups`` as many as are held (no mixer reads a
    head's index)."""

    hidden: int = 2048
    pattern: str = ""
    q_heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    layers: int = 4
    experts: int = 128
    experts_per_tok: int = 8
    expert_width: int = 768
    experts_held: t.Tuple[int, int] = (0, 16)
    block_length: int = 4
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    remat: int = 0  # the first n blocks are recomputed in the backward pass
    # float32 operands of the kernels' products (flash attention, grouped
    # expert products, the scan's) rounded to bfloat16: the TPU's default
    # precision.
    bf16_dots: bool = True
    qk_norm_rope: bool = True  # per-head norm and rotary positions on q and k
    router: str = "softmax"  # or "sigmoid", chosen by score plus a bias
    routed_scale: float = 1.0  # the scale of the chosen experts' renormalised weights
    expert_form: str = "silu_gated"  # ops.moe.FORMS
    expert_latent: int = 0  # the width the routed experts work in; 0: hidden
    shared_expert_width: int = 0  # an expert every token passes; 0: none
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    qk_norm: bool = True  # with qk_norm_rope: the per-head norm before rotary
    head_gate: bool = False  # a sigmoid gate a head on attention's output
    dense_width: int = 0  # the dense gated feed-forward of an "f" or "w" block
    # By attention kind: a sliding layer ("W", "w") sees the ``window`` latest
    # positions, holds ``window_q_heads`` query heads (0: ``q_heads``) and
    # rotates the whole head by ``window_rope_theta`` (0: ``rope_theta``).
    window: int = 0
    window_q_heads: int = 0
    window_rope_theta: float = 0.0
    # Every other attention layer: ``rope_theta`` over the first ``rope_share``
    # of the head, under YaRN where ``rope_yarn_factor`` is above 1
    # (:class:`ops.attention.Rope`; YaRN's betas and its scale of cosine and
    # sine are that module's constants).
    rope_share: float = 1.0
    rope_yarn_factor: float = 1.0
    rope_yarn_positions: int = 0

    @classmethod
    def from_config(cls, config) -> "TrunkSpec":
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(**{n: getattr(config, "trunk_" + n) for n in names})

    @property
    def kinds(self) -> str:
        return self.pattern or SDAR_BLOCK * self.layers

    def q_heads_of(self, kind: str) -> int:
        return (kind in WINDOWED and self.window_q_heads) or self.q_heads

    def window_of(self, kind: str) -> int | None:
        return self.window if kind in WINDOWED else None

    def rope_of(self, kind: str) -> float | Rope:
        """One ``theta`` over the whole head where that is all, else what
        the layer's kind rotates by."""
        if kind in WINDOWED:
            return self.window_rope_theta or self.rope_theta
        if self.rope_share == 1.0 and self.rope_yarn_factor <= 1.0:
            return self.rope_theta
        return Rope(
            self.rope_theta, self.rope_share, self.rope_yarn_factor, self.rope_yarn_positions
        )


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis, the
    statistics in float32."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, weight, self.eps)


class HeadNormRope(nn.Module):
    """A projection's heads on their way to the kernels, ``(B, T, heads, d)``
    to ``(B, heads, T, d)``: :class:`RMSNorm` over a head (the same ``weight``
    ``(d,)`` under the same name), rotary at the positions ``pos`` and the
    heads' transposition, as one function
    (:func:`ops.attention.qk_norm_rope`: ``impl`` chooses its one pass each
    way on a TPU or the three composed)."""

    eps: float
    theta: float
    impl: str = "auto"

    @nn.compact
    def __call__(self, y: jax.Array, pos: jax.Array) -> jax.Array:
        weight = self.param("weight", nn.initializers.ones, (y.shape[-1],))
        return qk_norm_rope(y, weight, pos, self.theta, self.eps, self.impl)


# A projection's kernel of this many elements or more has its weight gradient
# computed as a product of its own (:class:`OwnWeightGrad`); under it the
# barriers cost a write and a read of the operands and the gradient and buy
# nothing. Set by one sweep on the chip in the three trunk cells (PERF.md
# section 6, PR 46): halfway between the largest kernel that lost by it
# (2,097,152 elements, a 4096 x 512 ``q_proj``) and the smallest that gained
# (3,145,728, a 3072 x 1024 ``shared_up``).
OWN_WEIGHT_GRAD_MIN_ELEMENTS = 5 << 19


@jax.custom_vjp
def _project(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``x @ kernel`` over ``x``'s last axis, as ``nn.Dense`` makes it."""
    return jax.lax.dot_general(x, kernel, (((x.ndim - 1,), (0,)), ((), ())))


def _project_fwd(x, kernel):
    return _project(x, kernel), (x, kernel)


def _project_bwd(residuals, dy):
    """The input's cotangent as ``nn.Dense``'s transpose makes it, left to
    XLA; the kernel's gradient the same product of the same values (tokens
    contracted, default precision) between two barriers: XLA computes
    neither operand inside the product's tiles (the normed input, the gated
    pre-activation's cotangent: elementwise producers it would recompute for
    every tile that asks) and fuses no optimizer pass (Adam's moments, the
    parameter, the polyak target) behind it."""
    x, kernel = residuals
    dx = jax.lax.dot_general(dy, kernel, (((dy.ndim - 1,), (1,)), ((), ())))
    tokens = tuple(range(x.ndim - 1))
    x, dy = jax.lax.optimization_barrier((x, dy))
    dkernel = jax.lax.dot_general(x, dy, ((tokens, tokens), ((), ())))
    return dx, jax.lax.optimization_barrier(dkernel)


_project.defvjp(_project_fwd, _project_bwd)


class OwnWeightGrad(nn.Module):
    """``nn.Dense``'s product (its ``dot_general_cls``), whose backward pass
    gives a kernel of :data:`OWN_WEIGHT_GRAD_MIN_ELEMENTS` or more its
    gradient as a product of its own (:func:`_project_bwd`); a smaller kernel
    keeps ``lax.dot_general`` and its transpose. Forward the two are the same
    product. Sows the kernel's element count into the ``weight_grads``
    collection under ``"own"`` or ``"xla"``, by which backward pass it got,
    for whoever applies the trunk with that collection mutable (the name is
    the collection's structure, so it outlives a block's ``nn.remat``, which
    hands values back traced)."""

    def __call__(self, x, kernel, dimension_numbers, precision=None):
        own = kernel.size >= OWN_WEIGHT_GRAD_MIN_ELEMENTS
        if not self.is_initializing():  # ``init`` hands back what it did: the parameters
            self.sow("weight_grads", "own" if own else "xla", kernel.size)
        if own:
            return _project(x, kernel)
        return jax.lax.dot_general(x, kernel, dimension_numbers, precision=precision)


def _linear(features: int, dtype, name: str) -> nn.Dense:
    """A projection with no bias (the published layer has none anywhere)."""
    return nn.Dense(
        features, use_bias=False, kernel_init=torch_linear_kernel_init,
        dtype=dtype, param_dtype=jnp.float32, name=name,
        dot_general_cls=OwnWeightGrad,
    )


def _expert_kernel_init(key, shape, dtype=jnp.float32):
    """Uniform ``+-1/sqrt(fan_in)`` for ``(experts, fan_in, fan_out)``
    kernels: the leading expert axis is no fan-in."""
    bound = 1.0 / math.sqrt(shape[-2])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class GroupedQueryAttention(nn.Module):
    """``W_o [g * Attn(rope(norm(W_q u)), rope(norm(W_k u)), W_v u)]`` with
    the layer kind's query heads reading ``kv_heads`` shared key/value heads
    under the block-causal mask (``block_length`` 1: the causal one), inside
    the kind's window if it has one. Which family sets what: ``sdar_moe``
    norms and rotates q and k (``qk_norm_rope``, ``qk_norm``); ``nemotron_h``
    turns ``qk_norm_rope`` off and hands ``W_q u`` and ``W_k u`` to the
    kernels as they are, no norm, no positions; ``laguna`` turns ``qk_norm``
    off alone (rotary by the layer's kind, :meth:`TrunkSpec.rope_of`, and no
    norm), takes head count and window by ``kind`` and multiplies each
    head's output by ``g = sigmoid(W_g u)``, one gate a head
    (``head_gate``)."""

    spec: TrunkSpec
    attention_fn: AttentionFn = default_attention
    dtype: t.Any = jnp.float32
    kind: str = ""  # the layer's letter: head count, window and rotary by kind

    @nn.compact
    def __call__(self, u: jax.Array, pos: jax.Array) -> jax.Array:
        sp, dtype = self.spec, self.dtype
        b, s, _ = u.shape
        d, heads = sp.head_dim, sp.q_heads_of(self.kind)
        q = _linear(heads * d, dtype, "q_proj")(u).reshape(b, s, heads, d)
        k = _linear(sp.kv_heads * d, dtype, "k_proj")(u).reshape(b, s, sp.kv_heads, d)
        v = _linear(sp.kv_heads * d, dtype, "v_proj")(u).reshape(b, s, sp.kv_heads, d)
        # (batch, heads, seq, d) for the kernels. The kernels reading (batch,
        # seq, heads, d) in place, a head as a column block of their index
        # maps, measured slower on the v5e (PR 26's builder; the figure is no
        # longer on record), so the heads are transposed. A spec with
        # qk_norm_rope (SDAR's) does it for q, whose bytes are eight times
        # k's, in the one pass that norms and rotates it: one kernel over
        # q_proj's output and one back, wherever the attention_fn is the one
        # that reaches the flash kernels (the host mirror's xla_attention
        # keeps every kernel off a program compiled for the CPU). k stays
        # composed: XLA keeps what it writes itself in fast memory for the
        # kernels, which read each key block many times, a kernel's output it
        # does not, and the step measured slower with k in the pass (PERF.md
        # section 6, PR 39). A spec without it (nemotron_h's) has no such
        # pass to ride in: q and k are transposed as v is, and so are
        # laguna's behind their rotary, which XLA composes.
        if sp.qk_norm_rope and not sp.qk_norm:
            rope = sp.rope_of(self.kind)
            q = rotary(q, pos, rope).transpose(0, 2, 1, 3)
            k = rotary(k, pos, rope).transpose(0, 2, 1, 3)
        elif sp.qk_norm_rope:
            kernels = self.attention_fn is default_attention
            q = HeadNormRope(
                sp.rms_eps, sp.rope_theta, "auto" if kernels else "xla", name="q_norm"
            )(q, pos)
            k = HeadNormRope(sp.rms_eps, sp.rope_theta, "xla", name="k_norm")(k, pos)
        else:
            q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
        window = sp.window_of(self.kind)
        out = self.attention_fn(
            q, k, v.transpose(0, 2, 1, 3), causal=True,
            block_length=sp.block_length,
            # float32 tiles, one bfloat16 pass on the MXU: the TPU's default
            # precision for a float32 product, inside the kernels too.
            bf16_dots=sp.bf16_dots and dtype == jnp.float32,
            # a sliding layer alone names its window: an attention_fn that
            # knows none (the ring's) refuses it and never drops it
            **({} if window is None else {"window": window}),
        )
        # Back to (batch, seq, heads * d) for o_proj: XLA folds this
        # transposition into the product going forward and pays two relayouts
        # of the cotangent coming back; a kernel of our own that turned the
        # cotangent and wrote delta in one pass measured 1.7% slower for the
        # whole step (XLA then keeps less of the step in fast memory).
        out = out.transpose(0, 2, 1, 3)
        if sp.head_gate:
            with jax.named_scope(scopes.TRUNK_ATTENTION_GATE):
                gate = jax.nn.sigmoid(_linear(heads, dtype, "g_proj")(u))
                out = out * gate[..., None].astype(out.dtype)
        return _linear(sp.hidden, dtype, "o_proj")(out.reshape(b, s, heads * d))


class SparseMoE(nn.Module):
    """The sparse-expert feed-forward, this chip's share (:mod:`ops.moe`),
    in the form the spec gives: routing, the experts' form and the width
    they work in (``expert_latent``: a projection down before them and up
    after, whole on every chip), and a shared expert on the full width
    beside them (whole on every chip too).

    Sows ``sizes`` (tokens of every held expert) and ``choices`` (every
    token's chosen experts, of all) into the ``moe_stats`` collection for
    whoever applies the trunk with that collection mutable.

    ``selection`` is the ``impl`` of the router's selection
    (:func:`ops.moe.top_scores`): its kernels on a TPU, or ``"xla"`` from a
    block whose ``attention_fn`` keeps every kernel off the program (the
    host mirror's, compiled for the CPU beside a TPU)."""

    spec: TrunkSpec
    dtype: t.Any = jnp.float32
    selection: str = "auto"

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        sp = self.spec
        hidden = u.shape[-1]
        lo, hi = sp.experts_held
        x = u.reshape(-1, hidden)
        width = sp.expert_latent or hidden  # what the routed experts read and write
        w_router = self.param(
            "router", torch_linear_kernel_init, (hidden, sp.experts)
        )
        going_in = ("w_gate", "w_up") if sp.expert_form == "silu_gated" else ("w_up",)
        kernels = {
            name: self.param(name, _expert_kernel_init, (hi - lo, width, sp.expert_width))
            for name in going_in
        }
        w_down = self.param(
            "w_down", _expert_kernel_init, (hi - lo, sp.expert_width, width)
        )
        with jax.named_scope(scopes.TRUNK_MOE_ROUTE):
            bias = None
            if sp.router != "softmax":
                # Moves the choice alone, so no gradient reaches it: it is a
                # parameter that training by gradient leaves where it was.
                bias = self.param("router_bias", nn.initializers.zeros, (sp.experts,))
            top_e, top_w = moe.route(
                x, w_router, sp.experts_per_tok, sp.router, bias, sp.routed_scale,
                self.selection,
            )
        v = x
        if sp.expert_latent:
            with jax.named_scope(scopes.TRUNK_MOE_LATENT):
                v = _linear(width, self.dtype, "latent_down")(x)
        with jax.named_scope(scopes.TRUNK_MOE_EXPERTS):
            y, plan = moe.expert_ffn(
                v, kernels.get("w_gate"), kernels["w_up"], w_down, top_e, top_w,
                (lo, hi), num_experts=sp.experts, bf16_dots=sp.bf16_dots,
                form=sp.expert_form,
            )
        if sp.expert_latent:
            with jax.named_scope(scopes.TRUNK_MOE_LATENT):
                y = _linear(hidden, self.dtype, "latent_up")(y)
        if sp.shared_expert_width:
            with jax.named_scope(scopes.TRUNK_MOE_SHARED):
                pre = [
                    _linear(sp.shared_expert_width, self.dtype, "shared_" + name[2:])(x)
                    for name in going_in
                ]
                y = y + _linear(hidden, self.dtype, "shared_down")(
                    moe.FORMS[sp.expert_form](*pre)
                )
        self.sow("moe_stats", "sizes", plan.sizes)
        self.sow("moe_stats", "choices", top_e)
        return y.reshape(u.shape).astype(u.dtype)


# What shapes a state-space mixer's initial step sizes and decays (the
# ``time_step_min`` / ``_max`` / ``_floor`` of a ``nemotron_h`` config, and
# Mamba-2's range of ``A``): ``dt`` log-uniform in [0.001, 0.1], never under
# 1e-4, stored as its inverse softplus; ``A`` uniform in [1, 16], stored as
# its logarithm.
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 0.1, 1e-4
A_RANGE = (1.0, 16.0)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    span = math.log(DT_MAX) - math.log(DT_MIN)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * span + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus(this) == dt


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, *A_RANGE))


def _conv_init(key, shape, dtype=jnp.float32):
    """Uniform ``+-1/sqrt(taps)``: a depthwise kernel's fan-in is its taps."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class MambaMixer(nn.Module):
    """The Mamba-2 mixer over the ``ssm_heads`` heads and ``ssm_groups``
    groups this chip holds (:mod:`ops.ssm`): ``[z | xBC | dt] = u W_in``,
    ``xBC <- silu(conv(xBC))``, the selective recurrence a head with ``dt =
    softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, ``RMSNorm(y * silu(z))``
    over each group's channels, ``W_out``. No bias but the convolution's."""

    spec: TrunkSpec
    dtype: t.Any = jnp.float32

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        sp, dtype = self.spec, self.dtype
        b, s, hidden = u.shape
        heads, p, groups, n = sp.ssm_heads, sp.ssm_head_dim, sp.ssm_groups, sp.ssm_state
        inner, bc = heads * p, groups * n
        with jax.named_scope(scopes.TRUNK_SSM_PROJ):
            z, xbc, dt = jnp.split(
                _linear(2 * inner + 2 * bc + heads, dtype, "in_proj")(u),
                (inner, 2 * inner + 2 * bc), axis=-1,
            )
        with jax.named_scope(scopes.TRUNK_SSM_CONV):
            kernel = self.param("conv_kernel", _conv_init, (sp.ssm_conv, inner + 2 * bc))
            bias = self.param("conv_bias", _conv_init, (inner + 2 * bc,))
            xbc = jax.nn.silu(ssm.causal_conv(xbc, kernel, bias))
        with jax.named_scope(scopes.TRUNK_SSM_SCAN):
            x, b_in, c_out = jnp.split(xbc, (inner, inner + bc), axis=-1)
            dt = jax.nn.softplus(
                dt.astype(jnp.float32) + self.param("dt_bias", _dt_bias_init, (heads,))
            )
            y = ssm.ssd_scan(
                x.reshape(b, s, heads, p), dt,
                -jnp.exp(self.param("A_log", _a_log_init, (heads,))),
                b_in.reshape(b, s, groups, n), c_out.reshape(b, s, groups, n),
                self.param("D", nn.initializers.ones, (heads,)),
                sp.ssm_chunk, sp.bf16_dots and dtype == jnp.float32,
            ).reshape(b, s, inner)
        with jax.named_scope(scopes.TRUNK_SSM_GATE_NORM):
            weight = self.param("norm_weight", nn.initializers.ones, (inner,))
            y = ssm.gated_group_norm(y.astype(u.dtype), z, weight, groups, sp.rms_eps)
        with jax.named_scope(scopes.TRUNK_SSM_PROJ):
            return _linear(hidden, dtype, "out_proj")(y)


def _selection(attention_fn) -> str:
    """The router's selection beside ``attention_fn``: kernels where it is
    the one that reaches the flash kernels."""
    return "auto" if attention_fn is default_attention else "xla"


class DenseFFN(nn.Module):
    """``W_down (silu(W_gate v) * W_up v)``, ``width`` wide: the dense gated
    feed-forward, whole on every chip."""

    width: int
    dtype: t.Any = jnp.float32

    @nn.compact
    def __call__(self, v: jax.Array) -> jax.Array:
        pre = moe.FORMS["silu_gated"](
            _linear(self.width, self.dtype, "gate_proj")(v),
            _linear(self.width, self.dtype, "up_proj")(v),
        )
        return _linear(v.shape[-1], self.dtype, "down_proj")(pre)


class DecoderBlock(nn.Module):
    """``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``: a block of
    two sublayers, by ``kind``. ``S`` (``sdar_moe``): attention and sparse
    experts as the spec has them. ``F`` / ``W`` / ``f`` / ``w`` (``laguna``):
    full or sliding-window attention (head count, window and rotary by kind),
    then sparse experts or, small letter, the dense feed-forward."""

    spec: TrunkSpec
    kind: str = SDAR_BLOCK
    attention_fn: AttentionFn = default_attention
    dtype: t.Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, pos: jax.Array) -> jax.Array:
        sp, kind = self.spec, self.kind
        # a kind's scope starts with tac/trunk/attention: its readers sum them
        by_kind = scopes.TRUNK_ATTENTION if kind == SDAR_BLOCK else (
            scopes.TRUNK_ATTENTION_SLIDING if kind in WINDOWED
            else scopes.TRUNK_ATTENTION_FULL
        )
        with jax.named_scope(by_kind):
            h = x + GroupedQueryAttention(
                sp, self.attention_fn, self.dtype, kind, name="attention"
            )(RMSNorm(sp.rms_eps, name="input_norm")(x), pos)
        if kind in DENSE_FFN:
            with jax.named_scope(scopes.TRUNK_DENSE_FFN):
                u = RMSNorm(sp.rms_eps, name="post_attention_norm")(h)
                return h + DenseFFN(sp.dense_width, self.dtype, name="mlp")(u)
        with jax.named_scope(scopes.TRUNK_MOE_ROUTE):
            u = RMSNorm(sp.rms_eps, name="post_attention_norm")(h)
        return h + SparseMoE(sp, selection=_selection(self.attention_fn), name="moe")(u)


class MixerBlock(nn.Module):
    """``x + Mixer(RMSNorm(x))``: a layer of a stack whose layers are one
    mixer each behind one norm (``nemotron_h``), the mixer by ``kind``."""

    spec: TrunkSpec
    kind: str
    attention_fn: AttentionFn = default_attention
    dtype: t.Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, pos: jax.Array) -> jax.Array:
        sp = self.spec
        norm_scope = {
            STATE_SPACE: scopes.TRUNK_SSM_PROJ, ATTENTION: scopes.TRUNK_ATTENTION,
            EXPERTS: scopes.TRUNK_MOE_ROUTE,
        }[self.kind]
        with jax.named_scope(norm_scope):
            u = RMSNorm(sp.rms_eps, name="norm")(x)
        if self.kind == STATE_SPACE:
            return x + MambaMixer(sp, self.dtype, name="mixer")(u)
        if self.kind == EXPERTS:
            return x + SparseMoE(
                sp, self.dtype, _selection(self.attention_fn), name="mixer"
            )(u)
        with jax.named_scope(scopes.TRUNK_ATTENTION):
            return x + GroupedQueryAttention(
                sp, self.attention_fn, self.dtype, name="mixer"
            )(u, pos)


class SequenceTrunk(nn.Module):
    """Embed + N blocks over a history, the block taken from ``spec``.

    ``spec=None``: the small pre-LN transformer (learned positions, N causal
    :class:`TransformerBlock`, LayerNorm), sized by ``d_model`` /
    ``num_heads`` / ``num_layers``. A :class:`TrunkSpec`: a published decoder
    stack, one block a letter of the spec's pattern (``Dense(obs_dim ->
    hidden)`` where the token embedding was, positions inside the blocks that
    take any, one RMSNorm after the last).

    ``pos_offset`` is the global index of this chunk's first timestep —
    0 on a single device; ``axis_index('sp') * T_local`` under context
    parallelism, so positions stay globally consistent when the sequence
    is sharded.
    """

    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 512
    attention_fn: AttentionFn = default_attention
    dtype: t.Any = jnp.float32
    spec: TrunkSpec | None = None

    @nn.compact
    def __call__(self, obs_seq: jax.Array, pos_offset: jax.Array | int = 0):
        if self.spec is not None:
            return self._stack(obs_seq, pos_offset)
        dtype = self.dtype
        b, s, _ = obs_seq.shape
        # jnp.take clamps out-of-bounds rows silently — aliased positions
        # would train without error, so reject oversized histories here.
        # (Under sp sharding `s` is the local chunk; the context wrapper
        # checks the global length against max_len.)
        assert s <= self.max_len, (
            f"history length {s} exceeds max_len={self.max_len}"
        )
        x = Dense(self.d_model, dtype=dtype)(obs_seq)
        pos_table = self.param(
            "pos_embedding",
            nn.initializers.normal(0.02),
            (self.max_len, self.d_model),
        )
        pos = pos_offset + jnp.arange(s)
        # The f32 pos table would promote a bf16 residual stream back to
        # f32; cast the sum to the compute dtype explicitly.
        x = (x + jnp.take(pos_table, pos, axis=0)[None]).astype(dtype)
        for _ in range(self.num_layers):
            x = TransformerBlock(
                self.num_heads, attention_fn=self.attention_fn, dtype=dtype
            )(x)
        return nn.LayerNorm()(x)

    def _stack(self, obs_seq: jax.Array, pos_offset: jax.Array | int):
        sp = self.spec
        with jax.named_scope(scopes.TRUNK_EMBED):
            x = _linear(sp.hidden, self.dtype, "embed")(obs_seq)
        pos = pos_offset + jnp.arange(obs_seq.shape[1])
        for i, kind in enumerate(sp.kinds):
            block = DecoderBlock if kind in TWO_SUBLAYERS else MixerBlock
            its_kind = {} if kind == SDAR_BLOCK else {"kind": kind}
            # Recomputing a block saves its residuals (about 1 GB for an SDAR
            # block at the published widths and 8,192 tokens) for a fifth more
            # of its work.
            if i < sp.remat:
                block = nn.remat(block)
            x = block(
                spec=sp, attention_fn=self.attention_fn, dtype=self.dtype,
                name=f"layer_{i}", **its_kind,
            )(x, pos)
        with jax.named_scope(scopes.TRUNK_EMBED):
            return RMSNorm(sp.rms_eps, name="final_norm")(x)


class SequenceActor(nn.Module):
    """Squashed-Gaussian policy conditioned on an observation history.

    ``__call__`` maps ``(B, T, obs_dim)`` histories to the action for
    the latest timestep; :meth:`trunk` / :meth:`head` are exposed
    separately so the context-parallel wrapper can insert the
    cross-device last-token gather between them.
    """

    act_dim: int
    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 512
    act_limit: float = 1.0
    attention_fn: AttentionFn = default_attention
    # Sequence/context parallelism: when `sp_axis` names a *manual* mesh
    # axis (the module is being applied inside shard_map with the
    # sequence dimension sharded over it), positional offsets and the
    # last-token gather become sp-aware. Pair with a ring attention_fn
    # (`parallel.context.make_ring_attention_fn`). Attributes, not
    # params: the tree layout (and checkpoints) are unchanged.
    sp_axis: str | None = None
    sp_size: int = 1
    dtype: t.Any = jnp.float32  # see Actor.dtype; distribution math stays f32

    def setup(self):
        self._trunk = SequenceTrunk(
            self.d_model, self.num_heads, self.num_layers, self.max_len,
            self.attention_fn, dtype=self.dtype,
        )
        self._mu = Dense(self.act_dim, dtype=self.dtype)
        self._log_std = Dense(self.act_dim, dtype=self.dtype)

    def trunk(self, obs_seq: jax.Array, pos_offset: jax.Array | int = 0):
        return self._trunk(obs_seq, pos_offset)

    def head(
        self,
        h: jax.Array,
        key: jax.Array | None = None,
        deterministic: bool = False,
        with_logprob: bool = True,
    ):
        mu = self._mu(h).astype(jnp.float32)
        log_std = self._log_std(h).astype(jnp.float32)
        return squashed_gaussian_sample(
            key, mu, log_std, self.act_limit, deterministic, with_logprob
        )

    def __call__(
        self,
        obs_seq: jax.Array,
        key: jax.Array | None = None,
        deterministic: bool = False,
        with_logprob: bool = True,
    ):
        unbatched, obs_seq = _auto_batch(obs_seq)
        h_all = self.trunk(obs_seq, _sp_pos_offset(obs_seq, self.sp_axis))
        h = _sp_last_token(h_all, self.sp_axis, self.sp_size)
        action, logp = self.head(h, key, deterministic, with_logprob)
        if unbatched:
            action = jnp.squeeze(action, 0)
            logp = jnp.squeeze(logp, 0) if logp is not None else None
        return action, logp


class SequenceCritic(nn.Module):
    """Q(h_T, a): history-conditioned Q-network.

    The trunk encodes the history; the last token's representation is
    concatenated with the action and scored by a 2-layer MLP — the
    sequence analogue of ``Critic``'s concat([obs, act]) (ref
    ``networks/linear.py:62``).
    """

    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 512
    hidden: int = 256
    attention_fn: AttentionFn = default_attention
    sp_axis: str | None = None  # see SequenceActor.sp_axis
    sp_size: int = 1
    dtype: t.Any = jnp.float32  # see Critic.dtype; Q cast back to float32

    @nn.compact
    def __call__(self, obs_seq: jax.Array, action: jax.Array) -> jax.Array:
        dtype = self.dtype
        unbatched, obs_seq, action = _auto_batch(obs_seq, action)
        h_all = SequenceTrunk(
            self.d_model, self.num_heads, self.num_layers, self.max_len,
            self.attention_fn, dtype=dtype,
        )(obs_seq, _sp_pos_offset(obs_seq, self.sp_axis))
        h = _sp_last_token(h_all, self.sp_axis, self.sp_size)
        x = jnp.concatenate([h, action.astype(h.dtype)], axis=-1)
        x = nn.relu(Dense(self.hidden, dtype=dtype)(x))
        x = Dense(1, dtype=dtype)(x)
        q = jnp.squeeze(x.astype(jnp.float32), axis=-1)
        return jnp.squeeze(q, 0) if unbatched else q


class SequenceDoubleCritic(nn.Module):
    """Twin (or ``num_qs``-wide) ensemble of :class:`SequenceCritic`,
    vmapped over parameters like
    :class:`~torch_actor_critic_tpu.models.critic.DoubleCritic`."""

    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 512
    hidden: int = 256
    num_qs: int = 2
    attention_fn: AttentionFn = default_attention
    sp_axis: str | None = None  # see SequenceActor.sp_axis
    sp_size: int = 1
    dtype: t.Any = jnp.float32

    @nn.compact
    def __call__(self, obs_seq: jax.Array, action: jax.Array) -> jax.Array:
        ensemble = nn.vmap(
            SequenceCritic,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=None,
            out_axes=0,
            axis_size=self.num_qs,
        )
        return ensemble(
            self.d_model, self.num_heads, self.num_layers, self.max_len,
            self.hidden, self.attention_fn, self.sp_axis, self.sp_size,
            dtype=self.dtype,
            name="ensemble",
        )(obs_seq, action)


# --------------------------------------------------------------------------
# One trunk shared by actor and critics (SACConfig.trunk_pattern, or
# trunk_block="sdar_moe" for trunk_layers SDAR blocks)
# --------------------------------------------------------------------------

TRUNK = "trunk"  # the trunk's subtree, under the same name in both modules


def policy_params(actor_params: t.Any, critic_params: t.Any) -> t.Any:
    """The parameters the policy acts with, for every site that acts (the
    trainer's device and host actors, the serving registry, a checkpoint's
    actor restore). Separate networks: ``actor_params`` as they are. A shared
    trunk lives in the critic's tree (the critic loss trains it); the policy
    is that trunk under the actor's heads."""
    try:
        trunk = critic_params["params"][TRUNK]
    except (KeyError, TypeError, IndexError):
        return actor_params
    return {"params": {**actor_params["params"], TRUNK: trunk}}


class QHead(nn.Module):
    """``Q(h_T, a)``: the last step's features with the action, a 2-layer MLP."""

    hidden: int = 256
    dtype: t.Any = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array, action: jax.Array) -> jax.Array:
        x = jnp.concatenate([h, action.astype(h.dtype)], axis=-1)
        x = nn.relu(Dense(self.hidden, dtype=self.dtype)(x))
        x = Dense(1, dtype=self.dtype)(x)
        return jnp.squeeze(x.astype(jnp.float32), axis=-1)


class SharedTrunkCritic(nn.Module):
    """The shared history trunk and ``num_qs`` Q heads on its last step.

    ``features`` and ``q`` are applied apart by the shared-trunk losses
    (one trunk pass feeds the Q heads and the policy head); ``__call__`` is
    the plain critic contract ``(obs_seq, action) -> (num_qs, batch)``."""

    spec: TrunkSpec
    hidden: int = 256
    num_qs: int = 2
    attention_fn: AttentionFn = default_attention
    dtype: t.Any = jnp.float32
    shared_trunk: t.ClassVar[bool] = True

    def setup(self):
        self.trunk = SequenceTrunk(
            attention_fn=self.attention_fn, dtype=self.dtype, spec=self.spec
        )
        self.ensemble = nn.vmap(
            QHead,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=None,
            out_axes=0,
            axis_size=self.num_qs,
        )(self.hidden, self.dtype)

    def features(self, obs_seq: jax.Array) -> jax.Array:
        return self.trunk(obs_seq)[:, -1]

    def q(self, h: jax.Array, action: jax.Array) -> jax.Array:
        return self.ensemble(h, action)

    def __call__(self, obs_seq: jax.Array, action: jax.Array) -> jax.Array:
        unbatched, obs_seq, action = _auto_batch(obs_seq, action)
        q = self.q(self.features(obs_seq), action)
        return jnp.squeeze(q, 1) if unbatched else q


class SharedTrunkActor(nn.Module):
    """The policy over the shared trunk: trunk, then the squashed-Gaussian
    head on the last step. Its trained parameters are the heads alone
    (``TrainState.actor_params``); acting takes :func:`policy_params`."""

    act_dim: int
    spec: TrunkSpec
    act_limit: float = 1.0
    attention_fn: AttentionFn = default_attention
    dtype: t.Any = jnp.float32
    shared_trunk: t.ClassVar[bool] = True

    def setup(self):
        self.trunk = SequenceTrunk(
            attention_fn=self.attention_fn, dtype=self.dtype, spec=self.spec
        )
        self.mu = Dense(self.act_dim, dtype=self.dtype)
        self.log_std = Dense(self.act_dim, dtype=self.dtype)

    def head(
        self,
        h: jax.Array,
        key: jax.Array | None = None,
        deterministic: bool = False,
        with_logprob: bool = True,
    ):
        mu = self.mu(h).astype(jnp.float32)
        log_std = self.log_std(h).astype(jnp.float32)
        return squashed_gaussian_sample(
            key, mu, log_std, self.act_limit, deterministic, with_logprob
        )

    def __call__(
        self,
        obs_seq: jax.Array,
        key: jax.Array | None = None,
        deterministic: bool = False,
        with_logprob: bool = True,
    ):
        unbatched, obs_seq = _auto_batch(obs_seq)
        h = self.trunk(obs_seq)[:, -1]
        action, logp = self.head(h, key, deterministic, with_logprob)
        if unbatched:
            action = jnp.squeeze(action, 0)
            logp = jnp.squeeze(logp, 0) if logp is not None else None
        return action, logp
