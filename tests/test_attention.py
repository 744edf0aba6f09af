"""Attention stack: blockwise/flash kernels, sequence models, ring
context parallelism.

All extension capability (the reference has no attention or sequence
axis — SURVEY.md §5), tested the way the distributed suite tests DP:
exact numerics against a dense reference, and real collective semantics
on the 8-virtual-device CPU mesh from ``conftest.py``. The Pallas
kernel runs in interpreter mode here (same kernel code path the TPU
compiles).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flax import linen as nn

from torch_actor_critic_tpu.models import SequenceActor, SequenceDoubleCritic
from jax import shard_map
from torch_actor_critic_tpu.ops import attention as attention_ops
from torch_actor_critic_tpu.ops.attention import (
    attention,
    blockwise_attention,
    flash_attention,
    qk_norm_rope,
    reference_attention,
)
from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.parallel.context import (
    context_parallel_actor_step,
    ring_attention,
)
from jax.sharding import PartitionSpec as P


def qkv(seed, b=2, h=2, t=32, d=16):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, h, t, d)
    return tuple(jax.random.normal(k, shape) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_k", [8, 16, 13])  # 13: pad-tail path
def test_blockwise_matches_reference(causal, block_k):
    q, k, v = qkv(0, t=40)
    expected = reference_attention(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, causal=causal, block_k=block_k)
    np.testing.assert_allclose(got, expected, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_reference(causal):
    q, k, v = qkv(1, t=32, d=16)
    expected = reference_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, 8, 8, True)  # interpret mode
    np.testing.assert_allclose(got, expected, atol=1e-5)


def test_pallas_dispatch_off_tpu_fails_loudly():
    """VERDICT r2 weak #7: requesting the TPU (Pallas) kernel from a
    process whose default backend is CPU must raise a clear trace-time
    RuntimeError naming the fix — not a cryptic Mosaic lowering error
    (the 'auto'-dispatch footgun documented on attention())."""
    q, k, v = qkv(3)
    with pytest.raises(RuntimeError, match="default backend is 'cpu'"):
        flash_attention(q, k, v, False, 8, 8)  # compiled mode, no TPU
    # Same guard through the dispatcher inside a jit trace — the shape a
    # user hits when a sequence model built for TPU is jitted on CPU.
    with pytest.raises(RuntimeError, match="impl='xla'"):
        jax.jit(lambda q, k, v: attention(q, k, v, impl="pallas"))(q, k, v)


def test_auto_block_selection():
    """Default (None) block sizes resolve to the largest of
    {128, 256, 512} tiling the sequence — the chip block-sweep optimum
    — while accepting EXACTLY the shape set the old fixed-128 default
    did: shapes the old default sent to XLA (or rejected) must not
    silently acquire degenerate Pallas tiles."""
    from torch_actor_critic_tpu.ops.attention import _auto_block, _check_blocks

    assert _auto_block(2048) == 512
    assert _auto_block(8192) == 512
    assert _auto_block(640) == 128   # 640 % 512 != 0, 640 % 128 == 0
    assert _auto_block(64) == 64     # <= 128: one block, as before
    # Old default rejected these (not 128-divisible, > 128): auto must
    # too, not hand them 8-wide tiles the chip never validated.
    assert _auto_block(264) is None
    assert _auto_block(1032) is None
    assert _check_blocks(1024, 640, None, None) == (512, 128)
    with pytest.raises(ValueError, match="ragged"):
        _check_blocks(1032, 1032, None, None)
    # Explicit values still pass through (and still validate).
    assert _check_blocks(1024, 1024, 128, 256) == (128, 256)
    # The dispatcher routes auto-rejected lengths to XLA (same result,
    # no Pallas trace — this would raise off-TPU if it tried Pallas).
    q, k, v = qkv(9, t=264)
    np.testing.assert_allclose(
        attention(q, k, v, causal=True),
        reference_attention(q, k, v, causal=True),
        atol=1e-5,
    )
    # Auto equals explicit at the resolved sizes in interpret mode.
    q, k, v = qkv(7, t=24)  # 24 <= 128 -> single (24, 24) block
    np.testing.assert_allclose(
        flash_attention(q, k, v, True, None, None, True),
        flash_attention(q, k, v, True, 24, 24, True),
        atol=1e-6,
    )


def test_flash_rejects_ragged_lengths():
    q, k, v = qkv(20, t=20)  # 20 % 8 != 0
    with pytest.raises(ValueError, match="ragged"):
        flash_attention(q, k, v, False, 8, 8, True)


def test_flash_pads_head_dim():
    # d=16 is not lane-aligned; the wrapper zero-pads to 128 and slices.
    q, k, v = qkv(21, t=16, d=16)
    expected = reference_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, True, 8, 8, True)
    np.testing.assert_allclose(got, expected, atol=1e-5)


def test_flash_pad_lanes_64_matches_reference():
    """pad_lanes=64 keeps a d=64 head at true width (half the HBM
    traffic of the zero-padded layout); math must be identical, fwd
    and bwd."""
    q, k, v = qkv(31, t=32, d=64)
    expected = reference_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, True, 8, 8, True, 64)
    np.testing.assert_allclose(got, expected, atol=1e-5)

    def loss(fn):
        return jax.grad(
            lambda q: jnp.sum(fn(q) ** 2)
        )(q)

    g64 = loss(lambda q: flash_attention(q, k, v, True, 8, 8, True, 64))
    g128 = loss(lambda q: flash_attention(q, k, v, True, 8, 8, True, 128))
    np.testing.assert_allclose(g64, g128, atol=1e-5)

    # d=48 actually exercises the lanes=64 pad/slice branch (d=64 is a
    # no-op there): pad 48 -> 64, output sliced back to 48.
    q48, k48, v48 = qkv(33, t=16, d=48)
    np.testing.assert_allclose(
        flash_attention(q48, k48, v48, True, 8, 8, True, 64),
        reference_attention(q48, k48, v48, causal=True),
        atol=1e-5,
    )


def test_flash_gradients_match_reference():
    q, k, v = qkv(2, b=1, h=1, t=16, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 8, 8, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(gf, gr, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,d,blk", [(32, 16, 8), (24, 5, 8)])
def test_flash_backward_kernel_parity(causal, t, d, blk):
    """The Pallas dq/dk/dv kernels (multi-block grids, head-dim padding)
    against the dense reference VJP, with a non-trivial cotangent."""
    q, k, v = qkv(30, b=2, h=2, t=t, d=d)
    g = jax.random.normal(jax.random.key(31), q.shape)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal, blk, blk, True)

    def f_ref(q, k, v):
        return reference_attention(q, k, v, causal=causal)

    _, vjp_flash = jax.vjp(f_flash, q, k, v)
    _, vjp_ref = jax.vjp(f_ref, q, k, v)
    for gf, gr in zip(vjp_flash(g), vjp_ref(g)):
        np.testing.assert_allclose(gf, gr, atol=1e-4)


def test_flash_backward_is_pallas_not_recompute():
    """The VJP lowers to Pallas custom calls, not an XLA softmax
    recompute: the backward HLO must contain no `reduce`-based softmax
    normalizer outside custom calls — we assert on the jaxpr instead:
    every attention matmul in the bwd jaxpr lives inside a pallas_call."""
    q, k, v = qkv(32, b=1, h=1, t=16, d=8)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 8, 8, True))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    # grad-of-flash should introduce pallas_call(s) and no lax.scan
    # (the blockwise recompute path would bring a scan in).
    flat = jaxpr.jaxpr.pretty_print(use_color=False)
    assert "pallas_call" in flat
    assert "scan" not in prims


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    """Sequence sharded over sp=8: ring result == dense attention on the
    unsharded sequence, including cross-device causal masking."""
    mesh = make_mesh(dp=1, sp=8)
    q, k, v = qkv(3, t=32)  # t_local = 4
    expected = reference_attention(q, k, v, causal=causal)

    def body(q, k, v):
        return ring_attention(q, k, v, "sp", 8, causal=causal)

    got = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, None, "sp"), P(None, None, "sp"), P(None, None, "sp")),
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(got, expected, atol=1e-5)


def test_ring_attention_differentiable():
    mesh = make_mesh(dp=1, sp=8)
    q, k, v = qkv(4, b=1, h=1, t=16, d=8)

    def ring_loss(q, k, v):
        def body(q, k, v):
            return ring_attention(q, k, v, "sp", 8, causal=True)

        out = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )(q, k, v)
        return jnp.sum(out**2)

    def ref_loss(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, ge in zip(g_ring, g_ref):
        np.testing.assert_allclose(gr, ge, atol=1e-4)


def _tiny_actor(obs_dim=5, act_dim=3, t=16):
    actor = SequenceActor(
        act_dim=act_dim, d_model=32, num_heads=2, num_layers=1, max_len=64
    )
    obs = jax.random.normal(jax.random.key(5), (2, t, obs_dim))
    params = actor.init(jax.random.key(6), obs, jax.random.key(7))
    return actor, params, obs


def test_sequence_actor_shapes():
    actor, params, obs = _tiny_actor()
    action, logp = actor.apply(params, obs, jax.random.key(8))
    assert action.shape == (2, 3)
    assert logp.shape == (2,)
    assert bool(jnp.all(jnp.abs(action) <= 1.0))
    assert bool(jnp.all(jnp.isfinite(logp)))


def test_sequence_trunk_is_causal():
    """Perturbing future observations must not change past positions."""
    actor, params, obs = _tiny_actor()
    h = actor.apply(params, obs, method=SequenceActor.trunk)
    obs2 = obs.at[:, -1].set(obs[:, -1] + 100.0)
    h2 = actor.apply(params, obs2, method=SequenceActor.trunk)
    np.testing.assert_allclose(h[:, :-1], h2[:, :-1], atol=1e-6)
    assert not np.allclose(h[:, -1], h2[:, -1])


def test_context_parallel_actor_matches_single_device():
    actor, params, obs = _tiny_actor(t=16)
    mesh = make_mesh(dp=1, sp=8)
    a_single, _ = actor.apply(params, obs, None, True)  # deterministic
    a_ring, _ = context_parallel_actor_step(
        actor, params, obs, None, mesh, deterministic=True
    )
    np.testing.assert_allclose(a_ring, a_single, atol=1e-5)


def test_context_parallel_actor_stochastic_logprob():
    actor, params, obs = _tiny_actor(t=16)
    mesh = make_mesh(dp=1, sp=8)
    action, logp = context_parallel_actor_step(
        actor, params, obs, jax.random.key(9), mesh
    )
    assert action.shape == (2, 3)
    assert bool(jnp.all(jnp.isfinite(logp)))


@pytest.mark.slow
def test_sequence_double_critic_shapes():
    critic = SequenceDoubleCritic(d_model=32, num_heads=2, num_layers=1, max_len=64)
    obs = jax.random.normal(jax.random.key(10), (4, 8, 5))
    act = jax.random.normal(jax.random.key(11), (4, 3))
    params = critic.init(jax.random.key(12), obs, act)
    qs = critic.apply(params, obs, act)
    assert qs.shape == (2, 4)
    assert bool(jnp.all(jnp.isfinite(qs)))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_operands_match_reference(causal):
    """bf16 q/k/v through fwd AND bwd: the kernels keep operands in
    their storage dtype on the MXU (f32 accumulation; probability/ds
    tiles cast down for the second matmul), so the result must track a
    dense f32 reference within bf16 tolerance — pins the
    mixed-precision path the sequence stack uses under
    compute_dtype=bfloat16."""
    q32, k32, v32 = qkv(40, b=2, h=2, t=32, d=16)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
    g = jax.random.normal(jax.random.key(41), q32.shape)

    expected = reference_attention(q32, k32, v32, causal=causal)
    got = flash_attention(q, k, v, causal, 8, 8, True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(jnp.float32), expected, atol=3e-2, rtol=3e-2
    )

    _, vjp_flash = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal, 8, 8, True), q, k, v
    )
    _, vjp_ref = jax.vjp(
        lambda q, k, v: reference_attention(q, k, v, causal=causal),
        q32, k32, v32,
    )
    for gf, gr in zip(vjp_flash(g.astype(jnp.bfloat16)), vjp_ref(g)):
        assert gf.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            gf.astype(jnp.float32), gr, atol=6e-2, rtol=6e-2
        )


def test_flash_rejects_mixed_operand_dtypes():
    q, k, v = qkv(50, t=16, d=16)
    with pytest.raises(ValueError, match="share one dtype"):
        flash_attention(q, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                        False, 8, 8, True)


def test_flash_surface_has_no_offset_masking():
    """Pin the NaN-safety precondition of the guard-free flash kernels.

    The in-kernel softmax dropped its isneginf guards on the invariant
    that NO row can be fully masked: causal rows always see key 0, and
    the public surface has no q/k position offsets or mask argument
    that could break that (ops/attention.py _flash_kernel comments).
    Whoever extends flash_attention with offset-style masking (e.g. a
    ring-attention Pallas path — blockwise_attention has exactly those
    params and keeps its guards) must re-add the guards and retire this
    pin.
    """
    import inspect

    from torch_actor_critic_tpu.ops import attention

    forbidden = {"q_offset", "k_offset", "offset", "mask", "segment_ids"}
    assert not (set(inspect.signature(attention.flash_attention).parameters)
                & forbidden)
    # The guarded blockwise path (ring attention's building block) DOES
    # carry offsets — the asymmetry is the design, keep it visible.
    assert {"q_offset", "k_offset"} <= set(
        inspect.signature(attention.blockwise_attention).parameters
    )


# ------------------------- between the projections and the kernels, each way


def _composed(y, weight, pos):
    """What the one pass replaces: ``RMSNorm``'s arithmetic, ``rotary`` and
    the heads' transposition, one after another (``impl='xla'`` is this
    composition, held to its spelling here)."""
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    normed = y * jax.lax.rsqrt(var + 1e-6) * weight
    return attention_ops.rotary(normed, pos, 1e6).transpose(0, 2, 1, 3)


def _one_pass(y, weight, pos):
    return qk_norm_rope(y, weight, pos, 1e6, 1e-6, "interpret")


def _remat(fn):
    """``fn`` inside a block that ``nn.remat`` recomputes, as the trunk's
    first block is."""

    class Block(nn.Module):
        @nn.compact
        def __call__(self, y, weight, pos):
            return fn(y, weight, pos)

    return lambda y, weight, pos: nn.remat(Block)().apply({}, y, weight, pos)


def _vmapped(fn):
    """``fn`` under the burst's ``vmap`` over its device axis: activations
    and weights batched, positions shared."""
    return lambda y, weight, pos: jax.vmap(fn, in_axes=(0, 0, None))(
        jnp.stack([y, 2.0 * y]), jnp.stack([weight, weight + 0.5]), pos
    )


WRAPS = {"plain": lambda fn: fn, "vmap": _vmapped, "remat": _remat}


def _close(got, want, rel=1e-6):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, atol=rel * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("heads", [32, 4], ids=["32-heads", "4-heads"])
@pytest.mark.parametrize("pos_offset", [0, 37])
@pytest.mark.parametrize("wrap", sorted(WRAPS))
def test_one_pass_norm_rope_transpose_matches_the_composition(wrap, pos_offset, heads):
    """Output and the gradients to the projection's output and to the norm's
    weight, for the trunk's 32 query heads and for 4 (its key/value heads, or
    an ungrouped layer's), tables made at ``pos`` (a chunk that starts at 37
    as under sequence sharding): float32, the output to 1e-6 of the largest
    value, the gradients to 1e-5."""
    k = jax.random.split(jax.random.key(heads), 3)
    b, t, d = 2, 16, 128
    y = jax.random.normal(k[0], (b, t, heads, d))
    weight = 1.0 + 0.1 * jax.random.normal(k[1], (d,))
    pos = pos_offset + jnp.arange(t)
    got_fn, want_fn = WRAPS[wrap](_one_pass), WRAPS[wrap](_composed)
    want = want_fn(y, weight, pos)
    cot = jax.random.normal(k[2], want.shape)
    _close(got_fn(y, weight, pos), want)
    _close(WRAPS[wrap](lambda *a: qk_norm_rope(*a, 1e6, 1e-6, "xla"))(y, weight, pos), want)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda y, w: jnp.sum(fn(y, w, pos) * cot), (0, 1)
    )(y, weight)
    for got, want in zip(grads(got_fn), grads(want_fn)):
        _close(got, want, rel=1e-5)  # the weight's sums 2,048 rows in another order


def test_the_passes_blocks_tile_whole_lanes_and_sublanes():
    fits = attention_ops._pass_fits
    assert fits(1024, 32, 128, jnp.float32) and fits(8, 1, 256, jnp.float32)
    assert not fits(1024, 32, 64, jnp.float32)  # half a lane row a head
    assert not fits(12, 4, 128, jnp.float32)  # no block of whole sublanes
    assert not fits(1024, 32, 128, jnp.bfloat16)  # float32 statistics, float32 tiles
    # four heads a block (a kernel's body is unrolled over them), 2 MiB of rows
    assert attention_ops._pass_block(1024, 32, 128) == (1024, 4)
    assert attention_ops._pass_block(2048, 4, 128) == (1024, 4)
    assert attention_ops._pass_block(16, 3, 256) == (16, 3)
    # on a CPU 'auto' composes, and never reaches a kernel
    y = jnp.ones((1, 8, 2, 128))
    jaxpr = jax.make_jaxpr(lambda y: qk_norm_rope(y, jnp.ones(128), jnp.arange(8), 1e6, 1e-6))(y)
    assert "pallas_call" not in str(jaxpr)


@pytest.mark.parametrize("kv_heads", [8, 1], ids=["ungrouped", "group-8"])
@pytest.mark.parametrize("block_length", [1, 4])
def test_flash_gradients_from_compact_row_statistics(block_length, kv_heads):
    """The backward kernels read ``lse`` and ``delta`` as the forward kernel
    and ``delta``'s reduce wrote them, a row of statistics in the lanes
    ``(batch * heads, 1, seq)``: no lane-wide copy is kept, and output and
    gradients are the dense reference's, two q blocks by two k blocks."""
    k = jax.random.split(jax.random.key(block_length), 4)
    q = jax.random.normal(k[0], (2, 8, 16, 8))
    kk, v = (jax.random.normal(x, (2, kv_heads, 16, 8)) for x in k[1:3])
    cot = jax.random.normal(k[3], q.shape)
    flash = lambda q, k, v: flash_attention(q, k, v, True, 8, 8, True, 128, block_length)  # noqa: E731
    ref = lambda q, k, v: reference_attention(q, k, v, True, block_length=block_length)  # noqa: E731
    out, residuals = attention_ops._flash_fwd(q, kk, v, True, 8, 8, True, 128, block_length, False)
    assert residuals[-1].shape == (2 * 8, 1, 16)
    np.testing.assert_allclose(out, ref(q, kk, v), atol=5e-6)
    for got, want in zip(jax.vjp(flash, q, kk, v)[1](cot), jax.vjp(ref, q, kk, v)[1](cot)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_way_out_is_the_transposition():
    """The kernels' ``(batch, heads, seq, d)`` reaches ``o_proj`` as
    ``transpose`` + ``reshape`` make it, ``(batch, seq, heads * d)`` with a
    head's ``d`` together, and the cotangent comes back the same way (XLA's
    own relayouts: a pass of ours there measured slower, PERF.md section 6)."""
    from torch_actor_critic_tpu.models.sequence import GroupedQueryAttention, TrunkSpec

    spec = TrunkSpec(hidden=16, q_heads=4, kv_heads=2, head_dim=8, bf16_dots=False)
    seen = {}

    def spy(q, k, v, causal=True, **mask):
        seen["shapes"] = (q.shape, k.shape, v.shape)
        # head h's row t holds 100 h + t in every column
        out = 100.0 * jnp.arange(4.0)[None, :, None, None] + jnp.arange(6.0)[None, None, :, None]
        return jnp.broadcast_to(out, q.shape)

    layer = GroupedQueryAttention(spec, attention_fn=spy)
    u = jnp.ones((2, 6, 16))
    params = layer.init(jax.random.key(0), u, jnp.arange(6))
    # an o_proj that keeps the first 16 columns: heads 0 and 1
    params["params"]["o_proj"]["kernel"] = jnp.eye(32, 16)
    out = layer.apply(params, u, jnp.arange(6))
    assert seen["shapes"] == ((2, 4, 6, 8), (2, 2, 6, 8), (2, 2, 6, 8))
    want = jnp.concatenate([jnp.broadcast_to(100.0 * h + jnp.arange(6.0)[:, None], (6, 8)) for h in (0, 1)], -1)
    np.testing.assert_array_equal(out[0], want)
