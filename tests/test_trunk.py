"""The SDAR history trunk at a small size on the CPU, seeded weights: the
attention mask and grouped heads in every attention implementation, the
expert layer's share arithmetic, and the shared-trunk SAC step against the
plain reference (``benchmark/harness/reference_trunk.py``, which imports
nothing of the program)."""

import hashlib
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_trunk, trunk_weights  # noqa: E402
from torch_actor_critic_tpu.core.types import Batch  # noqa: E402
from torch_actor_critic_tpu.models import (  # noqa: E402
    SequenceTrunk,
    TrunkSpec,
    policy_params,
)
from torch_actor_critic_tpu.ops import moe  # noqa: E402
from torch_actor_critic_tpu.ops.attention import (  # noqa: E402
    blockwise_attention,
    flash_attention,
    reference_attention,
)
from torch_actor_critic_tpu.sac.trainer import build_models, make_learner  # noqa: E402
from torch_actor_critic_tpu.utils.config import SACConfig  # noqa: E402

HISTORY, OBS, ACT = 16, 5, 3
SMALL = dict(
    trunk_block="sdar_moe", trunk_hidden=32, trunk_q_heads=4, trunk_kv_heads=2,
    trunk_head_dim=8, trunk_layers=2, trunk_experts=16, trunk_experts_per_tok=4,
    trunk_expert_width=24, trunk_experts_held=(2, 6), trunk_block_length=4,
    history_len=HISTORY, batch_size=4, update_every=3, buffer_size=64,
    trunk_bf16_dots=False,  # true float32 on the CPU, held to the `highest` reference
)
MODEL = dict(  # the same sizes as the reference reads them
    q_heads=4, kv_heads=2, head_dim=8, layers=2, experts_per_tok=4, experts_held=[2, 6],
    block_length=4, rms_eps=1e-6, rope_theta=1e6, act_limit=1.0,
)
SAC_MATH = dict(alpha=0.2, gamma=0.99, polyak=0.995, lr=3e-4, reward_scale=1.0)


def _learner(**overrides):
    cfg = SACConfig(**{**SMALL, **overrides})
    env = types.SimpleNamespace(
        act_dim=ACT, act_limit=1.0,
        obs_spec=jax.ShapeDtypeStruct((HISTORY, OBS), jnp.float32),
    )
    return cfg, make_learner(cfg, *build_models(cfg, env), ACT)


def _seeded_state(sac, seed=7):
    example = jnp.zeros((HISTORY, OBS))
    actor0, critic0 = trunk_weights.seeded_params(sac, example, jax.random.key(seed))
    state = sac.init_state(jax.random.key(0), example)
    return state.replace(
        actor_params=actor0, critic_params=critic0,
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic0),
    )


def _batch(seed, b=4):
    k = jax.random.split(jax.random.key(seed), 5)
    return Batch(
        states=jax.random.normal(k[0], (b, HISTORY, OBS)),
        actions=jax.random.uniform(k[1], (b, ACT), minval=-1.0, maxval=1.0),
        rewards=jax.random.normal(k[2], (b,)),
        next_states=jax.random.normal(k[3], (b, HISTORY, OBS)),
        done=(jax.random.uniform(k[4], (b,)) < 0.3).astype(jnp.float32),
    )


# ------------------------------------------------------------ attention


def _qkv(heads=8, kv_heads=2, t=256, d=64):
    k = jax.random.split(jax.random.key(0), 3)
    return (
        jax.random.normal(k[0], (2, heads, t, d)),
        jax.random.normal(k[1], (2, kv_heads, t, d)),
        jax.random.normal(k[2], (2, kv_heads, t, d)),
    )


IMPLS = {
    "blockwise": lambda b: lambda q, k, v: blockwise_attention(
        q, k, v, True, block_k=64, block_length=b
    ),
    "flash": lambda b: lambda q, k, v: flash_attention(
        q, k, v, True, 128, 128, True, 128, b, False
    ),
}


@pytest.mark.parametrize("block_length", [1, 4, 48])
@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_block_mask_and_grouped_heads_match_the_dense_reference(impl, block_length):
    """Forward and gradients of the scanned and of the three flash kernels
    (interpreted) against ``reference_attention``, 8 query heads over 2
    key/value heads, a block length that divides the kernels' tiles (4), one
    that does not (48) and the causal mask (1).  Tolerance: float32 sums in
    another order (online softmax over tiles), a few ulp of O(10) values."""
    q, k, v = _qkv()
    ref = lambda q, k, v: reference_attention(q, k, v, True, block_length=block_length)  # noqa: E731
    fn = IMPLS[impl](block_length)
    np.testing.assert_allclose(fn(q, k, v), ref(q, k, v), atol=5e-6)
    loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)  # noqa: E731
    got = jax.grad(loss(fn), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape  # dK, dV come back with the shared heads' shape
        np.testing.assert_allclose(g, w, atol=1e-4)


def test_the_mask_is_causal_across_blocks_and_full_inside_one():
    q, k, v = _qkv(heads=2, kv_heads=2, t=8, d=4)
    v = jnp.broadcast_to(jnp.eye(8)[None, None], (2, 2, 8, 8))  # row i of out: weights
    w = reference_attention(q, k, v, True, block_length=4)[0, 0]
    sees = np.asarray(w) > 0
    i, j = np.indices((8, 8))
    np.testing.assert_array_equal(sees, j // 4 <= i // 4)


@pytest.mark.parametrize("impl", ["reference", "blockwise", "flash"])
def test_block_length_one_is_bit_equal_to_causal(impl):
    q, k, v = _qkv(heads=2, kv_heads=2, t=256, d=64)
    if impl == "reference":
        a, b = reference_attention(q, k, v, True), reference_attention(q, k, v, True, block_length=1)
    elif impl == "blockwise":
        a = blockwise_attention(q, k, v, True, block_k=64)
        b = blockwise_attention(q, k, v, True, block_k=64, block_length=1)
    else:
        a = flash_attention(q, k, v, True, 128, 128, True)
        b = flash_attention(q, k, v, True, 128, 128, True, 128, 1, False)
    np.testing.assert_array_equal(a, b)


def test_grouped_heads_equal_repeated_heads():
    """Reading the shared head through the index maps is repeating k and v."""
    q, k, v = _qkv()
    rep = lambda x: jnp.repeat(x, 4, axis=1)  # noqa: E731
    a = flash_attention(q, k, v, True, 128, 128, True, 128, 4, False)
    b = flash_attention(q, rep(k), rep(v), True, 128, 128, True, 128, 4, False)
    np.testing.assert_array_equal(a, b)


def test_bf16_dots_round_operands_and_keep_float32_tiles():
    """``bf16_dots`` is the TPU's default precision inside the kernels: the
    result is float32 and equals the kernel fed operands already rounded, up
    to the rounding of the probability tile (2^-8 relative)."""
    q, k, v = _qkv(heads=2, kv_heads=2, t=128, d=64)
    low = flash_attention(q, k, v, True, 128, 128, True, 128, 1, True)
    assert low.dtype == jnp.float32
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    np.testing.assert_allclose(
        low, flash_attention(r(q), r(k), r(v), True, 128, 128, True), atol=2e-2
    )
    assert float(jnp.max(jnp.abs(low - flash_attention(q, k, v, True, 128, 128, True)))) > 1e-4


# --------------------------------------------------------------- the router


def route_by_sort_and_mask(
    u, w_router, top_k, scoring="softmax", bias=None, scale=1.0, impl=None
):
    """``ops.moe.route`` with the selection as it stood before PR 41, kept
    as what the selection is held to: ``lax.top_k`` (whole sorts of a token's
    scores on the TPU) and the chosen scores by a mask over tokens x top_k x
    experts (``impl``: ``route``'s signature; there is one form of this).
    ``scale`` multiplies either router's renormalised weights (PR 45)."""
    logits = jnp.dot(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring == "softmax":
        p = select = jax.nn.softmax(logits, axis=-1)
    else:
        p = jax.nn.sigmoid(logits)
        select = p if bias is None else p + bias
    _, top_e = jax.lax.top_k(jax.lax.stop_gradient(select), top_k)
    chosen = top_e[:, :, None] == jnp.arange(p.shape[-1], dtype=top_e.dtype)
    top_p = jnp.sum(jnp.where(chosen, p[:, None, :], 0.0), axis=-1)
    if scoring == "softmax":
        return top_e, scale * (top_p / jnp.sum(top_p, axis=-1, keepdims=True))
    return top_e, scale * top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)


def _scores_with_ties(n, experts, biased, seed):
    """Scores a router could give, ``(n, experts)``, and a bias: random
    rows; rows on a grid of sixty-fourths, so that many scores are equal;
    and, with a bias (on the same grid), rows whose scores all differ and
    tie only once the bias is added."""
    k = jax.random.split(jax.random.key(seed), 4)
    p = jax.nn.sigmoid(jax.random.normal(k[0], (n, experts)))
    third = n // 3
    p = p.at[:third].set(jnp.round(p[:third] * 64) / 64)
    if not biased:
        return p, None
    bias = jnp.round(jax.random.uniform(k[1], (experts,), minval=-4, maxval=4)) / 64
    apart = jax.random.permutation(k[2], experts)[None, :] / (64.0 * experts)
    tied = jnp.round(p[third:2 * third] * 16) / 16 - bias  # p + bias on a coarser grid
    return p.at[third:2 * third].set(jnp.where(tied > 0, tied, apart)), bias


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("experts,top_k", [(16, 4), (128, 8), (512, 22)])
def test_the_selection_is_top_k_and_the_mask_to_the_element(experts, top_k, scoring, impl):
    """``ops.moe.top_scores`` (the rounds as XLA composes them, and the
    kernels in the interpreter) against ``lax.top_k`` and the mask: the same
    experts in the same order whatever ties there are, the same scores, the
    same weights, the same gradients; none to the bias. 300 tokens are no
    whole tile."""
    n, biased = 300, scoring == "sigmoid"
    p, bias = _scores_with_ties(n, experts, biased, seed=experts + top_k)
    select = p if bias is None else p + bias
    ordered = jnp.sort(select, -1)
    assert int(jnp.sum(ordered[:, 1:] == ordered[:, :-1])) > n // 2  # equal scores there are
    _, want_e = jax.lax.top_k(select, top_k)
    top_e, top_p = moe.top_scores(p, bias, top_k, impl)
    np.testing.assert_array_equal(top_e, want_e)
    np.testing.assert_array_equal(top_p, jnp.take_along_axis(p, want_e, axis=-1))
    # through the router: the weights to the bit, the gradients to rounding
    k = jax.random.split(jax.random.key(top_k), 3)
    u = jax.random.normal(k[0], (n, 32))
    u = u.at[:40].set(u[0])
    w_r = jax.random.normal(k[1], (32, experts)) * 0.3
    w_r = w_r.at[:, 5].set(w_r[:, 3])  # two experts every token scores alike
    if biased:
        bias = bias.at[5].set(bias[3])
    cot = jax.random.normal(k[2], (n, top_k))
    want = route_by_sort_and_mask(u, w_r, top_k, scoring, bias, 2.5)
    got = moe.route(u, w_r, top_k, scoring, bias, 2.5, impl)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])

    def loss(route):
        return lambda u, w, b: jnp.sum(route(u, w, top_k, scoring, b, 2.5)[1] * cot)

    wrt = (0, 1, 2) if biased else (0, 1)
    want_g = jax.grad(loss(route_by_sort_and_mask), wrt)(u, w_r, bias)
    got_g = jax.grad(loss(lambda *a: moe.route(*a, impl)), wrt)(u, w_r, bias)
    for a, b in zip(got_g[:2], want_g[:2]):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * float(jnp.max(jnp.abs(b)))
    assert not biased or not np.any(got_g[2])


# ------------------------------------------------------------ expert layer


def _expert_weights(n_experts=16, hidden=32, width=24, seed=3):
    k = jax.random.split(jax.random.key(seed), 5)
    return dict(
        router=jax.random.normal(k[0], (hidden, n_experts)) * 0.5,
        w_gate=jax.random.normal(k[1], (n_experts, hidden, width)) * 0.2,
        w_up=jax.random.normal(k[2], (n_experts, hidden, width)) * 0.2,
        w_down=jax.random.normal(k[3], (n_experts, width, hidden)) * 0.2,
    ), jax.random.normal(k[4], (96, hidden))


# The grouped products in true float32 against the `highest` reference, and
# with bfloat16 operands (`bf16_dots`, what the configurations state and the
# chip runs) against the reference that rounds the same operands.
PRECISIONS = pytest.mark.parametrize(
    "mode,bf16", [("highest", False), ("bf16_operands", True)], ids=["float32", "bf16_dots"]
)


def _share(p, u, lo, hi, **kw):
    top_e, top_w = moe.route(u, p["router"], 4)
    out, plan = moe.expert_ffn(
        u, p["w_gate"][lo:hi], p["w_up"][lo:hi], p["w_down"][lo:hi], top_e, top_w,
        (lo, hi), num_experts=16, **kw,
    )
    return out, plan


def _held(p, lo=4, hi=8):
    return {**p, **{k: p[k][lo:hi] for k in ("w_gate", "w_up", "w_down")}}


def _one_chunk_form(p, u, lo, hi, bf16):
    """The layer as it was written before the pieces: every sorted row gathered
    at once, one grouped product a kernel over them all, one scatter-add."""
    top_e, top_w = moe.route(u, p["router"], 4)
    plan = moe.plan_assignments(top_e, (lo, hi))
    tok = plan.order // 4
    live = (jnp.arange(plan.order.shape[0]) < plan.n_rows)[:, None]
    w = jnp.where(plan.held, top_w, 0.0).reshape(-1)[plan.order][:, None]
    mxu = lambda x: x.astype(jnp.bfloat16) if bf16 else x  # noqa: E731
    dot = lambda x, k: jax.lax.ragged_dot(  # noqa: E731
        mxu(x), mxu(p[k][lo:hi]), plan.sizes, preferred_element_type=jnp.float32
    )
    xs = u[tok]
    y = dot(jax.nn.silu(dot(xs, "w_gate")) * dot(xs, "w_up"), "w_down")
    return jnp.zeros_like(u).at[tok].add(jnp.where(live, y, 0) * jnp.where(live, w, 0))


# How the 92 assignments that experts 4-7 hold of `_expert_weights` fall into
# chunks (``chunk_rows``) and a chunk into pieces (``moe.PIECE_ROWS``).
LIVE = 92
SPLITS = pytest.mark.parametrize("chunk_rows,piece_rows", [
    pytest.param(None, None, id="default"),
    pytest.param(LIVE + 1, None, id="one-row-under-the-chunk"),
    pytest.param(LIVE, None, id="the-chunk-to-the-row"),
    pytest.param(LIVE - 1, None, id="one-row-over-the-chunk"),
    pytest.param(64, 16, id="two-chunks-of-four-pieces"),
    pytest.param(LIVE, 23, id="four-whole-pieces"),
    pytest.param(96, 32, id="the-last-piece-part-held"),
])


@pytest.fixture
def pieces(monkeypatch):
    def of(rows):
        if rows is not None:
            monkeypatch.setattr(moe, "PIECE_ROWS", rows)
    return of


def _starved(p, u):
    """Inputs and a router under which no token chooses experts 4-7."""
    return {**p, "router": p["router"].at[:, 4:8].set(-1.0)}, jnp.abs(u)


@PRECISIONS
@SPLITS
def test_the_shares_partial_sums_add_up_to_the_whole_layer(
    chunk_rows, piece_rows, mode, bf16, pieces
):
    """Guide section 4's share test: the partial sums of all four shares of
    4 experts add up to the uncut reference's layer output over all 16
    (attention is upstream of the split and counted once).  Tolerance:
    float32 sums in another order.  However the held rows fall into chunks
    and pieces, a share is the one-chunk form's to the bit at float32: a
    token's terms are added in the same order."""
    pieces(piece_rows)
    p, u = _expert_weights()
    whole, _ = reference_trunk._moe(
        p, u, dict(experts_held=[0, 16], experts_per_tok=4), mode
    )
    shares, plans = zip(*(
        _share(p, u, lo, lo + 4, chunk_rows=chunk_rows, bf16_dots=bf16)
        for lo in (0, 4, 8, 12)
    ))
    assert int(plans[1].n_rows) == LIVE
    np.testing.assert_allclose(sum(shares), whole, atol=2e-5)
    one, _ = reference_trunk._moe(
        _held(p), u, dict(experts_held=[4, 8], experts_per_tok=4), mode
    )
    np.testing.assert_allclose(shares[1], one, atol=2e-5)  # the same terms left out
    before = _one_chunk_form(p, u, 4, 8, bf16)
    if bf16:  # the CPU's bfloat16 product blocks a row's sum by the batch's size
        np.testing.assert_allclose(shares[1], before, atol=1e-6)
    else:
        np.testing.assert_array_equal(shares[1], before)


@PRECISIONS
@pytest.mark.parametrize("chunk_rows,piece_rows", [(None, None), (64, None), (64, 16), (None, 16)])
def test_no_token_is_dropped_when_every_token_chooses_the_same_expert(
    chunk_rows, piece_rows, mode, bf16, pieces
):
    """Every token's first choice is expert 5, held here: it gets all 96
    tokens, four and a half times a balanced expert's 24, in one chunk or in
    six, a chunk in one piece or in many, and the output is the dense one."""
    pieces(piece_rows)
    p, u = _expert_weights()
    u = jnp.abs(u)  # positive inputs, so a positive router column wins for every token
    p["router"] = (p["router"] * 1e-3).at[:, 5].set(1.0)
    out, plan = _share(p, u, 4, 8, chunk_rows=chunk_rows, bf16_dots=bf16)
    assert int(plan.sizes[1]) == u.shape[0] and int(plan.n_rows) >= u.shape[0]
    want, _ = reference_trunk._moe(
        _held(p), u, dict(experts_held=[4, 8], experts_per_tok=4), mode
    )
    np.testing.assert_allclose(out, want, atol=2e-5)


@PRECISIONS
@pytest.mark.parametrize("starved", [False, True], ids=["held", "no-row-held"])
def test_a_share_nobody_chooses_is_zero_and_so_are_its_gradients(starved, mode, bf16):
    """No assignment lands on experts 4-7: the share's output and its five
    gradients are zeros (the dense form's too), and nothing of it runs. The
    same inputs with the published router are the control."""
    p, u = _expert_weights()
    p, u = _starved(p, u) if starved else (p, jnp.abs(u))
    loss = lambda u, p: jnp.sum(_share(p, u, 4, 8, bf16_dots=bf16)[0] ** 2)  # noqa: E731
    out, plan = _share(p, u, 4, 8, bf16_dots=bf16)
    grads = jax.tree_util.tree_leaves(jax.grad(loss, (0, 1))(u, p))
    assert (int(plan.n_rows) == 0) == starved
    assert all(bool(jnp.all(g == 0)) for g in [out] + grads) == starved
    want, _ = reference_trunk._moe(
        _held(p), u, dict(experts_held=[4, 8], experts_per_tok=4), mode
    )
    np.testing.assert_allclose(out, want, atol=2e-5)


@PRECISIONS
def test_rows_past_the_held_ones_reach_no_result(mode, bf16, pieces, monkeypatch):
    """The buffers a loop fills piece by piece start uninitialised on the chip
    (``lax.empty``): with NaN in their place, the output and the five
    gradients are what cleared buffers give, to the bit, in two chunks of four
    pieces each with its last piece part held."""
    pieces(16)
    p, u = _expert_weights()
    loss = lambda u, p: jnp.sum(  # noqa: E731
        _share(p, u, 4, 8, chunk_rows=64, bf16_dots=bf16)[0] ** 2
    )
    results = []
    for fill in (jnp.zeros, lambda shape, dtype: jnp.full(shape, jnp.nan, dtype)):
        monkeypatch.setattr(moe, "_buffer", fill)
        out, _ = _share(p, u, 4, 8, chunk_rows=64, bf16_dots=bf16)
        results.append([out] + jax.tree_util.tree_leaves(jax.grad(loss, (0, 1))(u, p)))
    for cleared, poisoned in zip(*results):
        np.testing.assert_array_equal(poisoned, cleared)


@PRECISIONS
@SPLITS
def test_expert_layer_gradients_match_the_dense_form_under_vmap(
    chunk_rows, piece_rows, mode, bf16, pieces
):
    """The hand-written backward pass against autodiff of the dense form, and
    the same under ``vmap`` (the data-parallel burst maps the update over its
    device axis), where both passes run a mapped element at a time.  A
    kernel's gradient is summed chunk by chunk, so where a chunk's edge falls
    changes the order of that sum and nothing else."""
    pieces(piece_rows)
    p, u = _expert_weights()
    chunk_rows = 64 if chunk_rows is None else chunk_rows  # the case this test had

    def dense(u, p):
        out, _ = reference_trunk._moe(
            _held(p), u, dict(experts_held=[4, 8], experts_per_tok=4), mode
        )
        return jnp.sum(out ** 2)

    sparse = lambda u, p: jnp.sum(  # noqa: E731
        _share(p, u, 4, 8, chunk_rows=chunk_rows, bf16_dots=bf16)[0] ** 2
    )
    want = jax.grad(dense, (0, 1))(u, p)
    got = jax.grad(sparse, (0, 1))(u, p)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.max(jnp.abs(w)) + 1))
    mapped = jax.vmap(jax.grad(sparse), in_axes=(0, None))(jnp.stack([u, 0.5 * u]), p)
    np.testing.assert_allclose(mapped[0], got[0], atol=1e-5)


# ------------------------------------------------------- trunk and SAC step


def test_trunk_forward_matches_the_reference():
    cfg, sac = _learner()
    state = _seeded_state(sac)
    obs = _batch(1).states
    trunk = SequenceTrunk(spec=TrunkSpec.from_config(cfg))
    got = trunk.apply({"params": state.critic_params["params"]["trunk"]}, obs)
    want, _ = reference_trunk.trunk(state.critic_params["params"]["trunk"], obs, MODEL, "highest")
    np.testing.assert_allclose(got, want, atol=2e-5)  # float32, another order of sums


def test_a_trunk_that_keeps_the_kernels_off_keeps_the_selections_off_too(monkeypatch):
    """The Trainer's host mirror is compiled for the CPU beside a TPU, and
    ``auto`` is resolved by the process's default backend: a trunk handed
    ``xla_attention`` takes the selection as XLA composes it too, so its
    program holds no kernel (128 experts: a size the kernels have blocks for)."""
    from torch_actor_critic_tpu.models.sequence import SparseMoE, xla_attention

    cfg, _ = _learner(trunk_experts=128, trunk_experts_held=(8, 16))
    spec = TrunkSpec.from_config(cfg)
    obs = _batch(1).states
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mirror = SequenceTrunk(spec=spec, attention_fn=xla_attention)
    params = mirror.init(jax.random.key(0), obs)
    text = jax.jit(mirror.apply).lower(params, obs).as_text()
    assert "tpu_custom_call" not in text and "router_top_k" not in text
    assert np.all(np.isfinite(mirror.apply(params, obs)))
    # the layer by itself reaches for the kernels, which a CPU cannot lower
    layer, u = SparseMoE(spec), jnp.zeros((4, HISTORY, spec.hidden))
    with pytest.raises(Exception, match="[Ii]nterpret|CPU|cpu"):
        jax.jit(layer.apply).lower(jax.eval_shape(layer.init, jax.random.key(0), u), u)


def test_the_stated_precision_rounds_the_kernels_operands_on_the_cpu_too():
    """``trunk_bf16_dots`` (the default, what the benchmark's configuration
    states and the chip times) is a property of the configuration and not of
    the platform: on the CPU the expert products round their operands too.
    XLA:CPU's own products stay float32, so the trunk lands a bfloat16
    rounding (2^-9 of values near 3, through two layers) from the float32
    reference: well over float32's order-of-sums 2e-5, well under 1e-2. One
    step at that precision keeps the critic loss within 1% of the reference
    that rounds every product."""
    cfg, sac = _learner(trunk_bf16_dots=True)
    assert TrunkSpec.from_config(cfg).bf16_dots and TrunkSpec().bf16_dots
    state, batch = _seeded_state(sac), _batch(2)
    params = state.critic_params["params"]["trunk"]
    got = SequenceTrunk(spec=TrunkSpec.from_config(cfg)).apply({"params": params}, batch.states)
    want, _ = reference_trunk.trunk(params, batch.states, MODEL, "highest")
    assert 1e-4 < float(jnp.max(jnp.abs(got - want))) < 1e-2
    _, metrics = jax.jit(sac.update)(state, batch)
    _, key_q, key_pi = jax.random.split(state.rng, 3)
    eps = lambda k: jax.random.normal(k, (1, 4, ACT), jnp.float32)  # noqa: E731
    b = dict(states=batch.states, actions=batch.actions, rewards=batch.rewards,
             next_states=batch.next_states, done=batch.done)
    _, loss_q, _, _, _ = reference_trunk.update(
        reference_trunk.init_state(state.actor_params, state.critic_params),
        jax.tree_util.tree_map(lambda x: x[None], b), eps(key_q), eps(key_pi),
        MODEL, SAC_MATH, "bf16_operands",
    )
    assert float(metrics["loss_q"]) == pytest.approx(float(loss_q), rel=1e-2)


@pytest.mark.parametrize("remat", [0, 1])
def test_shared_trunk_step_matches_the_reference(remat):
    """One gradient step of the program (``SAC.update``) against the plain
    reference on the same batch and noise: losses, every parameter after the
    step, the polyak target, Adam's second moments, and every expert choice.
    Tolerances as ``test_bench_correct.py``'s: float32, another order."""
    cfg, sac = _learner(trunk_remat=remat, trunk_report_choices=True)
    state, batch = _seeded_state(sac), _batch(2)
    new_state, metrics = jax.jit(sac.update)(state, batch)
    _, key_q, key_pi = jax.random.split(state.rng, 3)
    eps = lambda k: jax.random.normal(k, (1, 4, ACT), jnp.float32)  # noqa: E731
    lead = lambda tree: jax.tree_util.tree_map(lambda x: x[None], tree)  # noqa: E731
    b = dict(states=batch.states, actions=batch.actions, rewards=batch.rewards,
             next_states=batch.next_states, done=batch.done)
    ref, loss_q, loss_pi, chosen, _ = reference_trunk.update(
        reference_trunk.init_state(state.actor_params, state.critic_params), lead(b),
        eps(key_q), eps(key_pi), MODEL, SAC_MATH,
    )
    assert float(metrics["loss_q"]) == pytest.approx(float(loss_q), rel=1e-5)
    assert float(metrics["loss_pi"]) == pytest.approx(float(loss_pi), rel=1e-5)
    np.testing.assert_array_equal(metrics["trunk/choices_first"], chosen[0])
    assert float(metrics["trunk/held_assignments"]) == float(
        np.isin(np.asarray(chosen[0]), [2, 3, 4, 5]).sum()
    )
    # Adam's first step moves an element by lr * g / (|g| + 1e-8): where the
    # gradient is near that epsilon the two sides' float32 sums decide what
    # fraction of lr = 3e-4 it moves, so parameters are held to 1e-5 absolute
    # (3% of a step) and Adam's second moments, which are plain squares, to
    # a relative 1e-3.
    for got, want, tol in (
        (new_state.actor_params, ref["actor"], dict(rtol=2e-4, atol=1e-5)),
        (new_state.critic_params, ref["critic"], dict(rtol=2e-4, atol=1e-5)),
        (new_state.target_critic_params, ref["target"], dict(rtol=2e-4, atol=1e-7)),
        (new_state.q_opt_state[0].nu, ref["q_nu"], dict(rtol=1e-3, atol=1e-12)),
        (new_state.pi_opt_state[0].nu, ref["pi_nu"], dict(rtol=1e-3, atol=1e-12)),
    ):
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, w, **tol)


def test_the_trunk_is_trained_by_the_critic_loss_alone():
    """The actor's trained parameters are the policy head; the trunk lives in
    the critic's tree, the target covers it, and acting takes both."""
    _, sac = _learner()
    state = _seeded_state(sac)
    assert set(state.actor_params["params"]) == {"mu", "log_std"}
    assert set(state.critic_params["params"]) == {"trunk", "ensemble"}
    assert set(state.target_critic_params["params"]) == {"trunk", "ensemble"}
    new_state, _ = jax.jit(sac.update)(state, _batch(3))
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), new_state.critic_params, state.critic_params
    )
    assert all(jax.tree_util.tree_leaves(moved))  # every leaf of trunk and heads
    acting = policy_params(new_state.actor_params, new_state.critic_params)
    assert set(acting["params"]) == {"mu", "log_std", "trunk"}
    action = sac.select_action(acting, _batch(3).states, jax.random.key(1))
    assert action.shape == (4, ACT) and bool(jnp.all(jnp.abs(action) <= 1.0))


def test_policy_params_is_the_identity_for_separate_networks():
    cfg = SACConfig(batch_size=4)
    env = types.SimpleNamespace(
        act_dim=ACT, act_limit=1.0, obs_spec=jax.ShapeDtypeStruct((OBS,), jnp.float32)
    )
    sac = make_learner(cfg, *build_models(cfg, env), ACT)
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS,)))
    assert policy_params(state.actor_params, state.critic_params) is state.actor_params


# ------------------------------------------ the programs the benchmark has

# sha256 of the lowered (StableHLO) data-parallel burst of the reference MLP,
# the visual stack and the small transformer sequence stack, read on the
# parent commit of PR 26 (09dbf80) and on PR 26: the trunk rewired the losses
# those programs share, and they lower to the same text.  A PR that means to
# change one of these programs replaces its hash (scripts in CHANGES.md, PR 26).
GOLDEN = {
    "mlp": "6607b7a076c9a5453c89339f460dae787fb2b6855ecdf1259849ebb8fd9476a6",
    "visual": "4d30ef026bbd543419d11e497cc54a95f30a98692a59026881a5672d7e681621",
    "sequence": "b638b4523cb7e1661437e32ac1670a3ead78a71741e9683d4d99dac3b26d9c7a",
}


def _burst_text(cfg, obs_spec, act_dim):
    from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
    from torch_actor_critic_tpu.core.types import BufferState
    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC
    from torch_actor_critic_tpu.parallel.mesh import make_mesh

    env = types.SimpleNamespace(act_dim=act_dim, act_limit=1.0, obs_spec=obs_spec)
    sac = make_learner(cfg, *build_models(cfg, env), act_dim)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=jax.devices()[:1]))
    example = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), obs_spec)
    state = jax.eval_shape(sac.init_state, jax.random.key(0), example)

    def rows(n):
        one = jax.eval_shape(lambda: init_replay_buffer(n, obs_spec, act_dim).data)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), one
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(data=rows(256), ptr=index, size=index)
    chunk = rows(cfg.update_every)
    return learner._build_burst(cfg.update_every, state, ring, chunk).lower(
        state, ring, chunk
    ).as_text()


@pytest.mark.parametrize("family", sorted(GOLDEN))
def test_the_bursts_the_benchmark_has_lower_to_what_they_did(family):
    from torch_actor_critic_tpu.core.types import MultiObservation

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    if family == "mlp":
        text = _burst_text(SACConfig(batch_size=8, update_every=4, buffer_size=256), f32(17), 6)
    elif family == "visual":
        spec = MultiObservation(
            features=f32(12), frame=jax.ShapeDtypeStruct((44, 44, 3), jnp.uint8)
        )
        text = _burst_text(SACConfig(batch_size=4, update_every=2, buffer_size=256), spec, 5)
    else:
        cfg = SACConfig(batch_size=4, update_every=2, buffer_size=256, history_len=8)
        text = _burst_text(cfg, f32(8, 5), 3)
    assert hashlib.sha256(text.encode()).hexdigest() == GOLDEN[family]


def test_the_trunks_parts_carry_their_own_scopes():
    """The compiled step names the trunk's parts inside ``tac/critic``: the
    innermost scope of an instruction is the part's, in the forward pass and
    in the hand-written backward passes (flash kernels, expert layer) alike."""
    from torch_actor_critic_tpu.telemetry import scopes

    _, sac = _learner()
    state, batch = _seeded_state(sac), _batch(4)
    text = jax.jit(sac.update).lower(state, batch).compile().as_text()
    table = scopes.scope_table(text)
    found = {s.rstrip(scopes.INHERITED) for counts in table.values() for s in counts if s}
    assert {
        scopes.TRUNK_EMBED, scopes.TRUNK_ATTENTION, scopes.TRUNK_MOE_ROUTE,
        scopes.TRUNK_MOE_EXPERTS, scopes.CRITIC, scopes.ACTOR, scopes.OPTIMIZER,
        scopes.POLYAK,
    } <= found
    assert scopes.scope_of("jit(f)/tac/critic/jvp(x)/tac/trunk/moe/experts/dot") == (
        scopes.TRUNK_MOE_EXPERTS
    )
    # a backward-pass instruction of the expert layer keeps the layer's scope
    backward = [
        line for line in text.splitlines()
        if "transpose(" in line and scopes.TRUNK_MOE_EXPERTS in line
    ]
    assert backward
