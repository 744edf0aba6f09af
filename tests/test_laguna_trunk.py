"""The ``laguna`` history trunk at a small size on the CPU, seeded weights:
the sliding window in every attention path (the flash kernels in interpret
mode, forward and both gradients), partial and YaRN rotary against a direct
complex rotation, the per-head gate, the dense block, each sublayer and sublayer against the plain reference (``benchmark/harness/
reference_laguna_trunk.py``, which imports nothing of the program), and a
chip's shares adding up to the uncut layer. The whole stack, one SAC step and
the stack through ``build_models`` and ``Trainer`` are
``test_laguna_trunk_stack.py``'s."""

import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from laguna_helpers import HIDDEN, SHARE, WHOLE, YARN, T, _inputs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_laguna_trunk as reference  # noqa: E402
from benchmark.harness import trunk_weights  # noqa: E402
from torch_actor_critic_tpu.models import TrunkSpec  # noqa: E402
from torch_actor_critic_tpu.models import sequence  # noqa: E402
from torch_actor_critic_tpu.ops import attention as ops  # noqa: E402

# ------------------------------------------------------------- the window


def _qkv(t, heads=4, kv_heads=2, d=16, batch=1, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    return (
        jax.random.normal(k[0], (batch, heads, t, d)),
        jax.random.normal(k[1], (batch, kv_heads, t, d)),
        jax.random.normal(k[2], (batch, kv_heads, t, d)),
        jax.random.normal(k[3], (batch, heads, t, d)),
    )


def test_the_window_is_the_latest_positions_the_querys_own_among_them():
    i = jnp.arange(7)
    seen = np.asarray(ops._visible(i[:, None], i[None, :], 1, 3))
    assert seen[5].tolist() == [False, False, False, True, True, True, False]
    assert seen[1].tolist() == [True, True, False, False, False, False, False]
    assert seen.sum() == 1 + 2 + 3 * 5
    assert np.array_equal(seen, np.asarray(reference.sees(7, 3)))
    # a window as long as the history is the causal mask, and so is none
    assert np.array_equal(np.asarray(ops._visible(i[:, None], i[None, :], 1, 7)),
                          np.asarray(ops._visible(i[:, None], i[None, :])))


# Windows smaller than, equal to and larger than a block; a history (128)
# that is no multiple of the window; blocks of unequal size; a block-causal
# mask inside the window.
WINDOW_CASES = {
    "smaller_than_a_block": (12, 32, 32, 1),
    "a_block": (32, 32, 32, 1),
    "larger_than_a_block": (50, 32, 32, 1),
    "two_blocks_and_a_part": (75, 32, 32, 1),
    "wide_q_blocks": (20, 64, 32, 1),
    "wide_k_blocks": (20, 32, 64, 1),
    "block_causal_inside": (12, 32, 32, 4),
}


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_windowed_flash_kernels_match_the_masked_reference(case):
    """Forward, dQ and dK/dV in the Pallas interpreter against the full score
    matrix under the same mask, grouped heads, ``bf16_dots`` off."""
    window, block_q, block_k, block_length = WINDOW_CASES[case]
    q, k, v, mix = _qkv(128, heads=2, kv_heads=1)

    def flash(q, k, v):
        return ops.flash_attention(
            q, k, v, True, block_q, block_k, True, 128, block_length, False, window
        )

    def dense(q, k, v):
        return ops.reference_attention(
            q, k, v, causal=True, block_length=block_length, window=window
        )

    def both(fn):  # the output, and the gradients of a scalar of it
        return jax.jit(jax.value_and_grad(
            lambda *a: (lambda out: (jnp.sum(out * mix), out))(fn(*a)), (0, 1, 2), has_aux=True
        ))

    with jax.default_matmul_precision("highest"):
        (_, out), got = both(flash)(q, k, v)
        (_, ref), want = both(dense)(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, atol=5e-5, err_msg=name)


@pytest.mark.parametrize("window, t", [(5, 40), (64, 200)])
def test_the_xla_path_takes_the_window_too(window, t):
    """``blockwise_attention`` (the host mirror's ``xla_attention``) under the
    window, a history that is no multiple of its block, with its gradient."""
    q, k, v, mix = _qkv(t, seed=2)
    want = ops.reference_attention(q, k, v, causal=True, window=window)
    got = sequence.xla_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)
    grads = [
        jax.grad(lambda q: jnp.sum(fn(q) * mix))(q) for fn in (
            lambda q: ops.blockwise_attention(q, k, v, True, block_k=64, window=window),
            lambda q: ops.reference_attention(q, k, v, causal=True, window=window),
        )
    ]
    np.testing.assert_allclose(*grads, atol=5e-5)


def _blocks_with_a_visible_pair(t, block_q, block_k, block_length, window):
    i = np.arange(t)
    seen = np.asarray(ops._visible(i[:, None], i[None, :], block_length, window))
    tiles = seen.reshape(t // block_q, block_q, t // block_k, block_k).any(axis=(1, 3))
    return int(tiles.sum())


@pytest.mark.parametrize("t, block_q, block_k, block_length, window", [
    (4096, 512, 512, 1, 512), (4096, 256, 256, 1, 512), (4096, 128, 128, 1, 512),
    (4096, 512, 512, 1, None), (1024, 512, 512, 4, None), (512, 128, 64, 1, 100),
    (512, 64, 128, 4, 70), (256, 64, 64, 1, 300),
])
def test_the_kernels_visit_the_blocks_with_a_visible_pair_and_no_other(
    t, block_q, block_k, block_length, window,
):
    """The schedules the grids are built from, against a brute-force count
    over the mask: every block with a visible pair is a step, and no block
    without one is (both edges are skipped)."""
    visited = ops.visited_key_blocks(t, block_q, block_k, block_length, window)
    assert visited == _blocks_with_a_visible_pair(t, block_q, block_k, block_length, window)
    needed = ops._pair_needed(t, t, block_q, block_k, True, block_length, window)
    for group in (None, 1):  # the forward and dQ sweep, and the dK/dV sweep: the same pairs
        assert len(ops._tile_schedule(needed, group)[0]) == visited


def test_a_window_of_512_at_4096_visits_under_half_of_the_causal_blocks():
    causal = ops.visited_key_blocks(4096, 512, 512)
    assert (causal, ops.visited_key_blocks(4096, 512, 512, 1, 512)) == (36, 15)
    assert ops.visited_key_blocks(4096, 128, 128, 1, 512) / ops.visited_key_blocks(4096, 128, 128) < 0.31


@pytest.mark.parametrize("block_length", [1, 4])
def test_without_a_window_the_kernels_are_the_kernels_there_were(block_length):
    """The window went into the kernels under both trunk cells' feet: with
    none they give what the full score matrix under the causal or block-causal
    mask gives, forward and both gradients, bit for bit what a window as long
    as the history gives, and they visit every block that mask lets through.
    (That the unwindowed programs are the parent's text is
    ``scripts/burst_stablehlo.py``'s to show, between two checkouts.)"""
    q, k, v, mix = _qkv(128, heads=4, kv_heads=2)

    def both(window, fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (lambda out: (jnp.sum(out * mix), out))(fn(*a, window)), (0, 1, 2), has_aux=True
        ))(q, k, v)

    flash = lambda q, k, v, w: ops.flash_attention(  # noqa: E731
        q, k, v, True, 32, 32, True, 128, block_length, False, w
    )
    dense = lambda q, k, v, w: ops.reference_attention(  # noqa: E731
        q, k, v, causal=True, block_length=block_length, window=w
    )
    with jax.default_matmul_precision("highest"):
        (_, out), got = both(None, flash)
        (_, ref), want = both(None, dense)
        (_, whole), got_whole = both(128, flash)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    np.testing.assert_array_equal(out, whole)
    for g, w, gw, name in zip(got, want, got_whole, ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, atol=5e-5, err_msg=name)
        np.testing.assert_array_equal(g, gw, err_msg=name)
    assert ops.visited_key_blocks(128, 32, 32, block_length) == 4 * 5 // 2


def test_a_window_is_refused_where_it_is_no_causal_mask_over_one_history():
    q, k, v, _ = _qkv(128)
    with pytest.raises(ValueError, match="a window is a causal mask"):
        ops.flash_attention(q, k, v, False, None, None, True, 128, 1, False, 8)
    with pytest.raises(ValueError, match="a window is a causal mask"):
        ops.flash_attention(q, k[:, :, :64], v[:, :, :64], True, 64, 64, True, 128, 1, False, 8)


# ---------------------------------------------------------------- rotary

# Laguna-S-2.1's rope_parameters.full_attention; its beta_fast 32, beta_slow 1
# and attention_factor 1.4852030263919618 are YaRN's own constants
PUBLISHED_FULL = ops.Rope(500000.0, 0.5, 128.0, 8192)


def _complex_rotation(x, pos, freq, factor):
    """``x`` ``(B, T, heads, d)``: the first ``2 * len(freq)`` channels as
    complex numbers ``x_c + i x_{c + half}``, each turned by ``pos * freq_c``."""
    half = len(freq)
    z = np.asarray(x[..., :half]) + 1j * np.asarray(x[..., half:2 * half])
    z = z * factor * np.exp(1j * np.asarray(pos)[None, :, None, None] * freq)
    return np.concatenate([z.real, z.imag, np.asarray(x[..., 2 * half:])], axis=-1)


def test_yarn_blends_each_frequency_by_its_band():
    """The published full-attention rotary over half of a head of 128: pairs
    0-9 turn at their own frequency, pairs 18-31 at a 128th of it, those
    between by the linear ramp; the attention factor is YaRN's default."""
    r = PUBLISHED_FULL.rotated(128)
    assert r == 64
    own = 500000.0 ** (-np.arange(32) / 32.0)
    got = np.asarray(PUBLISHED_FULL.inv_freq(128), np.float64)
    turns = lambda pair: 8192 * own[pair] / (2 * math.pi)  # noqa: E731
    assert turns(9) > 32 > turns(10) and turns(17) > 1 > turns(18)
    np.testing.assert_allclose(got[:10], own[:10], rtol=1e-6)
    np.testing.assert_allclose(got[18:], own[18:] / 128, rtol=1e-6)
    ramp = (np.arange(10, 18) - 9) / 9.0
    np.testing.assert_allclose(got[10:18], own[10:18] * (1 - ramp + ramp / 128), rtol=1e-5)
    # what the program holds as YaRN's constants is what the row states
    assert PUBLISHED_FULL.scale == pytest.approx(1.4852030263919618, rel=1e-12)
    assert (ops.YARN_BETA_FAST, ops.YARN_BETA_SLOW, ops.Rope(1e4).scale) == (32.0, 1.0, 1.0)
    model = dict(
        head_dim=128, rope_share=0.5, rope_theta=500000.0, rope_yarn_factor=128.0,
        rope_yarn_positions=8192, rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
        rope_attention_factor=1.4852030263919618,
    )
    freq, factor = reference.pair_frequencies(model, "F")
    np.testing.assert_allclose(freq, got, rtol=1e-6)
    assert factor == pytest.approx(PUBLISHED_FULL.scale, rel=1e-12)


@pytest.mark.parametrize("rope", [
    PUBLISHED_FULL, ops.Rope(1e4, 0.5), ops.Rope(1e4, 1.0, 8.0, 16),
], ids=["published_full", "partial", "yarn_whole_head"])
def test_partial_and_scaled_rotary_is_a_complex_rotation_of_the_turned_pairs(rope):
    x = jax.random.normal(jax.random.key(1), (2, 10, 3, 128))
    pos = jnp.arange(40, 50)
    want = _complex_rotation(x, pos, np.asarray(rope.inv_freq(128), np.float64), rope.scale)
    np.testing.assert_allclose(ops.rotary(x, pos, rope), want, atol=2e-5)
    if rope.share < 1.0:  # the other channels pass
        np.testing.assert_array_equal(ops.rotary(x, pos, rope)[..., 64:], x[..., 64:])


def test_a_plain_rope_is_the_rotary_there_was():
    x = jax.random.normal(jax.random.key(2), (1, 6, 2, 16))
    pos = jnp.arange(6)
    np.testing.assert_allclose(
        ops.rotary(x, pos, ops.Rope(1e4)), ops.rotary(x, pos, 1e4), atol=1e-6
    )
    # and a spec that states no more than a theta hands the kernels' pass a float
    assert TrunkSpec().rope_of("S") == 1e6 and TrunkSpec(window_rope_theta=1e4).rope_of("W") == 1e4
    assert TrunkSpec(rope_share=0.5).rope_of("F") == ops.Rope(1e6, 0.5)


# ------------------------------------------------- sublayers against the reference

def _model(**changed):
    return {**WHOLE, **YARN, **changed}


def _abstract(module, *args):
    return jax.eval_shape(lambda: module.init(jax.random.key(0), *args))["params"]


def _seeded(module, *args, seed=3):
    return trunk_weights.init_params(jax.random.key(seed), _abstract(module, *args))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention(spec, kind, params, u):
    return sequence.GroupedQueryAttention(spec, kind=kind).apply(
        {"params": params}, u, jnp.arange(u.shape[1])
    )


def _attention_reference(kind, params, u, model):
    return jax.jit(jax.vmap(
        lambda p, u_b: reference._attention(p, u_b, model, kind, "highest"), (None, 0)
    ))(params, u)


@functools.partial(jax.jit, static_argnums=0)
def _experts(spec, params, u):
    return sequence.SparseMoE(spec).apply({"params": params}, u, mutable=["moe_stats"])[0]


def _experts_reference(params, u, model):
    return jax.jit(lambda p, u: reference._experts(
        p, u.reshape(-1, HIDDEN), model, "highest"
    )[0].reshape(u.shape))(params, u)


def _assert_gradients_match(got, want, atol=3e-5):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(g, w, atol=atol * scale, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("kind", ["F", "W"])
def test_an_attention_sublayer_matches_the_reference_forward_and_gradient(kind):
    """Head count, window and rotary by the layer's kind and the gate a head,
    given a share, against the reference given the same share."""
    spec, model, u = TrunkSpec(**{**WHOLE, **SHARE}), _model(**SHARE), _inputs()
    params = _seeded(sequence.GroupedQueryAttention(spec, kind=kind), u, jnp.arange(T))
    heads = 3 if kind == "W" else 2
    assert params["q_proj"]["kernel"].shape == (HIDDEN, heads * 8)
    assert params["g_proj"]["kernel"].shape == (HIDDEN, heads)
    mix = jax.random.normal(jax.random.key(9), u.shape)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            _attention(spec, kind, params, u), _attention_reference(kind, params, u, model), atol=2e-5
        )
        got = jax.grad(lambda p, u: jnp.sum(_attention(spec, kind, p, u) * mix), (0, 1))(params, u)
        want = jax.grad(
            lambda p, u: jnp.sum(_attention_reference(kind, p, u, model) * mix), (0, 1)
        )(params, u)
    _assert_gradients_match(got, want)


def test_the_gate_multiplies_each_heads_output_before_o_proj():
    """A gate shut on one head takes that head's rows of ``W_o`` out of the
    sum; a gate wide open leaves the ungated layer's output."""
    spec, u = TrunkSpec(**{**WHOLE, **SHARE}), _inputs(seed=8)
    params = _seeded(sequence.GroupedQueryAttention(spec, kind="F"), u, jnp.arange(T))
    ungated = {k: v for k, v in params.items() if k != "g_proj"}
    # the gate reads u's first channel alone, held at 1: sigmoid(+-30) a head
    u_gate = u.at[..., 0].set(1.0)
    gate = jnp.zeros((HIDDEN, 2)).at[0].set(jnp.array([30.0, 30.0]))
    opened = _attention(spec, "F", {**params, "g_proj": {"kernel": gate}}, u_gate)
    plain = _attention(
        TrunkSpec(**{**WHOLE, **SHARE, "head_gate": False}), "F", ungated, u_gate
    )
    np.testing.assert_allclose(opened, plain, atol=1e-5)
    shut = gate.at[0, 1].set(-30.0)
    one_head = {
        **ungated, "q_proj": {"kernel": params["q_proj"]["kernel"][:, :8]},
        "o_proj": {"kernel": params["o_proj"]["kernel"][:8]},
    }
    first_alone = _attention(
        TrunkSpec(**{**WHOLE, **SHARE, "head_gate": False, "q_heads": 1}), "F", one_head, u_gate
    )
    np.testing.assert_allclose(
        _attention(spec, "F", {**params, "g_proj": {"kernel": shut}}, u_gate), first_alone, atol=1e-5
    )


def test_softmax_routing_renormalises_and_scales_and_the_sdar_default_does_not():
    """A softmax router's renormalised weights times ``routed_scale``: the
    layer's output less the shared expert goes with the scale, and the
    reference's router says the same weights."""
    from torch_actor_critic_tpu.ops import moe

    u = jax.random.normal(jax.random.key(4), (20, HIDDEN))
    w = jax.random.normal(jax.random.key(5), (HIDDEN, 32))
    top_e, top_w = moe.route(u, w, 4, "softmax", None, 2.5, "xla")
    want_e, want_w = reference.route(u, w, 4, 2.5)
    np.testing.assert_array_equal(top_e, want_e)
    np.testing.assert_allclose(top_w, want_w, atol=1e-6)
    np.testing.assert_allclose(moe.route(u, w, 4, "softmax", None, 1.0, "xla")[1], want_w / 2.5, atol=1e-6)
    np.testing.assert_allclose(jnp.sum(want_w, axis=-1), 2.5, atol=1e-5)
    base = {**WHOLE, **SHARE, "shared_expert_width": 0}
    x = _inputs(seed=6)
    params = _seeded(sequence.SparseMoE(TrunkSpec(**base)), x)
    plain = _experts(TrunkSpec(**{**base, "routed_scale": 1.0}), params, x)
    np.testing.assert_allclose(_experts(TrunkSpec(**base), params, x), 2.5 * plain, atol=1e-5)
    assert float(jnp.max(jnp.abs(plain))) > 1e-3


def test_the_expert_sublayer_matches_the_reference_forward_and_gradient():
    spec, model = TrunkSpec(**{**WHOLE, **SHARE}), _model(**SHARE)
    u = _inputs(seed=6)
    params = _seeded(sequence.SparseMoE(spec), u)
    assert {"shared_gate", "shared_up", "shared_down", "w_gate", "router"} <= set(params)
    mix = jax.random.normal(jax.random.key(9), u.shape)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            _experts(spec, params, u), _experts_reference(params, u, model), atol=2e-5
        )
        got = jax.grad(lambda p, u: jnp.sum(_experts(spec, p, u) * mix), (0, 1))(params, u)
        want = jax.grad(lambda p, u: jnp.sum(_experts_reference(p, u, model) * mix), (0, 1))(params, u)
    _assert_gradients_match(got, want)


def test_the_dense_block_matches_the_reference():
    """An ``f`` block whole: full attention, then the dense gated
    feed-forward behind its norm, no expert anywhere in it."""
    spec, model = TrunkSpec(**{**WHOLE, **SHARE}), _model(**SHARE)
    x, pos = _inputs(seed=7), jnp.arange(T)
    block = sequence.DecoderBlock(spec, kind="f")
    params = _seeded(block, x, pos)
    assert set(params) == {"attention", "input_norm", "post_attention_norm", "mlp"}
    assert params["mlp"]["gate_proj"]["kernel"].shape == (HIDDEN, 48)
    with jax.default_matmul_precision("highest"):
        got = block.apply({"params": params}, x, pos)
        h = reference.attention_sublayer(params, x, "f", model, "highest")
        want, choices = reference.ffn_sublayer(params, h, "f", model, "highest")
    assert choices is None
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------------------ the shares add up


@pytest.mark.parametrize("kind", ["F", "W"])
def test_all_shares_of_a_layer_add_up_to_the_uncut_layer(kind):
    """The share test that ties the cut to the model, on one input.  The
    attention sublayer divided four ways by heads (a key/value head with the
    2 or 3 query heads that read it, their gates' columns and their rows of
    ``W_o``): the four partial outputs add up to the uncut reference's.  The
    expert sublayer divided four ways by experts (the published 32 ways
    collapsed to four ranges of 8): every share computes the router and the
    shared expert alike, so the routed parts add up and the shared expert is
    counted once."""
    uncut, u, pos = TrunkSpec(**WHOLE), _inputs(seed=5), jnp.arange(T)
    group = (12 if kind == "W" else 8) // 4
    p = _seeded(sequence.GroupedQueryAttention(uncut, kind=kind), u, pos)
    share = TrunkSpec(**{**WHOLE, "q_heads": 2, "window_q_heads": 3, "kv_heads": 1})
    d = WHOLE["head_dim"]

    def of(j):
        q, kv = slice(group * j * d, group * (j + 1) * d), slice(j * d, (j + 1) * d)
        return {
            "q_proj": {"kernel": p["q_proj"]["kernel"][:, q]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, kv]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, kv]},
            "g_proj": {"kernel": p["g_proj"]["kernel"][:, group * j:group * (j + 1)]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][q]},
        }

    with jax.default_matmul_precision("highest"):
        parts = [_attention(share, kind, of(j), u) for j in range(4)]
        np.testing.assert_allclose(
            sum(parts), _attention_reference(kind, p, u, _model()), atol=1e-4
        )
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in parts)

    p = _seeded(sequence.SparseMoE(uncut), u)
    flat = u.reshape(-1, HIDDEN)
    with jax.default_matmul_precision("highest"):
        shared = reference._gated(
            flat, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
            p["shared_down"]["kernel"], "highest",
        ).reshape(u.shape)
        routed = []
        for lo in range(0, 32, 8):
            held = {**p, **{name: p[name][lo:lo + 8] for name in ("w_gate", "w_up", "w_down")}}
            routed.append(
                _experts(TrunkSpec(**{**WHOLE, "experts_held": (lo, lo + 8)}), held, u) - shared
            )
        np.testing.assert_allclose(
            sum(routed) + shared, _experts_reference(p, u, _model()), atol=1e-4
        )
    assert all(float(jnp.max(jnp.abs(r))) > 0 for r in routed)
