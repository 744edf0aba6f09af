"""The SDAR history trunk's expert layer at a small size on the CPU, seeded
weights: the router's selection against ``lax.top_k`` and the mask, and what
a share does at its edges (every token on one expert, a share nobody chooses,
rows past the held ones). The shares' sums are
``test_trunk_expert_shares.py``'s, their gradients
``test_trunk_expert_gradients.py``'s, attention ``test_trunk_attention.py``'s,
the trunk and the SAC step ``test_trunk_step.py``'s; ``trunk_helpers.py``
holds what they share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trunk_helpers import (  # noqa: F401  (``pieces`` is a fixture)
    PRECISIONS,
    _expert_weights,
    _held,
    _share,
    pieces,
    route_by_sort_and_mask,
)

from benchmark.harness import reference_trunk
from torch_actor_critic_tpu.ops import moe

# --------------------------------------------------------------- the router


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


def _starved(p, u):
    """Inputs and a router under which no token chooses experts 4-7."""
    return {**p, "router": p["router"].at[:, 4:8].set(-1.0)}, jnp.abs(u)


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
