"""The ``nemotron_h`` history trunk's layers at a small size on the CPU,
seeded weights: each mixer against the plain reference
(``benchmark/harness/reference_nemotron_trunk.py``, which imports nothing of
the program), and the shares of each layer kind adding up to the uncut layer.
The scan and the router are ``test_hybrid_trunk_scan.py``'s, the stack through
``build_models`` and ``Trainer`` ``test_hybrid_trunk_stack.py``'s."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import hybrid_weights  # noqa: E402
from benchmark.harness import reference_nemotron_trunk as reference  # noqa: E402
from torch_actor_critic_tpu.models import TrunkSpec  # noqa: E402
from torch_actor_critic_tpu.models.sequence import (  # noqa: E402
    GroupedQueryAttention,
    MambaMixer,
    SparseMoE,
)

HIDDEN, T, BATCH = 32, 12, 3
# The uncut layer at a small size: 16 state-space heads in 8 groups, 8 query
# heads over 2 key/value heads, 128 experts of which a token takes 6.
WHOLE = dict(
    hidden=HIDDEN, q_heads=8, kv_heads=2, head_dim=8, experts=128, experts_per_tok=6,
    expert_width=12, experts_held=(0, 128), block_length=1, rms_eps=1e-5, bf16_dots=False,
    qk_norm_rope=False, router="sigmoid", routed_scale=2.5, expert_form="relu2",
    expert_latent=16, shared_expert_width=20, ssm_heads=16, ssm_head_dim=4, ssm_groups=8,
    ssm_state=8, ssm_conv=4, ssm_chunk=4,
)


def _model(**changed):
    """The sizes as the reference reads them."""
    return {**WHOLE, **changed}


def _abstract(module, *args):
    return jax.eval_shape(lambda: module.init(jax.random.key(0), *args))["params"]


def _seeded(module, *args, seed=3):
    return hybrid_weights.init_params(jax.random.key(seed), _abstract(module, *args))


def _inputs(seed=1, batch=BATCH, t=T):
    return jax.random.normal(jax.random.key(seed), (batch, t, HIDDEN))


# ------------------------------------------------- each mixer, forward and gradient

SHARE = dict(  # one chip's share of WHOLE: an eighth of the heads, 4 of the experts
    q_heads=2, kv_heads=1, experts_held=(8, 12), ssm_heads=2, ssm_groups=1,
)
MIXERS = {
    "M": (MambaMixer, reference._mamba),
    "*": (GroupedQueryAttention, reference._attention),
    "E": (SparseMoE, lambda p, u, model, mode: reference._experts(p, u, model, mode)[0]),
}


def _apply(kind, spec, params, u):
    module = MIXERS[kind][0](spec)
    args = (u, jnp.arange(u.shape[1])) if kind == "*" else (u,)
    out = module.apply({"params": params}, *args, mutable=["moe_stats"])[0]
    return out


def _reference(kind, params, u, model):
    fn = MIXERS[kind][1]
    if kind == "E":
        return fn(params, u.reshape(-1, HIDDEN), model, "highest").reshape(u.shape)
    return jax.vmap(lambda u_b: fn(params, u_b, model, "highest"))(u)


@pytest.mark.parametrize("kind", sorted(MIXERS))
def test_a_mixer_matches_the_reference_forward_and_gradient(kind):
    """The program's mixer, given a share, against the reference given the
    same share, on seeded weights (the family's conventions for ``A_log``,
    ``dt_bias``, ``D`` and the correction bias): the output and the gradient
    of a scalar of it with respect to every parameter and the input.  The
    history (12) is three chunks of 4.  Tolerance: float32 sums in another
    order."""
    spec = TrunkSpec(**{**WHOLE, **SHARE})
    model = _model(**SHARE)
    u = _inputs()
    args = (u, jnp.arange(T)) if kind == "*" else (u,)
    params = _seeded(MIXERS[kind][0](spec), *args)
    mix = jax.random.normal(jax.random.key(9), u.shape)
    ours = lambda p, u: _apply(kind, spec, p, u)  # noqa: E731
    theirs = lambda p, u: _reference(kind, p, u, model)  # noqa: E731
    with jax.default_matmul_precision("highest"):  # each a compiled call, traced in here
        got, want = jax.jit(ours)(params, u), jax.jit(theirs)(params, u)
        np.testing.assert_allclose(got, want, atol=2e-5)
        g_got, g_want = (
            jax.jit(jax.grad(lambda p, u: jnp.sum(f(p, u) * mix), (0, 1)))(params, u)
            for f in (ours, theirs)
        )
    flat_got, _ = jax.tree_util.tree_flatten_with_path(g_got)
    for (path, g), w in zip(flat_got, jax.tree_util.tree_leaves(g_want)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(g, w, atol=3e-5 * scale, err_msg=jax.tree_util.keystr(path))
    if kind == "E":  # the bias moves the choice alone
        assert not np.any(g_got[0]["router_bias"])


# ------------------------------------------------------------ the shares add up


def _columns(*runs):
    return np.concatenate([np.arange(lo, hi) for lo, hi in runs])


def _mamba_share(p, j, heads=2, groups=1):
    """Share ``j`` of an uncut mixer's weights: ``heads`` heads and their
    ``groups`` groups: columns of ``W_in`` (``z | x | B | C | dt``), the
    convolution's channels, rows of ``W_out``."""
    hd, n = WHOLE["ssm_head_dim"], WHOLE["ssm_state"]
    inner, bc = WHOLE["ssm_heads"] * hd, WHOLE["ssm_groups"] * n
    x = (j * heads * hd, (j + 1) * heads * hd)
    b = (inner + j * groups * n, inner + (j + 1) * groups * n)
    c = (inner + bc + j * groups * n, inner + bc + (j + 1) * groups * n)
    conv = _columns(x, b, c)
    dt = (2 * inner + 2 * bc + j * heads, 2 * inner + 2 * bc + (j + 1) * heads)
    cols = np.concatenate([_columns(x), inner + conv, _columns(dt)])
    head = slice(j * heads, (j + 1) * heads)
    return {
        "in_proj": {"kernel": p["in_proj"]["kernel"][:, cols]},
        "conv_kernel": p["conv_kernel"][:, conv], "conv_bias": p["conv_bias"][conv],
        "dt_bias": p["dt_bias"][head], "A_log": p["A_log"][head], "D": p["D"][head],
        "norm_weight": p["norm_weight"][x[0]:x[1]],
        "out_proj": {"kernel": p["out_proj"]["kernel"][x[0]:x[1]]},
    }


def test_all_eight_head_shares_of_a_state_space_layer_add_up():
    """Guide section 4's share test for ``M``: the mixer divided eight ways by
    heads (2 heads with their group a share), every share's partial output
    through its rows of ``W_out``, summed: the uncut reference's mixer."""
    uncut = TrunkSpec(**WHOLE)
    u = _inputs(seed=4)
    p = _seeded(MambaMixer(uncut), u)
    share = TrunkSpec(**{**WHOLE, "ssm_heads": 2, "ssm_groups": 1})
    with jax.default_matmul_precision("highest"):  # eight shares, one compiled call
        of_share = jax.jit(lambda p, u: _apply("M", share, p, u))
        parts = [of_share(_mamba_share(p, j), u) for j in range(8)]
        whole = jax.jit(lambda p, u: _reference("M", p, u, _model()))(p, u)
        np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
        np.testing.assert_allclose(
            jax.jit(lambda p, u: _apply("M", uncut, p, u))(p, u), whole, atol=2e-5
        )
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-3  # a share is not nothing


def test_all_head_shares_of_an_attention_layer_add_up():
    """``*``: 8 query heads over 2 key/value heads divided four ways: 2 query
    heads a share with the key/value head they read, which two shares hold
    alike."""
    uncut = TrunkSpec(**WHOLE)
    u, pos = _inputs(seed=5), jnp.arange(T)
    p = _seeded(GroupedQueryAttention(uncut), u, pos)
    share = TrunkSpec(**{**WHOLE, "q_heads": 2, "kv_heads": 1})
    d = WHOLE["head_dim"]

    def of(j):
        q, kv = slice(2 * j * d, 2 * (j + 1) * d), slice((j // 2) * d, (j // 2 + 1) * d)
        return {
            "q_proj": {"kernel": p["q_proj"]["kernel"][:, q]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, kv]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, kv]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][q]},
        }

    with jax.default_matmul_precision("highest"):  # four shares, one compiled call
        of_share = jax.jit(lambda p, u: _apply("*", share, p, u))
        parts = [of_share(of(j), u) for j in range(4)]
        whole = jax.jit(lambda p, u: _reference("*", p, u, _model()))(p, u)
        np.testing.assert_allclose(sum(parts), whole, atol=2e-5)


def test_all_sixty_four_expert_shares_of_a_latent_expert_layer_add_up():
    """``E``: 128 experts divided 64 ways. Every share computes the router,
    both latent projections and the shared expert alike; the routed parts,
    each through the projection up, add up, and what every chip computes
    alike is counted once."""
    uncut = TrunkSpec(**WHOLE)
    u = _inputs(seed=6, batch=2, t=8)
    p = _seeded(SparseMoE(uncut), u)
    flat = u.reshape(-1, HIDDEN)
    with jax.default_matmul_precision("highest"):
        shared = jnp.dot(
            reference._relu2(jnp.dot(flat, p["shared_up"]["kernel"])), p["shared_down"]["kernel"]
        ).reshape(u.shape)
        routed = []
        for j in range(64):
            lo, hi = 2 * j, 2 * j + 2
            share = TrunkSpec(**{**WHOLE, "experts_held": (lo, hi)})
            held = {**p, "w_up": p["w_up"][lo:hi], "w_down": p["w_down"][lo:hi]}
            routed.append(_apply("E", share, held, u) - shared)
        whole = _reference("E", p, u, _model())
        np.testing.assert_allclose(sum(routed) + shared, whole, atol=3e-5)
    # every token's six assignments each landed on exactly one share
    assert sum(float(jnp.max(jnp.abs(r))) > 0 for r in routed) > 32
