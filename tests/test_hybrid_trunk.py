"""The ``nemotron_h`` history trunk at a small size on the CPU, seeded weights:
the chunked scan against the time-step recurrence, the sigmoid router, each
mixer against the plain reference (``benchmark/harness/
reference_nemotron_trunk.py``, which imports nothing of the program), the
shares of each layer kind adding up to the uncut layer, and the stack through
``build_models`` and ``Trainer``."""

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

from benchmark.harness import hybrid_weights  # noqa: E402
from benchmark.harness import reference_nemotron_trunk as reference  # noqa: E402
from torch_actor_critic_tpu.models import TrunkSpec  # noqa: E402
from torch_actor_critic_tpu.models.sequence import (  # noqa: E402
    GroupedQueryAttention,
    MambaMixer,
    SparseMoE,
)
from torch_actor_critic_tpu.ops import moe, ssm  # noqa: E402
from torch_actor_critic_tpu.sac.trainer import build_models, make_learner  # noqa: E402
from torch_actor_critic_tpu.telemetry import scopes  # noqa: E402
from torch_actor_critic_tpu.utils.config import SACConfig  # noqa: E402

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


# ------------------------------------------------------------------ the scan


def _scan_operands(t, seed, heads=4, p=8, groups=2, n=16, batch=2):
    k = jax.random.split(jax.random.key(seed), 6)
    return (
        jax.random.normal(k[0], (batch, t, heads, p)),
        jax.nn.softplus(jax.random.normal(k[1], (batch, t, heads))),
        -jnp.exp(jax.random.normal(k[2], (heads,))),
        jax.random.normal(k[3], (batch, t, groups, n)),
        jax.random.normal(k[4], (batch, t, groups, n)),
        jax.random.normal(k[5], (heads,)),
    )


def _recurrence(x, dt, a, b, c, d):
    per = x.shape[2] // b.shape[2]
    one = lambda x, dt, b, c: reference.recurrence(  # noqa: E731
        x, dt, a, jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1), d
    )
    return jax.vmap(one)(x, dt, b, c)


@pytest.mark.parametrize("t", [128, 256, 8, 100, 200, 300])
def test_the_chunked_scan_is_the_time_step_recurrence(t):
    """Forward and every gradient of ``ops.ssm.ssd_scan`` at chunk 128 against
    the recurrence as a scan over time steps, at lengths that are whole chunks
    (128, 256), shorter than one (8) and whole chunks and a part (100 is one
    part, 200 and 300 one and two chunks and a part).  Tolerance: float32 sums
    in another order, values up to 90."""
    operands = _scan_operands(t, seed=t)
    chunked = lambda *v: ssm.ssd_scan(*v, chunk=128)  # noqa: E731
    both = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda *v: (lambda y: (jnp.sum(y ** 2), y))(f(*v)), range(6), has_aux=True
    ))
    with jax.default_matmul_precision("highest"):
        (_, got), grads = both(chunked)(*operands)
        (_, want), wants = both(_recurrence)(*operands)
    np.testing.assert_allclose(got, want, atol=5e-4)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(w))))


def test_the_scan_rounds_its_products_operands_and_keeps_its_decay_float32():
    """``bf16_dots`` changes the result by bfloat16's rounding and no more;
    a decay so long that bfloat16 could not tell it from none still decays."""
    operands = _scan_operands(64, seed=5)
    exact = ssm.ssd_scan(*operands, chunk=16)
    rounded = ssm.ssd_scan(*operands, chunk=16, bf16_dots=True)
    gap = float(jnp.max(jnp.abs(exact - rounded)) / jnp.max(jnp.abs(exact)))
    assert 1e-5 < gap < 3e-2
    x, dt, a, b, c, d = operands
    slow = ssm.ssd_scan(x, dt * 1e-4, a, b, c, d, chunk=16, bf16_dots=True)
    none = ssm.ssd_scan(x, dt * 1e-4, a * 0, b, c, d, chunk=16, bf16_dots=True)
    assert float(jnp.max(jnp.abs(slow - none))) > 0


def test_the_convolution_is_causal_and_depthwise():
    x = jax.random.normal(jax.random.key(0), (2, 9, 5))
    kernel, bias = jax.random.normal(jax.random.key(1), (4, 5)), jnp.arange(5.0)
    y = ssm.causal_conv(x, kernel, bias)
    want = np.zeros((2, 9, 5), np.float32) + np.asarray(bias)
    for t in range(9):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += np.asarray(x[:, t - 3 + i] * kernel[i])
    np.testing.assert_allclose(y, want, atol=1e-5)
    moved = ssm.causal_conv(x.at[:, 6].add(1.0), kernel, bias) - y
    assert not np.any(moved[:, :6]) and np.all(moved[:, 6] != 0)  # no step sees a later one


# ---------------------------------------------------------------- the router


def test_the_sigmoid_routers_choice_uses_the_bias_and_its_weights_do_not():
    k = jax.random.split(jax.random.key(2), 3)
    u, w_r = jax.random.normal(k[0], (40, HIDDEN)), jax.random.normal(k[1], (HIDDEN, 16)) * 0.2
    bias = jax.random.uniform(k[2], (16,), minval=-0.3, maxval=0.3)
    scores = jax.nn.sigmoid(jnp.dot(u, w_r, precision="highest"))
    plain_e, plain_w = moe.route(u, w_r, 4, "sigmoid", None, 2.5)
    top_e, top_w = moe.route(u, w_r, 4, "sigmoid", bias, 2.5)
    assert np.any(np.sort(plain_e, -1) != np.sort(top_e, -1))  # the bias moved choices
    np.testing.assert_array_equal(
        np.sort(top_e, -1), np.sort(np.argsort(-(scores + bias), -1)[:, :4], -1)
    )
    chosen = jnp.take_along_axis(scores, top_e, axis=-1)  # the scores, not score + bias
    np.testing.assert_allclose(
        top_w, 2.5 * chosen / jnp.sum(chosen, -1, keepdims=True), rtol=1e-6
    )
    np.testing.assert_allclose(jnp.sum(top_w, -1), 2.5, rtol=1e-6)
    ref_e, ref_w = reference.route(u, w_r, bias, 4, 2.5)
    np.testing.assert_array_equal(top_e, ref_e)
    np.testing.assert_allclose(top_w, ref_w, rtol=1e-6)
    # no gradient reaches the bias; the router's own comes through the weights
    g_bias, g_router = jax.grad(
        lambda b, w: jnp.sum(moe.route(u, w, 4, "sigmoid", b, 2.5)[1] ** 2), (0, 1)
    )(bias, w_r)
    assert not np.any(g_bias) and np.any(g_router)


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
    with jax.default_matmul_precision("highest"):
        got = _apply(kind, spec, params, u)
        want = _reference(kind, params, u, model)
        np.testing.assert_allclose(got, want, atol=2e-5)
        g_got = jax.grad(lambda p, u: jnp.sum(_apply(kind, spec, p, u) * mix), (0, 1))(params, u)
        g_want = jax.grad(lambda p, u: jnp.sum(_reference(kind, p, u, model) * mix), (0, 1))(
            params, u
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
    with jax.default_matmul_precision("highest"):
        parts = [_apply("M", share, _mamba_share(p, j), u) for j in range(8)]
        whole = _reference("M", p, u, _model())
        np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
        np.testing.assert_allclose(_apply("M", uncut, p, u), whole, atol=2e-5)
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

    with jax.default_matmul_precision("highest"):
        parts = [_apply("*", share, of(j), u) for j in range(4)]
        np.testing.assert_allclose(sum(parts), _reference("*", p, u, _model()), atol=2e-5)


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


# --------------------------------------------------------- the stack, the normal path

HISTORY, OBS, ACT = 12, 5, 3
SMALL = dict(
    trunk_pattern="EMEM*", trunk_hidden=HIDDEN, trunk_q_heads=2, trunk_kv_heads=1,
    trunk_head_dim=8, trunk_experts=16, trunk_experts_per_tok=4, trunk_expert_width=12,
    trunk_experts_held=(2, 6), trunk_block_length=1, trunk_rms_eps=1e-5,
    trunk_qk_norm_rope=False, trunk_router="sigmoid", trunk_routed_scale=2.5,
    trunk_expert_form="relu2", trunk_expert_latent=16, trunk_shared_expert_width=20,
    trunk_ssm_heads=4, trunk_ssm_head_dim=4, trunk_ssm_groups=2, trunk_ssm_state=8,
    trunk_ssm_chunk=4, trunk_remat=5, trunk_bf16_dots=False,
    history_len=HISTORY, batch_size=4, update_every=3, buffer_size=64,
)


def _learner(**overrides):
    cfg = SACConfig(**{**SMALL, **overrides})
    env = types.SimpleNamespace(
        act_dim=ACT, act_limit=1.0, obs_spec=jax.ShapeDtypeStruct((HISTORY, OBS), jnp.float32),
    )
    return cfg, make_learner(cfg, *build_models(cfg, env), ACT)


def test_the_stack_is_built_from_the_pattern():
    """One block a letter, each one mixer behind one norm; the SDAR stack is
    the same mechanism's ``S`` blocks, under the names it always had."""
    _, sac = _learner()
    state = jax.eval_shape(sac.init_state, jax.random.key(0), jnp.zeros((HISTORY, OBS)))
    trunk = state.critic_params["params"]["trunk"]
    assert sorted(trunk) == ["embed", "final_norm"] + [f"layer_{i}" for i in range(5)]
    assert all(set(trunk[f"layer_{i}"]) == {"norm", "mixer"} for i in range(5))
    assert set(trunk["layer_1"]["mixer"]) == {
        "in_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "norm_weight", "out_proj",
    }
    assert set(trunk["layer_4"]["mixer"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert set(trunk["layer_0"]["mixer"]) == {
        "router", "router_bias", "latent_down", "latent_up", "w_up", "w_down",
        "shared_up", "shared_down",
    }
    assert trunk["layer_0"]["mixer"]["w_up"].shape == (4, 16, 12)  # held, latent, width
    assert trunk["layer_1"]["mixer"]["in_proj"]["kernel"].shape == (HIDDEN, 2 * 16 + 2 * 16 + 4)
    sdar = SACConfig(trunk_block="sdar_moe", trunk_layers=3, history_len=HISTORY)
    assert sdar.shared_trunk and TrunkSpec.from_config(sdar).kinds == "SSS"
    assert TrunkSpec.from_config(SACConfig(**SMALL)).kinds == "EMEM*"
    assert not SACConfig().shared_trunk
    with pytest.raises(ValueError, match="one letter a layer"):
        SACConfig(trunk_pattern="EMX")
    with pytest.raises(ValueError, match="whole groups"):
        SACConfig(**{**SMALL, "trunk_ssm_heads": 3})


def test_the_sdar_burst_chooses_and_learns_what_it_did_with_top_k_and_the_mask(monkeypatch):
    """The selection changed ``route`` under the SDAR cell's feet (PR 41; until
    then this test pinned that burst's lowered text, PR 40): its small
    data-parallel burst, run with the selection and with ``lax.top_k`` and the
    mask in ``route``'s place, reports the same choices to the element and
    leaves the same state within float32 rounding."""
    from test_trunk import route_by_sort_and_mask
    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.parallel.dp import (
        DataParallelSAC, init_sharded_buffer, shard_chunk,
    )
    from torch_actor_critic_tpu.parallel.mesh import make_mesh

    cfg = SACConfig(
        trunk_block="sdar_moe", history_len=64, batch_size=4, update_every=3, buffer_size=256,
        burst_unroll=1, trunk_hidden=64, trunk_q_heads=4, trunk_kv_heads=2, trunk_head_dim=16,
        trunk_layers=2, trunk_experts=16, trunk_experts_held=(2, 6), trunk_experts_per_tok=4,
        trunk_expert_width=48, trunk_remat=1, trunk_report_choices=True, trunk_bf16_dots=False,
    )
    spec = jax.ShapeDtypeStruct((64, 5), jnp.float32)
    env = types.SimpleNamespace(act_dim=3, act_limit=1.0, obs_spec=spec)
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    k = jax.random.split(jax.random.key(11), 5)
    rows = Batch(
        states=jax.random.normal(k[0], (1, 40, 64, 5)),
        actions=jax.random.uniform(k[1], (1, 40, 3), minval=-1.0, maxval=1.0),
        rewards=jax.random.normal(k[2], (1, 40)),
        next_states=jax.random.normal(k[3], (1, 40, 64, 5)),
        done=(jax.random.uniform(k[4], (1, 40)) < 0.3).astype(jnp.float32),
    )

    def burst(route):
        if route is not None:
            monkeypatch.setattr(moe, "route", route)
        learner = DataParallelSAC(make_learner(cfg, *build_models(cfg, env), 3), mesh)
        state = learner.init_state(jax.random.key(5), jnp.zeros(spec.shape))
        ring = init_sharded_buffer(256, spec, 3, mesh)
        state, _, metrics = learner.update_burst(state, ring, shard_chunk(rows, mesh), 3)
        return jax.device_get((state, metrics))

    (state, metrics), (want_state, want) = burst(None), burst(route_by_sort_and_mask)
    assert metrics["trunk/choices_first"].shape == (2, 4 * 64, 4)
    np.testing.assert_array_equal(metrics["trunk/choices_first"], want["trunk/choices_first"])
    for tree, want_tree in (
        (state.critic_params, want_state.critic_params),
        (state.actor_params, want_state.actor_params),
        (state.target_critic_params, want_state.target_critic_params),
    ):
        # Three Adam steps of 3e-4 each. Adam divides a gradient by its own
        # size, so where one is all rounding (a router's, a sum of terms that
        # cancel) the two programs' last bits move a parameter by a few
        # hundredths of a step (read: 1.1e-5 in 37 of a router's 1,024).
        for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(want_tree)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=0.03 * 3 * cfg.lr)
    assert float(metrics["loss_q"]) == pytest.approx(float(want["loss_q"]), rel=1e-6)


@pytest.fixture(scope="module")
def compiled_step():
    """One SAC step on seeded weights, compiled once for the tests that run
    it and the one that reads its text."""
    from torch_actor_critic_tpu.core.types import Batch

    _, sac = _learner(trunk_report_choices=True)
    example = jnp.zeros((HISTORY, OBS))
    actor0, critic0 = hybrid_weights.seeded_params(sac, example, jax.random.key(7))
    state = sac.init_state(jax.random.key(0), example).replace(
        actor_params=actor0, critic_params=critic0,
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic0),
    )
    k = jax.random.split(jax.random.key(3), 5)
    batch = Batch(
        states=jax.random.normal(k[0], (4, HISTORY, OBS)),
        actions=jax.random.uniform(k[1], (4, ACT), minval=-1.0, maxval=1.0),
        rewards=jax.random.normal(k[2], (4,)),
        next_states=jax.random.normal(k[3], (4, HISTORY, OBS)),
        done=(jax.random.uniform(k[4], (4,)) < 0.3).astype(jnp.float32),
    )
    return state, batch, jax.jit(sac.update).lower(state, batch).compile()


def test_a_step_trains_every_leaf_but_the_correction_bias_and_counts_its_experts(compiled_step):
    state, batch, step = compiled_step
    new_state, metrics = step(state, batch)
    moved, _ = jax.tree_util.tree_flatten_with_path(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), new_state.critic_params, state.critic_params
    ))
    still = [jax.tree_util.keystr(path) for path, m in moved if not m]
    assert len(still) == 2 and all("router_bias" in name for name in still), still
    # the two expert layers' choices (4 a token) and the held experts' counters
    assert metrics["trunk/choices_first"].shape == (2, 4 * HISTORY, 4)
    assert 0 < float(metrics["trunk/held_assignments"]) <= 2 * 4 * HISTORY * 4
    assert float(metrics["trunk/expert_load_max"]) >= float(metrics["trunk/expert_load_mean"])
    assert np.isfinite(float(metrics["loss_q"])) and np.isfinite(float(metrics["loss_pi"]))


def test_the_hybrid_trunks_parts_carry_their_own_scopes(compiled_step):
    """The compiled step names the new parts: the state-space mixer's four,
    the latent projections, the shared expert and the grouped products inside
    the expert layer's scope, in the backward pass too."""
    table = scopes.scope_table(compiled_step[2].as_text())
    found = {s.rstrip(scopes.INHERITED) for counts in table.values() for s in counts if s}
    assert {
        scopes.TRUNK_SSM_PROJ, scopes.TRUNK_SSM_CONV, scopes.TRUNK_SSM_SCAN,
        scopes.TRUNK_SSM_GATE_NORM, scopes.TRUNK_MOE_LATENT, scopes.TRUNK_MOE_SHARED,
        scopes.TRUNK_MOE_PRODUCTS, scopes.TRUNK_MOE_EXPERTS, scopes.TRUNK_MOE_ROUTE,
        scopes.TRUNK_ATTENTION, scopes.TRUNK_EMBED,
    } <= found
    assert scopes.scope_of(
        "jit(f)/tac/critic/tac/trunk/moe/experts/tac/trunk/moe/experts/products/ragged_dot"
    ) == scopes.TRUNK_MOE_PRODUCTS
    assert scopes.scope_of("jit(f)/tac/trunk/moe/experts/gather") == scopes.TRUNK_MOE_EXPERTS
    assert set(scopes.SCOPES) >= found


def test_the_trainer_builds_and_updates_the_hybrid_trunk():
    """``Trainer`` on a history env with the pattern in its configuration:
    the normal path, no side script (the CLI hands ``--trunk-pattern`` to the
    same field)."""
    from torch_actor_critic_tpu.sac.trainer import Trainer

    cfg = SACConfig(**{
        **SMALL, "history_len": 6, "epochs": 1, "steps_per_epoch": 40, "start_steps": 10,
        "update_after": 10, "update_every": 10, "buffer_size": 200, "max_ep_len": 20,
    })
    trainer = Trainer("Pendulum-v1", cfg, seed=1)
    try:
        metrics = trainer.train()
        trunk = trainer.state.critic_params["params"]["trunk"]
        assert set(trunk["layer_1"]["mixer"]) >= {"in_proj", "A_log", "out_proj"}
        assert int(trainer.state.step) == 30 and np.isfinite(metrics["loss_q"])
    finally:
        trainer.close()
