"""A dense projection's weight gradient as a product of its own
(``models/sequence.py::OwnWeightGrad``): the projection against ``nn.Dense``
with the same kernel (forward, both gradients, at float32 and with a bfloat16
``dtype``, under ``nn.remat``, ``vmap`` and ``lax.scan``), the parameter tree
of every family against the one plain ``nn.Dense`` builds from the same key,
the shape rule by name at the three cells' widths, and the counter the
shared-trunk step reports."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from flax import traverse_util

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from torch_actor_critic_tpu.models import SequenceTrunk, TrunkSpec  # noqa: E402
from torch_actor_critic_tpu.models import sequence  # noqa: E402
from torch_actor_critic_tpu.models.mlp import torch_linear_kernel_init  # noqa: E402


def _plain_linear(features, dtype, name):
    """``_linear`` as it was before the projection had a backward pass of its own."""
    return nn.Dense(
        features, use_bias=False, kernel_init=torch_linear_kernel_init,
        dtype=dtype, param_dtype=jnp.float32, name=name,
    )


@pytest.fixture
def every_kernel(monkeypatch):
    """The rule lowered so that the small kernels of a test are taken."""
    monkeypatch.setattr(sequence, "OWN_WEIGHT_GRAD_MIN_ELEMENTS", 1)


# ------------------------------------------------ the projection against nn.Dense

FEATURES, WIDTH = 24, 40


class _Layer(nn.Module):
    """Something elementwise in front of the projection and behind it, as a
    block has: the norm whose output the product reads, the activation that
    makes its cotangent."""

    linear: object
    dtype: object

    @nn.compact
    def __call__(self, x):
        u = sequence.RMSNorm(name="norm")(x)
        return jax.nn.silu(self.linear(WIDTH, self.dtype, "proj")(u)).astype(jnp.float32)


def _plain(layer):
    return lambda params, x: jnp.sum(layer.apply({"params": params}, x) ** 2)


def _rematted(layer):
    again = nn.remat(type(layer))(layer.linear, layer.dtype)
    return lambda params, x: jnp.sum(again.apply({"params": params}, x) ** 2)


def _vmapped(layer):
    # the data-parallel burst maps the whole update over its device axis
    one = jax.vmap(_plain(layer))
    return lambda params, x: jnp.sum(one(
        jax.tree_util.tree_map(lambda p: jnp.stack([p, 2 * p]), params), jnp.stack([x, x + 1])
    ))


def _scanned(layer):
    # the burst: steps that carry the parameters
    def loss(params, x):
        def step(carry, shift):
            return carry, _plain(layer)(jax.tree_util.tree_map(lambda p: p + shift, carry), x)

        return jnp.sum(jax.lax.scan(step, params, jnp.arange(3.0) / 10)[1])

    return loss


CONTEXTS = {"plain": _plain, "remat": _rematted, "vmap": _vmapped, "scan": _scanned}


@pytest.mark.parametrize("context", sorted(CONTEXTS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_projection_is_nn_dense_with_a_backward_pass_of_its_own(every_kernel, dtype, context):
    x = jax.random.normal(jax.random.key(1), (2, 6, FEATURES))
    ours, dense = _Layer(sequence._linear, dtype), _Layer(_plain_linear, dtype)
    params = dense.init(jax.random.key(0), x)["params"]
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        ours.init(jax.random.key(0), x)["params"]
    )
    np.testing.assert_array_equal(  # forward bitwise
        ours.apply({"params": params}, x), dense.apply({"params": params}, x)
    )
    wrap = CONTEXTS[context]
    grads = jax.jit(jax.value_and_grad(wrap(ours), argnums=(0, 1)))
    jaxpr = str(jax.make_jaxpr(jax.grad(wrap(ours)))(params, x))
    assert jaxpr.count("optimization_barrier") == 2, jaxpr  # the operands, then the result
    assert "optimization_barrier" not in str(jax.make_jaxpr(jax.grad(wrap(dense)))(params, x))
    (got, (dparams, dx)) = grads(params, x)
    (want, (dparams_want, dx_want)) = jax.jit(
        jax.value_and_grad(wrap(dense), argnums=(0, 1))
    )(params, x)
    assert dparams["proj"]["kernel"].dtype == jnp.float32
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(dx, dx_want, **tol)
    for g, w in zip(jax.tree_util.tree_leaves(dparams), jax.tree_util.tree_leaves(dparams_want)):
        np.testing.assert_allclose(g, w, **tol)


def test_a_small_kernel_keeps_nn_denses_backward_pass():
    x = jax.random.normal(jax.random.key(1), (2, 6, FEATURES))
    layer = _Layer(sequence._linear, jnp.float32)
    params = layer.init(jax.random.key(0), x)["params"]
    assert FEATURES * WIDTH < sequence.OWN_WEIGHT_GRAD_MIN_ELEMENTS
    assert "optimization_barrier" not in str(jax.make_jaxpr(jax.grad(_plain(layer)))(params, x))


# ------------------------------------------------------- every family's tree

FAMILIES = {
    "sdar_moe": TrunkSpec(
        hidden=32, q_heads=4, kv_heads=2, head_dim=8, layers=2, experts=16,
        experts_per_tok=4, expert_width=24, experts_held=(2, 6), remat=1,
    ),
    "nemotron_h": TrunkSpec(
        hidden=32, pattern="EMEM*", q_heads=2, kv_heads=1, head_dim=8, experts=16,
        experts_per_tok=4, expert_width=24, experts_held=(2, 6), block_length=1,
        qk_norm_rope=False, router="sigmoid", routed_scale=2.5, expert_form="relu2",
        expert_latent=16, shared_expert_width=40, ssm_heads=4, ssm_head_dim=8,
        ssm_groups=2, ssm_state=16, ssm_chunk=4, remat=5,
    ),
    "laguna": TrunkSpec(
        hidden=32, pattern="fWWF", q_heads=2, window_q_heads=3, kv_heads=1, head_dim=8,
        window=3, window_rope_theta=1e4, rope_share=0.5, qk_norm=False, head_gate=True,
        dense_width=48, experts=16, experts_per_tok=4, expert_width=24, experts_held=(2, 6),
        routed_scale=2.5, shared_expert_width=24, block_length=1, remat=3,
    ),
}
OBS = jnp.zeros((2, 8, 5))


def _trunk(family):
    return SequenceTrunk(spec=FAMILIES[family], attention_fn=sequence.xla_attention)


def _built(family, obs):
    """A family's tree from key 4 and its forward pass, each one compiled call.
    Module constants and ``_linear`` are read when a call is traced, so a test
    that patches one builds under its patch: a function of its own a call, for
    ``jit`` to trace anew whatever it has seen of an equal module."""
    trunk = _trunk(family)
    params = jax.jit(lambda key, obs: trunk.init(key, obs))(jax.random.key(4), obs)["params"]
    return params, jax.jit(lambda p, obs: trunk.apply({"params": p}, obs))(params, obs)


@pytest.fixture(scope="module")
def stacks():
    """``stacks(family)``: the histories and the family's tree by the rule as
    it stands, built once for the tests that read them."""
    made = {}

    def of(family):
        if family not in made:
            obs = jax.random.normal(jax.random.key(2), OBS.shape)
            made[family] = (obs, _built(family, obs)[0])
        return made[family]

    return of


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_tree_is_the_one_nn_dense_builds_from_the_same_key(
    monkeypatch, every_kernel, stacks, family
):
    """Names, shapes, dtypes and every byte: a tree the parent saved loads,
    and gives the forward pass it gave."""
    obs, _ = stacks(family)
    ours, out = _built(family, obs)
    monkeypatch.setattr(sequence, "_linear", _plain_linear)
    parents, parents_out = _built(family, obs)
    ours_flat, parents_flat = (
        traverse_util.flatten_dict(tree, sep="/") for tree in (ours, parents)
    )
    assert list(ours_flat) == list(parents_flat)
    for name, leaf in ours_flat.items():
        assert (leaf.shape, leaf.dtype) == (parents_flat[name].shape, parents_flat[name].dtype)
        assert np.asarray(leaf).tobytes() == np.asarray(parents_flat[name]).tobytes(), name
    np.testing.assert_array_equal(parents_out, out)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_stack_trains_as_it_did_with_every_weight_gradient_its_own_product(
    monkeypatch, stacks, family
):
    """The whole stack's gradient, blocks recomputed, with every projection
    taken against none: the same products of the same values."""
    trunk, (obs, params) = _trunk(family), stacks(family)
    grad = lambda: jax.jit(jax.grad(  # noqa: E731
        lambda p: jnp.sum(trunk.apply({"params": p}, obs) ** 2)
    ))(params)
    want = grad()
    monkeypatch.setattr(sequence, "OWN_WEIGHT_GRAD_MIN_ELEMENTS", 1)
    got = grad()
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6, err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------ the rule at the cells' widths

# What each cell's stack holds of dense projections, by the last two parts
# of its name (every layer of a kind alike), and which the rule takes.
TAKEN = {
    "sdar30b_a3b_trunk_burst": {
        "own": {"attention/q_proj", "attention/o_proj"},
        "xla": {"trunk/embed", "attention/k_proj", "attention/v_proj"},
        "products": (8, 17),
    },
    "nemotron3_super_trunk_burst": {
        "own": {
            "mixer/in_proj", "mixer/out_proj", "mixer/latent_down", "mixer/latent_up",
            "mixer/shared_up", "mixer/shared_down",
        },
        "xla": {"trunk/embed", "mixer/q_proj", "mixer/k_proj", "mixer/v_proj", "mixer/o_proj"},
        "products": (30, 35),
    },
    "laguna_s21_trunk_burst": {
        "own": {
            "attention/q_proj", "attention/o_proj", "mlp/gate_proj", "mlp/up_proj",
            "mlp/down_proj", "moe/shared_gate", "moe/shared_up", "moe/shared_down",
        },
        "xla": {"trunk/embed", "attention/k_proj", "attention/v_proj", "attention/g_proj"},
        "products": (25, 41),
    },
}


def _cell_spec(cell_name):
    from benchmark.drivers import trunkburst
    from benchmark.harness import registry, spans

    _, cell, config = registry.resolve(cell_name)
    driver = registry.load_driver(cell["driver"])(
        cell, config, 1, spans.Spans(), {"rehearsal": False}
    )
    cfg = driver.sac_config()
    return TrunkSpec.from_config(cfg), trunkburst.Spec(driver.model), cfg


@pytest.mark.parametrize("cell", sorted(TAKEN))
def test_the_rule_takes_the_large_kernels_of_a_cell_and_leaves_the_small(cell):
    """Traced at the cell's own widths, nothing computed: which projections
    say ``own`` and which ``xla``, and the count the step reports."""
    spec, env, cfg = _cell_spec(cell)
    trunk = SequenceTrunk(spec=spec, attention_fn=sequence.xla_attention)
    obs = jax.ShapeDtypeStruct((cfg.batch_size,) + env.obs_spec.shape, jnp.float32)
    variables = jax.eval_shape(lambda o: trunk.init(jax.random.key(0), o), obs)
    assert "weight_grads" not in variables  # ``init`` hands back what the parent's did
    _, sown = jax.eval_shape(
        lambda p, o: trunk.apply({"params": p}, o, mutable=["weight_grads"]),
        variables["params"], obs,
    )
    said = traverse_util.flatten_dict(sown["weight_grads"])
    by_part = {"own": set(), "xla": set()}
    for path in said:
        by_part[path[-1]].add("/".join((("trunk",) + path)[-4:-2]))
    assert by_part == {k: TAKEN[cell][k] for k in ("own", "xla")}
    kernels = {
        path[:-1]: leaf for path, leaf in
        traverse_util.flatten_dict(variables["params"]).items() if path[-1] == "kernel"
    }
    assert set(kernels) == {path[:-2] for path in said}  # every kernel of the stack is a projection's
    for path in said:
        size = kernels[path[:-2]].size
        assert (size >= sequence.OWN_WEIGHT_GRAD_MIN_ELEMENTS) == (path[-1] == "own"), path
    own = [path for path in said if path[-1] == "own"]
    assert (len(own), len(said)) == TAKEN[cell]["products"]


# ----------------------------------------------------- the step's counter

HISTORY, OBS_DIM, ACT_DIM = 12, 5, 3
SMALL = dict(
    trunk_pattern="fW", trunk_hidden=32, trunk_q_heads=2, trunk_window_q_heads=3,
    trunk_kv_heads=1, trunk_head_dim=8, trunk_window=5, trunk_window_rope_theta=1e4,
    trunk_rope_share=0.5, trunk_qk_norm=False, trunk_head_gate=True, trunk_dense_width=48,
    trunk_experts=32, trunk_experts_per_tok=4, trunk_expert_width=12, trunk_experts_held=(8, 16),
    trunk_routed_scale=2.5, trunk_shared_expert_width=12, trunk_block_length=1, trunk_remat=2,
    trunk_bf16_dots=False, history_len=HISTORY, batch_size=4, update_every=3, buffer_size=64,
)


def _step_metrics():
    import types

    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
    from torch_actor_critic_tpu.utils.config import SACConfig

    cfg = SACConfig(**SMALL)
    env = types.SimpleNamespace(
        act_dim=ACT_DIM, act_limit=1.0,
        obs_spec=jax.ShapeDtypeStruct((HISTORY, OBS_DIM), jnp.float32),
    )
    sac = make_learner(cfg, *build_models(cfg, env), ACT_DIM)
    state = jax.jit(sac.init_state)(jax.random.key(0), jnp.zeros((HISTORY, OBS_DIM)))
    k = jax.random.split(jax.random.key(3), 4)
    batch = Batch(
        states=jax.random.normal(k[0], (4, HISTORY, OBS_DIM)),
        actions=jax.random.uniform(k[1], (4, ACT_DIM), minval=-1.0, maxval=1.0),
        rewards=jax.random.normal(k[2], (4,)),
        next_states=jax.random.normal(k[3], (4, HISTORY, OBS_DIM)),
        done=jnp.zeros((4,)),
    )
    return jax.jit(sac.update)(state, batch)[1]


# 32 x 48 = 1,536 elements: the dense block's three kernels and nothing else
@pytest.mark.parametrize("least, products, share", [
    (32 * 48, 3, 3 * 32 * 48), (1, 17, None), (5 << 19, 0, 0),
], ids=["the-dense-block", "every-projection", "the-rule-as-it-stands"])
def test_the_step_reports_how_many_weight_gradients_are_products_of_their_own(
    monkeypatch, capfd, least, products, share
):
    """``trunk/weight_grad_own_products`` and ``..._share`` beside the expert
    layers' counters, blocks recomputed, and the line on standard error."""
    from torch_actor_critic_tpu.sac import algorithm

    monkeypatch.setattr(sequence, "OWN_WEIGHT_GRAD_MIN_ELEMENTS", least)
    algorithm._say_once.cache_clear()
    metrics = _step_metrics()
    # embed; q, k, v, g, o of the full layer (2 heads of 8) and of the sliding
    # one (3); the dense block's three; the shared expert's three
    every = 5 * 32 + 32 * (16 + 8 + 8 + 2 + 16) + 32 * (24 + 8 + 8 + 3 + 24) + 3 * 32 * 48 + 3 * 32 * 12
    assert float(metrics["trunk/weight_grad_own_products"]) == products
    assert float(metrics["trunk/weight_grad_own_share"]) == pytest.approx(
        (every if share is None else share) / every
    )
    assert float(metrics["trunk/held_assignments"]) > 0  # beside the counters there were
    assert (
        f"trunk: weight_grad_own_products {products} of 17 dense projections"
        in capfd.readouterr().err
    )
