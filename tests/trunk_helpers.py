"""What the SDAR trunk's test files share (``test_trunk.py``,
``test_trunk_expert_shares.py``, ``test_trunk_expert_gradients.py``,
``test_trunk_step.py``, ``test_trunk_bursts.py``): the small configuration and its learner, seeded
state and batch; the router as it stood before PR 41; the expert layer's
weights, shares and splits, and what no split changes of them. A plain module, imported by name."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_trunk, trunk_weights  # noqa: E402
from torch_actor_critic_tpu.core.types import Batch  # noqa: E402
from torch_actor_critic_tpu.ops import moe  # noqa: E402
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
    state = jax.jit(sac.init_state)(jax.random.key(0), example)
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
    @jax.jit  # one program a call: what is patched (``moe.PIECE_ROWS``) is read as it is traced
    def share(p, u):
        top_e, top_w = moe.route(u, p["router"], 4)
        return moe.expert_ffn(
            u, p["w_gate"][lo:hi], p["w_up"][lo:hi], p["w_down"][lo:hi], top_e, top_w,
            (lo, hi), num_experts=16, **kw,
        )

    return share(p, u)


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


@pytest.fixture(scope="module")
def unsplit():
    """``unsplit(mode, bf16)``: what the reference and the one-chunk form
    make of ``_expert_weights()``, which no split of the held rows changes
    (neither reads ``chunk_rows`` or ``moe.PIECE_ROWS``), computed once a
    precision: the uncut reference's layer over all 16 experts, its layer
    over experts 4-7, the one-chunk form of that share, and the gradient of
    the dense form's squared sum by the input and the weights."""
    done = {}

    def of(mode, bf16):
        if mode not in done:
            p, u = _expert_weights()
            whole, _ = reference_trunk._moe(
                p, u, dict(experts_held=[0, 16], experts_per_tok=4), mode
            )

            def one(u, p):
                return reference_trunk._moe(
                    _held(p), u, dict(experts_held=[4, 8], experts_per_tok=4), mode
                )[0]

            done[mode] = dict(
                whole=whole, one=one(u, p), before=_one_chunk_form(p, u, 4, 8, bf16),
                gradient=jax.grad(lambda u, p: jnp.sum(one(u, p) ** 2), (0, 1))(u, p),
            )
        return done[mode]

    return of
