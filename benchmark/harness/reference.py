"""Plain reference: one Soft Actor-Critic gradient step, written from the
equations (Haarnoja et al. 2018; the torch reference's ``eval_q_loss`` /
``eval_pi_loss``), in float32 ``jax.numpy`` at ``highest`` matmul precision.

It imports nothing of the program.  Parameters are read by the names of the
program's checkpoint layout (the one public shape the two share), the random
draws of a step (sampled rows, the two reparameterisation noises) are inputs,
and every multiplication goes through :func:`_mm` / :func:`_conv`, which
compute either in plain float32 (``mode="highest"``) or as a configuration
that states the TPU's default precision for float32 does
(``mode="bf16_operands"``: operands rounded to bfloat16, float32 accumulation,
in the backward pass too).

A batch carries a leading *stream* axis ``D``: the ``D`` data-parallel
replicas whose gradients the program averages every step (``D == 1`` on one
chip).  The mean over streams of the per-stream mean losses is differentiated
once, which is what averaging the replicas' gradients computes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LOG_STD_MIN, LOG_STD_MAX = -20.0, 2.0
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


_LOW = {"bf16_operands": jnp.bfloat16, "fp8_operands": jnp.float8_e5m2}


def _rounder(mode: str):
    dtype = _LOW[mode]
    return lambda x: x.astype(dtype).astype(jnp.float32)


def _dot_hi(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_low(x, w, mode):
    r = _rounder(mode)
    return _dot_hi(r(x), r(w))


def _mm_low_fwd(x, w, mode):
    r = _rounder(mode)
    xr, wr = r(x), r(w)
    return _dot_hi(xr, wr), (xr, wr)


def _mm_low_bwd(mode, res, g):
    xr, wr = res
    gr = _rounder(mode)(g)
    return _dot_hi(gr, wr.T), _dot_hi(xr.T, gr)


_mm_low.defvjp(_mm_low_fwd, _mm_low_bwd)


def _conv_hi(x, w, stride: int):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv_low(x, w, stride, mode):
    r = _rounder(mode)
    return _conv_hi(r(x), r(w), stride)


def _conv_low_fwd(x, w, stride, mode):
    r = _rounder(mode)
    xr, wr = r(x), r(w)
    return _conv_hi(xr, wr, stride), (xr, wr)


def _conv_low_bwd(stride, mode, res, g):
    xr, wr = res
    _, vjp = jax.vjp(lambda a, b: _conv_hi(a, b, stride), xr, wr)
    return vjp(_rounder(mode)(g))


_conv_low.defvjp(_conv_low_fwd, _conv_low_bwd)


def _mm(x, w, mode: str):
    """``highest``: float32 products.  ``bf16_operands``: every product of
    the forward and of the backward pass takes its two operands rounded to
    bfloat16 and accumulates in float32, which is what a float32
    multiplication at the TPU's default precision computes.
    ``fp8_operands``: the same with operands rounded to float8 (e5m2), the
    precision below it, for the control of the correctness check."""
    if mode in _LOW:
        return _mm_low(x, w, mode)
    return _dot_hi(x, w)


def _conv(x, w, stride: int, mode: str):
    if mode in _LOW:
        return _conv_low(x, w, stride, mode)
    return _conv_hi(x, w, stride)


def _dense(p, x, mode):
    return _mm(x, p["kernel"], mode) + p["bias"]


def _mlp(p, x, n_layers: int, activate_final: bool, mode):
    """``p["Dense_i"]["col" | "row"]``: even layers are stored under
    ``col``, odd under ``row`` (the layout's tensor-parallel roles)."""
    for i in range(n_layers):
        x = _dense(p[f"Dense_{i}"]["col" if i % 2 == 0 else "row"], x, mode)
        if activate_final or i < n_layers - 1:
            x = jax.nn.relu(x)
    return x


def _cnn(p, frame, model: dict, mode):
    x = frame.astype(jnp.float32)  # raw 0..255 pixels, as the source feeds them
    for i, s in enumerate(model["strides"]):
        x = jax.nn.relu(_conv(x, p[f"conv_{i}"]["kernel"], s, mode) + p[f"conv_{i}"]["bias"])
    x = x.reshape(x.shape[0], -1)
    x = _dense(p["Dense_0"]["col"], x, mode)
    return _dense(p["Dense_1"]["row"], x, mode)


def _squash(mu, log_std, eps, act_limit: float):
    """Reparameterised tanh-Gaussian sample and its log-density."""
    log_std = jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX)
    u = mu + jnp.exp(log_std) * eps
    logp = jnp.sum(
        -0.5 * eps * eps - log_std - 0.5 * math.log(2.0 * math.pi), axis=-1
    ) - jnp.sum(2.0 * (math.log(2.0) - u - jax.nn.softplus(-2.0 * u)), axis=-1)
    return jnp.tanh(u) * act_limit, logp


def actor(params, obs, eps, model: dict, mode: str):
    p = params["params"]
    n = len(model["hidden_sizes"])
    if model["family"] == "mlp":
        x = _mlp(p["MLP_0"], obs, n, True, mode)
    else:
        x = _mlp(p["MLP_0"], obs["features"], n, True, mode)
        x = jnp.concatenate(
            [x, _cnn(p["visual_network"], obs["frame"], model, mode)], axis=-1
        )
    mu = _dense(p["Dense_0"]["Dense_0"], x, mode)
    log_std = _dense(p["Dense_1"]["Dense_0"], x, mode)
    return _squash(mu, log_std, eps, model["act_limit"])


def critic(params, obs, action, model: dict, mode: str):
    """Twin Q values, shape ``(2, batch)``."""
    p = params["params"]
    n = len(model["hidden_sizes"]) + 1
    if model["family"] == "mlp":
        x = jnp.concatenate([obs, action], axis=-1)
        q = jax.vmap(lambda pe: _mlp(pe, x, n, False, mode))(p["ensemble"]["MLP_0"])
        return q[..., 0]
    x = jnp.concatenate([obs["features"], action], axis=-1)
    qs = []
    for i in range(model["num_qs"]):
        pe = p[f"ensemble_{i}"]
        h = _mlp(pe["MLP_0"], x, n, True, mode)  # the source ReLUs its last layer too
        h = jnp.concatenate(
            [h, _cnn(pe["visual_network"], obs["frame"], model, mode)], axis=-1
        )
        qs.append(_dense(pe["final"]["Dense_0"], h, mode)[..., 0])
    return jnp.stack(qs)


def _adam(grads, mu, nu, count, lr: float):
    count = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
    c1 = 1 - B1 ** count.astype(jnp.float32)
    c2 = 1 - B2 ** count.astype(jnp.float32)
    step = jax.tree_util.tree_map(
        lambda m, v: -lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS), mu, nu
    )
    return step, mu, nu, count


def init_state(actor_params, critic_params) -> dict:
    zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)  # noqa: E731
    return {
        "actor": actor_params, "critic": critic_params,
        "target": jax.tree_util.tree_map(jnp.array, critic_params),
        "pi_mu": zeros(actor_params), "pi_nu": zeros(actor_params),
        "q_mu": zeros(critic_params), "q_nu": zeros(critic_params),
        "count": jnp.int32(0),
    }


def update(state: dict, batch: dict, eps_q, eps_pi, model: dict, sac: dict, mode: str = "highest"):
    """One gradient step.  ``batch`` leaves and the noises carry the stream
    axis ``D`` first.  Returns the new state, ``loss_q`` and ``loss_pi``."""
    alpha, gamma = sac["alpha"], sac["gamma"]

    def q_loss(critic_p, b, e):
        a2, logp2 = actor(state["actor"], b["next_states"], e, model, mode)
        q_t = jnp.min(critic(state["target"], b["next_states"], a2, model, mode), axis=0)
        backup = sac["reward_scale"] * b["rewards"] + gamma * (1.0 - b["done"]) * (
            q_t - alpha * logp2
        )
        backup = jax.lax.stop_gradient(backup)
        q = critic(critic_p, b["states"], b["actions"], model, mode)
        return jnp.sum(jnp.mean((q - backup[None, :]) ** 2, axis=-1))

    loss_q, g_q = jax.value_and_grad(
        lambda p: jnp.mean(jax.vmap(lambda b, e: q_loss(p, b, e))(batch, eps_q))
    )(state["critic"])
    step, q_mu, q_nu, count = _adam(g_q, state["q_mu"], state["q_nu"], state["count"], sac["lr"])
    critic_p = jax.tree_util.tree_map(jnp.add, state["critic"], step)

    def pi_loss(actor_p, b, e):
        pi, logp = actor(actor_p, b["states"], e, model, mode)
        q_pi = jnp.min(critic(critic_p, b["states"], pi, model, mode), axis=0)
        return jnp.mean(alpha * logp - q_pi)

    loss_pi, g_pi = jax.value_and_grad(
        lambda p: jnp.mean(jax.vmap(lambda b, e: pi_loss(p, b, e))(batch, eps_pi))
    )(state["actor"])
    step, pi_mu, pi_nu, _ = _adam(g_pi, state["pi_mu"], state["pi_nu"], state["count"], sac["lr"])
    actor_p = jax.tree_util.tree_map(jnp.add, state["actor"], step)

    rho = sac["polyak"]
    target = jax.tree_util.tree_map(
        lambda tgt, src: rho * tgt + (1.0 - rho) * src, state["target"], critic_p
    )
    new = {
        "actor": actor_p, "critic": critic_p, "target": target,
        "pi_mu": pi_mu, "pi_nu": pi_nu, "q_mu": q_mu, "q_nu": q_nu, "count": count,
    }
    return new, loss_q, loss_pi


def follow(state: dict, batches: dict, eps_q, eps_pi, model: dict, sac: dict, mode: str = "highest"):
    """Follow ``steps`` updates: every leaf of ``batches`` and the noises is
    ``(steps, D, batch, ...)``.  Returns the final state and the mean losses,
    which is what one call of the program reports."""

    def body(st, xs):
        b, eq, ep = xs
        st, lq, lp = update(st, b, eq, ep, model, sac, mode)
        return st, (lq, lp)

    state, (lq, lp) = jax.lax.scan(body, state, (batches, eps_q, eps_pi))
    return state, jnp.mean(lq), jnp.mean(lp)
