"""Plain reference for the SDAR history trunk: the decoder stack's forward from
its published equations, the SAC losses on one shared trunk, their gradients,
Adam and polyak, in float32 ``jax.numpy``.

It imports nothing of the program.  Parameters are read by the names of the
program's checkpoint layout, the random draws of a step (sampled rows, the two
noises) are inputs, and every product of the model goes through
``reference._mm``: plain float32 (``mode="highest"``), or operands rounded to
bfloat16 (``"bf16_operands"``, what the TPU's default precision makes of a
float32 product) or to float8 (``"fp8_operands"``, the control), in the
backward pass too.  The router's product alone is always at ``highest``, as
in the program: its top-k is a discrete choice, and the two sides' choices
should differ only through what rounding did upstream.  The reference makes
its own choices and is never handed the program's.

Layer, as published (``sdar_moe``; ``u`` is the normed input, no bias
anywhere, RMSNorm eps 1e-6 with a learned weight, statistics in float32)::

    h = x + W_o Attn(q, k, v)          q = rope(norm(W_q u))   32 heads of 128
                                       k = rope(norm(W_k u)), v = W_v u   4 of 128
    y = h + sum_e w_e W_down^e (silu(W_gate^e u') * W_up^e u')   u' = norm(h)

query head ``i`` reads key/value head ``i // 8``; position ``i`` sees ``j`` iff
``j // b <= i // b``; ``w_e = p_e / (sum of the 8 largest p)`` over the 8
largest of ``p = softmax(W_r u')`` over all 128.  Of the chosen experts the
terms of those in ``experts_held = [lo, hi)`` are computed (the kernels hold
those alone); the others belong to other chips and are left out, as in the
program.  No token is dropped, there is no capacity and no auxiliary loss.

Departures from the published model, each forced by what this system is:

- the observation projection ``Dense(obs_dim -> hidden)`` (no bias) stands
  where the token embedding was: the inputs are continuous observations;
- the squashed-Gaussian policy head and twin Q heads on the last step's
  output stand where the LM head was;
- no diffusion noise: the SAC path has no denoising objective; the
  block-causal mask is what remains of generation by diffusion over blocks.

Attention is computed one batch element at a time and the experts one at a
time, each recomputed in the backward pass, so that the whole fits the chip
after ``driver.free()``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import _adam, _mm, _squash, init_state  # noqa: F401


def _rms(x, weight, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta: float):
    """Rotate-half rotary positions 0..T-1 on ``x`` ``(T, heads, d)``."""
    t, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(p, u, model: dict, mode: str):
    """``u``: ``(T, hidden)``, one batch element."""
    t = u.shape[0]
    qh, kvh, d = model["q_heads"], model["kv_heads"], model["head_dim"]
    b, eps = model["block_length"], model["rms_eps"]
    q = _mm(u, p["q_proj"]["kernel"], mode).reshape(t, qh, d)
    k = _mm(u, p["k_proj"]["kernel"], mode).reshape(t, kvh, d)
    v = _mm(u, p["v_proj"]["kernel"], mode).reshape(t, kvh, d)
    q = _rope(_rms(q, p["q_norm"]["weight"], eps), model["rope_theta"])
    k = _rope(_rms(k, p["k_norm"]["weight"], eps), model["rope_theta"])
    i = jnp.arange(t)
    sees = (i[None, :] // b) <= (i[:, None] // b)  # [query, key]

    def head(qi, ki, vi):
        s = _mm(qi, ki.T, mode) / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1)
        return _mm(w, vi, mode)

    group = qh // kvh
    kv_of = jnp.arange(qh) // group
    out = jax.vmap(head, in_axes=(1, 1, 1), out_axes=1)(
        q, jnp.take(k, kv_of, axis=1), jnp.take(v, kv_of, axis=1)
    )
    return _mm(out.reshape(t, qh * d), p["o_proj"]["kernel"], mode)


def route(u, w_router, top_k: int):
    """``(choices (N, top_k), weights (N, top_k))`` over all experts."""
    logits = jnp.matmul(u, w_router, precision=jax.lax.Precision.HIGHEST)
    prob = jax.nn.softmax(logits, axis=-1)
    choices = jnp.argsort(-prob, axis=-1, stable=True)[:, :top_k]
    top = jnp.take_along_axis(prob, choices, axis=-1)
    return choices, top / jnp.sum(top, axis=-1, keepdims=True)


def _moe(p, u, model: dict, mode: str):
    """``u``: ``(N, hidden)``.  Returns the held experts' partial sum and the
    choices."""
    lo, hi = model["experts_held"]
    choices, weights = route(u, p["router"], model["experts_per_tok"])

    @jax.checkpoint
    def expert(xs):
        e, w_gate, w_up, w_down = xs
        w_e = jnp.sum(jnp.where(choices == e, weights, 0.0), axis=-1)
        y = _mm(jax.nn.silu(_mm(u, w_gate, mode)) * _mm(u, w_up, mode), w_down, mode)
        return w_e[:, None] * y

    terms = jax.lax.map(expert, (jnp.arange(lo, hi), p["w_gate"], p["w_up"], p["w_down"]))
    out = jnp.sum(terms, axis=0)
    return out, choices


def trunk(p, obs, model: dict, mode: str):
    """``obs``: ``(B, T, obs_dim)``.  Returns the stack's output after its last
    norm ``(B, T, hidden)`` and every layer's choices ``(layers, B*T, top_k)``."""
    bsz, t, _ = obs.shape
    eps = model["rms_eps"]
    x = _mm(obs.reshape(bsz * t, -1), p["embed"]["kernel"], mode).reshape(bsz, t, -1)
    chosen = []
    for i in range(model["layers"]):
        lp = p[f"layer_{i}"]

        @jax.checkpoint
        def attend(x_b, lp=lp):
            u = _rms(x_b, lp["input_norm"]["weight"], eps)
            return x_b + _attention(lp["attention"], u, model, mode)

        h = jax.lax.map(attend, x)
        u = _rms(h, lp["post_attention_norm"]["weight"], eps).reshape(bsz * t, -1)
        y, choices = _moe(lp["moe"], u, model, mode)
        x = h + y.reshape(h.shape)
        chosen.append(choices)
    return _rms(x, p["final_norm"]["weight"], eps), jnp.stack(chosen)


def features(critic_p, obs, model: dict, mode: str):
    out, chosen = trunk(critic_p["params"]["trunk"], obs, model, mode)
    return out[:, -1], chosen


def q_heads(critic_p, h, action, mode: str):
    """Twin Q values ``(num_qs, batch)`` from the last step's features."""
    p = critic_p["params"]["ensemble"]
    x = jnp.concatenate([h, action], axis=-1)

    def one(first, second):
        y = jax.nn.relu(_mm(x, first["kernel"], mode) + first["bias"])
        return (_mm(y, second["kernel"], mode) + second["bias"])[..., 0]

    return jax.vmap(one)(p["Dense_0"]["Dense_0"], p["Dense_1"]["Dense_0"])


def policy_head(actor_p, h, eps, model: dict, mode: str):
    p = actor_p["params"]
    mu = _mm(h, p["mu"]["Dense_0"]["kernel"], mode) + p["mu"]["Dense_0"]["bias"]
    log_std = _mm(h, p["log_std"]["Dense_0"]["kernel"], mode) + (
        p["log_std"]["Dense_0"]["bias"]
    )
    return _squash(mu, log_std, eps, model["act_limit"])


def update(
    state, batch, eps_q, eps_pi, model: dict, sac: dict, mode: str = "highest",
    features=features,
):
    """One gradient step on the shared trunk.  ``batch`` leaves and the noises
    carry the stream axis ``D`` first (the data-parallel replicas whose
    gradients are averaged).  Returns the new state, ``loss_q``, ``loss_pi``,
    the online pass's choices ``(D, layers, tokens, top_k)`` and the size of
    the policy loss's two terms.

    ``features(critic_p, obs, model, mode)`` is the trunk's forward, from the
    critic's parameters and a batch of histories to the last step's features
    ``(batch, hidden)`` and the routed choices: this family's by default,
    another family's reference hands in its own and shares the step.

    Two trunk passes a step: the target trunk on ``next_states`` feeds the
    target Q heads and the policy head that draws ``a'``; the online trunk on
    ``states`` is differentiated by the critic loss, and the policy loss reads
    its features as constants against the updated Q heads."""
    alpha, gamma = sac["alpha"], sac["gamma"]

    def q_loss(critic_p, b, e):
        h_next, _ = features(state["target"], b["next_states"], model, mode)
        a2, logp2 = policy_head(state["actor"], h_next, e, model, mode)
        q_t = jnp.min(q_heads(state["target"], h_next, a2, mode), axis=0)
        backup = sac["reward_scale"] * b["rewards"] + gamma * (1.0 - b["done"]) * (
            q_t - alpha * logp2
        )
        backup = jax.lax.stop_gradient(backup)
        h, chosen = features(critic_p, b["states"], model, mode)
        q = q_heads(critic_p, h, b["actions"], mode)
        loss = jnp.sum(jnp.mean((q - backup[None, :]) ** 2, axis=-1))
        return loss, (jax.lax.stop_gradient(h), chosen)

    def mean_q_loss(p):
        loss, aux = jax.vmap(lambda b, e: q_loss(p, b, e))(batch, eps_q)
        return jnp.mean(loss), aux

    (loss_q, (h, chosen)), g_q = jax.value_and_grad(mean_q_loss, has_aux=True)(
        state["critic"]
    )
    step, q_mu, q_nu, count = _adam(g_q, state["q_mu"], state["q_nu"], state["count"], sac["lr"])
    critic_p = jax.tree_util.tree_map(jnp.add, state["critic"], step)

    def pi_loss(actor_p, h_d, e):
        pi, logp = policy_head(actor_p, h_d, e, model, mode)
        q_pi = jnp.min(q_heads(critic_p, h_d, pi, mode), axis=0)
        # The loss is the difference of two terms of like size and crosses
        # zero from seed to seed: their sizes are what a gap in it is judged by.
        terms = jnp.abs(jnp.mean(alpha * logp)) + jnp.abs(jnp.mean(q_pi))
        return jnp.mean(alpha * logp - q_pi), terms

    def mean_pi_loss(p):
        loss, terms = jax.vmap(lambda h_d, e: pi_loss(p, h_d, e))(h, eps_pi)
        return jnp.mean(loss), jnp.mean(terms)

    (loss_pi, pi_terms), g_pi = jax.value_and_grad(mean_pi_loss, has_aux=True)(
        state["actor"]
    )
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
    return new, loss_q, loss_pi, chosen, pi_terms


def follow(
    state, batches, eps_q, eps_pi, model: dict, sac: dict, mode: str = "highest",
    features=features,
):
    """Follow ``steps`` updates (leaves ``(steps, D, batch, ...)``) through
    the trunk ``features`` computes.  Returns the final state, the mean
    losses, the first update's choices and the mean size of the policy loss's
    two terms (``|alpha logp| + |min Q|``)."""

    def body(st, xs):
        st, lq, lp, chosen, terms = update(st, *xs, model, sac, mode, features)
        return st, (lq, lp, chosen, terms)

    state, (lq, lp, chosen, terms) = jax.lax.scan(body, state, (batches, eps_q, eps_pi))
    return state, jnp.mean(lq), jnp.mean(lp), chosen[0], jnp.mean(terms)
