"""Plain reference for the ``laguna`` history trunk: the decoder stack's
forward from its published equations, in float32 ``jax.numpy``.  The SAC step
on it (losses on one shared trunk, their gradients, Adam and polyak) is
``reference_trunk.update``, which takes this file's ``features``.

It imports nothing of the program.  Parameters are read by the names of the
program's checkpoint layout, the random draws of a step are inputs, and every
matrix product of the model goes through ``reference._mm`` (``highest``,
``bf16_operands`` or the control's ``fp8_operands``, in the backward pass
too), as in ``reference_trunk.py``, whose norm this file shares.  The
router's product alone is always at ``highest``, as in the program, and the
reference makes its own choices.  Attention is the full score matrix under
the layer's mask, one batch element and one key/value head's group of query
heads at a time (18 heads x 4,096^2 scores are 1.2 GB a batch element).

The stack (``Laguna-S-2.1``'s ``config.json``, ``model_type`` ``laguna``);
``u = RMSNorm(x)``, eps 1e-6, no bias anywhere; layer ``l``'s kind a letter
of ``model["pattern"]`` (``F`` / ``f`` full attention, ``W`` / ``w`` sliding;
capital: sparse experts, small: the dense feed-forward)::

    h = x + W_o [ g * Attn(rope(W_q u), rope(W_k u), W_v u) ]     g = sigmoid(W_g u), one a head
    y = h + FFN(RMSNorm(h)),   and one RMSNorm after the last block

``Attn``: ``n`` query heads of ``head_dim`` over ``kv_heads`` shared ones
(``q_heads`` on a full layer, ``window_q_heads`` on a sliding one; query head
``i`` reads key/value head ``i // (n / kv_heads)``), ``softmax(q k / sqrt(d))``
over the keys ``j <= i`` and, on a sliding layer, ``j > i - window``.

``rope``, by the layer's kind.  Sliding: every channel pair ``(c, c + d/2)``
of a head turns by ``pos * theta_w^(-2c/d)``.  Full: the first ``r = share *
d`` channels turn, pair ``(c, c + r/2)`` by ``pos * f_c``, the rest pass;
``f_c`` is YaRN's: ``theta^(-2c/r)`` blended with itself over ``factor`` by
the ramp ``clip((c - low) / (high - low), 0, 1)``, ``low`` / ``high`` the
pair indices that turn ``beta_fast`` / ``beta_slow`` times in
``yarn_positions`` positions (rounded down / up); cosine and sine times
``attention_factor``.

``FFN``, dense: ``W_down (silu(W_gate v) * W_up v)``.  Sparse: ``p =
softmax(v W_r)`` over all experts; the ``top_k`` largest, ``w = scale * p /
(sum of the chosen p)``; the routed sum over the chosen experts in
``experts_held`` (a loop over the held experts; the others belong to other
chips and are left out, as in the program) plus the shared expert, both of
the dense form.

Departures from the published model, each forced by what this system is: the
observation projection stands where the token embedding was; the policy and
twin Q heads on the last step's output where the LM head was.

A layer's sublayers are recomputed in the backward pass, attention and the
dense feed-forward one batch element at a time and the experts one at a
time, so that the whole fits the chip after ``driver.free()``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import _mm
from benchmark.harness.reference_trunk import _rms

WINDOWED, DENSE = "Ww", "fw"


def pair_frequencies(model: dict, kind: str):
    """``(angular frequencies of the channel pairs that turn, the factor on
    cosine and sine)`` of a layer of ``kind``."""
    d = model["head_dim"]
    if kind in WINDOWED:
        return model["window_rope_theta"] ** (-2.0 * jnp.arange(d // 2) / d), 1.0
    r = int(d * model["rope_share"])
    pair = jnp.arange(r // 2, dtype=jnp.float32)
    freq = model["rope_theta"] ** (-2.0 * pair / r)
    factor = model["rope_yarn_factor"]
    if factor > 1.0:
        def pair_that_turns(times):
            span = model["rope_yarn_positions"] / (times * 2 * math.pi)
            return r * math.log(span) / (2 * math.log(model["rope_theta"]))

        low = max(math.floor(pair_that_turns(model["rope_yarn_beta_fast"])), 0)
        high = min(math.ceil(pair_that_turns(model["rope_yarn_beta_slow"])), r - 1)
        ramp = jnp.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
        freq = (1.0 - ramp) * freq + ramp * freq / factor
    return freq, model["rope_attention_factor"]


def rope(x, model: dict, kind: str):
    """Positions 0..T-1 on ``x`` ``(T, heads, d)``."""
    freq, factor = pair_frequencies(model, kind)
    half = freq.shape[0]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = factor * jnp.cos(ang), factor * jnp.sin(ang)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def sees(t: int, window: int | None):
    """``[query, key]``: the causal mask, inside the window if there is one."""
    i = jnp.arange(t)
    mask = i[None, :] <= i[:, None]
    if window is not None:
        mask &= i[None, :] > i[:, None] - window
    return mask


def _attention(p, u, model: dict, kind: str, mode: str):
    """``u``: ``(T, hidden)``, one batch element."""
    t = u.shape[0]
    kvh, d = model["kv_heads"], model["head_dim"]
    qh = model["window_q_heads"] if kind in WINDOWED else model["q_heads"]
    group = qh // kvh
    q = rope(_mm(u, p["q_proj"]["kernel"], mode).reshape(t, qh, d), model, kind)
    k = rope(_mm(u, p["k_proj"]["kernel"], mode).reshape(t, kvh, d), model, kind)
    v = _mm(u, p["v_proj"]["kernel"], mode).reshape(t, kvh, d)
    mask = sees(t, model["window"] if kind in WINDOWED else None)

    def head(qi, ki, vi):
        s = _mm(qi, ki.T, mode) / math.sqrt(d)
        return _mm(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1), vi, mode)

    @jax.checkpoint
    def shared_head(xs):  # the query heads that read one key/value head
        q_g, k_h, v_h = xs
        return jax.vmap(head, in_axes=(1, None, None), out_axes=1)(q_g, k_h, v_h)

    out = jax.lax.map(shared_head, (
        q.reshape(t, kvh, group, d).transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
        v.transpose(1, 0, 2),
    ))  # (kv heads, T, group, d)
    gate = jax.nn.sigmoid(_mm(u, p["g_proj"]["kernel"], mode))  # (T, heads)
    out = out.transpose(1, 0, 2, 3).reshape(t, qh, d) * gate[:, :, None]
    return _mm(out.reshape(t, qh * d), p["o_proj"]["kernel"], mode)


def _gated(v, w_gate, w_up, w_down, mode: str):
    return _mm(jax.nn.silu(_mm(v, w_gate, mode)) * _mm(v, w_up, mode), w_down, mode)


def route(u, w_router, top_k: int, scale: float):
    """``(choices (N, top_k), weights (N, top_k))`` over all experts."""
    logits = jnp.matmul(u, w_router, precision=jax.lax.Precision.HIGHEST)
    prob = jax.nn.softmax(logits, axis=-1)
    choices = jnp.argsort(-prob, axis=-1, stable=True)[:, :top_k]
    top = jnp.take_along_axis(prob, choices, axis=-1)
    return choices, scale * top / jnp.sum(top, axis=-1, keepdims=True)


def _experts(p, u, model: dict, mode: str):
    """``u``: ``(N, hidden)``.  Returns the held experts' partial sum plus the
    shared expert, and the choices."""
    lo, hi = model["experts_held"]
    choices, weights = route(u, p["router"], model["experts_per_tok"], model["routed_scale"])

    @jax.checkpoint
    def expert(xs):
        e, w_gate, w_up, w_down = xs
        w_e = jnp.sum(jnp.where(choices == e, weights, 0.0), axis=-1)
        return w_e[:, None] * _gated(u, w_gate, w_up, w_down, mode)

    routed = jnp.sum(
        jax.lax.map(expert, (jnp.arange(lo, hi), p["w_gate"], p["w_up"], p["w_down"])), axis=0
    )
    shared = _gated(
        u, p["shared_gate"]["kernel"], p["shared_up"]["kernel"], p["shared_down"]["kernel"], mode
    )
    return routed + shared, choices


def attention_sublayer(lp, x, kind: str, model: dict, mode: str):
    """``x + Attn(RMSNorm(x))`` on ``x`` ``(B, T, hidden)``."""
    @jax.checkpoint
    def element(x_b):
        u = _rms(x_b, lp["input_norm"]["weight"], model["rms_eps"])
        return x_b + _attention(lp["attention"], u, model, kind, mode)

    return jax.lax.map(element, x)


def ffn_sublayer(lp, h, kind: str, model: dict, mode: str):
    """``h + FFN(RMSNorm(h))`` on ``h`` ``(B, T, hidden)``, and an expert
    layer's choices ``(B*T, top_k)``."""
    eps = model["rms_eps"]
    if kind in DENSE:
        @jax.checkpoint
        def element(h_b):
            u = _rms(h_b, lp["post_attention_norm"]["weight"], eps)
            w = lp["mlp"]
            return h_b + _gated(
                u, w["gate_proj"]["kernel"], w["up_proj"]["kernel"], w["down_proj"]["kernel"], mode
            )

        return jax.lax.map(element, h), None

    @jax.checkpoint
    def experts(lp, h):
        u = _rms(h, lp["post_attention_norm"]["weight"], eps).reshape(-1, h.shape[-1])
        y, choices = _experts(lp["moe"], u, model, mode)
        return h + y.reshape(h.shape), choices

    return experts(lp, h)


def trunk(p, obs, model: dict, mode: str):
    """``obs``: ``(B, T, obs_dim)``.  Returns the stack's output after its last
    norm ``(B, T, hidden)`` and every expert layer's choices ``(expert layers,
    B*T, top_k)``."""
    bsz, t, _ = obs.shape
    x = _mm(obs.reshape(bsz * t, -1), p["embed"]["kernel"], mode).reshape(bsz, t, -1)
    chosen = []
    for i, kind in enumerate(model["pattern"]):
        lp = p[f"layer_{i}"]
        x, choices = ffn_sublayer(lp, attention_sublayer(lp, x, kind, model, mode), kind, model, mode)
        if choices is not None:
            chosen.append(choices)
    return _rms(x, p["final_norm"]["weight"], model["rms_eps"]), jnp.stack(chosen)


def features(critic_p, obs, model: dict, mode: str):
    out, chosen = trunk(critic_p["params"]["trunk"], obs, model, mode)
    return out[:, -1], chosen
