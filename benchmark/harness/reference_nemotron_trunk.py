"""Plain reference for the ``nemotron_h`` history trunk: the hybrid stack's
forward from its published equations, in float32 ``jax.numpy``.  The SAC step
on it (losses on one shared trunk, their gradients, Adam and polyak) is
``reference_trunk.update``, which takes this file's ``features``.

It imports nothing of the program.  Parameters are read by the names of the
program's checkpoint layout, the random draws of a step are inputs, and every
matrix product of the model goes through ``reference._mm`` (``highest``,
``bf16_operands`` or the control's ``fp8_operands``, in the backward pass
too), as in ``reference_trunk.py``, whose norm this file shares.  The
router's product alone is always at ``highest``, as in the program, and the
reference makes its own choices.

The stack (``NVIDIA-Nemotron-3-Super-120B-A12B``'s ``config.json``,
``model_type`` ``nemotron_h``): every layer is ``h <- h + Mixer(RMSNorm(h))``,
eps 1e-5, the mixer's kind a letter of ``model["pattern"]``; one RMSNorm after
the last layer; no bias but the convolution's.

``M``, the Mamba-2 mixer (``heads`` of ``ssm_head_dim``, ``groups`` of
``ssm_state``; head ``i`` reads group ``i // (heads / groups)``)::

    [z | xBC | dt] = u W_in          xBC <- silu(conv1d(xBC)): causal, depthwise, 4 taps, bias
    [x | B | C] = xBC                dt = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,    y_t = S_t C_t + D x_t     (a head)
    out = W_out RMSNorm_group(y * silu(z))         each group's channels apart

The recurrence is a plain ``lax.scan`` over time steps, not the program's
chunked form.  In a lower-precision mode the operands the program's products
round are rounded here: ``dt x``, ``B`` and ``C``; the state stays float32.

``*``, attention: ``q_heads`` query heads over ``kv_heads`` of ``head_dim``,
causal, ``softmax(Q K^T / sqrt(d))``, no norm and no positions on q or k.

``E``, the latent expert layer: ``s = sigmoid(u W_r)``; the ``top_k`` chosen
are the largest of ``s + b`` (the correction bias moves the choice alone);
``w = scale * s / (sum of the chosen s + 1e-20)``; ``v = u W_dn``; routed
``r = sum_k w_k W2_e relu(W1_e v)^2`` over the chosen experts in
``experts_held`` (a loop over the held experts; the others belong to other
chips and are left out, as in the program); mixer output ``r W_up +
W2_s relu(W1_s u)^2``, the shared expert on the full width.

Departures from the published model, each forced by what this system is: the
observation projection stands where the token embedding was; the policy and
twin Q heads on the last step's output where the LM head was; no
multi-token-prediction module (it predicts through the vocabulary head, and
this trunk holds no vocabulary).

A layer is recomputed in the backward pass, the state-space and attention
mixers one batch element at a time and the experts one at a time: a
time-step scan keeps a state a step, 0.5 GB an element and layer at the
published widths, a batch's would not fit: four copies of the trunk (weights,
target, Adam's two moments) and a gradient are 11.3 GB of the chip's 15.75
GiB before any activation.  The whole runs after ``driver.free()``; the driver
(``drivers/hybridburst.py``) donates the initial parameters and takes back
only what is compared (the compiler's account of ``follow`` for the described
v5e: 12.36 GB; 19.03 GB with a layer's batch recomputed at once, all of the
state returned and nothing donated: sandbox compiles, PR 40).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import _LOW, _mm, _rounder
from benchmark.harness.reference_trunk import _rms


def _group_rms(x, weight, groups: int, eps: float):
    run = x.reshape(x.shape[:-1] + (groups, -1))
    run = run * jax.lax.rsqrt(jnp.mean(run * run, axis=-1, keepdims=True) + eps)
    return run.reshape(x.shape) * weight


def recurrence(x, dt, a, b, c, d, mode: str = "highest"):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T``, ``y_t = S_t c_t + d x_t``
    over time, a head at a time.  ``x``: ``(T, heads, p)``; ``dt``: ``(T,
    heads)``; ``a``, ``d``: ``(heads,)``; ``b``, ``c``: ``(T, heads, n)``."""
    r = _rounder(mode) if mode in _LOW else (lambda v: v)

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        fed = r(dt_t[:, None] * x_t)[:, :, None] * r(b_t)[:, None, :]
        state = jnp.exp(dt_t * a)[:, None, None] * state + fed
        return state, jnp.sum(state * r(c_t)[:, None, :], axis=-1) + d[:, None] * x_t

    zero = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)
    return jax.lax.scan(step, zero, (x, dt, b, c))[1]


def _mamba(p, u, model: dict, mode: str):
    """``u``: ``(T, hidden)``, one batch element."""
    t = u.shape[0]
    heads, hd = model["ssm_heads"], model["ssm_head_dim"]
    groups, n, taps = model["ssm_groups"], model["ssm_state"], model["ssm_conv"]
    inner, bc = heads * hd, groups * n
    z, xbc, dt = jnp.split(
        _mm(u, p["in_proj"]["kernel"], mode), (inner, 2 * inner + 2 * bc), axis=-1
    )
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(
        p["conv_bias"] + sum(padded[i:i + t] * p["conv_kernel"][i] for i in range(taps))
    )
    x, b, c = jnp.split(xbc, (inner, inner + bc), axis=-1)
    of_head = lambda v: jnp.repeat(v.reshape(t, groups, n), heads // groups, axis=1)  # noqa: E731
    y = recurrence(
        x.reshape(t, heads, hd), jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        of_head(b), of_head(c), p["D"], mode,
    ).reshape(t, inner)
    y = _group_rms(y * jax.nn.silu(z), p["norm_weight"], groups, model["rms_eps"])
    return _mm(y, p["out_proj"]["kernel"], mode)


def _attention(p, u, model: dict, mode: str):
    """``u``: ``(T, hidden)``, one batch element."""
    t = u.shape[0]
    qh, kvh, d = model["q_heads"], model["kv_heads"], model["head_dim"]
    q = _mm(u, p["q_proj"]["kernel"], mode).reshape(t, qh, d)
    k = _mm(u, p["k_proj"]["kernel"], mode).reshape(t, kvh, d)
    v = _mm(u, p["v_proj"]["kernel"], mode).reshape(t, kvh, d)
    i = jnp.arange(t)
    sees = i[None, :] <= i[:, None]  # [query, key]

    def head(qi, ki, vi):
        s = _mm(qi, ki.T, mode) / math.sqrt(d)
        return _mm(jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1), vi, mode)

    kv_of = jnp.arange(qh) // (qh // kvh)
    out = jax.vmap(head, in_axes=(1, 1, 1), out_axes=1)(
        q, jnp.take(k, kv_of, axis=1), jnp.take(v, kv_of, axis=1)
    )
    return _mm(out.reshape(t, qh * d), p["o_proj"]["kernel"], mode)


def route(u, w_router, bias, top_k: int, scale: float):
    """``(choices (N, top_k), weights (N, top_k))`` over all experts."""
    s = jax.nn.sigmoid(jnp.matmul(u, w_router, precision=jax.lax.Precision.HIGHEST))
    choices = jnp.argsort(-(s + bias), axis=-1, stable=True)[:, :top_k]
    top = jnp.take_along_axis(s, choices, axis=-1)
    return choices, scale * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _experts(p, u, model: dict, mode: str):
    """``u``: ``(N, hidden)``.  Returns the mixer's output with the held
    experts' partial sum in it, and the choices."""
    lo, hi = model["experts_held"]
    choices, weights = route(
        u, p["router"], p["router_bias"], model["experts_per_tok"], model["routed_scale"]
    )
    v = _mm(u, p["latent_down"]["kernel"], mode)

    @jax.checkpoint
    def expert(xs):
        e, w_up, w_down = xs
        w_e = jnp.sum(jnp.where(choices == e, weights, 0.0), axis=-1)
        return w_e[:, None] * _mm(_relu2(_mm(v, w_up, mode)), w_down, mode)

    routed = jnp.sum(jax.lax.map(expert, (jnp.arange(lo, hi), p["w_up"], p["w_down"])), axis=0)
    shared = _mm(_relu2(_mm(u, p["shared_up"]["kernel"], mode)), p["shared_down"]["kernel"], mode)
    return _mm(routed, p["latent_up"]["kernel"], mode) + shared, choices


def layer(lp, x, kind: str, model: dict, mode: str):
    """One layer on ``x`` ``(B, T, hidden)``: its output and, for an expert
    layer, its choices ``(B*T, top_k)``.  Recomputed in the backward pass: an
    expert layer whole, the others one batch element at a time (a time-step
    scan keeps a state a step: one element's are 0.5 GB at the published
    widths, a batch's would not fit beside the parameters)."""
    eps = model["rms_eps"]
    if kind == "E":
        @jax.checkpoint
        def experts(lp, x):
            u = _rms(x, lp["norm"]["weight"], eps).reshape(-1, x.shape[-1])
            y, choices = _experts(lp["mixer"], u, model, mode)
            return x + y.reshape(x.shape), choices

        return experts(lp, x)
    mixer = _mamba if kind == "M" else _attention

    @jax.checkpoint
    def element(x_b):
        return x_b + mixer(lp["mixer"], _rms(x_b, lp["norm"]["weight"], eps), model, mode)

    return jax.lax.map(element, x), None


def trunk(p, obs, model: dict, mode: str):
    """``obs``: ``(B, T, obs_dim)``.  Returns the stack's output after its last
    norm ``(B, T, hidden)`` and every expert layer's choices ``(expert layers,
    B*T, top_k)``."""
    bsz, t, _ = obs.shape
    x = _mm(obs.reshape(bsz * t, -1), p["embed"]["kernel"], mode).reshape(bsz, t, -1)
    chosen = []
    for i, kind in enumerate(model["pattern"]):
        x, choices = layer(p[f"layer_{i}"], x, kind, model, mode)
        if choices is not None:
            chosen.append(choices)
    return _rms(x, p["final_norm"]["weight"], model["rms_eps"]), jnp.stack(chosen)


def features(critic_p, obs, model: dict, mode: str):
    out, chosen = trunk(critic_p["params"]["trunk"], obs, model, mode)
    return out[:, -1], chosen
