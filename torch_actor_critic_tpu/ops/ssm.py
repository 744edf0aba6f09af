"""The sequence operations of a Mamba-2 mixer, for the heads this chip holds.

The mixer (``models/sequence.py::MambaMixer``) projects a token to ``x``
(``heads`` of ``head_dim``), ``B`` and ``C`` (``groups`` of ``state`` each; a
head reads its group's) and a step size ``dt`` a head, passes ``x``, ``B`` and
``C`` through a short causal depthwise convolution, and runs, a head at a time,
the selective state-space recurrence over the history::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (head_dim x state)
    y_t = S_t C_t + D x_t

A deployment divides the mixer by heads: a chip holds whole groups with their
heads, the recurrence of one head reads nothing of another, so a share is the
same computation over fewer heads and every function here takes the held ones
in its shapes.

:func:`ssd_scan` computes the recurrence in the chunked state-space-duality
form (Dao & Gu 2024, "Transformers are SSMs", section 6): inside a chunk of
``chunk`` steps the output is a masked product, ``(C B^T * L) (dt x)`` with
``L[i, j] = exp(sum_{j < s <= i} dt_s A)`` the decay between two positions;
between chunks a state is carried, ``chunk`` times fewer sequential steps than
the recurrence has.  It is composed of ``jnp`` products, so autodiff gives the
backward pass.  The decay terms (cumulative sums of ``dt A``, their
exponentials) and the carried state are float32 whatever the inputs; the
operands of the four products are rounded to bfloat16 under ``bf16_dots``
(float32 accumulation), as the trunk's other kernels are.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(x: jax.Array, kernel: jax.Array, bias: jax.Array) -> jax.Array:
    """Depthwise causal convolution over time: ``x`` ``(B, T, C)``, ``kernel``
    ``(K, C)``, ``bias`` ``(C,)``; ``y_t = bias + sum_i kernel[i] x_{t-K+1+i}``
    with zeros before the history's first step."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = bias.astype(x.dtype)
    for i in range(taps):
        y = y + padded[:, i:i + t] * kernel[i].astype(x.dtype)
    return y


def _dot(spec: str, a, b, bf16_dots: bool):
    if bf16_dots:
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def ssd_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    d: jax.Array, chunk: int = 128, bf16_dots: bool = False,
) -> jax.Array:
    """The recurrence above for every held head, chunked.

    ``x``: ``(B, T, heads, head_dim)``; ``dt``: ``(B, T, heads)``, positive
    (after its softplus); ``a``: ``(heads,)``, negative; ``b``, ``c``:
    ``(B, T, groups, state)``, head ``h`` reading group ``h // (heads //
    groups)``; ``d``: ``(heads,)``.  Returns ``y`` ``(B, T, heads, head_dim)``
    in float32.  ``T`` need not be a multiple of ``chunk``: the history is
    padded with steps of ``dt = 0`` (no decay, no input), whose outputs are cut
    off."""
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2:]
    per = heads // groups
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, dt, b, c)
        )
    nc = (t + pad) // q
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)

    def chunks(v):
        """``(B, T, groups, per, ...)`` with a chunk's steps behind the heads,
        ``(B, chunks, groups, per, Q, ...)``: no small axis is the last."""
        return jnp.moveaxis(v.reshape((bsz, nc, q) + v.shape[2:]), 2, 4)

    xc = chunks((x * dt[..., None]).reshape(bsz, nc * q, groups, per, p))  # (B, nc, g, h, Q, p)
    bc = jnp.moveaxis(b.astype(jnp.float32).reshape(bsz, nc, q, groups, n), 2, 3)  # (B, nc, g, Q, n)
    cc = jnp.moveaxis(c.astype(jnp.float32).reshape(bsz, nc, q, groups, n), 2, 3)
    # Cumulative log-decay inside a chunk, inclusive of the step itself.
    log_decay = chunks((dt * a.astype(jnp.float32)).reshape(bsz, nc * q, groups, per))
    cum = jnp.cumsum(log_decay, axis=-1)  # (B, nc, g, h, Q)

    # Inside a chunk: position i reads j <= i through C_i . B_j, decayed.
    scores = _dot("zcgin,zcgjn->zcgij", cc, bc, bf16_dots)
    between = cum[..., :, None] - cum[..., None, :]  # (B, nc, g, h, i, j)
    causal = jnp.tril(jnp.ones((q, q), bool))
    mixed = scores[:, :, :, None] * jnp.exp(jnp.where(causal, between, -jnp.inf))
    y = _dot("zcghij,zcghjp->zcghip", mixed, xc, bf16_dots)

    # What a chunk leaves behind: its inputs decayed to the chunk's end.
    to_end = jnp.exp(cum[..., -1:] - cum)
    left = _dot("zcgjn,zcghjp->zcghpn", bc, xc * to_end[..., None], bf16_dots)

    # Between chunks: the carried state at each chunk's start, float32.
    through = jnp.exp(cum[..., -1])  # (B, nc, g, h): a whole chunk's decay

    def carry(state, chunk_of):
        decay_c, left_c = chunk_of
        return state * decay_c[..., None, None] + left_c, state

    zero = jnp.zeros((bsz, groups, per, p, n), jnp.float32)
    _, starts = jax.lax.scan(
        carry, zero, (jnp.moveaxis(through, 1, 0), jnp.moveaxis(left, 1, 0))
    )
    starts = jnp.moveaxis(starts, 0, 1)  # (B, nc, g, h, p, n)
    y = y + _dot("zcgin,zcghpn->zcghip", cc, starts, bf16_dots) * jnp.exp(cum)[..., None]

    y = jnp.moveaxis(y, 4, 2).reshape(bsz, nc * q, heads, p)[:, :t]
    return y + x[:, :t] * d.astype(jnp.float32)[:, None]


def gated_group_norm(
    y: jax.Array, z: jax.Array, weight: jax.Array, groups: int, eps: float
) -> jax.Array:
    """``RMSNorm(y * silu(z))`` over each of ``groups`` equal runs of the last
    axis separately, one ``weight`` over the whole axis; statistics float32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    run = gated.reshape(gated.shape[:-1] + (groups, -1))
    run = run * jax.lax.rsqrt(jnp.mean(run * run, axis=-1, keepdims=True) + eps)
    return run.reshape(gated.shape).astype(y.dtype) * weight
