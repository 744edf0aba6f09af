"""The ``nemotron_h`` history trunk's operations at a small size on the CPU:
the chunked scan against the time-step recurrence, the causal convolution,
and the sigmoid router against the plain reference
(``benchmark/harness/reference_nemotron_trunk.py``). The layers are
``test_hybrid_trunk.py``'s, the stack ``test_hybrid_trunk_stack.py``'s."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_nemotron_trunk as reference  # noqa: E402
from torch_actor_critic_tpu.ops import moe, ssm  # noqa: E402

HIDDEN = 32

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
