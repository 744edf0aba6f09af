"""The SDAR history trunk's attention at a small size on the CPU: the
block mask and grouped heads in every attention implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.ops.attention import (
    blockwise_attention,
    flash_attention,
    reference_attention,
)

def _qkv(heads=8, kv_heads=2, t=256, d=64):
    k = jax.random.split(jax.random.key(0), 3)
    return (
        jax.random.normal(k[0], (2, heads, t, d)),
        jax.random.normal(k[1], (2, kv_heads, t, d)),
        jax.random.normal(k[2], (2, kv_heads, t, d)),
    )


IMPLS = {
    "blockwise": lambda b: lambda q, k, v: blockwise_attention(
        q, k, v, True, block_k=64, block_length=b
    ),
    "flash": lambda b: lambda q, k, v: flash_attention(
        q, k, v, True, 128, 128, True, 128, b, False
    ),
}


@pytest.mark.parametrize("block_length", [1, 4, 48])
@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_block_mask_and_grouped_heads_match_the_dense_reference(impl, block_length):
    """Forward and gradients of the scanned and of the three flash kernels
    (interpreted) against ``reference_attention``, 8 query heads over 2
    key/value heads, a block length that divides the kernels' tiles (4), one
    that does not (48) and the causal mask (1).  Tolerance: float32 sums in
    another order (online softmax over tiles), a few ulp of O(10) values."""
    q, k, v = _qkv()
    ref = lambda q, k, v: reference_attention(q, k, v, True, block_length=block_length)  # noqa: E731
    fn = IMPLS[impl](block_length)
    np.testing.assert_allclose(fn(q, k, v), ref(q, k, v), atol=5e-6)
    loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)  # noqa: E731
    got = jax.grad(loss(fn), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape  # dK, dV come back with the shared heads' shape
        np.testing.assert_allclose(g, w, atol=1e-4)


def test_the_mask_is_causal_across_blocks_and_full_inside_one():
    q, k, v = _qkv(heads=2, kv_heads=2, t=8, d=4)
    v = jnp.broadcast_to(jnp.eye(8)[None, None], (2, 2, 8, 8))  # row i of out: weights
    w = reference_attention(q, k, v, True, block_length=4)[0, 0]
    sees = np.asarray(w) > 0
    i, j = np.indices((8, 8))
    np.testing.assert_array_equal(sees, j // 4 <= i // 4)


@pytest.mark.parametrize("impl", ["reference", "blockwise", "flash"])
def test_block_length_one_is_bit_equal_to_causal(impl):
    q, k, v = _qkv(heads=2, kv_heads=2, t=256, d=64)
    if impl == "reference":
        a, b = reference_attention(q, k, v, True), reference_attention(q, k, v, True, block_length=1)
    elif impl == "blockwise":
        a = blockwise_attention(q, k, v, True, block_k=64)
        b = blockwise_attention(q, k, v, True, block_k=64, block_length=1)
    else:
        a = flash_attention(q, k, v, True, 128, 128, True)
        b = flash_attention(q, k, v, True, 128, 128, True, 128, 1, False)
    np.testing.assert_array_equal(a, b)


def test_grouped_heads_equal_repeated_heads():
    """Reading the shared head through the index maps is repeating k and v."""
    q, k, v = _qkv()
    rep = lambda x: jnp.repeat(x, 4, axis=1)  # noqa: E731
    a = flash_attention(q, k, v, True, 128, 128, True, 128, 4, False)
    b = flash_attention(q, rep(k), rep(v), True, 128, 128, True, 128, 4, False)
    np.testing.assert_array_equal(a, b)


def test_bf16_dots_round_operands_and_keep_float32_tiles():
    """``bf16_dots`` is the TPU's default precision inside the kernels: the
    result is float32 and equals the kernel fed operands already rounded, up
    to the rounding of the probability tile (2^-8 relative)."""
    q, k, v = _qkv(heads=2, kv_heads=2, t=128, d=64)
    low = flash_attention(q, k, v, True, 128, 128, True, 128, 1, True)
    assert low.dtype == jnp.float32
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    np.testing.assert_allclose(
        low, flash_attention(r(q), r(k), r(v), True, 128, 128, True), atol=2e-2
    )
    assert float(jnp.max(jnp.abs(low - flash_attention(q, k, v, True, 128, 128, True)))) > 1e-4
