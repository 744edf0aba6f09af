"""The SDAR trunk's expert layer, however its held rows fall into chunks and
pieces: the hand-written backward pass is autodiff of the dense form's, under
``vmap`` too. The dense form's gradient, which no split changes, is computed
once a precision (``trunk_helpers.unsplit``)."""

import jax
import jax.numpy as jnp
import numpy as np

from trunk_helpers import (  # noqa: F401  (``pieces`` and ``unsplit`` are fixtures)
    PRECISIONS,
    SPLITS,
    _expert_weights,
    _share,
    pieces,
    unsplit,
)


@PRECISIONS
@SPLITS
def test_expert_layer_gradients_match_the_dense_form_under_vmap(
    chunk_rows, piece_rows, mode, bf16, pieces, unsplit
):
    """The hand-written backward pass against autodiff of the dense form, and
    the same under ``vmap`` (the data-parallel burst maps the update over its
    device axis), where both passes run a mapped element at a time.  A
    kernel's gradient is summed chunk by chunk, so where a chunk's edge falls
    changes the order of that sum and nothing else."""
    pieces(piece_rows)
    p, u = _expert_weights()
    chunk_rows = 64 if chunk_rows is None else chunk_rows  # the case this test had

    sparse = lambda u, p: jnp.sum(  # noqa: E731
        _share(p, u, 4, 8, chunk_rows=chunk_rows, bf16_dots=bf16)[0] ** 2
    )
    want = unsplit(mode, bf16)["gradient"]
    got = jax.grad(sparse, (0, 1))(u, p)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.max(jnp.abs(w)) + 1))
    mapped = jax.vmap(jax.grad(sparse), in_axes=(0, None))(jnp.stack([u, 0.5 * u]), p)
    np.testing.assert_allclose(mapped[0], got[0], atol=1e-5)
