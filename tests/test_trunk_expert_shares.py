"""The SDAR trunk's expert layer, however its held rows fall into chunks and
pieces: the shares' partial sums add up to the whole layer. The gradients are
``test_trunk_expert_gradients.py``'s. What no split changes (the reference's
layer, the one-chunk form) is computed once a precision
(``trunk_helpers.unsplit``)."""

import numpy as np

from trunk_helpers import (  # noqa: F401  (``pieces`` and ``unsplit`` are fixtures)
    LIVE,
    PRECISIONS,
    SPLITS,
    _expert_weights,
    _share,
    pieces,
    unsplit,
)


@PRECISIONS
@SPLITS
def test_the_shares_partial_sums_add_up_to_the_whole_layer(
    chunk_rows, piece_rows, mode, bf16, pieces, unsplit
):
    """Guide section 4's share test: the partial sums of all four shares of
    4 experts add up to the uncut reference's layer output over all 16
    (attention is upstream of the split and counted once).  Tolerance:
    float32 sums in another order.  However the held rows fall into chunks
    and pieces, a share is the one-chunk form's to the bit at float32: a
    token's terms are added in the same order."""
    pieces(piece_rows)
    p, u = _expert_weights()
    whole, one, before = (unsplit(mode, bf16)[k] for k in ("whole", "one", "before"))
    shares, plans = zip(*(
        _share(p, u, lo, lo + 4, chunk_rows=chunk_rows, bf16_dots=bf16)
        for lo in (0, 4, 8, 12)
    ))
    assert int(plans[1].n_rows) == LIVE
    np.testing.assert_allclose(sum(shares), whole, atol=2e-5)
    np.testing.assert_allclose(shares[1], one, atol=2e-5)  # the same terms left out
    if bf16:  # the CPU's bfloat16 product blocks a row's sum by the batch's size
        np.testing.assert_allclose(shares[1], before, atol=1e-6)
    else:
        np.testing.assert_array_equal(shares[1], before)
