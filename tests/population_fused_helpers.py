"""What the fused population's two test files share
(``test_population_fused.py``, ``test_population_fused_driver.py``): trees
compared to the bit. A plain module, imported by name."""

import jax
import jax.numpy as jnp
import numpy as np


def _leaves(tree):
    """Comparable numpy leaves (typed PRNG keys as their uint32 data)."""
    return [
        np.asarray(
            jax.random.key_data(x)
            if jnp.issubdtype(x.dtype, jax.dtypes.prng_key)
            else x
        )
        for x in jax.tree_util.tree_leaves(tree)
    ]


def _assert_bitwise(a, b, what=""):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y, err_msg=what)
