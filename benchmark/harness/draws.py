"""The random draws of an update burst, as inputs for the plain reference.

The reference takes its sampled rows and its noise as inputs; they have to be
the ones the program drew, and the program draws them inside its compiled
burst from the key in its state.  This file re-derives them from that key with
``jax.random`` alone, by the program's key discipline, which is part of what
it promises (ROADMAP: the loss stream is checked against the parent commit):

- each update splits the state key in two, the second half draws the batch's
  row indices uniformly over the filled ring (``run_update_burst``);
- the first half is split in three: next state key, the critic loss's noise
  key, the policy loss's noise key (``SAC.update``); each noise is one
  standard-normal draw of shape ``(batch, act_dim)``
  (``squashed_gaussian_sample``);
- a data-parallel burst folds the replica's index into the state key first,
  and afterwards continues from the pre-burst key folded with ``0xB0057``
  (``DataParallelSAC._build_burst``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DP_RNG_FOLD = 0xB0057


def burst_draws(rng, n_updates: int, batch: int, act_dim: int, ring_size: int):
    """(next key, idx ``(n, batch)``, eps_q ``(n, batch, act)``, eps_pi)."""

    def body(key, _):
        key, sample_key = jax.random.split(key)
        idx = jax.random.randint(sample_key, (batch,), 0, max(ring_size, 1))
        key, key_q, key_pi = jax.random.split(key, 3)
        eps_q = jax.random.normal(key_q, (batch, act_dim), jnp.float32)
        eps_pi = jax.random.normal(key_pi, (batch, act_dim), jnp.float32)
        return key, (idx, eps_q, eps_pi)

    rng, (idx, eps_q, eps_pi) = jax.lax.scan(body, rng, None, length=n_updates)
    return rng, idx, eps_q, eps_pi


def dp_burst_draws(rng, n_dev: int, n_updates: int, batch: int, act_dim: int, ring_size: int):
    """Draws of one data-parallel burst: leaves ``(n, n_dev, batch, ...)``."""

    def one(dev):
        return burst_draws(
            jax.random.fold_in(rng, dev), n_updates, batch, act_dim, ring_size
        )[1:]

    idx, eps_q, eps_pi = jax.vmap(one, out_axes=1)(jnp.arange(n_dev))
    return jax.random.fold_in(rng, jnp.uint32(DP_RNG_FOLD)), idx, eps_q, eps_pi
