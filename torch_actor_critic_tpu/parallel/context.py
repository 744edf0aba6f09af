"""Ring-attention sequence/context parallelism over an ``sp`` mesh axis.

A capability **extension** beyond the reference, which has no sequence
axis at all (SURVEY.md §5 "Long-context: absent by construction");
listed as such in PARITY.md. It makes long observation histories
first-class: the sequence axis of a
:class:`~torch_actor_critic_tpu.models.sequence.SequenceActor` is
sharded across devices and attention runs as a **ring** — each device
keeps its Q chunk resident and circulates K/V chunks around the ``sp``
axis with ``lax.ppermute`` (one ICI hop per step), accumulating exact
softmax attention with the same online-softmax update the single-device
flash path uses (:mod:`torch_actor_critic_tpu.ops.attention`). Peak
memory per device is O(T/n · T/n) scores instead of O(T·T), and the
K/V transfer for step ``s+1`` overlaps the block compute of step ``s``
under XLA's async collectives.

Works on any mesh from :func:`~torch_actor_critic_tpu.parallel.mesh.make_mesh`
(which lays ``sp`` fastest-varying so ring hops ride neighboring ICI
links) and composes with the ``dp`` axis: batch-sharded replicas each
run their own sequence ring.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from torch_actor_critic_tpu.ops.attention import (
    finalize_online,
    online_block_update,
)

NEG_INF = float("-inf")


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    axis_size: int,
    causal: bool = True,
) -> jax.Array:
    """Exact attention with the sequence axis sharded over ``axis_name``.

    Call **inside** ``shard_map``: ``q``/``k``/``v`` are this device's
    local chunks ``(B, H, T_local, D)`` of a global ``(B, H, n·T_local,
    D)`` sequence, device ``i`` holding positions ``[i·T_local,
    (i+1)·T_local)``. Runs ``axis_size`` steps, each attending the local
    Q against the currently-held K/V chunk (masked in *global*
    coordinates, so causality is correct across devices) and then
    rotating K/V one hop around the ring. The loop is unrolled —
    ``axis_size`` is a small static mesh dimension — which lets XLA
    overlap each ``ppermute`` with the next block's matmuls.
    Differentiable end-to-end (``ppermute`` transposes to the reverse
    rotation in the backward pass).
    """
    b, h, t_local, d = q.shape
    my = jax.lax.axis_index(axis_name)
    q_offset = my * t_local
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    qf = q.astype(jnp.float32)
    m = jnp.full((b, h, t_local), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t_local), jnp.float32)
    acc = jnp.zeros((b, h, t_local, d), jnp.float32)

    k_cur, v_cur = k, v
    for s in range(axis_size):
        src = (my - s) % axis_size  # owner of the chunk we hold now
        m, l, acc = online_block_update(
            qf, k_cur, v_cur, m, l, acc,
            causal=causal,
            q_offset=q_offset,
            k_offset=src * t_local,
        )
        if s + 1 < axis_size:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
    return finalize_online(m, l, acc).astype(q.dtype)


def make_ring_attention_fn(axis_name: str, axis_size: int):
    """An ``attention_fn`` for
    :class:`~torch_actor_critic_tpu.models.sequence.SequenceTrunk`:
    same signature as the single-device kernel, ring semantics."""

    def fn(q, k, v, causal=True):
        return ring_attention(q, k, v, axis_name, axis_size, causal=causal)

    return fn


@functools.lru_cache(maxsize=32)
def _build_context_actor_step(
    actor, mesh: Mesh, deterministic: bool, with_logprob: bool
):
    """Compiled (actor, mesh, flags) → step callable. Cached so repeated
    calls (the per-env-step acting path) hit one jitted executable
    instead of re-tracing a fresh shard_map closure each time; flax
    modules and Mesh are hashable by value, so equal configs share an
    entry."""
    n = mesh.shape["sp"]
    # The sp-aware module handles the positional offset and the masked
    # psum last-token gather itself (models/sequence.py
    # ``_sp_pos_offset``/``_sp_last_token``) — one shared implementation
    # with the gradient path in ``parallel/dp.py``.
    ring_actor = actor.clone(
        attention_fn=make_ring_attention_fn("sp", n), sp_axis="sp", sp_size=n
    )

    def body(params, obs_local, key):
        return ring_actor.apply(
            params, obs_local, key, deterministic, with_logprob
        )

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(None, "sp", None), P()),
            out_specs=P(),
            check_vma=False,
        )
    )


def context_parallel_actor_step(
    actor,
    params,
    obs_seq: jax.Array,
    key: jax.Array | None,
    mesh: Mesh,
    deterministic: bool = False,
    with_logprob: bool = True,
):
    """Run a :class:`SequenceActor` with its sequence sharded over the
    mesh's ``sp`` axis.

    ``obs_seq`` is the global ``(B, T, obs_dim)`` history (``T`` must be
    divisible by the ``sp`` size). The trunk runs under ``shard_map``
    with ring attention and per-device ``pos_offset``; the global last
    token (resident on the last ``sp`` device) is broadcast with a
    masked ``psum`` and fed to the squashed-Gaussian head on every
    device, so the returned ``(action, log_prob)`` are replicated.
    Single-device ``sp=1`` reduces exactly to ``actor(obs_seq, key)``.
    """
    n = mesh.shape["sp"]
    assert obs_seq.shape[1] % n == 0, (obs_seq.shape, n)
    assert obs_seq.shape[1] <= actor.max_len, (
        f"global history length {obs_seq.shape[1]} exceeds the actor's "
        f"max_len={actor.max_len} (positional table would alias)"
    )
    step = _build_context_actor_step(actor, mesh, deterministic, with_logprob)
    return step(params, obs_seq, key)
