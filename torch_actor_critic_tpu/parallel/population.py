"""Population training: N independent learners in ONE compiled program.

The chip-utilization answer to the reference configuration's shape:
one learner at batch 64, hidden [256,256] is latency-bound and leaves
the MXU nearly empty (what 32 members do on one chip is PERF.md
section 5, ``cheetah_pop32_fused``). RL fills that
idle silicon not with bigger batches (which change the algorithm) but
with MORE SEEDS: every deep-RL result is a multi-seed result, and the
reference can only obtain seeds by running the whole program N times
(one process per seed, ref ``sac/mpi.py:10-34`` — and its MPI mode
*averages* gradients, so its N workers are one logical seed, not N).

Here a population is ``jax.vmap`` over the member axis of everything
the learner owns — ``TrainState``, ``BufferState``, replay chunks, PRNG
streams — so one XLA program advances N completely independent
training runs per dispatch:

- every matmul in the fused update batches over members (XLA folds the
  member axis into the MXU tiles: N x batch 64 effective rows instead
  of 64), converting latency-bound steps into throughput-bound ones;
- members share NOTHING: no ``pmean``, separate replay rings, separate
  optimizer states, separate exploration keys (``init_state`` splits
  the root key per member) — bitwise-equal to N sequential runs of the
  single-learner burst (pinned by ``tests/test_population.py``);
- the member axis is data-parallel by construction, so on a multi-chip
  mesh it shards over ``dp`` with NO collectives at all (cf.
  :class:`~torch_actor_critic_tpu.parallel.dp.DataParallelSAC`, whose
  replicas must allreduce every step): placement is one
  ``NamedSharding(mesh, P('dp'))`` on the leading axis and XLA runs N/D
  members per device.

Interface mirrors :class:`DataParallelSAC` (init_state / update_burst /
push_chunk / select_action) so the host :class:`Trainer` swaps one for
the other when ``config.population > 1``.
"""

from __future__ import annotations

import typing as t

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torch_actor_critic_tpu.buffer.replay import init_replay_buffer, push
from torch_actor_critic_tpu.core.types import Batch, BufferState, TrainState
from torch_actor_critic_tpu.parallel import chunk_block
from torch_actor_critic_tpu.sac.algorithm import Metrics
from torch_actor_critic_tpu.telemetry import recorder as spans
from torch_actor_critic_tpu.telemetry import scopes


class PopulationLearner:
    """N independent learners advanced by one vmapped burst.

    ``learner`` is any object with the SAC/TD3 functional surface
    (``init_state``, ``update_burst``, ``select_action`` — see
    :class:`~torch_actor_critic_tpu.sac.algorithm.SAC`). All state
    pytrees carry a leading ``n_members`` axis.
    """

    def __init__(self, learner, n_members: int, mesh: Mesh | None = None):
        if n_members < 1:
            raise ValueError(f"n_members must be >= 1, got {n_members}")
        self.learner = learner
        self.config = learner.config
        self.n_members = n_members
        self.mesh = mesh
        self._sharding = None
        if mesh is not None:
            # Guards apply to ANY mesh, including dp=1 ones: a tp/sp
            # mesh must fail loudly (members never shard over those
            # axes), and multi-host must fail before every host starts
            # redundantly simulating the whole population.
            if any(
                mesh.shape.get(a, 1) > 1 for a in ("fsdp", "tp", "sp")
            ):
                raise ValueError(
                    "population training shards members over the dp mesh "
                    "axis only; fsdp/tp/sp axes are not supported inside "
                    f"a population (mesh shape {dict(mesh.shape)})"
                )
            if jax.process_count() > 1:
                # Multi-host population needs per-process chunk assembly
                # (each host steps only its local members' envs) — not
                # wired yet.
                raise ValueError(
                    "population training is single-process for now "
                    "(members shard over the dp devices of one host)"
                )
        if mesh is not None and mesh.shape.get("dp", 1) > 1:
            dp = mesh.shape["dp"]
            if n_members % dp != 0:
                raise ValueError(
                    f"population={n_members} must divide evenly over the "
                    f"dp={dp} mesh axis (each device runs members/dp "
                    "members)"
                )
            self._sharding = NamedSharding(mesh, P("dp"))
        # Keyed by num_updates: the trainer's steady cadence is one
        # size, but callers alternating burst sizes (utd sweeps, warmup
        # tails, tests) must hit a cache per size — a single-slot cache
        # silently re-jitted EVERY call when two sizes alternate.
        self._bursts: t.Dict[int, t.Callable] = {}
        # What the bursts were built for (see DataParallelSAC).
        self.burst_abstract: tuple = ()
        self._push = None
        self._select = None

    # DataParallelSAC interface compatibility: the trainer consults
    # effective_sp when laying out buffers/chunks; a population never
    # shards sequence history.
    effective_sp = 1

    def _place(self, tree):
        """Shard the leading member axis over dp (no-op off-mesh)."""
        if self._sharding is None:
            return tree
        from torch_actor_critic_tpu.parallel.mesh import global_device_put

        return jax.tree_util.tree_map(
            lambda x: global_device_put(x, self._sharding), tree
        )

    # ----------------------------------------------------------- state init

    def init_state(self, key: jax.Array, example_obs: t.Any) -> TrainState:
        """One root key fans out to ``n_members`` independent member
        keys — each member gets its own init draw AND its own
        exploration/sampling stream thereafter (the population analogue
        of the reference's per-rank ``10000 * rank`` seeds, ref
        ``sac/algorithm.py:203-205``, except the members really are
        independent runs, not gradient-averaged replicas)."""
        keys = jax.random.split(key, self.n_members)
        state = jax.vmap(self.learner.init_state, in_axes=(0, None))(
            keys, example_obs
        )
        return self._place(state)

    def init_buffer(
        self, capacity_per_member: int, obs_spec: t.Any, act_dim: int
    ) -> BufferState:
        """Member-stacked replay rings: data ``(N, cap, ...)``,
        ptr/size ``(N,)``. Each member owns its full ``capacity``
        transitions (a population is N independent runs, so total HBM
        scales with N — callers budget via
        :func:`~torch_actor_critic_tpu.buffer.replay.warn_if_buffer_exceeds_hbm`
        with ``capacity * N``)."""
        single = init_replay_buffer(capacity_per_member, obs_spec, act_dim)

        def rep(x):
            # numpy broadcast view (zero host RAM), materialized only
            # at device placement — same trick as init_sharded_buffer
            # (parallel/dp.py).
            return np.broadcast_to(
                np.asarray(x)[None], (self.n_members,) + x.shape
            )

        state = jax.tree_util.tree_map(rep, single)
        if self._sharding is not None:
            return self._place(state)
        return jax.tree_util.tree_map(jnp.asarray, state)

    def place_chunk(self, chunk: Batch) -> Batch:
        """Device placement for a host-built chunk with leading axes
        ``(n_members, window, ...)`` (the trainer's staging layout with
        one env per member). As ``shard_chunk_from_local``: a chunk
        that is the views of one block crosses in one transfer
        (:mod:`~torch_actor_critic_tpu.parallel.chunk_block`), any other
        leaf by leaf. The window's ``place_chunk`` span; ``packed=`` on
        it says which way the chunk crossed."""
        with spans.span(spans.PLACE_CHUNK) as span:
            over_dp = self._sharding  # None: one device, nothing committed
            placed = chunk_block.place_block(
                chunk,
                over_dp and jax.tree_util.tree_map(lambda _: over_dp, chunk),
                over_dp,
            )
            span.tag(packed=int(placed is not None))
            if placed is None:
                with spans.span(spans.PLACE_TRANSFER):
                    if over_dp is None:
                        placed = jax.tree_util.tree_map(jnp.asarray, chunk)
                    else:
                        placed = self._place(chunk)
            return placed

    # ----------------------------------------------------------- the burst

    def _build_burst(self, num_updates: int):
        """The jitted population burst: one member's burst, vmapped
        over the member axis, state and rings donated."""

        def one_member(st, buf, ch):
            return self.learner.update_burst(
                st, buf, ch, num_updates, axis_name=None
            )

        return jax.jit(jax.vmap(one_member), donate_argnums=(0, 1))

    def update_burst(
        self,
        state: TrainState,
        buffer: BufferState,
        chunk: Batch,
        num_updates: int,
    ) -> t.Tuple[TrainState, BufferState, Metrics]:
        """Push each member's chunk into its own ring, then run
        ``num_updates`` gradient steps for every member — one device
        dispatch for the whole population. Metrics keep their leading
        member axis: N real learning curves, not one averaged one.

        Dispatches inside a ``train/population_burst`` watchdog scope:
        once the trainer marks the ``train/`` regime steady, any XLA
        compile landing here is flagged as a hot-path recompile
        anomaly (docs/OBSERVABILITY.md). The window's ``burst_dispatch``
        span: the call until it returns (``build=1`` where it builds the
        program first)."""
        from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

        with spans.span(spans.BURST_DISPATCH) as span:
            fn = self._bursts.get(num_updates)
            if fn is None:
                span.tag(build=1)
                fn = self._bursts[num_updates] = self._build_burst(num_updates)
                self.burst_abstract = scopes.abstract_of(state, buffer, chunk)
            with get_watchdog().source("train/population_burst"):
                return fn(state, buffer, chunk)

    # Cost-registry key: matches the watchdog source scope above.
    burst_cost_name = "train/population_burst"

    def burst_jit(self, num_updates: int):
        """The cached jitted population burst (None before its first
        dispatch) — same cost-registry lowering hook as
        :meth:`DataParallelSAC.burst_jit`."""
        return self._bursts.get(num_updates)

    def burst_scope_table(self) -> dict:
        """Which ``tac/`` scope each instruction of the compiled burst
        (the one built last) belongs to (telemetry/scopes.py); one
        compile, after a burst ran."""
        fn = list(self._bursts.values())[-1]
        return scopes.scope_table_for(fn, *self.burst_abstract)

    def push_chunk(self, buffer: BufferState, chunk: Batch) -> BufferState:
        """Warmup-path store (no gradient steps), vmapped per member. A
        ``burst_dispatch`` span like :meth:`update_burst`."""
        with spans.span(spans.BURST_DISPATCH) as span:
            if self._push is None:
                span.tag(build=1)
                self._push = jax.jit(jax.vmap(push), donate_argnums=(0,))
            return self._push(buffer, chunk)

    # ------------------------------------------------------------- acting

    def select_action(self, params, obs, key=None, deterministic: bool = False):
        """Per-member action selection: member ``i``'s policy acts on
        observation row ``i``. ``key`` fans out per member so
        exploration streams stay independent."""
        if self._select is None:

            def _select(params, obs, key, deterministic=False):
                keys = jax.random.split(key, self.n_members)

                def one(p, o, k):
                    return self.learner.select_action(
                        p, o, k, deterministic=deterministic
                    )

                return jax.vmap(one)(params, obs, keys)

            self._select = jax.jit(
                _select, static_argnames=("deterministic",)
            )
        return self._select(params, obs, key, deterministic=deterministic)
