"""Synchronous data-parallel SAC over a named device mesh.

The TPU-native re-design of the reference's MPI data parallelism
(SURVEY.md §2): each worker owns a model replica, its own env stream
and its own replay buffer, with gradients allreduce-averaged per step
(ref ``sac/algorithm.py:138``, ``sac/mpi.py:77-85``) and params
broadcast from rank 0 at start (ref ``sac/algorithm.py:198-200``).

Mapping:

================================  =====================================
reference (MPI)                    here (mesh)
================================  =====================================
``mpirun -np N`` re-exec fork      one controller, ``Mesh`` over devices
per-rank replica + buffer          replicated params, ``dp``-sharded
                                   :class:`BufferState` (leading device
                                   axis)
``mpi_avg_grads`` per update       ``lax.pmean`` *inside* the compiled
                                   burst, riding ICI
``sync_params`` Bcast              params device_put replicated once;
                                   pmean'd grads keep replicas
                                   bit-identical thereafter
per-rank seeds ``10000*rank``      ``fold_in(rng, device_index)``
per-step stat send/recv            metrics reduced in-program (the
                                   reference's per-step blocking
                                   exchange, ref ``algorithm.py:262-271``,
                                   moves off the hot path entirely)
================================  =====================================

Substrate (the PR-8 rebuild): the whole N-device burst — push N env
chunks, run K gradient steps with cross-device averaging — is ONE
jitted program on the **GSPMD auto-partitioning surface**:
``jax.jit`` with ``in_shardings``/``out_shardings`` over
``NamedSharding`` trees, ``with_sharding_constraint`` pinning the
parameter layout (:func:`~torch_actor_critic_tpu.parallel.sharding.
param_specs` — tp roles + size-thresholded fsdp), and the per-device
view expressed as ``jax.vmap(..., axis_name='dp')`` over the leading
device axis so ``lax.pmean``/``pmax``/``pmin`` keep their named-axis
spelling while XLA inserts the actual collectives. The dp+tp/fsdp
hybrid is ordinary auto partitioning. Ring-attention sequence
parallelism (``sp``) is the one manual algorithm left; that burst is a
``jax.shard_map`` with ``(dp, sp)`` manual and tp/fsdp auto.
"""

from __future__ import annotations

import typing as t

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torch_actor_critic_tpu.buffer.replay import init_replay_buffer, push
from torch_actor_critic_tpu.core.types import Batch, BufferState, TrainState
from torch_actor_critic_tpu.diagnostics import ingraph as diag
from torch_actor_critic_tpu.parallel import chunk_block, sharding as tp_sharding
from torch_actor_critic_tpu.parallel.mesh import global_device_put
from torch_actor_critic_tpu.telemetry import recorder as spans
from torch_actor_critic_tpu.telemetry import scopes

# Per-device metrics whose cross-replica spread (pmax - pmin) is the
# replica-desync leading indicator (docs/OBSERVABILITY.md): param-norm
# skew must be exactly 0.0 while pmean'd grads keep replicas
# bit-identical; grad-norm skew tracks per-shard batch disagreement.
_SKEW_KEYS = ("diag/grad_norm_q", "diag/grad_norm_pi", "diag/param_norm")

# Replicated-rng fold constant: the post-burst state carries one rng
# stream derived from the pre-burst key, identical on every device.
_RNG_FOLD = 0xB0057


def _leaf_spec(leaf, sp: int) -> P:
    """Placement spec for one replay/chunk leaf.

    Everything is sharded over ``dp`` on its leading device axis; when
    the mesh has an ``sp`` axis, *sequence* observation leaves — float
    arrays shaped ``(n_dev, n, T, D)`` with ``T`` divisible by ``sp`` —
    additionally shard the history axis over ``sp``, so long-context
    replay memory divides across the ring. Non-sequence leaves (flat
    obs ``(n_dev, n, D)``, visual uint8 frames ``(n_dev, n, H, W, C)``,
    actions/rewards) stay dp-only.
    """
    if (
        sp > 1
        and leaf.ndim == 4
        and jnp.issubdtype(leaf.dtype, jnp.floating)
        and leaf.shape[2] % sp == 0
    ):
        return P("dp", None, "sp")
    return P("dp")


def _batch_specs(batch: Batch, sp: int) -> Batch:
    """Per-leaf PartitionSpecs for a chunk/ring ``Batch``; obs fields
    follow :func:`_leaf_spec`, scalar fields are dp-sharded."""
    obs = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _leaf_spec(x, sp), tree
    )
    return Batch(
        states=obs(batch.states),
        actions=P("dp"),
        rewards=P("dp"),
        next_states=obs(batch.next_states),
        done=P("dp"),
    )


def _buffer_specs(buffer: BufferState, sp: int) -> BufferState:
    return BufferState(
        data=_batch_specs(buffer.data, sp), ptr=P("dp"), size=P("dp")
    )


def _shardings(mesh: Mesh, specs: t.Any) -> t.Any:
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def init_sharded_buffer(
    capacity_per_device: int,
    obs_spec: t.Any,
    act_dim: int,
    mesh: Mesh,
    sp: int | None = None,
) -> BufferState:
    """Per-device replay shards as one ``BufferState`` with a leading
    ``dp`` axis on every leaf (data ``(n_dev, cap, ...)``, ptr/size
    ``(n_dev,)``), sharded ``P('dp')`` — the analogue of the reference's
    per-worker buffers built post-fork (ref ``main.py:141,168``). On an
    ``sp>1`` mesh, sequence-history leaves also shard their T axis over
    ``sp`` (:func:`_leaf_spec`), dividing long-context buffer HBM
    across the ring.

    ``sp`` overrides the sequence-sharding factor — pass
    ``DataParallelSAC.effective_sp`` so at-rest layout always agrees
    with the burst's compiled specs (a non-sequence model on an sp>1
    mesh must keep dp-only layout or every burst would reshard).
    """
    n_dev = mesh.shape["dp"]
    if sp is None:
        sp = mesh.shape.get("sp", 1)
    single = init_replay_buffer(capacity_per_device, obs_spec, act_dim)

    def rep(x):
        # numpy zero-copy view, NOT jnp: a jnp.broadcast_to would
        # materialize the (n_global_dev, cap, ...) GLOBAL buffer on one
        # device per process before sharding — OOM that scales with pod
        # size. The view costs nothing and global_device_put's callback
        # only ever reads this process's rows.
        return np.broadcast_to(np.asarray(x)[None], (n_dev,) + x.shape)

    state = jax.tree_util.tree_map(rep, single)
    specs = _buffer_specs(state, sp)
    return jax.tree_util.tree_map(
        lambda x, s: global_device_put(x, NamedSharding(mesh, s)), state, specs
    )


def shard_chunk(chunk: Batch, mesh: Mesh, sp: int | None = None) -> Batch:
    """Place a host-built chunk with leading axes ``(n_dev, per_dev, ...)``
    onto the ``dp`` (and, for sequence histories, ``sp``) mesh axes.
    ``sp`` as in :func:`init_sharded_buffer`.

    Multi-host: every process must pass the same full logical value
    (see :func:`~torch_actor_critic_tpu.parallel.mesh.global_device_put`);
    the trainer instead uses :func:`shard_chunk_from_local` so each
    host only builds the rows its envs produced.
    """
    if sp is None:
        sp = mesh.shape.get("sp", 1)
    specs = _batch_specs(chunk, sp)
    return jax.tree_util.tree_map(
        lambda x, s: global_device_put(x, NamedSharding(mesh, s)), chunk, specs
    )


def shard_chunk_from_local(
    chunk_local: Batch, mesh: Mesh, sp: int | None = None
) -> Batch:
    """Assemble the global dp-sharded chunk from PROCESS-LOCAL rows.

    ``chunk_local`` leaves have leading axis = this process's dp-slice
    count (:func:`~torch_actor_critic_tpu.parallel.mesh.local_dp_info`);
    each host contributes only the transitions its own envs produced —
    no global chunk is ever staged in host RAM. Single-process meshes
    reduce exactly to :func:`shard_chunk`.

    A chunk whose leaves are the views of one block, as the Trainer
    stages it, crosses as that block in ONE transfer and is taken apart
    on the device (:mod:`~torch_actor_critic_tpu.parallel.chunk_block`);
    any other chunk (the prefetcher's refill, separately allocated
    leaves), and any chunk on a mesh that reaches past this process,
    crosses leaf by leaf. Same arrays, shapes, dtypes and shardings
    either way. The window's ``place_chunk`` span; ``packed=`` on it
    says which way the chunk crossed.
    """

    def put(x, s):
        sharding = NamedSharding(mesh, s)
        if sharding.is_fully_addressable:
            return jax.device_put(x, sharding)
        return jax.make_array_from_process_local_data(sharding, np.asarray(x))

    with spans.span(spans.PLACE_CHUNK) as span:
        if sp is None:
            sp = mesh.shape.get("sp", 1)
        specs = _batch_specs(chunk_local, sp)
        placed = chunk_block.place_block(
            chunk_local, _shardings(mesh, specs), NamedSharding(mesh, P("dp"))
        )
        span.tag(packed=int(placed is not None))
        if placed is None:
            with spans.span(spans.PLACE_TRANSFER):
                placed = jax.tree_util.tree_map(put, chunk_local, specs)
        return placed


class DataParallelSAC:
    """Wraps a :class:`~torch_actor_critic_tpu.sac.algorithm.SAC` learner
    with a mesh; exposes the same functional surface, compiled for DP.

    Single-device training is just ``dp=1`` — one code path, no
    "degrades to no-ops when world size is 1" special-casing (cf. ref
    ``sac/mpi.py:79-80,94-95``).

    ``fsdp_min_bytes`` is the parameter-size threshold below which the
    ``fsdp`` axis replicates instead of sharding
    (:func:`~torch_actor_critic_tpu.parallel.sharding.fsdp_spec`);
    tiny-model tests pass 0 to force real sharding.
    """

    AXIS = "dp"

    def __init__(
        self, sac, mesh: Mesh, fsdp_min_bytes: int | None = None
    ):
        self.sac = sac
        self.mesh = mesh
        self.n_devices = mesh.shape["dp"]
        self.fsdp = mesh.shape.get("fsdp", 1)
        self.tp = mesh.shape.get("tp", 1)
        self.sp = mesh.shape.get("sp", 1)
        self.fsdp_min_bytes = (
            tp_sharding.FSDP_MIN_BYTES
            if fsdp_min_bytes is None else fsdp_min_bytes
        )
        # Sequence/context parallelism in the GRADIENT path: on an sp>1
        # mesh with sequence models (identified by their injectable
        # attention_fn), the burst runs the actor/critic applies inside
        # the losses with ring attention over the manual `sp` axis and
        # histories sharded over T. Gradients then need pmean over BOTH
        # axes: per-rank grads of the replicated loss sum to sp times
        # the true gradient (each rank contributes its chunk's terms;
        # verified against the unsharded path in tests/test_parallel.py).
        self._sp_active = self.sp > 1 and hasattr(sac.actor_def, "attention_fn")
        if self._sp_active and getattr(sac, "shared_trunk", False):
            # The ring knows the causal mask alone: a block-causal or windowed
            # layer under it would be handed another mask than its own.
            raise ValueError(
                f"a shared history trunk is not sharded over sp={self.sp}: ring "
                "attention applies no block-causal or sliding-window mask "
                "(models/sequence.py::TrunkSpec); use sp=1"
            )
        if self._sp_active:
            from torch_actor_critic_tpu.parallel.context import (
                make_ring_attention_fn,
            )
            from torch_actor_critic_tpu.sac.algorithm import SAC

            ring = make_ring_attention_fn("sp", self.sp)
            self.sac_sp = SAC(
                sac.config,
                sac.actor_def.clone(
                    attention_fn=ring, sp_axis="sp", sp_size=self.sp
                ),
                sac.critic_def.clone(
                    attention_fn=ring, sp_axis="sp", sp_size=self.sp
                ),
                sac.act_dim,
            )
        else:
            self.sac_sp = None
        self._burst = None
        # What the burst was built for (shape, dtype, sharding of state,
        # ring and chunk): the burst donates state and ring, so whoever
        # lowers it again (cost registry, scope table) asks here.
        self.burst_abstract: tuple = ()
        self._push = None
        self._select_action = None

    @property
    def effective_sp(self) -> int:
        """The sequence-sharding factor actually used by the burst: the
        mesh's ``sp`` for sequence models, else 1. Pass this to
        :func:`shard_chunk` / :func:`init_sharded_buffer` so at-rest
        layout matches the compiled specs."""
        return self.sp if self._sp_active else 1

    def _check_sp_shapes(self, chunk: Batch) -> None:
        """Hard errors for the silent-garbage sp misuses: with ring
        attention engaged, every rank's chunk MUST be a true shard of
        the global sequence (T divisible by sp) and the global length
        must fit the positional table (the trunk's own assert only sees
        the local chunk; cf. the acting-path check at
        ``parallel/context.py``)."""
        t_global = chunk.states.shape[2]
        if t_global % self.sp != 0:
            raise ValueError(
                f"sequence length {t_global} is not divisible by sp="
                f"{self.sp}: ring attention would treat replicated "
                "copies as distinct chunks of a longer sequence. Pad "
                "the history or change the mesh."
            )
        max_len = getattr(self.sac.actor_def, "max_len", None)
        if max_len is not None and t_global > max_len:
            raise ValueError(
                f"global history length {t_global} exceeds the actor's "
                f"max_len={max_len} (positions would alias silently "
                "under sp sharding)."
            )

    # ----------------------------------------------------------- state init

    def init_state(self, key: jax.Array, example_obs: t.Any) -> TrainState:
        """Initialize once and place on the mesh — the moral equivalent
        of rank-0 init + ``sync_params`` Bcast (ref
        ``sac/algorithm.py:198-200``); thereafter pmean'd grads keep
        every replica bit-identical. Weight matrices land tensor- or
        fsdp-sharded per :func:`~torch_actor_critic_tpu.parallel.
        sharding.param_specs` (replicated on a trivial mesh)."""
        state = self.sac.init_state(key, example_obs)
        return tp_sharding.shard_params(
            state, self.mesh, self.fsdp_min_bytes
        )

    def _state_shardings(self, state: TrainState) -> t.Any:
        """Per-leaf NamedShardings of the at-rest TrainState layout —
        the jit ``in_shardings``/``out_shardings`` for the state slot,
        matching :meth:`init_state`'s placement exactly so the donated
        buffers are reusable and nothing reshards between bursts."""
        specs = tp_sharding.param_specs(
            state, self.mesh, self.fsdp_min_bytes
        )
        return _shardings(self.mesh, specs)

    # ----------------------------------------------------------- the burst

    def _build_burst(
        self, num_updates: int, state: TrainState, buffer: BufferState,
        chunk: Batch,
    ):
        """The GSPMD burst: one ``jit`` with explicit shardings.

        The per-device view of the old manual code — strip the device
        axis, fold the device index into the rng, run the shared
        ``update_burst`` with ``axis_name='dp'`` — is expressed as
        ``jax.vmap(..., axis_name='dp')`` over the leading device axis:
        identical per-device math and key streams (pinned bitwise by
        the substrate-parity test), with the ``lax.pmean`` resolving
        against the vmap axis and XLA's partitioner emitting the actual
        cross-device all-reduce because that axis is sharded ``P('dp')``.
        """
        if self._sp_active:
            return self._build_ring_burst(num_updates, buffer, chunk)
        sac = self.sac
        mesh = self.mesh
        n_dev = self.n_devices
        min_bytes = self.fsdp_min_bytes
        buf_sh = _shardings(mesh, _buffer_specs(buffer, 1))
        chunk_sh = _shardings(mesh, _batch_specs(chunk, 1))
        state_sh = self._state_shardings(state)
        rep = NamedSharding(mesh, P())

        def burst(state: TrainState, buffer: BufferState, chunk: Batch):
            # Pin the parameter layout (tp/fsdp specs) for the
            # partitioner; trivial meshes pass through untouched.
            state = tp_sharding.constrain(state, mesh, min_bytes)

            def per_device(dev, buf, ch):
                # Decorrelate per-device noise/sampling streams — the
                # analogue of per-rank seeds (ref sac/algorithm.py:
                # 203-205). Fold in the dp index ONLY: params stay
                # shared (closed over, unbatched under vmap).
                local = state.replace(
                    rng=jax.random.fold_in(state.rng, dev)
                )
                local, buf, metrics = sac.update_burst(
                    local, buf, ch, num_updates,
                    axis_name=DataParallelSAC.AXIS,
                )
                if sac.config.diagnostics == "off":
                    # Parity path: the historical whole-tree pmean,
                    # traced bit-identically to a build without
                    # diagnostics.
                    metrics = jax.lax.pmean(
                        metrics, DataParallelSAC.AXIS
                    )
                else:
                    skew = (
                        diag.replica_skew(
                            metrics, _SKEW_KEYS, DataParallelSAC.AXIS
                        )
                        if n_dev > 1 else {}
                    )
                    # Suffix-aware collectives: per-burst maxima stay
                    # maxima across replicas, histogram counts add.
                    metrics = diag.cross_replica_reduce(
                        metrics, DataParallelSAC.AXIS
                    )
                    metrics.update(skew)
                return local, buf, metrics

            locals_out, buffer, metrics = jax.vmap(
                per_device, axis_name=DataParallelSAC.AXIS
            )(jnp.arange(n_dev), buffer, chunk)
            # Params/opt-states are replicated (pmean'd grads keep the
            # per-device copies bit-identical); collapse the device
            # axis and restore a replicated rng stream derived from the
            # pre-burst key so the output TrainState is one logical
            # value.
            state_out = jax.tree_util.tree_map(
                lambda x: x[0], locals_out
            )
            state_out = state_out.replace(
                rng=jax.random.fold_in(state.rng, jnp.uint32(_RNG_FOLD))
            )
            metrics = jax.tree_util.tree_map(lambda x: x[0], metrics)
            return state_out, buffer, metrics

        return jax.jit(
            burst,
            in_shardings=(state_sh, buf_sh, chunk_sh),
            out_shardings=(state_sh, buf_sh, rep),
            donate_argnums=(0, 1),
        )

    def _build_ring_burst(
        self, num_updates: int, buffer: BufferState, chunk: Batch
    ):
        """The sp (ring-attention) burst: manual by nature — the K/V
        rotation needs a real named manual axis — so it is a
        ``jax.shard_map`` over ``(dp, sp)``; tp/fsdp stay GSPMD auto
        axes inside the body.
        """
        sac = self.sac_sp
        mesh = self.mesh
        sp = self.effective_sp
        self._check_sp_shapes(chunk)
        # Grad/metric averaging axes: per-rank grads need pmean over dp
        # (data-parallel shards, as the reference's mpi_avg_grads) AND
        # over sp (the sequence ring is in the loss path — see
        # __init__ note).
        axes = ("dp", "sp")
        manual = {"dp", "sp"}
        min_bytes = self.fsdp_min_bytes
        buf_specs = _buffer_specs(buffer, sp)
        chunk_specs = _batch_specs(chunk, sp)
        rep_spec = P()

        def burst_body(state: TrainState, buffer: BufferState, chunk: Batch):
            # Per-shard view: strip the leading device axis shard_map
            # leaves on the block arguments.
            buffer = jax.tree_util.tree_map(lambda x: x[0], buffer)
            chunk = jax.tree_util.tree_map(lambda x: x[0], chunk)

            # Fold in dp ONLY: all sp ranks of one replica must draw the
            # same replay rows / action noise (the sequence is sharded,
            # the batch is not).
            dev = jax.lax.axis_index(DataParallelSAC.AXIS)
            local = state.replace(rng=jax.random.fold_in(state.rng, dev))
            # tp/fsdp are GSPMD *auto* axes inside this manual body:
            # re-assert the parameter layout for the partitioner.
            local = tp_sharding.constrain(local, mesh, min_bytes)

            local, buffer, metrics = sac.update_burst(
                local, buffer, chunk, num_updates, axis_name=axes
            )
            state_out = local.replace(
                rng=jax.random.fold_in(state.rng, jnp.uint32(_RNG_FOLD))
            )
            if sac.config.diagnostics == "off":
                metrics = jax.lax.pmean(metrics, axes)
            else:
                skew = (
                    diag.replica_skew(metrics, _SKEW_KEYS, "dp")
                    if mesh.shape["dp"] > 1 else {}
                )
                metrics = diag.cross_replica_reduce(metrics, axes)
                metrics.update(skew)
            # Re-attach the device axis for the dp-sharded outputs.
            buffer = jax.tree_util.tree_map(lambda x: x[None], buffer)
            return state_out, buffer, metrics

        mapped = jax.shard_map(
            burst_body,
            mesh=mesh,
            in_specs=(rep_spec, buf_specs, chunk_specs),
            out_specs=(rep_spec, buf_specs, rep_spec),
            axis_names=manual,
            check_vma=False,
        )
        return jax.jit(mapped, donate_argnums=(0, 1))

    # The cost-registry key this learner's burst registers under — the
    # same source name the recompilation watchdog attributes its
    # compiles to (telemetry/costmodel.py).
    burst_cost_name = "train/update_burst"

    def update_burst(
        self,
        state: TrainState,
        buffer: BufferState,
        chunk: Batch,
        num_updates: int,
    ) -> t.Tuple[TrainState, BufferState, t.Dict[str, jax.Array]]:
        """Push per-device chunks and run ``num_updates`` DP gradient
        steps as one device dispatch. ``chunk`` leaves have leading axes
        ``(n_dev, per_dev, ...)`` (see :func:`shard_chunk`). The window's
        ``burst_dispatch`` span: the call until it returns (``build=1``
        where it builds the program first)."""
        with spans.span(spans.BURST_DISPATCH) as span:
            if self._burst is None or self._burst[0] != num_updates:
                span.tag(build=1)
                self._burst = (
                    num_updates,
                    self._build_burst(num_updates, state, buffer, chunk),
                )
                self.burst_abstract = scopes.abstract_of(state, buffer, chunk)
            return self._burst[1](state, buffer, chunk)

    def burst_jit(self, num_updates: int):
        """The cached jitted burst for ``num_updates`` (None before its
        first dispatch) — the cost registry lowers this with abstract
        args to read the program's FLOPs/bytes without re-running it."""
        if self._burst is not None and self._burst[0] == num_updates:
            return self._burst[1]
        return None

    def burst_scope_table(self) -> dict:
        """Which ``tac/`` scope each instruction of the compiled burst
        belongs to (telemetry/scopes.py); one compile, after a burst ran."""
        return scopes.scope_table_for(self._burst[1], *self.burst_abstract)

    def push_chunk(self, buffer: BufferState, chunk: Batch) -> BufferState:
        """Store per-device chunks without gradient steps — the warmup
        path before ``update_after`` (the reference stores every step
        but only updates after warmup, ref ``sac/algorithm.py:249,273``).

        Pure per-ring data movement (no collectives): ``jax.vmap`` of
        the single-ring ``push`` over the device axis, jitted with the
        at-rest shardings. A ``burst_dispatch`` span like
        :meth:`update_burst`: it dispatches the window's device work.
        """
        with spans.span(spans.BURST_DISPATCH) as span:
            if self._push is None:
                span.tag(build=1)
                sp = self.effective_sp
                if self._sp_active:
                    self._check_sp_shapes(chunk)
                buf_sh = _shardings(self.mesh, _buffer_specs(buffer, sp))
                chunk_sh = _shardings(self.mesh, _batch_specs(chunk, sp))

                self._push = jax.jit(
                    jax.vmap(push),
                    in_shardings=(buf_sh, chunk_sh),
                    out_shardings=buf_sh,
                    donate_argnums=(0,),
                )
            return self._push(buffer, chunk)

    # ------------------------------------------------------------- acting

    def select_action(self, params, obs, key=None, deterministic: bool = False):
        """Batched action selection for the host env loop (replicated
        params, host-resident obs)."""
        if self._select_action is None:
            self._select_action = jax.jit(
                self.sac.select_action, static_argnames=("deterministic",)
            )
        return self._select_action(params, obs, key, deterministic=deterministic)
