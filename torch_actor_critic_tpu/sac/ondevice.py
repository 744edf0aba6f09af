"""Fully-fused on-device training: env + replay + learner in ONE program.

The reference's throughput ceiling is its host loop — one Python
``env.step`` and one buffer op per step, a gradient step crossing the
host/native boundary several times (ref ``sac/algorithm.py:220-283``).
The host :class:`~torch_actor_critic_tpu.sac.trainer.Trainer` already
batches that boundary to ~2 transfers per window; this module removes
it entirely for envs with a pure-JAX twin
(:mod:`torch_actor_critic_tpu.envs.ondevice`): an *entire epoch* —
vectorized env stepping, policy sampling, replay pushes, and every
gradient burst — is one ``lax.scan`` under one ``jit``, the
Podracer/"anakin" topology (PAPERS.md) where nothing leaves the chip
until the epoch's metrics.

Capability **extension**: the reference cannot express this (its
physics is host C code). The algorithm inside is byte-identical SAC —
the same :meth:`SAC.update_burst` the host trainer dispatches.
"""

from __future__ import annotations

import typing as t

import jax
import jax.numpy as jnp

from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torch_actor_critic_tpu.buffer.replay import init_replay_buffer, push
from torch_actor_critic_tpu.core.types import Batch, BufferState, TrainState
from torch_actor_critic_tpu.envs.ondevice import EnvState
from torch_actor_critic_tpu.utils.sync import drain
from torch_actor_critic_tpu.sac.algorithm import SAC
from torch_actor_critic_tpu.telemetry import recorder as spans
from torch_actor_critic_tpu.telemetry import scopes

Metrics = t.Dict[str, jax.Array]


# The pixel-task training recipe shared by every surface that trains
# the 32x32 PixelPendulum family: the committed evidence runs
# (scripts/evidence_run.py pixelbal-*/pixelpend-* presets, the
# runs/train_proof/ pixel proofs). ONE definition so they cannot
# silently train different configs. Conv geometry sized for 32x32
# frames (the Atari defaults need >=36px); DrQ shift + learned
# temperature are the stabilizers the committed curves document.
PIXEL_CONV = dict(
    filters=(16, 32), kernel_sizes=(4, 3), strides=(2, 2),
    cnn_dense_size=128, cnn_features=64, normalize_pixels=True,
)
PIXEL_RECIPE = dict(PIXEL_CONV, frame_augment="shift", learn_alpha=True)


class _EpochPrograms:
    """A fused loop's jitted epoch programs by signature ``(steps,
    update_every, warmup)``, each with what it was built for: the
    program donates its state and ring, so whoever lowers it again
    (the cost registry, the scope table) needs the shapes kept."""

    def _epoch_program(self, sig: tuple, args: tuple):
        if sig not in self._epoch_fns:
            self._epoch_fns[sig] = self._build_epoch(*sig)
            self._epoch_abstract[sig] = scopes.abstract_of(*args)
        return self._epoch_fns[sig]

    def epoch_jit(self, steps: int, update_every: int, warmup: bool = False):
        """The cached jitted epoch program for a signature (None before
        its first dispatch) — the cost registry lowers this with
        :meth:`epoch_abstract` (telemetry/costmodel.py)."""
        return self._epoch_fns.get((steps, update_every, warmup))

    def epoch_abstract(self, steps: int, update_every: int, warmup: bool = False):
        """Shape, dtype and sharding of the program's four arguments
        (``()`` before its first dispatch)."""
        return self._epoch_abstract.get((steps, update_every, warmup), ())

    def epoch_scope_table(self, steps: int, update_every: int, warmup: bool = False):
        """Which ``tac/`` scope each instruction of the compiled epoch
        program belongs to (telemetry/scopes.py); one compile."""
        sig = (steps, update_every, warmup)
        return scopes.scope_table_for(
            self._epoch_fns[sig], *self._epoch_abstract[sig]
        )


class OnDeviceLoop(_EpochPrograms):
    """Collect+update loop compiled end-to-end — one device or a mesh.

    ``n_envs`` pure-JAX envs step in a vmapped batch; every
    ``update_every`` steps their transitions are pushed and
    ``update_every`` gradient steps run — the reference's cadence
    (ref ``sac/algorithm.py:273-283``) with zero host involvement.

    With a ``mesh``, the loop data-parallelizes like
    :class:`~torch_actor_critic_tpu.parallel.dp.DataParallelSAC`:
    every ``dp`` slice runs its own ``n_envs`` envs against its own
    replay shard (leading device axis on env/buffer state), params stay
    replicated, gradients ``pmean`` over ICI inside the fused bursts —
    the whole multi-chip epoch is still ONE dispatch. This is the
    TPU-native endpoint of the reference's per-rank env+buffer MPI
    layout (SURVEY.md §2 "Parallelism strategies"), minus its hosts.
    """

    AXIS = "dp"

    def __init__(
        self, sac: SAC, env_cls, n_envs: int = 16, mesh: Mesh | None = None
    ):
        self.sac = sac
        self.env = env_cls
        self.n_envs = n_envs  # per dp slice when mesh is given
        self.mesh = mesh
        self.n_dp = mesh.shape["dp"] if mesh is not None else 1
        self._epoch_fns: dict = {}
        self._epoch_abstract: dict = {}

    # ------------------------------------------------------------------ init

    def init(
        self, key: jax.Array, buffer_capacity: int = 1_000_000
    ) -> t.Tuple[TrainState, BufferState, EnvState, jax.Array]:
        """``buffer_capacity`` is per dp slice, matching the reference's
        per-worker buffers (ref ``main.py:140-141``)."""
        k_state, k_envs, k_act = jax.random.split(key, 3)
        obs_spec, zero_obs = _env_obs_spec(self.env)
        # Same HBM-budget check as the host trainer (shared helper so
        # the two loops' thresholds cannot drift): history windows
        # multiply the resident shard by horizon, and the fused loop
        # fails as an opaque allocator OOM otherwise.
        from torch_actor_critic_tpu.buffer.replay import (
            warn_if_buffer_exceeds_hbm,
        )

        warn_if_buffer_exceeds_hbm(
            buffer_capacity, obs_spec, self.env.act_dim,
            advice="reduce buffer_capacity (or history_len)",
        )
        train_state = self.sac.init_state(k_state, zero_obs)
        buffer = self._init_buffer(buffer_capacity, obs_spec)
        if self.mesh is None:
            env_states = jax.vmap(self.env.reset)(
                jax.random.split(k_envs, self.n_envs)
            )
            return train_state, buffer, env_states, k_act

        env_states = jax.vmap(jax.vmap(self.env.reset))(
            jax.random.split(k_envs, self.n_dp * self.n_envs).reshape(
                self.n_dp, self.n_envs
            )
        )
        dp_sharding = NamedSharding(self.mesh, P("dp"))
        rep = NamedSharding(self.mesh, P())
        put = jax.tree_util.tree_map
        train_state = put(lambda x: jax.device_put(x, rep), train_state)
        buffer = put(
            lambda x: jax.device_put(
                jnp.broadcast_to(x[None], (self.n_dp,) + x.shape), dp_sharding
            ),
            buffer,
        )
        env_states = put(lambda x: jax.device_put(x, dp_sharding), env_states)
        return train_state, buffer, env_states, k_act

    def _init_buffer(self, buffer_capacity: int, obs_spec):
        """Replay-ring constructor hook: the scenario loop overrides it
        to build the per-task striped ring (``buffer/striped.py``) for
        multi-task envs; the base loop's ring is unchanged."""
        return init_replay_buffer(buffer_capacity, obs_spec, self.env.act_dim)

    # ----------------------------------------------------------------- epoch

    def _collect_window(self, params, env_states, act_key, length, warmup):
        """``length`` vectorized env steps; returns transitions with
        leading axes (length, n_envs) plus episode-completion stats."""
        env = self.env

        def step_fn(carry, _):
            es, key = carry
            obs = es.obs
            with jax.named_scope(scopes.COLLECT_ACT):
                key, k_act = jax.random.split(key)
                if warmup:
                    actions = jax.random.uniform(
                        k_act,
                        (self.n_envs, env.act_dim),
                        minval=-env.act_limit,
                        maxval=env.act_limit,
                    )
                else:
                    actions, _ = self.sac.actor_def.apply(
                        params, obs, k_act, with_logprob=False
                    )
            with jax.named_scope(scopes.COLLECT_ENV):
                es, out = jax.vmap(env.step)(es, actions)
                transition = Batch(
                    states=obs,
                    actions=actions,
                    rewards=out.reward,
                    next_states=out.next_obs,
                    done=out.terminated,
                )
                ended = out.ended.astype(jnp.float32)
                stats = (jnp.sum(ended), jnp.sum(ended * out.final_return))
            return (es, key), (transition, stats)

        (env_states, act_key), (transitions, stats) = jax.lax.scan(
            step_fn, (env_states, act_key), xs=None, length=length
        )
        n_done = jnp.sum(stats[0])
        sum_ret = jnp.sum(stats[1])
        return env_states, act_key, transitions, n_done, sum_ret

    def _epoch_body(
        self,
        train_state,
        buffer,
        env_states,
        act_key,
        n_windows: int,
        update_every: int,
        warmup: bool,
        axis_name: str | None = None,
    ):
        """Scan of windows; returns raw stats (losses averaged, episode
        counts/returns summed locally — callers reduce across devices)."""

        def window(carry, _):
            ts, buf, es, key = carry
            es, key, transitions, n_done, sum_ret = self._collect_window(
                ts.actor_params, es, key, update_every, warmup
            )
            # (update_every, n_envs, ...) -> one flat chunk
            chunk = jax.tree_util.tree_map(
                lambda x: x.reshape((-1,) + x.shape[2:]), transitions
            )
            if warmup:
                buf = push(buf, chunk)
                m = {
                    "loss_q": jnp.float32(0.0),
                    "loss_pi": jnp.float32(0.0),
                }
            else:
                # UTD (config.utd) scales gradient steps per window —
                # static at trace time, so the compiled epoch bakes in
                # the exact scan length (default 1.0 = the reference's
                # one-update-per-env-step cadence). The ONE cadence
                # formula lives in SACConfig.updates_per_window;
                # re-derive it for this loop's (possibly caller-
                # overridden) window length.
                num_updates = self.sac.config.replace(
                    update_every=update_every
                ).updates_per_window
                ts, buf, m = self.sac.update_burst(
                    ts, buf, chunk, num_updates, axis_name=axis_name
                )
            stats = {
                "loss_q": m["loss_q"],
                "loss_pi": m["loss_pi"],
                "episodes": n_done,
                "return_sum": sum_ret,
            }
            return (ts, buf, es, key), stats

        (train_state, buffer, env_states, act_key), stats = jax.lax.scan(
            window,
            (train_state, buffer, env_states, act_key),
            xs=None,
            length=n_windows,
        )
        raw = {
            "loss_q": jnp.mean(stats["loss_q"]),
            "loss_pi": jnp.mean(stats["loss_pi"]),
            "episodes": jnp.sum(stats["episodes"]),
            "return_sum": jnp.sum(stats["return_sum"]),
        }
        return train_state, buffer, env_states, act_key, raw

    @staticmethod
    def _cross_replica_raw(raw: Metrics, axis: str) -> Metrics:
        """dp reduction of the epoch-body raw stats (losses averaged,
        counts/returns summed) — a hook so the scenario loop can reduce
        its extra per-agent/per-task keys; the base ops are verbatim
        the historical inline dict (bitwise-pinned)."""
        return {
            "loss_q": jax.lax.pmean(raw["loss_q"], axis),
            "loss_pi": jax.lax.pmean(raw["loss_pi"], axis),
            "episodes": jax.lax.psum(raw["episodes"], axis),
            "return_sum": jax.lax.psum(raw["return_sum"], axis),
        }

    @staticmethod
    def _finalize_metrics(raw: Metrics) -> Metrics:
        episodes = raw["episodes"]
        return {
            "loss_q": raw["loss_q"],
            "loss_pi": raw["loss_pi"],
            "episodes": episodes,
            # NaN, not 0, when nothing finished: for reward-negative
            # tasks a silent 0 would read as a perfect score.
            "reward": jnp.where(
                episodes > 0,
                raw["return_sum"] / jnp.maximum(episodes, 1.0),
                jnp.float32(jnp.nan),
            ),
        }

    def _build_epoch(self, steps: int, update_every: int, warmup: bool):
        n_windows, rem = divmod(steps, update_every)
        if rem:
            raise ValueError(f"steps={steps} not a multiple of update_every={update_every}")

        if self.mesh is None:

            def epoch(train_state, buffer, env_states, act_key):
                ts, buf, es, key, raw = self._epoch_body(
                    train_state, buffer, env_states, act_key,
                    n_windows, update_every, warmup,
                )
                return ts, buf, es, key, self._finalize_metrics(raw)

            return jax.jit(epoch, donate_argnums=(0, 1))

        mesh = self.mesh
        axis = OnDeviceLoop.AXIS
        n_dp = self.n_dp

        def dp_epoch(train_state, buffer, env_states, act_key):
            # The per-device view — strip the device axis, fold the
            # device index into the rng/act streams, run the shared
            # epoch body with named-axis collectives — expressed as
            # ``jax.vmap(axis_name='dp')`` over the leading device
            # axis; XLA turns the pmean/psum into real cross-device
            # all-reduces because that axis is sharded P('dp'). Same
            # math and key streams as the retired shard_map body.
            def per_device(dev, buf, es):
                # Per-device streams (the reference's per-rank seeds,
                # ref sac/algorithm.py:203-205); env randomness already
                # diverges via the per-env rng in EnvState.
                local = train_state.replace(
                    rng=jax.random.fold_in(train_state.rng, dev)
                )
                key = jax.random.fold_in(act_key, dev)
                ts, buf, es, _, raw = self._epoch_body(
                    local, buf, es, key,
                    n_windows, update_every, warmup, axis_name=axis,
                )
                raw = self._cross_replica_raw(raw, axis)
                return ts, buf, es, raw

            ts_all, buffer, env_states, raw = jax.vmap(
                per_device, axis_name=axis
            )(jnp.arange(n_dp), buffer, env_states)
            # pmean'd grads keep params replicated (per-device copies
            # bit-identical); collapse the device axis and emit a
            # replicated rng and act key derived from the pre-epoch
            # values.
            ts = jax.tree_util.tree_map(lambda x: x[0], ts_all)
            ts = ts.replace(
                rng=jax.random.fold_in(train_state.rng, jnp.uint32(0xB0057))
            )
            key_out = jax.random.fold_in(act_key, jnp.uint32(0xB0057))
            raw = jax.tree_util.tree_map(lambda x: x[0], raw)
            return ts, buffer, env_states, key_out, self._finalize_metrics(raw)

        dp_sh = NamedSharding(mesh, P(axis))
        rep = NamedSharding(mesh, P())
        return jax.jit(
            dp_epoch,
            in_shardings=(rep, dp_sh, dp_sh, rep),
            out_shardings=(rep, dp_sh, dp_sh, rep, rep),
            donate_argnums=(0, 1),
        )

    # Watchdog/cost-registry source name of the fused epoch program —
    # every compile in epoch() is attributed here, and the driver
    # registers the program's XLA cost analysis under the same key.
    epoch_cost_name = "train/ondevice_epoch"

    def epoch(
        self,
        train_state: TrainState,
        buffer: BufferState,
        env_states: EnvState,
        act_key: jax.Array,
        steps: int,
        update_every: int = 50,
        warmup: bool = False,
    ):
        """Run ``steps`` vectorized env steps (x ``n_envs`` transitions)
        with a fused gradient burst per ``update_every`` window — one
        device dispatch for the whole call. ``warmup=True`` collects
        with uniform-random actions and skips updates (the reference's
        ``start_steps``/``update_after`` phase, ref
        ``sac/algorithm.py:227-228,273``). A ``burst_dispatch`` span: the
        call of the program until it returns (``build=1`` where it
        builds the program first)."""
        from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

        args = (train_state, buffer, env_states, act_key)
        sig = (steps, update_every, warmup)
        with spans.span(spans.BURST_DISPATCH) as span:
            if sig not in self._epoch_fns:
                span.tag(build=1)
            fn = self._epoch_program(sig, args)
            with get_watchdog().source(self.epoch_cost_name):
                return fn(*args)


def loop_class_for(env_cls) -> type:
    """Pick the fused-loop class for an env class: scenario envs (a
    multi-agent or multi-task structure advertised by ``n_agents`` /
    ``n_tasks`` class attributes) train under
    :class:`~torch_actor_critic_tpu.scenarios.loop.ScenarioOnDeviceLoop`
    (per-agent/per-task metrics, striped replay, its own watchdog/cost
    entry point); everything else — including the purely procedural
    family, which needs no epoch changes — stays on the bitwise-pinned
    base :class:`OnDeviceLoop`."""
    if (
        getattr(env_cls, "n_agents", 1) > 1
        or getattr(env_cls, "n_tasks", 0) > 1
    ):
        from torch_actor_critic_tpu.scenarios.loop import ScenarioOnDeviceLoop

        return ScenarioOnDeviceLoop
    return OnDeviceLoop


@struct.dataclass
class PBTState:
    """On-device population-based-training bookkeeping.

    ``return_ema`` is the in-loop per-member episode-return EMA the
    exploit step ranks on; ``ema_count`` counts epochs that contributed
    (a member with no finished episodes yet must not be ranked —
    exploit is gated until every member has a real estimate); ``rng``
    drives the winner-pick and explore-perturbation draws. All device
    arrays: the whole exploit/explore decision is in-graph.
    """

    return_ema: jax.Array  # (n_members,) float32
    ema_count: jax.Array   # (n_members,) int32
    rng: jax.Array         # PRNG key


class PopulationOnDeviceLoop(_EpochPrograms):
    """N complete fused training runs advanced by ONE device dispatch.

    The member axis is ``jax.vmap`` over the ENTIRE
    :class:`OnDeviceLoop` epoch program — vectorized envs, replay
    rings, PRNG streams and the update bursts all inside the one
    ``lax.scan`` under one ``jit`` — so each dispatch advances N
    complete, independent learning curves (acting included, not just
    gradient steps). This is the Anakin topology (PAPERS.md) stretched
    over the population axis: the MXU that one learner at the product
    config leaves idle is spent on aggregate env steps and gradient
    steps, because XLA folds the member axis into the matmul tiles
    (PERF.md section 5, ``cheetah_pop32_fused``; the curve over N is
    not measured, ROADMAP S5).

    Independence contract (pinned by ``tests/test_population_fused.py``):
    members share NOTHING — separate env batches, replay rings,
    optimizer states and PRNG streams; member ``i``'s epoch output is
    bitwise invariant to what the other slots contain. With PBT off
    the per-member program is the SAME ``_epoch_body`` the
    single-learner loop compiles, so a population epoch is N stacked
    single-learner epochs (collect/replay/PRNG/loss streams bitwise;
    parameter trajectories agree to float-accumulation order, which
    vmap's batched backward matmuls may legally reassociate).

    With ``pbt=True``, per-member hyperparameters (learning rates,
    alpha or target entropy, TD3 target noise — see
    ``SAC.default_hyperparams``) ride ``TrainState.hyperparams`` as
    traced arrays, and :meth:`pbt_step` runs the Jaderberg-style
    exploit/explore entirely on device: rank by the return EMA, copy
    params + optimizer state from top-quantile to bottom-quantile
    members, multiplicatively perturb the losers' hyperparameters.

    With a ``mesh``, the member axis itself is the parallelism axis:
    every leaf of the member-stacked state — params, optimizer states,
    replay rings, env batches, PRNG streams, PBT score arrays — is
    sharded ``P('dp')`` on its leading member dimension, so
    ``n_members`` spread ``n_members/dp`` per device and the vmapped
    epoch partitions across the mesh with ZERO collectives (members
    share nothing). Only :meth:`pbt_step`'s exploit gather crosses
    devices — one GSPMD-inserted collective every ``pbt_every`` epochs
    when a loser copies a winner that lives on another chip. Requires
    ``n_members`` divisible by the ``dp`` size and a pure-dp mesh
    (``fsdp``/``tp``/``sp`` all 1 — members never shard over those).
    """

    def __init__(
        self, sac: SAC, env_cls, n_members: int, n_envs: int = 16,
        pbt: bool = False, mesh: Mesh | None = None,
    ):
        if n_members < 1:
            raise ValueError(f"n_members must be >= 1, got {n_members}")
        self.sac = sac
        self.env = env_cls
        self.n_members = n_members
        self.n_envs = n_envs
        self.pbt = pbt
        self.mesh = mesh
        self._member_sharding = None
        self._rep_sharding = None
        if mesh is not None:
            bad = {
                a: mesh.shape[a]
                for a in ("fsdp", "tp", "sp")
                if mesh.shape.get(a, 1) > 1
            }
            if bad:
                raise ValueError(
                    "the fused population shards members over the dp "
                    f"mesh axis only; got non-trivial axes {bad} (mesh "
                    f"shape {dict(mesh.shape)})"
                )
            dp = mesh.shape.get("dp", 1)
            if n_members % dp != 0:
                raise ValueError(
                    f"population={n_members} must divide evenly over "
                    f"the dp={dp} mesh axis (each device runs "
                    "members/dp members)"
                )
            self._member_sharding = NamedSharding(mesh, P("dp"))
            self._rep_sharding = NamedSharding(mesh, P())
        # Scenario envs route the member program through the scenario
        # loop (striped replay, per-agent/per-task stats); classic envs
        # keep the bitwise-pinned base body.
        self.inner = loop_class_for(env_cls)(sac, env_cls, n_envs=n_envs)
        self._epoch_fns: dict = {}
        self._epoch_abstract: dict = {}
        self._pbt_fn = None
        self._ema_fn = None

    def _place_members(self, tree):
        """Shard the leading member axis over ``dp`` (no-op off-mesh)."""
        if self._member_sharding is None:
            return tree
        from torch_actor_critic_tpu.parallel.mesh import global_device_put

        return jax.tree_util.tree_map(
            lambda x: global_device_put(x, self._member_sharding), tree
        )

    # ------------------------------------------------------------------ init

    def init(self, key: jax.Array, buffer_capacity: int = 1_000_000):
        """Member-stacked ``(train_state, buffer, env_states, act_keys,
        pbt_state)``. The root key fans out to ``n_members`` member
        keys, and each member's init is EXACTLY the single-learner
        :meth:`OnDeviceLoop.init` key discipline — so member ``i`` of a
        population equals a lone ``OnDeviceLoop`` seeded with member
        key ``i`` (the equivalence the tests pin). ``buffer_capacity``
        is per member: total replay HBM scales with N."""
        obs_spec, zero_obs = _env_obs_spec(self.env)
        from torch_actor_critic_tpu.buffer.replay import (
            warn_if_buffer_exceeds_hbm,
        )

        warn_if_buffer_exceeds_hbm(
            buffer_capacity * self.n_members, obs_spec, self.env.act_dim,
            advice="reduce buffer_capacity (or population)",
        )
        env = self.env
        n_envs = self.n_envs

        def member_init(k):
            k_state, k_envs, k_act = jax.random.split(k, 3)
            ts = self.sac.init_state(k_state, zero_obs)
            buf = self.inner._init_buffer(buffer_capacity, obs_spec)
            es = jax.vmap(env.reset)(jax.random.split(k_envs, n_envs))
            return ts, buf, es, k_act

        member_keys = jax.random.split(key, self.n_members)
        init_members = jax.jit(jax.vmap(member_init))
        state, buffer, env_states, act_keys = init_members(member_keys)
        if self.pbt:
            state = state.replace(
                hyperparams=self._init_hyperparams(
                    jax.random.fold_in(key, 0x9B7)
                )
            )
        pbt_state = PBTState(
            return_ema=jnp.zeros(self.n_members, jnp.float32),
            ema_count=jnp.zeros(self.n_members, jnp.int32),
            rng=jax.random.fold_in(key, 0x9B8),
        )
        if self._member_sharding is not None:
            state = self._place_members(state)
            buffer = self._place_members(buffer)
            env_states = self._place_members(env_states)
            act_keys = self._place_members(act_keys)
            # Score/count arrays carry the member axis; the exploit rng
            # is one shared stream, replicated.
            pbt_state = PBTState(
                return_ema=self._place_members(pbt_state.return_ema),
                ema_count=self._place_members(pbt_state.ema_count),
                rng=jax.device_put(pbt_state.rng, self._rep_sharding),
            )
        return state, buffer, env_states, act_keys, pbt_state

    def _init_hyperparams(self, key: jax.Array):
        """Per-member starting hyperparameters: the configured base
        values log-uniformly jittered within one explore step
        (``pbt_perturb^U[-1,1]``) so the population begins diverse —
        exploit then reallocates members toward what works."""
        base = self.sac.default_hyperparams()
        perturb = float(self.sac.config.pbt_perturb)
        hp = {}
        for i, k in enumerate(sorted(base)):
            u = jax.random.uniform(
                jax.random.fold_in(key, i), (self.n_members,),
                minval=-1.0, maxval=1.0,
            )
            hp[k] = base[k] * perturb ** u
        return hp

    # ----------------------------------------------------------------- epoch

    def _build_epoch(self, steps: int, update_every: int, warmup: bool):
        n_windows, rem = divmod(steps, update_every)
        if rem:
            raise ValueError(
                f"steps={steps} not a multiple of update_every={update_every}"
            )
        inner = self.inner

        def member_epoch(ts, buf, es, key):
            return inner._epoch_body(
                ts, buf, es, key, n_windows, update_every, warmup
            )

        def epoch(state, buffer, env_states, act_keys):
            state, buffer, env_states, act_keys, raw = jax.vmap(
                member_epoch
            )(state, buffer, env_states, act_keys)
            # _finalize_metrics is elementwise (broadcasting over any
            # trailing agent/task axis), so it maps over the member
            # axis unchanged: every metric keeps its leading (N,) — N
            # real learning curves, never one averaged one. Routed
            # through the inner loop so scenario envs finalize their
            # per-agent/per-task extras; for classic envs this IS
            # OnDeviceLoop._finalize_metrics.
            return (
                state, buffer, env_states, act_keys,
                inner._finalize_metrics(raw),
            )

        if self._member_sharding is None:
            return jax.jit(epoch, donate_argnums=(0, 1))
        # Member-sharded: pin the leading member axis to P('dp') on
        # every input and output, so the vmapped member programs
        # partition across devices (members share nothing — the epoch
        # compiles with no collectives) and the donated state/rings
        # keep their layout across dispatches.
        mem = self._member_sharding
        return jax.jit(
            epoch,
            in_shardings=(mem, mem, mem, mem),
            out_shardings=(mem, mem, mem, mem, mem),
            donate_argnums=(0, 1),
        )

    # Watchdog/cost-registry source of the vmapped population epoch.
    epoch_cost_name = "train/population_epoch"

    def epoch(
        self,
        state: TrainState,
        buffer: BufferState,
        env_states: EnvState,
        act_keys: jax.Array,
        steps: int,
        update_every: int = 50,
        warmup: bool = False,
    ):
        """One population epoch: ``steps`` vectorized env steps times
        ``n_envs`` envs times ``n_members`` members, with a fused
        gradient burst per ``update_every`` window per member — one
        device dispatch for everything. A ``burst_dispatch`` span: the
        call of the program until it returns (``build=1`` where it
        builds the program first)."""
        from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

        args = (state, buffer, env_states, act_keys)
        sig = (steps, update_every, warmup)
        with spans.span(spans.BURST_DISPATCH) as span:
            if sig not in self._epoch_fns:
                span.tag(build=1)
            fn = self._epoch_program(sig, args)
            with get_watchdog().source(self.epoch_cost_name):
                return fn(*args)

    # ------------------------------------------------------------------- pbt

    def update_ema(self, pbt_state: PBTState, metrics: Metrics) -> PBTState:
        """Fold an epoch's per-member mean returns into the ranking
        EMA (device-side; inputs are the epoch's output arrays, so no
        host round-trip). Members with no finished episodes this epoch
        keep their estimate unchanged and uncounted."""
        if self._ema_fn is None:
            tau = float(self.sac.config.pbt_ema)

            def f(ps, episodes, reward):
                has = episodes > 0
                blended = jnp.where(
                    ps.ema_count == 0,
                    reward,
                    (1.0 - tau) * ps.return_ema + tau * reward,
                )
                return ps.replace(
                    # reward is NaN for no-episode members; the where()
                    # keeps their old EMA (NaN never selected).
                    return_ema=jnp.where(has, blended, ps.return_ema),
                    ema_count=ps.ema_count + has.astype(jnp.int32),
                )

            self._ema_fn = jax.jit(f)
        return self._ema_fn(
            pbt_state, metrics["episodes"], metrics["reward"]
        )

    def pbt_step(self, state: TrainState, pbt_state: PBTState):
        """One exploit/explore step, entirely in-graph.

        Rank members by ``return_ema``; every bottom-quantile member
        copies params + ALL optimizer state from a uniformly drawn
        top-quantile member (one gather along the member axis — no
        host transfer) and multiplies each of its hyperparameters by
        ``pbt_perturb`` or ``1/pbt_perturb`` (fair coin each). Members
        keep their own PRNG streams (copying them would correlate the
        'independent' continuations) and their own replay rings (the
        winner's policy re-fills the loser's ring within a window).
        Exploit is identity until every member has a ranked EMA.

        Returns ``(state, pbt_state, event)`` where ``event`` holds
        the per-member source index, exploit mask, perturbation
        factors and the ranking EMA — small arrays the host fetches
        for the ``pbt`` telemetry record.
        """
        if self._pbt_fn is None:
            cfg = self.sac.config
            n = self.n_members
            n_cut = max(1, int(n * cfg.pbt_quantile))
            perturb = float(cfg.pbt_perturb)

            def f(st, ps):
                ready = jnp.all(ps.ema_count > 0)
                order = jnp.argsort(ps.return_ema)  # ascending
                bottom, top = order[:n_cut], order[n - n_cut:]
                rng, k_pick, k_fac = jax.random.split(ps.rng, 3)
                pick = jax.random.randint(k_pick, (n_cut,), 0, n_cut)
                src = jnp.arange(n).at[bottom].set(top[pick])
                src = jnp.where(ready, src, jnp.arange(n))
                exploited = src != jnp.arange(n)
                copied = jax.tree_util.tree_map(lambda x: x[src], st)
                hp = st.hyperparams
                factors = perturb ** jax.random.choice(
                    k_fac, jnp.array([-1.0, 1.0]),
                    (max(len(hp or {}), 1), n),
                )
                if hp is not None:
                    hp = {
                        k: jnp.where(
                            exploited, hp[k][src] * factors[i], hp[k]
                        )
                        for i, k in enumerate(sorted(hp))
                    }
                new_state = copied.replace(
                    # step is lockstep-identical across members; rng
                    # and hyperparams must NOT be the winner's copies.
                    step=st.step, rng=st.rng, hyperparams=hp,
                )
                event = {
                    "src": src,
                    "exploited": exploited,
                    "factors": factors,
                    "return_ema": ps.return_ema,
                    "ready": ready,
                }
                # Losers inherit the winner's EMA: a freshly cloned
                # member must compete as its new self, not be
                # re-exploited next round on its old score.
                new_ps = ps.replace(
                    return_ema=jnp.where(
                        exploited, ps.return_ema[src], ps.return_ema
                    ),
                    rng=rng,
                )
                if self._member_sharding is not None:
                    # The exploit gather is the one cross-device
                    # collective of a sharded population; pin its
                    # output back to the member layout so the copied
                    # winners land on the losers' devices instead of
                    # the whole population gathering anywhere. PRNG-key
                    # leaves are skipped: with_sharding_constraint on
                    # extended (key) dtypes trips a physical/logical
                    # rank mismatch on the installed jax, and the
                    # losers keep their own streams anyway (rng=st.rng
                    # below — never gathered).
                    mem = self._member_sharding
                    new_state = jax.tree_util.tree_map(
                        lambda x: x
                        if jax.dtypes.issubdtype(
                            x.dtype, jax.dtypes.prng_key
                        )
                        else jax.lax.with_sharding_constraint(x, mem),
                        new_state,
                    )
                    new_ps = new_ps.replace(
                        return_ema=jax.lax.with_sharding_constraint(
                            new_ps.return_ema, mem
                        ),
                        ema_count=jax.lax.with_sharding_constraint(
                            new_ps.ema_count, mem
                        ),
                    )
                return new_state, new_ps, event

            # No donation: the step runs once per pbt_every epochs and
            # callers (tests, the telemetry path) still read the
            # pre-exploit state afterwards.
            self._pbt_fn = jax.jit(f)
        return self._pbt_fn(state, pbt_state)

    # ----------------------------------------------------------- extraction

    def extract_member(self, state: TrainState, member: int) -> TrainState:
        """Member ``member``'s complete single-learner state (leading
        population axis sliced off every leaf) — loadable by the
        single-learner loop, the eval CLI and the serving plane."""
        return jax.tree_util.tree_map(lambda x: x[member], state)


def _env_obs_spec(env_cls):
    """Resolve an on-device env's observation spec and a zero example.

    Pytree-observation envs (e.g. the pixel twin) expose ``obs_spec()``
    /``zero_obs()`` classmethods; flat envs carry ``obs_dim`` (or
    ``obs_shape`` when history-wrapped) and stay float32 vectors.
    """
    if hasattr(env_cls, "obs_spec"):
        spec = env_cls.obs_spec()
        return spec, env_cls.zero_obs()
    shape = getattr(env_cls, "obs_shape", (env_cls.obs_dim,))
    return jax.ShapeDtypeStruct(shape, jnp.float32), jnp.zeros(shape)


class _SpecView:
    """The env-protocol triple ``build_models`` dispatches on, derived
    from an on-device env class (which carries shapes as class attrs)."""

    def __init__(self, env_cls):
        self.obs_spec, _ = _env_obs_spec(env_cls)
        self.act_dim = env_cls.act_dim
        self.act_limit = env_cls.act_limit
        # Scenario structure (scenarios/): multi-agent factorization
        # and multi-task conditioning ride the env class so
        # build_models can dispatch to the per-agent / task-embedding
        # heads. Defaults leave classic envs untouched.
        self.n_agents = getattr(env_cls, "n_agents", 1)
        self.agent_obs_dim = getattr(env_cls, "agent_obs_dim", 0)
        self.n_tasks = getattr(env_cls, "n_tasks", 0)


def _wrap_and_build(env_cls, config) -> t.Tuple[t.Any, SAC]:
    """History-wrap the env class per config and build its SAC.

    The ONE construction path of ``train_on_device``, sharing
    ``trainer.build_models`` with the host loop, so the fused loop can
    never train a differently-built model than the Trainer does.
    """
    from torch_actor_critic_tpu.envs.ondevice import history_env
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    if config.history_len > 1:
        env_cls = history_env(env_cls, config.history_len)
    actor, critic = build_models(config, _SpecView(env_cls))
    return env_cls, make_learner(config, actor, critic, env_cls.act_dim)


def _note_epoch_cost(
    loop, sig, cost_state, metrics, dt, telemetry, e,
    devices: int = 1, compute_dtype: str | None = None,
):
    """Fused-loop per-epoch cost attribution (telemetry on only):
    register the epoch program's XLA cost analysis once, then add
    ``cost/epoch_*`` metric columns and emit one ``cost`` telemetry
    event for the dispatch that just drained. ``cost_state`` is the
    mutable ``{"registered": bool, "peaks": Peaks|None}`` the driver
    threads through its loop. ``devices`` is the participating mesh
    size of a sharded epoch program — the whole-program analysis is
    divided down to per-device FLOPs/bytes so roofline/MFU stays
    honest against a single chip's peak."""
    from torch_actor_critic_tpu.telemetry.costmodel import (
        Peaks,
        get_cost_registry,
        roofline,
    )

    registry = get_cost_registry()
    if not cost_state["registered"]:
        cost_state["registered"] = True
        fn = loop.epoch_jit(*sig)
        abstract = loop.epoch_abstract(*sig)
        if fn is not None and abstract:
            registry.register_jit(
                loop.epoch_cost_name, fn, *abstract, devices=devices
            )
    cost = registry.get(loop.epoch_cost_name)
    if cost is None:
        return
    if cost_state["peaks"] is None:
        cost_state["peaks"] = Peaks.detect()
    rl = roofline(
        cost, dt, calls=1, peaks=cost_state["peaks"],
        compute_dtype=compute_dtype,
    )
    metrics["cost/epoch_gflops"] = cost["flops"] / 1e9
    metrics["cost/epoch_achieved_gflops_s"] = (
        rl.get("achieved_flops_per_sec", 0.0) / 1e9
    )
    if "arithmetic_intensity" in rl:
        metrics["cost/epoch_ai"] = rl["arithmetic_intensity"]
    if "mfu" in rl:
        metrics["cost/epoch_mfu"] = rl["mfu"]
    if "bound" in rl:
        metrics["cost/epoch_compute_bound"] = float(rl["bound"] == "compute")
    telemetry.event(
        "cost", epoch=int(e), programs={loop.epoch_cost_name: rl},
        device_kind=cost_state["peaks"].device_kind,
        compute_dtype=compute_dtype,
    )


def warmup_steps(start_steps: int, update_every: int) -> int:
    """Policy-free warmup length per env: ``start_steps`` rounded down
    to an ``update_every`` multiple, at least one window (ref warmup
    phase ``sac/algorithm.py:227-228``)."""
    return max(update_every, (start_steps // update_every) * update_every)


def train_on_device(
    env_name: str,
    config,
    mesh=None,
    tracker=None,
    checkpointer=None,
    seed: int = 0,
    telemetry=None,
) -> dict:
    """Host driver for the fused loop: one device dispatch per epoch,
    host work = logging + checkpoints. The CLI routes here for
    ``--on-device true`` (envs with a pure-JAX twin only).

    Env steps per epoch are ``steps_per_epoch x on_device_envs x dp``;
    the warmup phase covers ``start_steps`` policy-free steps (ref
    ``sac/algorithm.py:227-228``). Checkpoints persist learner + buffer
    state (env states re-reset on resume — episodes are seconds long).
    ``telemetry`` (a TelemetryRecorder) has no host phases to span
    here — the epoch IS one dispatch — but per-epoch ``cost`` events
    (fused-program FLOPs/roofline, telemetry/costmodel.py) stream
    through it and ``cost/epoch_*`` columns land in metrics.jsonl.
    """
    import numpy as np

    from torch_actor_critic_tpu.diagnostics.ingraph import (
        split_scenario_metrics,
    )
    from torch_actor_critic_tpu.envs.ondevice import (
        get_on_device_env,
        known_on_device_envs,
    )
    from torch_actor_critic_tpu.parallel.distributed import is_coordinator

    env_cls = get_on_device_env(env_name)
    if env_cls is None:
        raise ValueError(
            f"{env_name!r} has no pure-JAX twin; on-device training "
            f"supports {known_on_device_envs()}"
        )
    # history_len > 1 windows the env on-chip (fused HistoryEnv twin)
    # and dispatches to the causal-transformer stack via build_models.
    env_cls, sac = _wrap_and_build(env_cls, config)
    # Scenario envs (multi-agent/multi-task structure) train under the
    # scenario loop; classic envs keep the bitwise-pinned base program.
    loop = loop_class_for(env_cls)(
        sac, env_cls, n_envs=config.on_device_envs, mesh=mesh
    )
    state, buffer, env_states, act_key = loop.init(
        jax.random.key(seed), buffer_capacity=config.buffer_size
    )
    start_epoch = 0
    if checkpointer is not None and checkpointer.latest_epoch() is not None:
        state, buffer, meta = checkpointer.restore(state, buffer)
        start_epoch = int(meta["epoch"]) + 1

    n_warmup = warmup_steps(config.start_steps, config.update_every)
    if start_epoch == 0:
        state, buffer, env_states, act_key, _ = loop.epoch(
            state, buffer, env_states, act_key, steps=n_warmup,
            update_every=config.update_every, warmup=True,
        )

    import time

    metrics: dict = {}
    sig = (config.steps_per_epoch, config.update_every, False)
    cost_state = {"registered": False, "peaks": None}
    for e in range(start_epoch, start_epoch + config.epochs):
        t0 = time.time()
        state, buffer, env_states, act_key, m = loop.epoch(
            state,
            buffer,
            env_states,
            act_key,
            steps=config.steps_per_epoch,
            update_every=config.update_every,
        )
        # Host-fetch drain before reading the clock (utils/sync.py).
        # The host fetches below would drain too, but the timing
        # contract should not hinge on dict iteration order.
        drain(m["loss_q"])
        # Scalar metrics become floats exactly as before; scenario
        # per-axis vectors expand to the _a{i}/_t{i} suffix layout.
        metrics = split_scenario_metrics(jax.device_get(m))
        dt = time.time() - t0
        metrics["env_steps_per_sec"] = (
            config.steps_per_epoch * loop.n_envs * loop.n_dp / dt
        )
        # utd scales updates per window (the epoch runs
        # steps/update_every windows of updates_per_window steps each).
        metrics["grad_steps_per_sec"] = (
            (config.steps_per_epoch // config.update_every)
            * config.updates_per_window / dt
        )
        if telemetry is not None:
            _note_epoch_cost(
                loop, sig, cost_state, metrics, dt,
                telemetry, e, devices=loop.n_dp,
                compute_dtype=config.compute_dtype,
            )
        if tracker is not None and is_coordinator():
            tracker.log_metrics(metrics, e)
        # Final epoch always saves (same contract as the host Trainer):
        # short runs still produce a loadable checkpoint.
        if checkpointer is not None and (
            e % config.save_every == 0
            or e == start_epoch + config.epochs - 1
        ):
            checkpointer.save(e, state, buffer, extra={"config": config.to_json()})
        if not np.isfinite(metrics["loss_q"]):
            raise FloatingPointError(f"loss_q diverged at epoch {e}: {metrics}")
    if checkpointer is not None:
        checkpointer.wait()
    if telemetry is not None:
        telemetry.close()
    return metrics


def train_population_on_device(
    env_name: str,
    config,
    mesh=None,
    tracker=None,
    checkpointer=None,
    seed: int = 0,
    telemetry=None,
) -> dict:
    """Host driver for population-fused training: each epoch is ONE
    device dispatch advancing ``config.population`` complete learning
    curves; host work = logging, checkpoints and the (device-computed)
    PBT cadence. The CLI routes here for ``--on-device true
    --population N``.

    Per-member metrics flow to the tracker under the suffix-keyed
    member layout (``loss_q_m3``, ``reward_m7``, ... — see
    ``diagnostics.split_member_metrics``), so metrics.jsonl carries N
    curves. Checkpoints are population-aware: the stacked
    ``TrainState`` (with per-member hyperparams), the stacked replay
    rings, every member's env state, acting key and PBT bookkeeping —
    a resumed run continues bitwise (the fused-loop extension of the
    PR 2 lossless-resume guarantee). ``pbt`` telemetry events record
    every exploit/explore step.
    """
    import numpy as np

    from torch_actor_critic_tpu.diagnostics.ingraph import (
        split_member_metrics,
    )
    from torch_actor_critic_tpu.envs.ondevice import (
        get_on_device_env,
        known_on_device_envs,
    )
    from torch_actor_critic_tpu.parallel.distributed import is_coordinator

    # Member-axis sharding: on a pure-dp multi-device mesh with a
    # divisible population, members spread across devices (P('dp') on
    # the leading member dimension of everything); otherwise fall back
    # to the single-device layout with a warning so odd populations
    # keep training.
    pop_mesh = None
    if mesh is not None and int(np.prod(list(mesh.shape.values()))) > 1:
        import logging

        dp = mesh.shape.get("dp", 1)
        non_dp = {
            a: mesh.shape[a]
            for a in ("fsdp", "tp", "sp")
            if mesh.shape.get(a, 1) > 1
        }
        if non_dp or config.population % dp != 0:
            logging.getLogger(__name__).warning(
                "cannot shard the member axis over mesh %s (members "
                "shard over dp only and population=%d must divide dp); "
                "running the whole population on one device",
                dict(mesh.shape), config.population,
            )
        else:
            pop_mesh = mesh
            logging.getLogger(__name__).info(
                "sharding population=%d over dp=%d devices (%d members "
                "per device)", config.population, dp,
                config.population // dp,
            )
    env_cls = get_on_device_env(env_name)
    if env_cls is None:
        raise ValueError(
            f"{env_name!r} has no pure-JAX twin; on-device training "
            f"supports {known_on_device_envs()}"
        )
    env_cls, sac = _wrap_and_build(env_cls, config)
    loop = PopulationOnDeviceLoop(
        sac, env_cls, n_members=config.population,
        n_envs=config.on_device_envs, pbt=config.pbt_every > 0,
        mesh=pop_mesh,
    )
    state, buffer, env_states, act_keys, pbt_state = loop.init(
        jax.random.key(seed), buffer_capacity=config.buffer_size
    )
    start_epoch = 0
    if checkpointer is not None and checkpointer.latest_epoch() is not None:
        state, buffer, meta, arrays = checkpointer.restore(
            state, buffer,
            abstract_arrays={
                "env_states": env_states,
                "act_keys": act_keys,
                "pbt_state": pbt_state,
            },
        )
        saved_pop = int(meta.get("population", 1))
        if saved_pop != config.population:
            raise ValueError(
                f"checkpoint holds a population of {saved_pop}; this "
                f"run is configured for {config.population}"
            )
        if arrays is not None:
            env_states = arrays["env_states"]
            act_keys = arrays["act_keys"]
            pbt_state = arrays["pbt_state"]
        start_epoch = int(meta["epoch"]) + 1

    def save(epoch: int):
        checkpointer.save(
            epoch, state, buffer,
            extra={
                "config": config.to_json(),
                "population": config.population,
                "pbt": {
                    "return_ema": np.asarray(
                        pbt_state.return_ema
                    ).tolist(),
                    "ema_count": np.asarray(
                        pbt_state.ema_count
                    ).tolist(),
                },
            },
            arrays={
                "env_states": env_states,
                "act_keys": act_keys,
                "pbt_state": pbt_state,
            },
        )

    n_warmup = warmup_steps(config.start_steps, config.update_every)
    if start_epoch == 0:
        state, buffer, env_states, act_keys, _ = loop.epoch(
            state, buffer, env_states, act_keys, steps=n_warmup,
            update_every=config.update_every, warmup=True,
        )

    import time

    n_members = config.population
    metrics: dict = {}
    sig = (config.steps_per_epoch, config.update_every, False)
    cost_state = {"registered": False, "peaks": None}
    for e in range(start_epoch, start_epoch + config.epochs):
        t0 = time.time()
        state, buffer, env_states, act_keys, m = loop.epoch(
            state, buffer, env_states, act_keys,
            steps=config.steps_per_epoch,
            update_every=config.update_every,
        )
        pbt_state = loop.update_ema(pbt_state, m)
        pbt_event = None
        # Cadence on the ABSOLUTE epoch: a resumed run exploits at the
        # same epochs the uninterrupted run would have (part of the
        # bitwise-resume contract).
        if config.pbt_every > 0 and (e + 1) % config.pbt_every == 0:
            state, pbt_state, pbt_event = loop.pbt_step(state, pbt_state)
        # Host-fetch drain before reading the clock (see train_on_device).
        drain(m["loss_q"])
        dt = time.time() - t0
        # N per-member curves + the suffix-keyed aggregates.
        metrics = split_member_metrics(jax.device_get(m))
        metrics["env_steps_per_sec"] = (
            config.steps_per_epoch * loop.n_envs * n_members / dt
        )
        metrics["grad_steps_per_sec"] = (
            (config.steps_per_epoch // config.update_every)
            * config.updates_per_window * n_members / dt
        )
        if telemetry is not None:
            # Whole-population program cost: the FLOPs carry the member
            # axis (one vmapped executable); with the member axis
            # sharded, the per-device divide keeps MFU the aggregate
            # utilization of ONE chip's slice of the population.
            _note_epoch_cost(
                loop, sig, cost_state, metrics, dt,
                telemetry, e,
                devices=(
                    pop_mesh.shape["dp"] if pop_mesh is not None else 1
                ),
                compute_dtype=config.compute_dtype,
            )
        if pbt_event is not None:
            ev = jax.device_get(pbt_event)
            exploited = np.flatnonzero(ev["exploited"])
            metrics["pbt_exploits"] = int(exploited.size)
            if telemetry is not None:
                hp = jax.device_get(state.hyperparams) or {}
                telemetry.event(
                    "pbt",
                    epoch=e,
                    exploited=[int(i) for i in exploited],
                    src=[int(s) for s in ev["src"]],
                    ready=bool(ev["ready"]),
                    return_ema=[
                        round(float(x), 4) for x in ev["return_ema"]
                    ],
                    hyperparams={
                        k: [float(x) for x in np.asarray(v)]
                        for k, v in hp.items()
                    },
                )
        if tracker is not None and is_coordinator():
            tracker.log_metrics(metrics, e)
        if checkpointer is not None and (
            e % config.save_every == 0
            or e == start_epoch + config.epochs - 1
        ):
            save(e)
        bad = [
            i for i in range(n_members)
            if not np.isfinite(metrics.get(f"loss_q_m{i}", 0.0))
        ]
        if bad:
            raise FloatingPointError(
                f"loss_q diverged at epoch {e} for members {bad}: "
                f"{ {k: v for k, v in metrics.items() if 'loss_q' in k} }"
            )
    if checkpointer is not None:
        checkpointer.wait()
    if telemetry is not None:
        telemetry.close()
    return metrics

