"""Host training shell: env stepping, staging, bursts, metrics, ckpt.

The re-design of the reference's ``SAC.train`` loop (ref
``sac/algorithm.py:182-307``) for the host<->TPU boundary (SURVEY.md §7
hard-part (a)). Structure per epoch:

- one **vectorized policy call** per env step for all ``n_envs`` envs
  (the reference runs one env per MPI rank, stepping under
  ``torch.no_grad`` per process, ref ``:227-236``);
- transitions accumulate in a host **staging buffer** and cross to the
  device once per ``update_every`` window — either a pure push (warmup;
  ref stores every step, ``:249``) or the fused
  push+K-updates burst (ref inner loop ``:274-283``), so
  host<->device traffic is ~2 transfers per 50 env steps instead of
  the reference's per-update sample conversion;
- episode bookkeeping, the ``max_ep_len`` done-bypass (ref ``:241``)
  expressed as gymnasium truncation, per-epoch metric means under the
  reference's metric names (``episode_length``, ``reward``, ``loss_q``,
  ``loss_pi``, ref ``:285-290``), tqdm progress (ref ``:213,299``);
- rank-0-gated checkpoint every ``save_every`` epochs
  (ref ``:291-293``) via Orbax, and metric logging via the tracker.

One env per ``dp`` mesh slice feeds that device's replay shard —
exactly the reference's worker<->buffer pairing (per-rank env + buffer,
SURVEY.md §2 "Parallelism strategies") with ranks -> mesh slices.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
import typing as t

import jax
import jax.numpy as jnp
import numpy as np

from torch_actor_critic_tpu.buffer.replay import warn_if_buffer_exceeds_hbm
from torch_actor_critic_tpu.core.types import Batch, MultiObservation
from torch_actor_critic_tpu.envs.vec_env import make_env_pool
from torch_actor_critic_tpu.envs.wrappers import is_visual_env
from torch_actor_critic_tpu.models import Actor, DoubleCritic, VisualActor, VisualDoubleCritic
from torch_actor_critic_tpu.parallel import (
    DataParallelSAC,
    chunk_block,
    init_sharded_buffer,
    make_mesh,
    shard_chunk_from_local,
)
from torch_actor_critic_tpu.parallel.mesh import local_dp_info
from torch_actor_critic_tpu.parallel.distributed import global_statistics, is_coordinator
from torch_actor_critic_tpu.resilience.preemption import Preempted, PreemptionGuard
from torch_actor_critic_tpu.resilience.sentinel import (
    DivergenceSentinel,
    TrainingDiverged,
)
from torch_actor_critic_tpu.sac.algorithm import SAC
from torch_actor_critic_tpu.telemetry import TelemetryRecorder
from torch_actor_critic_tpu.telemetry import recorder as spans
from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
from torch_actor_critic_tpu.utils.config import SACConfig
from torch_actor_critic_tpu.utils.normalize import (
    FeaturesNormalizer,
    IdentityNormalizer,
    PerMemberNormalizer,
    WelfordNormalizer,
)
from torch_actor_critic_tpu.utils.sync import drain
from torch_actor_critic_tpu.utils.tracking import Tracker

logger = logging.getLogger(__name__)

# The phases the Trainer marks itself (integer indices into
# telemetry.PHASES), hoisted to module constants so the hot loop's
# instrumentation is `rec.begin(_PH_ENV)` — no dict or attribute lookups
# per phase mark (docs/OBSERVABILITY.md). The device window's spans
# (stage, place_chunk, burst_dispatch, drain) are opened by the
# functions that do the work, through telemetry.recorder.span.
_PH_ACT = spans.ACT
_PH_ENV = spans.ENV_STEP
_PH_BURST = spans.BURST_DISPATCH
_PH_DRAIN = spans.DRAIN
_PH_SENTINEL = spans.SENTINEL
_PH_CKPT = spans.CHECKPOINT
_PH_SYNC = spans.PARAM_SYNC


def build_models(config: SACConfig, env) -> t.Tuple[t.Any, t.Any]:
    """Model-family dispatch on observation structure — the typed
    replacement of the reference's env-name string dispatch
    (ref ``main.py:63-90``)."""
    dtype = config.model_dtype
    if config.frame_augment != "none" and not isinstance(
        env.obs_spec, MultiObservation
    ):
        # Fail-at-construction policy (see SACConfig.__post_init__): a
        # frame augmentation silently no-opping on flat/sequence
        # observations would let a user believe DrQ was active.
        raise ValueError(
            f"frame_augment={config.frame_augment!r} requires a visual "
            f"(frame) observation; got obs spec {env.obs_spec}"
        )
    if config.pixel_pipeline == "fused" and not isinstance(
        env.obs_spec, MultiObservation
    ):
        # Same fail-at-construction policy: a fused pixel pipeline
        # silently no-opping on flat/sequence observations would let a
        # user believe the f32-free frame path was active.
        raise ValueError(
            "pixel_pipeline='fused' requires a visual (frame) "
            f"observation; got obs spec {env.obs_spec}"
        )
    # Scenario model dispatch (scenarios/, docs/SCENARIOS.md): the env
    # class advertises its multi-agent factorization / task count and
    # the heads follow. SAC-only and flat-observation-only — fail at
    # construction, same policy as the augment/pixel gates above.
    n_agents = getattr(env, "n_agents", 1)
    n_tasks = getattr(env, "n_tasks", 0)
    if n_agents > 1 or (n_tasks > 1 and config.task_embed_dim > 0):
        if config.algorithm != "sac":
            raise ValueError(
                "multi-agent / task-embedding heads are SAC-only; got "
                f"algorithm={config.algorithm!r}"
            )
        if isinstance(env.obs_spec, MultiObservation) or len(
            env.obs_spec.shape
        ) != 1:
            raise ValueError(
                "multi-agent / task-embedding heads need flat "
                f"observations; got obs spec {env.obs_spec} (drop "
                "history_len or use the plain one-hot conditioning)"
            )
    if n_agents > 1:
        from torch_actor_critic_tpu.models import (
            MultiAgentActor,
            MultiAgentDoubleCritic,
        )

        actor = MultiAgentActor(
            n_agents=n_agents,
            agent_obs_dim=env.agent_obs_dim,
            act_dim=env.act_dim,
            hidden_sizes=config.hidden_sizes,
            act_limit=env.act_limit,
            dtype=dtype,
        )
        if config.ma_critic == "centralized":
            # CTDE: the joint-(obs, action) twin critic IS the plain
            # DoubleCritic — centralized training, decentralized
            # per-agent actor heads.
            critic = DoubleCritic(
                hidden_sizes=config.hidden_sizes,
                num_qs=config.num_qs,
                dtype=dtype,
            )
        else:
            critic = MultiAgentDoubleCritic(
                n_agents=n_agents,
                agent_obs_dim=env.agent_obs_dim,
                agent_act_dim=env.act_dim // n_agents,
                hidden_sizes=config.hidden_sizes,
                num_qs=config.num_qs,
                dtype=dtype,
            )
        return actor, critic
    if n_tasks > 1 and config.task_embed_dim > 0:
        from torch_actor_critic_tpu.models import (
            TaskConditionedActor,
            TaskConditionedDoubleCritic,
        )

        actor = TaskConditionedActor(
            n_tasks=n_tasks,
            task_embed_dim=config.task_embed_dim,
            act_dim=env.act_dim,
            hidden_sizes=config.hidden_sizes,
            act_limit=env.act_limit,
            dtype=dtype,
        )
        critic = TaskConditionedDoubleCritic(
            n_tasks=n_tasks,
            task_embed_dim=config.task_embed_dim,
            hidden_sizes=config.hidden_sizes,
            num_qs=config.num_qs,
            dtype=dtype,
        )
        return actor, critic
    if config.algorithm == "td3":
        # TD3 (extension): deterministic tanh policy over the flat MLP
        # or visual stack (same twin critics as SAC). The sequence
        # stack is squashed-Gaussian-only for now — fail at
        # construction, not mid-training.
        if isinstance(env.obs_spec, MultiObservation):
            from torch_actor_critic_tpu.models import DeterministicVisualActor

            actor = DeterministicVisualActor(
                act_dim=env.act_dim,
                hidden_sizes=config.hidden_sizes,
                act_limit=env.act_limit,
                act_noise=config.act_noise,
                filters=config.filters,
                kernel_sizes=config.kernel_sizes,
                strides=config.strides,
                cnn_features=config.cnn_features,
                cnn_dense_size=config.cnn_dense_size,
                normalize_pixels=config.normalize_pixels,
                dtype=dtype,
            )
            critic = VisualDoubleCritic(
                hidden_sizes=config.hidden_sizes,
                filters=config.filters,
                kernel_sizes=config.kernel_sizes,
                strides=config.strides,
                cnn_features=config.cnn_features,
                cnn_dense_size=config.cnn_dense_size,
                normalize_pixels=config.normalize_pixels,
                num_qs=config.num_qs,
                dtype=dtype,
            )
            return actor, critic
        if len(env.obs_spec.shape) != 1:
            raise ValueError(
                "algorithm='td3' supports flat and visual observations "
                f"(got obs spec {env.obs_spec}); use algorithm='sac' for "
                "the sequence (history) stack"
            )
        from torch_actor_critic_tpu.models import DeterministicActor

        actor = DeterministicActor(
            act_dim=env.act_dim,
            hidden_sizes=config.hidden_sizes,
            act_limit=env.act_limit,
            act_noise=config.act_noise,
            dtype=dtype,
        )
        critic = DoubleCritic(
            hidden_sizes=config.hidden_sizes, num_qs=config.num_qs, dtype=dtype
        )
        return actor, critic
    if isinstance(env.obs_spec, MultiObservation):
        actor = VisualActor(
            act_dim=env.act_dim,
            hidden_sizes=config.hidden_sizes,
            act_limit=env.act_limit,
            filters=config.filters,
            kernel_sizes=config.kernel_sizes,
            strides=config.strides,
            cnn_features=config.cnn_features,
            cnn_dense_size=config.cnn_dense_size,
            normalize_pixels=config.normalize_pixels,
            dtype=dtype,
        )
        critic = VisualDoubleCritic(
            hidden_sizes=config.hidden_sizes,
            filters=config.filters,
            kernel_sizes=config.kernel_sizes,
            strides=config.strides,
            cnn_features=config.cnn_features,
            cnn_dense_size=config.cnn_dense_size,
            normalize_pixels=config.normalize_pixels,
            num_qs=config.num_qs,
            dtype=dtype,
        )
    elif len(env.obs_spec.shape) == 2:
        # (history, obs_dim) observations from HistoryEnv → the
        # causal-transformer sequence stack (extension; SURVEY.md §5).
        from torch_actor_critic_tpu.models import (
            SequenceActor,
            SequenceDoubleCritic,
        )

        if config.shared_trunk:
            # One trunk for actor and critics, its layers from the
            # configuration's pattern (models/sequence.py, SACConfig.trunk_*).
            from torch_actor_critic_tpu.models import (
                SharedTrunkActor,
                SharedTrunkCritic,
                TrunkSpec,
            )

            spec = TrunkSpec.from_config(config)
            return (
                SharedTrunkActor(
                    act_dim=env.act_dim, spec=spec, act_limit=env.act_limit,
                    dtype=dtype,
                ),
                SharedTrunkCritic(
                    spec=spec, hidden=config.trunk_q_hidden,
                    num_qs=config.num_qs, dtype=dtype,
                ),
            )
        horizon = env.obs_spec.shape[0]
        actor = SequenceActor(
            act_dim=env.act_dim,
            d_model=config.seq_d_model,
            num_heads=config.seq_num_heads,
            num_layers=config.seq_num_layers,
            max_len=horizon,
            act_limit=env.act_limit,
            dtype=dtype,
        )
        critic = SequenceDoubleCritic(
            d_model=config.seq_d_model,
            num_heads=config.seq_num_heads,
            num_layers=config.seq_num_layers,
            max_len=horizon,
            num_qs=config.num_qs,
            dtype=dtype,
        )
    else:
        actor = Actor(
            act_dim=env.act_dim,
            hidden_sizes=config.hidden_sizes,
            act_limit=env.act_limit,
            dtype=dtype,
        )
        critic = DoubleCritic(
            hidden_sizes=config.hidden_sizes, num_qs=config.num_qs, dtype=dtype
        )
    return actor, critic


def make_learner(config: SACConfig, actor_def, critic_def, act_dim: int):
    """The single algorithm-dispatch point: ``config.algorithm`` picks
    the learner class over already-built module defs. Every
    construction path (host Trainer, fused on-device loop, bench) goes
    through here so a new algorithm family plugs in at ONE site."""
    if config.algorithm == "td3":
        from torch_actor_critic_tpu.td3 import TD3

        return TD3(config, actor_def, critic_def, act_dim)
    return SAC(config, actor_def, critic_def, act_dim)


def _set_row(tree: t.Any, i: int, value: t.Any) -> None:
    jax.tree_util.tree_map(lambda dst, src: dst.__setitem__(i, src), tree, value)


class Trainer:
    """End-to-end SAC training over a device mesh.

    ``n_envs`` host envs (default: one per dp slice) step in lockstep;
    per-rank seeds follow the reference's ``10000 * rank`` scheme
    (ref ``sac/algorithm.py:203-205``).
    """

    def __init__(
        self,
        env_name: str,
        config: SACConfig | None = None,
        mesh=None,
        tracker: Tracker | None = None,
        checkpointer: Checkpointer | None = None,
        seed: int = 0,
        env_kwargs: dict | None = None,
        render: bool = False,
        preemption: PreemptionGuard | None = None,
        telemetry: TelemetryRecorder | None = None,
    ):
        import os
        import sys

        # gymnasium only draws when the env is CONSTRUCTED with a
        # render mode (unlike legacy gym's on-demand .render(), ref
        # run_agent.py:40), and constructing "human" mode headless
        # crashes — so rendering is decided here, once, for every
        # entry point. dm_control-backed envs keep their own (no-op)
        # render paths.
        self._render_ok = False
        if render:
            if env_name.startswith("dm:") or is_visual_env(env_name):
                self._render_ok = True
            elif os.environ.get("DISPLAY") or sys.platform == "darwin":
                env_kwargs = {**(env_kwargs or {}), "render_mode": "human"}
                self._render_ok = True
            else:
                logger.warning(
                    "rendering requested but no display is available; "
                    "running headless"
                )
        self.config = config or SACConfig()
        self.env_name = env_name
        self.seed = seed
        if (
            self.config.algorithm == "sac"
            and not self.config.learn_alpha
            and (
                env_name.startswith("dm:")
                or env_name == "DeepMindWallRunner-v0"
            )
        ):
            # Scope: dm_control-backed envs only — other visual envs
            # (e.g. PixelPendulum wrapping Pendulum-v1) pay
            # gymnasium-scale rewards where fixed alpha works fine.
            # dm_control tasks pay [0, 1]-per-step rewards; the fixed
            # alpha=0.2 entropy bonus (the reference's default, ref
            # main.py:148) is the same order of magnitude and swamps
            # them — measured on dm:cheetah:run at 100k steps: eval 0.5
            # with fixed alpha vs 228.0 with --learn-alpha true
            # (PARITY.md). The reference fails this way silently.
            logger.warning(
                "%s pays dm_control-scale rewards ([0, 1] per step) and "
                "SAC is running with a FIXED entropy temperature "
                "alpha=%g; the entropy bonus is likely to swamp the "
                "reward signal (measured: eval 0.5 vs 228.0 on "
                "dm:cheetah:run at 100k steps). Pass --learn-alpha true "
                "to tune the temperature automatically.",
                env_name,
                self.config.alpha,
            )
        self.mesh = mesh if mesh is not None else make_mesh()
        # One env per LOCAL dp slice: each host simulates only the envs
        # feeding replay shards it can address (multi-host: no
        # num_processes-fold redundant physics; single-host: all
        # slices). Seeds/stat streams use the GLOBAL slice index so a
        # run is invariant to how slices map onto hosts.
        self.population = self.config.population
        if self.population > 1:
            # Population mode: one env per MEMBER (members shard over
            # the dp axis inside the vmapped burst; the host loop still
            # steps every member's env — single-process only, enforced
            # by PopulationLearner).
            self.n_envs, self._env_offset = self.population, 0
        else:
            self.n_envs, self._env_offset = local_dp_info(self.mesh)
        self.tracker = tracker
        self.checkpointer = checkpointer
        # Resilience (docs/RESILIENCE.md): the divergence sentinel
        # validates every epoch boundary (and gates every checkpoint,
        # so "latest checkpoint" is always "last-good"); the preemption
        # guard, when given, is polled at window/epoch boundaries for
        # the emergency-save-and-requeue path.
        self.sentinel = (
            DivergenceSentinel(max_rollbacks=self.config.max_rollbacks)
            if self.config.sentinel
            else None
        )
        self.preemption = preemption
        self._resume_step: int | None = None
        # Observability (telemetry/, docs/OBSERVABILITY.md): phase spans,
        # HBM watermarks and a JSONL event stream. None when disabled —
        # every hot-path instrumentation site is then a single
        # `rec is not None` pointer check, and the metrics dict is
        # byte-identical to an uninstrumented build.
        if telemetry is None and self.config.telemetry:
            telemetry = TelemetryRecorder(
                run_dir=(
                    tracker.run_dir
                    if tracker is not None
                    and getattr(tracker, "enabled", False)
                    and is_coordinator()
                    else None
                ),
                sink_max_bytes=int(self.config.telemetry_max_mb * 1e6),
            )
        self.telemetry = telemetry
        if telemetry is not None:
            # The spans the window's own functions open (stage,
            # place_chunk, burst_dispatch, drain) are charged to the
            # process's current recorder: this one, until close().
            spans.install(telemetry)
        # Which way this Trainer's chunks crossed to the device is
        # counted where the choice is made, for the whole process; the
        # epoch event reports what was added since here.
        self._chunk_transfers_at_start = dict(chunk_block.transfers)
        # Compute-cost attribution (telemetry/costmodel.py): with
        # telemetry on, the first update epoch registers the burst's
        # XLA cost analysis (one extra lowering+compile, off the step
        # path) and every later epoch reports achieved-FLOPs / roofline
        # metrics against the burst's span time (dispatch, the wait for
        # it in param_sync, drain). telemetry=None leaves all of this
        # untouched — no lowering, no extra keys.
        self._cost_registered = False
        self._peaks = None  # costmodel.Peaks, detected lazily
        # Learning-health diagnostics (diagnostics/, docs/OBSERVABILITY
        # .md): with a tier on, per-burst in-graph metric rows are
        # collected (device arrays — no sync until the epoch drain),
        # reduced at epoch end, streamed to metrics.jsonl/telemetry,
        # fed through the early-warning monitor into the sentinel, and
        # the XLA recompilation watchdog attributes every compile to
        # its dispatch site. "off" leaves all of this as None — zero
        # hot-path work and byte-identical metric keys.
        if self.config.diagnostics != "off":
            from torch_actor_critic_tpu.diagnostics import (
                EarlyWarningMonitor,
                get_watchdog,
                make_td_histogram,
                reduce_metric_rows,
            )

            self.monitor = EarlyWarningMonitor()
            self.td_hist = make_td_histogram()
            self._reduce_rows = reduce_metric_rows
            self.watchdog = get_watchdog().install()
            self._wd_anomalies_seen = len(self.watchdog.snapshot()["anomalies"])
            self._first_update_epoch: int | None = None
        else:
            self.monitor = None
            self.td_hist = None
            self.watchdog = None
        self._diag_rows: t.List[dict] = []
        # --emit-bundle (aot/, docs/SERVING.md "Cold start"): one-shot
        # latch, independent of the diagnostics tier (the watchdog's
        # _first_update_epoch only exists with diagnostics on).
        self._bundle_emitted = not self.config.emit_bundle
        # Run-wide observability plane (obs/, docs/OBSERVABILITY.md
        # "Run-wide plane"): built here but STARTED at train() entry,
        # because fleet subclasses wire their transport/staging sources
        # after super().__init__ returns — an early scrape would count
        # failures against planes that are still being constructed.
        # None when off: no thread, no socket, no obs/ metric keys.
        self.obs = None
        self._obs_last_metrics: t.Dict[str, t.Any] = {}
        if self.config.obs:
            from torch_actor_critic_tpu.obs import ObsCollector, load_rules

            self.obs = ObsCollector(
                interval_s=self.config.obs_interval_s,
                run_dir=(
                    tracker.run_dir
                    if tracker is not None
                    and getattr(tracker, "enabled", False)
                    and is_coordinator()
                    else None
                ),
                port=self.config.obs_port,
                rules=(
                    load_rules(self.config.slo_config)
                    if self.config.slo_config else None
                ),
                telemetry=self.telemetry,
                max_bytes=int(self.config.telemetry_max_mb * 1e6),
            )
            self.obs.add_source("learner", self._obs_learner_source)
            for pair in filter(None, self.config.obs_scrape.split(",")):
                name, _, url = pair.partition("=")
                self.obs.add_source(name.strip(), url.strip())

        # One env per dp mesh slice, stepped as a pool: sequential
        # in-process by default, parallel worker processes over the
        # native shared-memory runtime with `parallel_envs`.
        # history_len > 1 selects the sequence-policy stack via the
        # HistoryEnv name suffix (string-only, so it reaches native
        # pool workers unchanged).
        pool_name = (
            f"{env_name}|history:{self.config.history_len}"
            if self.config.history_len > 1
            else env_name
        )
        self.pool = make_env_pool(
            pool_name,
            self.n_envs,
            base_seed=seed + 10000 * self._env_offset,
            parallel=self.config.parallel_envs,
            timeout_s=self.config.env_timeout_s,
            start_method=self.config.env_start_method,
            env_kwargs=env_kwargs,
        )
        self.visual = is_visual_env(env_name)
        flat_obs = (
            not self.visual and len(self.pool.obs_spec.shape) == 1
        )
        if (
            self.config.normalize_observations
            and flat_obs
            and self.population > 1
        ):
            # One Welford estimate PER MEMBER: pooling would couple the
            # independent seeds through their input scaling (this
            # combination used to be rejected outright).
            self.normalizer = PerMemberNormalizer(
                self.population, self.pool.obs_spec.shape[0]
            )
        elif self.config.normalize_observations and flat_obs:
            self.normalizer = WelfordNormalizer(self.pool.obs_spec.shape[0])
        elif self.config.normalize_observations and self.population > 1:
            # Visual/history population: per-member feature statistics
            # are not wired — run unnormalized rather than pool.
            logger.warning(
                "normalize_observations=True ignored for population > 1 "
                "with obs spec %s: only flat observations have a "
                "per-member normalizer; running unnormalized",
                self.pool.obs_spec,
            )
            self.normalizer = IdentityNormalizer()
        elif self.config.normalize_observations and isinstance(
            self.pool.obs_spec, MultiObservation
        ):
            # Visual envs: Welford the proprioceptive `features` leaf
            # (heterogeneous physical scales, e.g. the wall-runner's
            # 168 dims); frames keep their own whitening path
            # (normalize_pixels / DrQ) and uint8 replay layout.
            self.normalizer = FeaturesNormalizer(
                self.pool.obs_spec.features.shape[0]
            )
        else:
            # Welford tracks per-feature stats of flat vectors; history
            # stacks run unnormalized (windows replay PAST observations
            # — normalizing them with future statistics would leak).
            if self.config.normalize_observations:
                logger.warning(
                    "normalize_observations=True ignored: obs spec %s is "
                    "a history stack, which runs unnormalized",
                    self.pool.obs_spec.shape,
                )
            self.normalizer = IdentityNormalizer()

        actor_def, critic_def = build_models(self.config, self.pool)
        # Kept under the historical `sac` attribute name: it is "the
        # learner" everywhere downstream (mesh wrapper, bench, tests).
        self.sac = make_learner(
            self.config, actor_def, critic_def, self.pool.act_dim
        )
        if self.population > 1:
            from torch_actor_critic_tpu.parallel.population import (
                PopulationLearner,
            )

            self.dp = PopulationLearner(self.sac, self.population, self.mesh)
        else:
            self.dp = DataParallelSAC(self.sac, self.mesh)

        # Actor/learner split (Podracer-style): action selection runs on
        # the host CPU backend against a param mirror refreshed once per
        # update window, so the env loop never blocks on accelerator
        # dispatch latency (one small-param transfer per ~50 steps
        # instead of one device round trip per env step).
        self._host_device = None
        if self.config.host_actor:
            try:
                self._host_device = jax.local_devices(backend="cpu")[0]
            except RuntimeError as e:
                # JAX_PLATFORMS names the accelerator alone: the CPU
                # backend is not there to act on. Say so — a silent
                # switch of host_actor would change what is measured.
                raise RuntimeError(
                    "host_actor=True needs the CPU backend beside the "
                    f"accelerator, but JAX_PLATFORMS="
                    f"{os.environ.get('JAX_PLATFORMS')!r} leaves it out "
                    f"({e}); add it (JAX_PLATFORMS=tpu,cpu), unset the "
                    "variable, or pass --host-actor false"
                ) from e
        self._host_params = None  # refreshed lazily after each burst
        if self.config.host_actor:
            # The mirror compiles for the host CPU; a sequence actor's
            # auto-dispatched attention would bake in the Pallas TPU
            # kernel (no CPU lowering), so clone it onto the portable
            # XLA attention path — same params, different kernel.
            host_actor_def = self.sac.actor_def
            if hasattr(host_actor_def, "attention_fn"):
                from torch_actor_critic_tpu.models.sequence import xla_attention

                host_actor_def = host_actor_def.clone(attention_fn=xla_attention)

            if self.population > 1:
                # Member i's policy acts on observation row i, with a
                # per-member key fan-out (mirrors
                # PopulationLearner.select_action on the host backend).
                n_members = self.population

                def _select(params, obs, key, deterministic=False):
                    keys = jax.random.split(key, n_members)

                    def one(p, o, k):
                        action, _ = host_actor_def.apply(
                            p, o, k,
                            deterministic=deterministic, with_logprob=False,
                        )
                        return action

                    return jax.vmap(one)(params, obs, keys)
            else:

                def _select(params, obs, key, deterministic=False):
                    action, _ = host_actor_def.apply(
                        params, obs, key,
                        deterministic=deterministic, with_logprob=False,
                    )
                    return action

            self._host_select = jax.jit(
                _select, static_argnames=("deterministic",), backend="cpu"
            )
        else:
            self._host_select = None
        # One-transfer param mirroring: every device->host fetch pays a
        # fixed cost, so params are flattened into a single buffer
        # on-device and fetched with ONE transfer, then unflattened
        # host-side.
        self._flatten_params = jax.jit(
            lambda p: jnp.concatenate(
                [jnp.ravel(x) for x in jax.tree_util.tree_leaves(p)]
            )
        )
        self._param_struct = None  # (treedef, shapes, sizes) cache

        key = jax.random.key(seed)
        if self.config.host_actor:
            key = jax.device_put(key, self._host_device)
        self._act_key, init_key = jax.random.split(key)
        example_obs = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.pool.obs_spec
        )
        # init must run on the default (accelerator) backend even when
        # the acting key lives host-side — a CPU-committed key would
        # drag eager module init onto CPU, where a sequence actor's
        # Pallas attention cannot lower. local_devices, not devices:
        # global device 0 is unaddressable on non-coordinator hosts.
        init_key = jax.device_put(init_key, jax.local_devices()[0])
        self.state = self.dp.init_state(init_key, example_obs)
        if self.population > 1:
            # Each member is an independent run with its own FULL
            # buffer_size ring — total HBM scales with the population.
            warn_if_buffer_exceeds_hbm(
                self.config.buffer_size * self.population,
                self.pool.obs_spec, self.pool.act_dim,
                advice="reduce --buffer-size or --population",
            )
            self.buffer = self.dp.init_buffer(
                self.config.buffer_size, self.pool.obs_spec, self.pool.act_dim
            )
        else:
            # Divide by the GLOBAL dp size (n_envs is the local slice
            # count): total replay capacity is buffer_size regardless of
            # how many hosts the slices are spread over.
            per_dev_capacity = max(
                self.config.buffer_size // self.mesh.shape["dp"], 1
            )
            warn_if_buffer_exceeds_hbm(
                per_dev_capacity, self.pool.obs_spec, self.pool.act_dim,
                sp=self.dp.effective_sp,
                advice="reduce --buffer-size (or raise dp)",
            )
            self.buffer = init_sharded_buffer(
                per_dev_capacity, self.pool.obs_spec, self.pool.act_dim,
                self.mesh, sp=self.dp.effective_sp,
            )
        # Tiered replay (replay/, docs/REPLAY.md): host-RAM/disk tiers
        # shadowing the device ring, with counted spill/refill flows.
        # Default-off — None, and every hot path is exactly historical
        # (config validation rejects tiers with population > 1).
        self.tiered = None
        self._prefetcher = None
        if self.config.replay_tiers != "off":
            from torch_actor_critic_tpu.replay import (
                RefillPrefetcher,
                build_tiered_replay,
            )

            self.tiered = build_tiered_replay(
                self.config, self.pool.obs_spec, self.pool.act_dim,
                # The device ring's REAL total (per-shard capacity
                # rounds down, then multiplies back over dp) — the
                # shadow ring must evict exactly when the device ring
                # overwrites.
                hbm_capacity=(
                    max(self.config.buffer_size // self.mesh.shape["dp"], 1)
                    * self.mesh.shape["dp"]
                ),
                act_limit=float(getattr(self.pool, "act_limit", 1.0)),
                run_dir=(
                    str(self.tracker.run_dir)
                    if self.tracker is not None and self.tracker.enabled
                    else None
                ),
                seed=seed,
            )
            if self.config.replay_refill > 0:
                self._prefetcher = RefillPrefetcher(
                    self.tiered, self.n_envs, self.config.replay_refill,
                    async_prefetch=self.config.replay_prefetch,
                )
        self.start_epoch = 0
        # Current training epoch, maintained by the train loop (the
        # decoupled staging gate reads it as the staleness reference).
        self._epoch = 0
        # Runtime transfer sanitizer (--sanitize, docs/ANALYSIS.md):
        # False by default — every guarded site is then one bool check
        # and the dispatch path is exactly the historical one.
        self._sanitize = self.config.sanitize == "on"

    def _sanitized(self):
        """Device-phase guard context: ``jax.transfer_guard("disallow")``
        under ``--sanitize on`` (implicit host<->device transfers on
        the burst/drain path become hard failures; the explicit
        ``device_put``/``device_get`` placements the trainer already
        uses are exempt), a no-op otherwise."""
        if self._sanitize:
            return jax.transfer_guard("disallow")
        return contextlib.nullcontext()

    # ------------------------------------------------------------ helpers

    def _normalize(self, obs, update: bool, member: int | None = None):
        if isinstance(self.normalizer, IdentityNormalizer):
            return obs
        return self.normalizer.normalize(obs, update=update, member=member)

    def _policy_actions(self, obs_batch, deterministic=False) -> np.ndarray:
        self._act_key, sub = jax.random.split(self._act_key)
        if self.config.host_actor:
            if self._host_params is None:
                self._host_params = self._sync_host_params()
            actions = self._host_select(
                self._host_params, obs_batch, sub, deterministic=deterministic
            )
        else:
            actions = self.dp.select_action(
                self.serve_actor_params(), obs_batch, sub,
                deterministic=deterministic,
            )
        return np.asarray(actions)

    def _sync_host_params(self):
        """The mirror refresh as a phase of its own (``param_sync``): it
        waits for the burst that wrote the parameters, so inside an
        epoch it is charged apart from the phase it interrupts (``act``,
        or ``burst_dispatch`` under ``actor_param_lag``)."""
        rec = self.telemetry
        if rec is None or rec.open_phase < 0:  # off, or outside an epoch
            return self._fetch_params_single_transfer()
        prev = rec.begin(_PH_SYNC)
        params = self._fetch_params_single_transfer()
        rec.begin(prev)
        return params

    def _fetch_params_single_transfer(self):
        """Mirror actor params to the host with one device->host copy."""
        params = self.serve_actor_params()
        if self._param_struct is None:
            leaves, treedef = jax.tree_util.tree_flatten(params)
            shapes = [x.shape for x in leaves]
            sizes = [int(np.prod(s)) for s in shapes]
            self._param_struct = (treedef, shapes, sizes)
        treedef, shapes, sizes = self._param_struct
        flat = np.asarray(self._flatten_params(params))  # one transfer
        splits = np.split(flat, np.cumsum(sizes)[:-1])
        leaves = [s.reshape(shape) for s, shape in zip(splits, shapes)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _build_chunk(self, staging) -> Batch:
        """``staging`` is a list (one entry per lockstep step) of batched
        transition tuples with leading axis ``n_envs``; the chunk stacks
        them to leading axes ``(n_envs, window)``.

        The leaves are views of ONE freshly allocated block
        (:func:`~torch_actor_critic_tpu.parallel.chunk_block.block_views`),
        written here once and never again, so that
        ``shard_chunk_from_local`` can move the window in one transfer.
        Values, dtypes and shapes are what stacking leaf by leaf gives.
        Reads nothing of ``self``. The window's ``stage`` span."""
        with spans.span(spans.STAGE):
            first = Batch(*staging[0][:5])
            # One column a leaf: that leaf at every staged step, in the
            # order the Batch flattens (its fields' order is the tuple's).
            columns = [
                [np.asarray(x) for x in column]
                for column in zip(*(
                    jax.tree_util.tree_leaves(tuple(tr[:5])) for tr in staging
                ))
            ]
            # rewards and done are float32 in the chunk whatever was staged.
            as_f32 = jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(lambda _: False, first).replace(
                    rewards=True, done=True
                )
            )
            # A leaf lies in the block as its rows lie in memory (rows fetched
            # from a device are not in C order), the window axis before them:
            # writing a step is then a plain copy, as np.stack's was.
            specs = []
            for column, f32 in zip(columns, as_f32):
                row = column[0]
                if any(x.shape != row.shape for x in column):  # as np.stack refuses
                    raise ValueError(
                        "all staged steps of a leaf must have the same shape, "
                        f"got {sorted({x.shape for x in column})}"
                    )
                specs.append((
                    (len(staging),) + row.shape[1:],
                    np.float32 if f32
                    else np.result_type(*{x.dtype for x in column}),
                    (0,) + tuple(
                        1 + axis for axis in chunk_block.memory_order(row[0])
                    ),
                ))
            views = chunk_block.block_views(columns[0][0].shape[0], specs)
            for view, column in zip(views, columns):
                for step, rows in enumerate(column):
                    view[:, step] = rows
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(first), views
            )

    # Staging seams (overridden by decoupled/learner.py, where the host
    # list becomes a bounded StagingBuffer with backpressure and the
    # bounded-staleness admission gate): the base trainer's lockstep
    # semantics are exactly "append, then drain a full window".

    def _stage(self, staging: t.List[tuple], transition: tuple) -> None:
        """Admit one batched transition into the staging path."""
        staging.append(transition)

    def _drain_window(self, staging: t.List[tuple]):
        """Drain one update window into a local chunk, or None when the
        staging path cannot fill a fixed-size window this boundary (the
        decoupled gate may have dropped stale transitions; the window
        is then skipped — chunk shapes, and the jit cache, never
        vary). The base trainer always has exactly one window staged."""
        chunk = self._build_chunk(staging)
        del staging[:]
        return chunk

    def _maybe_refill(self) -> None:
        """Window-boundary host→HBM refill (replay/, docs/REPLAY.md):
        take a staged ``(n_envs, replay_refill)`` chunk off the
        prefetcher (already sampled on the background thread when
        ``replay_prefetch``), place it exactly like an env chunk and
        push it through the dedicated ``replay/prefetch_push`` program.
        Refilled rows re-enter the waterfall as fresh pushes (counted
        ``refill_rows_total``), keeping the conservation invariant
        closed."""
        local = self._prefetcher.poll_local_chunk()
        if local is None:
            return
        chunk = shard_chunk_from_local(
            local, self.mesh, sp=self.dp.effective_sp,
        )
        abstract = None
        if self.telemetry is not None and not self._prefetcher._cost_registered:
            try:
                abstract = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    (self.buffer, chunk),
                )
            except Exception:  # noqa: BLE001 — cost accounting must
                # never break training
                abstract = None
        with self._sanitized():
            self.buffer = self._prefetcher.push_into(self.buffer, chunk)
        if abstract is not None:
            self._prefetcher.maybe_register_cost(
                abstract[0], abstract[1],
                devices=int(self.mesh.devices.size),
            )
        from torch_actor_critic_tpu.replay import batch_to_rows

        self.tiered.note_refill(batch_to_rows(local, n_lead=2))

    def _epoch_boundary_hook(
        self, epoch: int, sentinel_ok: bool, saved: bool,
        last_metrics: dict, rec,
    ) -> None:
        """Subclass seam, called once per epoch after the sentinel and
        checkpoint save and before metrics logging (the decoupled
        trainer publishes the epoch to the serving registry and merges
        staging/degradation metrics here)."""

    # --------------------------------------------------- run-wide obs plane

    def _obs_learner_source(self) -> dict:
        """The learner plane's snapshot for the ObsCollector: telemetry
        phase aggregates, any subclass metrics_snapshot (the decoupled
        staging/transport view), and the numeric columns of the last
        logged epoch — the paths SLO rules address as
        ``learner.metrics.<key>``."""
        out: t.Dict[str, t.Any] = {}
        if self.telemetry is not None:
            out["telemetry"] = self.telemetry.snapshot()
        snap = getattr(self, "metrics_snapshot", None)
        if callable(snap):
            out.update(snap())
        metrics = self._obs_last_metrics
        if metrics:
            out["metrics"] = {
                k: v for k, v in metrics.items()
                if isinstance(v, (int, float, bool))
            }
        return out

    def extra_trace_events(self) -> t.List[dict]:
        """Cross-process trace events beyond this process's own
        recorder buffers — the fleet trainer returns its staging-plane
        spans (transport ingest, drain windows, actor push files) here
        so ``--trace-export`` merges every plane into one timeline."""
        return []

    # ------------------------------------------------------ cost accounting

    def _note_epoch_cost(self, rec, last_metrics, n_bursts, epoch):
        """Per-epoch compute-cost attribution (telemetry on only):
        register the burst program's XLA cost analysis on the first
        update epoch, then report achieved-FLOPs / arithmetic
        intensity / MFU / roofline class against the epoch's
        burst+drain span time — `cost/` columns in metrics.jsonl and
        one `cost` event per epoch in telemetry.jsonl."""
        if n_bursts == 0:
            return
        from torch_actor_critic_tpu.telemetry.costmodel import (
            Peaks,
            get_cost_registry,
            roofline,
        )

        registry = get_cost_registry()
        name = self.dp.burst_cost_name
        if not self._cost_registered:
            # Once per run, off the step path. One extra lowering (and
            # backend compile, for post-fusion byte honesty) of the
            # already-built burst; failures degrade to "no cost keys".
            self._cost_registered = True
            fn = self.dp.burst_jit(self.config.updates_per_window)
            if fn is not None and self.dp.burst_abstract:
                # Whole-mesh program -> per-device cost: the lowered
                # analysis spans every dp/fsdp/tp participant, so the
                # registered FLOPs divide by the mesh size and MFU
                # stays honest against one chip's peak.
                registry.register_jit(
                    name, fn, *self.dp.burst_abstract,
                    devices=int(self.mesh.devices.size),
                )
        cost = registry.get(name)
        if cost is None:
            return
        if self._peaks is None:
            self._peaks = Peaks.detect()
        burst_s = (
            rec.timer.sums[_PH_BURST] + rec.timer.sums[_PH_SYNC]
            + rec.timer.sums[_PH_DRAIN]
        )
        rl = roofline(
            cost, burst_s, calls=n_bursts, peaks=self._peaks,
            compute_dtype=self.config.compute_dtype,
        )
        last_metrics["cost/update_burst_gflops"] = cost["flops"] / 1e9
        last_metrics["cost/update_burst_achieved_gflops_s"] = (
            rl.get("achieved_flops_per_sec", 0.0) / 1e9
        )
        if "arithmetic_intensity" in rl:
            last_metrics["cost/update_burst_ai"] = rl[
                "arithmetic_intensity"
            ]
        if "mfu" in rl:
            last_metrics["cost/update_burst_mfu"] = rl["mfu"]
        if "bound" in rl:
            last_metrics["cost/update_burst_compute_bound"] = float(
                rl["bound"] == "compute"
            )
        rec.event(
            "cost", epoch=int(epoch), programs={name: rl},
            device_kind=self._peaks.device_kind,
            compute_dtype=self.config.compute_dtype,
        )

    # --------------------------------------------------------- resilience

    def _epoch_seed(self, epoch: int, i: int) -> int:
        """Env seed for slice ``i`` at the start of ``epoch`` — a pure
        function of (run seed, epoch, global slice), so epochs are
        replayable units: a resumed run reseeds its fresh envs exactly
        as the uninterrupted run reseeded its live ones at the same
        boundary (docs/RESILIENCE.md). At epoch 0 this reduces to the
        historical ``seed + 10000 * slice`` scheme."""
        return (
            self.seed
            + 1_000_003 * epoch
            + 10_000 * (self._env_offset + i)
        )

    def _checkpoint_extra(self, step: int) -> dict:
        """The JSON metadata saved beside the arrays; subclasses extend
        (the decoupled trainer adds staging counters and the serving
        plane's PRNG state, decoupled/learner.py)."""
        extra = {
            "config": self.config.to_json(),
            "normalizer": self.normalizer.state_dict(),
            "step": int(step),
            "act_key": np.asarray(
                jax.random.key_data(self._act_key)
            ).astype(np.uint32).tolist(),
        }
        if self.tiered is not None:
            # Tier counters only (JSON-small): disk chunks persist
            # themselves on disk; host-RAM residents are declared lost
            # on restore (counted, conservation-clean) rather than
            # serialized into every checkpoint.
            extra["replay_tiers"] = self.tiered.meta_state()
        return extra

    def _checkpoint_arrays(self):
        """Extra array pytree for the checkpoint ``arrays`` item (the
        decoupled trainer persists its staged-but-undrained transitions
        here); None = no item."""
        return None

    def _save_checkpoint(self, epoch: int, step: int, wait: bool = False):
        """One checkpoint = TrainState + buffer + the host-loop state a
        TrainState cannot carry: the lockstep step counter (warmup and
        update-gate thresholds continue, instead of re-randomizing
        ``start_steps`` actions on every resume) and the acting PRNG
        key (the exploration stream continues bitwise)."""
        self.checkpointer.save(
            epoch,
            self.state,
            self.buffer,
            extra=self._checkpoint_extra(step),
            wait=wait,
            arrays=self._checkpoint_arrays(),
        )

    def _emit_warm_start_bundle(self, epoch: int) -> None:
        """``--emit-bundle``: build the serve-plane warm-start bundle
        next to the Orbax checkpoint (aot/bundle.py) at the first
        update epoch — the earliest moment real actor params exist.
        One-shot and non-fatal: a failed build is logged and never
        retried (training must not pay the build every epoch), and the
        checkpoint itself is untouched either way."""
        self._bundle_emitted = True
        if self.checkpointer is None or not is_coordinator():
            return
        from torch_actor_critic_tpu.aot.bundle import (
            default_bundle_dir,
            emit_bundle,
        )

        try:
            params = jax.device_get(self.serve_actor_params())
            bundle = emit_bundle(
                self.checkpointer.directory,
                self.sac.actor_def,
                self.pool.obs_spec,
                params,
                max_batch=self.config.bundle_max_batch,
            )
            logger.info(
                "epoch %d: warm-start bundle emitted at %s "
                "(%d programs, %d cache entries) — serve.py "
                "--warm-start auto boots compile-free",
                epoch, bundle.root, len(bundle.programs()),
                bundle.manifest.get("cache_entries", 0),
            )
        except Exception:  # noqa: BLE001 — the bundle is an artifact,
            # not training state; a failed build costs the next serve
            # worker its cold start, never the run
            logger.exception(
                "epoch %d: warm-start bundle emission at %s failed; "
                "training continues (serve workers will live-compile)",
                epoch, default_bundle_dir(self.checkpointer.directory),
            )

    def serve_actor_params(self):
        """The actor-param subtree a serve worker would restore from a
        checkpoint of the current state — what the warm-start bundle
        must be built against for its avals to match at load time, and
        what this trainer's own actors act with: ``policy_params`` of the
        state (the actor's tree; with a shared history trunk, that trunk
        from the critic's tree under the actor's heads)."""
        from torch_actor_critic_tpu.models.sequence import policy_params

        return policy_params(self.state.actor_params, self.state.critic_params)

    def _load_checkpoint(
        self, epoch: int | None = None, include_buffer: bool = True
    ) -> dict:
        """Restore trainer state in place from the checkpointer; shared
        by :meth:`restore` (resume) and :meth:`_rollback` (divergence
        recovery). Returns the checkpoint metadata."""
        # Validate the algorithm family from metadata BEFORE the array
        # restore: a TD3 state has a target-actor subtree a SAC trainer
        # lacks (and vice versa), which would otherwise surface as an
        # opaque Orbax tree-structure error. The probe is reused by the
        # restore below (no second metadata round-trip).
        meta_probe = self.checkpointer.peek_meta(epoch)
        if meta_probe.get("config"):
            saved_algo = SACConfig.from_json(meta_probe["config"]).algorithm
            if saved_algo != self.config.algorithm:
                raise ValueError(
                    f"checkpoint was written by algorithm={saved_algo!r} "
                    f"but this trainer is configured for "
                    f"{self.config.algorithm!r}; pass --algorithm "
                    f"{saved_algo} to resume it"
                )
        abstract_arrays = self._checkpoint_abstract_arrays(meta_probe)
        out = self.checkpointer.restore(
            jax.tree_util.tree_map(lambda x: x, self.state),
            self.buffer if include_buffer else None,
            epoch=epoch,
            meta_probe=meta_probe,
            abstract_arrays=abstract_arrays,
        )
        if abstract_arrays is None:
            state, buffer, meta = out
            arrays = None
        else:
            state, buffer, meta, arrays = out
        self.state = state
        self._host_params = None  # mirror is stale
        if buffer is not None:
            self.buffer = buffer
        if "normalizer" in meta and meta["normalizer"]:
            self.normalizer.load_state_dict(meta["normalizer"])
        if meta.get("act_key"):
            key = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(meta["act_key"], dtype=np.uint32))
            )
            if self.config.host_actor:
                key = jax.device_put(key, self._host_device)
            self._act_key = key
        self._restore_extras(meta, arrays)
        if self.tiered is not None and meta.get("replay_tiers"):
            # Resume re-anchors the tier counters; the disk tier
            # already re-opened its chunk files from the manifest at
            # construction (replay/diskstore.py).
            self.tiered.load_meta(meta["replay_tiers"])
        return meta

    def _checkpoint_abstract_arrays(self, meta_probe: dict):
        """Abstract pytree for the checkpoint's extra ``arrays`` item,
        derived from the metadata probe (the decoupled trainer sizes
        its staged-transition restore from it); None = not requested."""
        return None

    def _restore_extras(self, meta: dict, arrays) -> None:
        """Subclass seam: apply checkpoint metadata/arrays beyond the
        base trainer's (decoupled staging contents, serving-plane PRNG,
        publish counters — decoupled/learner.py)."""

    def _rollback(self) -> int:
        """Divergence recovery: restore the newest (sentinel-validated)
        checkpoint and report its epoch. Checkpoints are only ever
        written after the sentinel passes, so the newest one is by
        construction the last-good state — params, optimizer moments
        AND the replay ring (a poisoned ring would re-diverge on the
        next unlucky sample)."""
        if self.checkpointer is None or self.checkpointer.latest_epoch() is None:
            raise TrainingDiverged(
                "training state is non-finite and there is no checkpoint "
                "to roll back to (no checkpointer configured, or "
                "divergence before the first save)"
            )
        meta = self._load_checkpoint(epoch=None, include_buffer=True)
        return int(meta["epoch"])

    # -------------------------------------------------------------- train

    def train(self, render: bool = False) -> dict:
        cfg = self.config
        n = self.n_envs
        # Loop-local alias: the telemetry checks below compile to one
        # predicted `is not None` branch per phase mark when disabled.
        rec = self.telemetry
        # Start the obs scraper here, not in __init__: every subclass
        # (fleet transport, decoupled staging) has finished wiring its
        # sources by the time super().train() runs.
        if self.obs is not None:
            self.obs.start()

        # Epoch-boundary seeds (resilience): a resumed run's fresh envs
        # reset exactly as the uninterrupted run's live envs were
        # reseeded at the same epoch boundary. epoch_reseed=False keeps
        # the historical flat scheme (epoch term zero).
        obs = self._normalize(
            self.pool.reset_all(
                [
                    self._epoch_seed(
                        self.start_epoch if cfg.epoch_reseed else 0, i
                    )
                    for i in range(n)
                ]
            ),
            update=True,
        )
        ep_ret = np.zeros(n)
        ep_len = np.zeros(n, np.int64)
        staging: t.List[tuple] = []

        # `step` counts LOCKSTEP iterations: every env (= every dp slice)
        # has taken `step` steps — identical to the reference's per-rank
        # counter (each MPI rank steps its one env, ref :226). Thus
        # start_steps/update_after are per-env thresholds and total data
        # volume scales with dp exactly as the reference's scales with
        # worker count (1000 warmup steps × N ranks there, × n_envs
        # here). Documented in PARITY.md §counters.
        # A resumed run CONTINUES the counter (checkpoint meta carries
        # it) instead of restarting at 0 — restarting would re-randomize
        # start_steps actions and re-gate update_after on every resume,
        # making each preemption cost a full warmup.
        step = (
            self._resume_step
            if self._resume_step is not None
            else self.start_epoch * cfg.steps_per_epoch
        )
        last_metrics: dict = {}
        episode_rewards: list = []
        episode_lengths: list = []
        # Population mode keeps per-member return curves too — N seeds
        # means N learning curves, not one average.
        member_rewards: t.List[list] = [[] for _ in range(n)]

        try:
            import tqdm

            epoch_iter = tqdm.trange(
                self.start_epoch,
                self.start_epoch + cfg.epochs,
                ncols=0,
                initial=self.start_epoch,
            )
        except ImportError:  # pragma: no cover
            epoch_iter = range(self.start_epoch, self.start_epoch + cfg.epochs)

        t_epoch = time.time()
        for e in epoch_iter:
            self._epoch = e
            if rec is not None:
                rec.epoch_begin(e)
                rec.begin(_PH_ACT)
            losses_q, losses_pi = [], []
            env_steps_this_epoch = 0

            for t_ in range(cfg.steps_per_epoch):
                # --- action selection (ref :227-236) ---
                if step < cfg.start_steps:
                    actions = self.pool.sample_actions()
                else:
                    actions = self._policy_actions(obs)
                if rec is not None:
                    rec.begin(_PH_ENV)

                # --- env step (one lockstep pool dispatch) + bookkeeping
                # (ref :238-260), batch numpy ops across envs — no
                # per-env Python in the common path ---
                epoch_ended = t_ == cfg.steps_per_epoch - 1
                next_obs, rewards, terms, truncs = self.pool.step(actions)
                next_obs = self._normalize(next_obs, update=True)
                terms = np.asarray(terms, bool)
                truncs = np.asarray(truncs, bool)
                rewards = np.asarray(rewards, np.float32)
                ep_len += 1
                ep_ret += rewards
                # max_ep_len bypass (ref :241): an episode cut by the
                # length cap is a truncation — do not zero the bootstrap.
                hit_cap = ep_len >= cfg.max_ep_len
                done_for_buffer = (terms & ~hit_cap).astype(np.float32)
                # Stage whole batched pytrees. next_obs is copied because
                # episode resets overwrite its rows in place below; obs
                # is never mutated after this point.
                self._stage(
                    staging,
                    (
                        obs,
                        actions,
                        rewards,
                        jax.tree_util.tree_map(np.array, next_obs),
                        done_for_buffer,
                    ),
                )

                if render and self._render_ok and is_coordinator():
                    self.pool.render_at(0)

                ended = terms | truncs | hit_cap
                if epoch_ended:
                    ended = np.ones_like(ended)
                if ended.any():
                    for i in map(int, np.flatnonzero(ended)):
                        episode_rewards.append(float(ep_ret[i]))
                        episode_lengths.append(int(ep_len[i]))
                        if self.population > 1:
                            member_rewards[i].append(float(ep_ret[i]))
                        # Epoch-boundary resets are SEEDED (pure
                        # function of seed/epoch/slice) so epochs are
                        # replayable after a preemption resume;
                        # mid-epoch episode ends keep the env's own
                        # stream, which that seed determines.
                        reset_seed = (
                            self._epoch_seed(e + 1, i)
                            if epoch_ended and cfg.epoch_reseed
                            else None
                        )
                        _set_row(
                            next_obs,
                            i,
                            self._normalize(
                                self.pool.reset_at(i, seed=reset_seed),
                                update=True,
                                # Per-member stats under population mode
                                # (env slot i IS member i there).
                                member=(
                                    i if self.population > 1 else None
                                ),
                            ),
                        )
                    ep_ret[ended] = 0.0
                    ep_len[ended] = 0
                obs = next_obs
                env_steps_this_epoch += n

                # --- device window: push or push+update (ref :273-283) ---
                # The window's stage, place_chunk and burst_dispatch
                # are spans of the functions that do the work; each
                # interrupts env_step and hands back to it.
                window_full = (step + 1) % cfg.update_every == 0
                if window_full:
                    local_chunk = self._drain_window(staging)
                # A None chunk (decoupled only: the admission gate
                # dropped staged transitions below one fixed-size
                # window) skips this boundary's device work entirely —
                # the leftover transitions ride into the next window.
                if window_full and local_chunk is not None:
                    if self.tiered is not None:
                        # Spill path (replay/): mirror the chunk into
                        # the host waterfall BEFORE device placement —
                        # host-side numpy only, the device stream is
                        # untouched.
                        self.tiered.ingest_chunk(local_chunk)
                    if self.population > 1:
                        # Leading axis is the member axis; the learner
                        # shards it over dp itself (no mesh resharding).
                        chunk = self.dp.place_chunk(local_chunk)
                    else:
                        chunk = shard_chunk_from_local(
                            local_chunk, self.mesh, sp=self.dp.effective_sp,
                        )
                    if step > cfg.update_after:
                        # (config validation guarantees host_actor here)
                        if cfg.actor_param_lag and step + 1 >= cfg.start_steps:
                            # Mirror the PRE-burst params now (their
                            # buffers are still valid — the burst
                            # donates them) so the next window's acting
                            # never waits on this burst: full
                            # env/learner overlap, one window of param
                            # staleness (opt-in; see SACConfig). While
                            # acting is still random (< start_steps)
                            # nothing reads the mirror — skip the sync.
                            self._host_params = self._sync_host_params()
                        if self.watchdog is None and not self._sanitize:
                            self.state, self.buffer, m = self.dp.update_burst(
                                self.state, self.buffer, chunk,
                                cfg.updates_per_window,
                            )
                        else:
                            # Watchdog source attribution (any
                            # compile in this dispatch belongs to the
                            # burst — post-steady ones are hot-path
                            # recompile anomalies).
                            with contextlib.ExitStack() as stack:
                                if self.watchdog is not None:
                                    stack.enter_context(
                                        self.watchdog.source(
                                            "train/update_burst"
                                        )
                                    )
                                if self._sanitize:
                                    # Sanitize tier: the burst dispatch
                                    # must see device arrays only — an
                                    # implicit transfer here is the
                                    # hot-path bug this tier exists to
                                    # catch (docs/ANALYSIS.md).
                                    stack.enter_context(self._sanitized())
                                self.state, self.buffer, m = (
                                    self.dp.update_burst(
                                        self.state, self.buffer, chunk,
                                        cfg.updates_per_window,
                                    )
                                )
                        if not cfg.actor_param_lag:
                            self._host_params = None  # mirror is stale
                        # Keep device scalars; materialize at epoch end
                        # so bursts stay async behind the env loop.
                        losses_q.append(m["loss_q"])
                        losses_pi.append(m["loss_pi"])
                        if self.monitor is not None:
                            # Everything beyond the two loss series —
                            # diagnostics AND the aux metrics (q_mean,
                            # entropy, alpha, ...) the pre-diagnostics
                            # trainer dropped on the floor. Device
                            # arrays only; fetched once at epoch end.
                            self._diag_rows.append({
                                k: v for k, v in m.items()
                                if k not in ("loss_q", "loss_pi")
                            })
                    elif self._sanitize:
                        with self._sanitized():
                            self.buffer = self.dp.push_chunk(
                                self.buffer, chunk
                            )
                    else:
                        self.buffer = self.dp.push_chunk(self.buffer, chunk)
                    if self._prefetcher is not None:
                        # Refill AFTER the burst: an archival run
                        # (replay_refill=0 has no prefetcher at all)
                        # and the burst's own sample stream stay
                        # bitwise-historical; the refill rows land for
                        # the NEXT window's sampling.
                        self._maybe_refill()
                if rec is not None:
                    # What follows this step and its device window: the
                    # next act, or the epoch's own fetches (the drain
                    # span of utils.sync.drain nests inside them).
                    rec.begin(_PH_DRAIN if epoch_ended else _PH_ACT)

                step += 1

                # Urgent preemption (repeated SIGTERM): the window
                # boundary is the safe step boundary — staging just
                # flushed, the burst dispatched — so checkpoint NOW and
                # unwind. The learner state is lossless; only this
                # epoch's un-stepped env tail is skipped on resume
                # (docs/RESILIENCE.md).
                if (
                    window_full
                    and self.preemption is not None
                    and self.preemption.urgent
                ):
                    if self.checkpointer is not None:
                        if losses_q:
                            drain(losses_q[-1])
                        else:
                            drain(self.buffer.size)
                        self._save_checkpoint(e, step, wait=True)
                    if rec is not None:
                        rec.event("preempted", epoch=e, urgent=True)
                    raise Preempted(epoch=e, urgent=True)

            # --- end of epoch: metrics + checkpoint (ref :285-296) ---
            # Drain queued device work BEFORE taking the epoch time (see
            # utils/sync.py). The last burst's loss chains through every
            # update this epoch. A pure-rollout epoch (no updates yet)
            # drains through buffer.size: size is an output of the same
            # XLA executable as the row scatters and chains through
            # every prior push.
            with self._sanitized():
                if losses_q:
                    drain(losses_q[-1])
                else:
                    drain(self.buffer.size)
            # dt covers the epoch's training work only (loop + drain):
            # t_epoch restarts at the END of the loop body, after the
            # sentinel check and checkpoint save, which report their own
            # sentinel_s/save_s metrics instead of silently deflating
            # the NEXT epoch's env_steps_per_sec/grad_steps_per_sec (the
            # pre-telemetry accounting bug).
            dt = time.time() - t_epoch
            # Multi-host: fold every host's observation statistics into
            # the shared global estimate (no-op single-process) so the
            # replicated networks see identically-normalized inputs on
            # every host.
            self.normalizer.sync_global()
            # Episode stats are aggregated across ALL processes here,
            # once per epoch (ref exchanges them per-step over MPI
            # point-to-point, sac/algorithm.py:262-271 — a hidden
            # per-step barrier we deliberately hoist off the hot loop).
            ep_ret_stats = global_statistics(episode_rewards)
            ep_len_stats = global_statistics(episode_lengths)
            grad_steps_this_epoch = (
                len(losses_q) * cfg.updates_per_window
                * max(self.population, 1)
            )
            last_metrics = {
                "episode_length": ep_len_stats["mean"],
                "reward": ep_ret_stats["mean"],
                "reward_std": ep_ret_stats["std"],
                "reward_min": ep_ret_stats["min"],
                "reward_max": ep_ret_stats["max"],
                # one stacked fetch per loss series, not one per burst
                "loss_q": float(jnp.mean(jnp.stack(losses_q))) if losses_q else 0.0,
                "loss_pi": float(jnp.mean(jnp.stack(losses_pi))) if losses_pi else 0.0,
                "env_steps_per_sec": env_steps_this_epoch / dt,
                "grad_steps_per_sec": grad_steps_this_epoch / dt,
            }
            if self.tiered is not None:
                # Tier observability (replay/): per-tier depths, spill/
                # refill counters and the conservation verdict, plus the
                # MEASURED device-ring bytes (satellite of the config-
                # only HBM budget). Keys appear only with tiers on — the
                # default metrics.jsonl schema is bitwise-historical.
                from torch_actor_critic_tpu.buffer.replay import (
                    nbytes as buffer_nbytes,
                )

                last_metrics.update(self.tiered.metrics())
                if self._prefetcher is not None:
                    last_metrics.update(self._prefetcher.metrics())
                last_metrics["replay/hbm_bytes"] = float(
                    buffer_nbytes(self.buffer)
                )
                if rec is not None:
                    rec.event("replay", epoch=e, **self.tiered.snapshot())
            # The loss materialization above and the diagnostics fetch
            # below are device fetches: charge them (plus the drain) to
            # the `drain` phase.
            # --- learning-health diagnostics (diagnostics/): ONE
            # device fetch for the epoch's per-burst diag rows (they
            # rode the same executables as the losses, so the drain
            # above already paid for them), suffix-reduced host-side.
            # Scalars land in metrics.jsonl; the TD-error counts merge
            # into the shared fixed-bucket histogram schema; the drift
            # monitor turns the stream into early-warning events that
            # feed telemetry and the sentinel as leading indicators.
            if self.monitor is not None and self._diag_rows:
                reduced = self._reduce_rows(jax.device_get(self._diag_rows))
                self._diag_rows = []
                hist = reduced.pop("diag/td_hist", None)
                if hist is not None:
                    self.td_hist.merge_counts(
                        hist,
                        total=float(reduced.get("diag/td_abs_sum", 0.0)),
                        vmin=float(reduced.get("diag/td_abs_min", np.inf)),
                        vmax=float(reduced.get("diag/td_abs_max", 0.0)),
                    )
                for k, v in reduced.items():
                    last_metrics[k] = float(v)
                for w in self.monitor.update(reduced):
                    logger.warning(
                        "early warning %s: %s=%.4g vs baseline %.4g "
                        "(deviation envelope %.4g) — leading indicator, "
                        "see docs/OBSERVABILITY.md",
                        w["kind"], w["key"], w["value"], w["baseline"],
                        w["spread"],
                    )
                    if self.sentinel is not None:
                        self.sentinel.note_warning(w["kind"])
                    if rec is not None:
                        rec.event("early_warning", epoch=e, **w)
                last_metrics["early_warnings"] = (
                    self.sentinel.warnings_total
                    if self.sentinel is not None
                    else self.monitor.fired_total
                )
                if rec is not None:
                    rec.event(
                        "diagnostics", epoch=e,
                        metrics={k: float(v) for k, v in reduced.items()},
                        td_hist=(
                            self.td_hist.snapshot(prefix="td_abs_", unit="")
                            if hist is not None else None
                        ),
                    )
            if self.watchdog is not None:
                wd_snap = self.watchdog.snapshot()
                last_metrics["xla_compiles"] = wd_snap["compiles_total"]
                # Cold-start accounting (aot/, docs/SERVING.md): the
                # live/warmup/bundle-load compile split plus the
                # persistent-cache hit/miss counters, onto
                # metrics.jsonl next to the compile total they explain.
                last_metrics["xla_live_compiles"] = wd_snap["live_compiles"]
                last_metrics["xla_cache_hits"] = wd_snap["cache_hits_total"]
                last_metrics["xla_cache_misses"] = (
                    wd_snap["cache_misses_total"]
                )
                last_metrics["bundle_hits"] = wd_snap["bundle_hits"]
                last_metrics["bundle_rejected"] = wd_snap["bundle_rejected"]
                new_anoms = wd_snap["anomalies"][self._wd_anomalies_seen:]
                self._wd_anomalies_seen = len(wd_snap["anomalies"])
                if rec is not None:
                    for a in new_anoms:
                        rec.event("recompile_anomaly", epoch=e, **a)
            if rec is not None:
                rec.begin(_PH_SENTINEL)
                # Per-program roofline for the epoch: burst FLOPs from
                # the cost registry over the burst's span time just
                # recorded (dispatch is async — queued device execution
                # surfaces under drain). Adds cost/ columns to
                # metrics.jsonl and a `cost` telemetry event; absent
                # entirely with telemetry off.
                self._note_epoch_cost(rec, last_metrics, len(losses_q), e)
            if self.population > 1:
                # Per-member epoch-mean returns: the N learning curves.
                for i in range(n):
                    if member_rewards[i]:
                        last_metrics[f"reward_m{i}"] = float(
                            np.mean(member_rewards[i])
                        )
                member_rewards = [[] for _ in range(n)]
            # --- divergence sentinel (resilience/sentinel.py): one
            # fused all-finite pass over learner state + replay ring +
            # this epoch's losses, BEFORE anything is checkpointed — so
            # every checkpoint on disk is sentinel-validated and
            # "latest" is always "last-good" for the rollback path. The
            # ring is included because a NaN transition outlives the
            # step that produced it (it sits in replay waiting to be
            # sampled); a params-only rollback would re-diverge.
            t_sentinel = time.perf_counter()
            sentinel_ok = True
            if self.sentinel is not None:
                sentinel_ok = self.sentinel.check(
                    self.state, self.buffer.data, losses_q, losses_pi
                )
                if not sentinel_ok:
                    # Budget first: raises TrainingDiverged once the
                    # consecutive-rollback allowance is exhausted.
                    self.sentinel.note_divergence(f"state at epoch {e}")
                    rolled_to = self._rollback()
                    logger.warning(
                        "epoch %d: non-finite training state detected; "
                        "rolled back to checkpoint epoch %d (rollback "
                        "%d, %d consecutive) — skipping save, resuming",
                        e, rolled_to, self.sentinel.total_rollbacks,
                        self.sentinel.consecutive,
                    )
                    if rec is not None:
                        rec.event("rollback", epoch=e, rolled_to=rolled_to)
                else:
                    self.sentinel.note_good()
                last_metrics["rollbacks"] = self.sentinel.total_rollbacks
            # Sentinel (and a rollback, when it fires) billed to its own
            # metric, not to the next epoch's throughput denominator.
            last_metrics["sentinel_s"] = round(
                time.perf_counter() - t_sentinel, 4
            )
            if rec is not None:
                rec.begin(_PH_CKPT)

            # Orbax saves of sharded arrays are collective: EVERY process
            # must call save (each host owns shards of the dp-sharded
            # buffer); rank-gating applies only to metric logging.
            # The final epoch always saves, so short runs (< save_every
            # epochs) still produce a checkpoint run_agent can load.
            saved_this_epoch = False
            t_save = time.perf_counter()
            if (
                sentinel_ok
                and self.checkpointer is not None
                and (
                    e % cfg.save_every == 0
                    or e == self.start_epoch + cfg.epochs - 1
                )
            ):
                self._save_checkpoint(e, step)
                saved_this_epoch = True
            # The synchronous slice of the save (array fetch + write
            # dispatch; Orbax finishes the IO in the background).
            last_metrics["save_s"] = round(time.perf_counter() - t_save, 4)
            if rec is not None:
                rec.end()

            # Decoupled-plane boundary work (no-op in the base class):
            # publish this epoch's params to the serving registry and
            # merge staging/degradation metrics before they are logged.
            self._epoch_boundary_hook(
                e, sentinel_ok, saved_this_epoch, last_metrics, rec
            )

            # Run-wide obs plane: mirror the collector's flat summary
            # into this epoch's metrics row, and hand the row back so
            # the learner scrape source (and SLO paths like
            # ``learner.metrics.env_steps_per_sec``) see real columns.
            if self.obs is not None:
                last_metrics.update(self.obs.metrics_columns())
                self._obs_last_metrics = dict(last_metrics)

            # --emit-bundle: first epoch with real updates (losses_q
            # non-empty — NOT the watchdog's first-update latch, which
            # only exists with diagnostics on) builds the serve-plane
            # warm-start bundle next to the checkpoint.
            if not self._bundle_emitted and losses_q:
                self._emit_warm_start_bundle(e)

            # Logged after the save so sentinel_s/save_s land in the
            # epoch that paid them.
            if is_coordinator() and self.tracker is not None:
                self.tracker.log_metrics(last_metrics, e)
            if rec is not None:
                rec.inc("env_steps", env_steps_this_epoch)
                rec.inc("grad_steps", grad_steps_this_epoch)
                for name, count in chunk_block.transfers.items():
                    rec.counters[name] = float(
                        count - self._chunk_transfers_at_start[name]
                    )
                extra = {
                    "step": step,
                    "env_steps": env_steps_this_epoch,
                    "grad_steps": grad_steps_this_epoch,
                    "env_steps_per_sec": round(
                        last_metrics["env_steps_per_sec"], 2
                    ),
                    "saved": saved_this_epoch,
                }
                if self.watchdog is not None:
                    extra["xla_compiles"] = last_metrics.get("xla_compiles")
                ev = rec.epoch_end(e, extra=extra)
                attr = ev.get("attribution")
                if attr is not None:
                    # The rolling view accumulates in rec.summary();
                    # the per-epoch line is the live signal ("the run
                    # went input-bound at epoch 40" is actionable NOW).
                    logger.info(
                        "epoch %d attribution: %s (device %.0f%%, host "
                        "%.0f%%, input %.0f%%)",
                        e, attr["class"],
                        100 * attr["device_busy_frac"],
                        100 * attr["host_frac"],
                        100 * attr["input_frac"],
                    )
            # Recompilation-watchdog steady marking: the first update
            # epoch pays the burst compile, and its END pays the
            # sentinel/save/mirror compiles — so the regime is declared
            # steady one full epoch later, after which any compile
            # attributed to the burst dispatch is a hot-path anomaly.
            if self.watchdog is not None:
                if losses_q and self._first_update_epoch is None:
                    self._first_update_epoch = e
                elif (
                    self._first_update_epoch is not None
                    and e > self._first_update_epoch
                ):
                    self.watchdog.mark_steady("train/")

            # --- graceful preemption (single SIGTERM/SIGINT): the
            # epoch is complete and, if it passed the sentinel,
            # checkpointed — the lossless exit point. The save is
            # synchronous: this process is about to die.
            if self.preemption is not None and self.preemption.triggered:
                if (
                    sentinel_ok
                    and self.checkpointer is not None
                    and not saved_this_epoch
                ):
                    self._save_checkpoint(e, step)
                if self.checkpointer is not None:
                    self.checkpointer.wait()
                if rec is not None:
                    rec.event("preempted", epoch=e, urgent=False)
                raise Preempted(epoch=e)

            if hasattr(epoch_iter, "set_postfix"):
                # Diagnostic keys stay in metrics.jsonl/telemetry; the
                # progress line keeps the historical compact view.
                epoch_iter.set_postfix({
                    **{
                        k: v for k, v in last_metrics.items()
                        if not k.startswith("diag/")
                    },
                    "step": step,
                })

            # (envs were already reset by the epoch_ended branch above —
            # the reference's extra epoch-boundary reset, ref :305, is a
            # redundant double physics re-init we deliberately drop)
            episode_rewards, episode_lengths = [], []
            # Restart the epoch clock only now: everything since the
            # drain (sentinel, save, logging) is accounted above and
            # must not leak into the next epoch's dt.
            t_epoch = time.time()

        if self.checkpointer is not None:
            self.checkpointer.wait()
        # One final obs window while every plane is still alive (the
        # fleet transport dies in close()): a run faster than the
        # scrape interval still ends with a row that saw real epoch
        # metrics.
        if self.obs is not None:
            self.obs.scrape_once()
        return last_metrics

    def close(self):
        """Release env pool resources (worker processes, shared memory)
        and finalize telemetry (flush the JSONL sink, stop a profiler
        trace left open by a short or interrupted run)."""
        if self.watchdog is not None:
            # The steady regime belongs to THIS trainer's compiled
            # programs; a successor trainer in the same process must
            # re-earn it (its first burst compile is legitimate).
            self.watchdog.clear_steady("train/")
        if self._prefetcher is not None:
            self._prefetcher.close()
        if self.tiered is not None:
            self.tiered.close()
        if self.obs is not None:
            # One final window (a run shorter than the interval still
            # gets a row), then the run-exit SLO table.
            if self.obs.scrapes_total == 0:
                self.obs.scrape_once()
            self.obs.close()
            for line in self.obs.slo.report().splitlines():
                logger.info("%s", line)
        if self.telemetry is not None:
            spans.uninstall(self.telemetry)
            self.telemetry.close()
        self.pool.close()

    # ------------------------------------------------------------- resume

    def restore(self, epoch: int | None = None, include_buffer: bool = True) -> int:
        """Resume full state (incl. buffer + normalizer) from the
        checkpointer — strictly more than the reference's
        ``load_session`` (ref ``main.py:28-51``, which drops buffer and
        target critic). ``include_buffer=False`` restores weights only
        (the eval CLI path, where buffer shapes may not match the eval
        mesh)."""
        if self.checkpointer is None:
            raise ValueError("no checkpointer configured")
        meta = self._load_checkpoint(epoch, include_buffer)
        self.start_epoch = int(meta["epoch"]) + 1
        # Pre-resilience checkpoints carry no step counter; fall back
        # to the epoch-aligned count (exact when the save was an epoch
        # boundary, which every non-urgent save is).
        self._resume_step = int(
            meta.get("step", self.start_epoch * self.config.steps_per_epoch)
        )
        return self.start_epoch

    # --------------------------------------------------------------- eval

    def evaluate(
        self,
        episodes: int = 10,
        deterministic: bool = True,
        render: bool = False,
        seed: int | None = None,
    ) -> dict:
        """Rollout loop (ref ``run_agent.run_agent``, ``run_agent.py:19-48``).

        ``seed`` makes the whole evaluation reproducible: episode ``i``
        resets its env with ``seed + i`` (the reference's per-episode
        seeding discipline, ref ``sac/algorithm.py:203-205``), and the
        acting PRNG key is re-keyed from ``seed`` so even
        ``deterministic=False`` rollouts replay exactly. ``None`` keeps
        OS-entropy resets.
        """
        saved_key = self._act_key
        if self.config.actor_param_lag:
            # Training may leave the mirror one window stale; evaluation
            # must always reflect the current policy.
            self._host_params = None
        if seed is not None:
            eval_key = jax.random.key(seed)
            if self.config.host_actor:
                # Keep the host_actor key placement (__init__ pins the
                # acting key host-side so per-step splits don't pay a
                # device round-trip).
                eval_key = jax.device_put(eval_key, self._host_device)
            self._act_key = eval_key
        try:
            if self.population > 1:
                return self._evaluate_population(
                    episodes, deterministic, render, seed
                )
            return self._evaluate_episodes(episodes, deterministic, render, seed)
        finally:
            # Restore the training exploration stream: a periodic seeded
            # eval must not make every post-eval epoch replay identical
            # exploration noise.
            self._act_key = saved_key

    def _evaluate_population(
        self, episodes: int, deterministic: bool, render: bool, seed: int | None
    ) -> dict:
        """Per-member evaluation: member ``i``'s policy rolls out
        ``episodes`` episodes on its own env slot. Episode ``j`` resets
        every member's env with ``seed + j`` — the SAME env realizations
        across members, so per-member differences measure the policies,
        not the reset draws. Returns the aggregate stats plus
        ``per_member`` mean/std lists (the N seed results).

        Shares :meth:`_evaluate_episodes`'s fixed-width rollout
        mechanics (padding rows for finished slots, the
        terminated/truncated/max_ep_len cut, reseed-on-reset) — a
        behavior change in one loop almost certainly applies to the
        other. The stochastic-eval caveat there applies here too: with
        ``deterministic=False`` the batched noise stream makes seeded
        results reproducible only at a fixed population size."""
        n = self.n_envs
        obs, rets, lens, ep_idx = [], [], [], []
        member_returns: t.List[list] = [[] for _ in range(n)]
        member_lengths: t.List[list] = [[] for _ in range(n)]
        for slot in range(n):
            ep_seed = None if seed is None else seed + 0
            o = self._normalize(
                self.pool.reset_at(slot, seed=ep_seed), update=False,
                member=slot,
            )
            obs.append(o)
            rets.append(0.0)
            lens.append(0)
            ep_idx.append(0)
        while any(idx < episodes for idx in ep_idx):
            batched = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *obs)
            actions = self._policy_actions(batched, deterministic=deterministic)
            for slot in range(n):
                if ep_idx[slot] >= episodes:
                    continue  # finished member: padding row, action dropped
                o, r, terminated, truncated = self.pool.step_at(
                    slot, actions[slot]
                )
                obs[slot] = self._normalize(o, update=False, member=slot)
                rets[slot] += r
                lens[slot] += 1
                if render and self._render_ok:
                    self.pool.render_at(slot)
                if (
                    terminated or truncated
                    or lens[slot] >= self.config.max_ep_len
                ):
                    member_returns[slot].append(rets[slot])
                    member_lengths[slot].append(lens[slot])
                    ep_idx[slot] += 1
                    if ep_idx[slot] < episodes:
                        ep_seed = (
                            None if seed is None else seed + ep_idx[slot]
                        )
                        obs[slot] = self._normalize(
                            self.pool.reset_at(slot, seed=ep_seed),
                            update=False,
                            member=slot,
                        )
                        rets[slot], lens[slot] = 0.0, 0
        all_returns = [r for m in member_returns for r in m]
        all_lengths = [l for m in member_lengths for l in m]
        return {
            "ep_ret_mean": float(np.mean(all_returns)),
            "ep_ret_std": float(np.std(all_returns)),
            "ep_len_mean": float(np.mean(all_lengths)),
            "per_member": [
                {
                    "ep_ret_mean": float(np.mean(m)),
                    "ep_ret_std": float(np.std(m)),
                }
                for m in member_returns
            ],
        }

    def _evaluate_episodes(
        self, episodes: int, deterministic: bool, render: bool, seed: int | None
    ) -> dict:
        """Concurrent rollouts over the whole env pool.

        Every pool env evaluates simultaneously: one batched policy
        call serves all in-flight episodes (fixed batch width, so the
        actor compiles once), and episode ``i`` still resets with
        ``seed + i`` regardless of which slot runs it — under a
        deterministic policy the per-episode trajectories are
        slot-assignment invariant, so seeded results match the
        single-env protocol while wall-clock drops ~n_envs-fold.
        The reference evaluates one env serially (ref
        ``run_agent.py:19-48``).

        Caveat (stochastic evals): with ``deterministic=False`` the
        acting noise is drawn from one batched stream shared by all
        slots, so a seeded stochastic eval is reproducible for a FIXED
        pool width but does not replay the old serial protocol and
        changes with ``n_envs``. Deterministic evals (the reference
        protocol and every committed artifact) are width-invariant.
        """
        n_slots = min(self.n_envs, episodes)
        next_ep = 0
        obs, rets, lens, live = [], [], [], []
        for slot in range(n_slots):
            ep_seed = None if seed is None else seed + next_ep
            next_ep += 1
            o = self._normalize(self.pool.reset_at(slot, seed=ep_seed), update=False)
            obs.append(o)
            rets.append(0.0)
            lens.append(0)
            live.append(True)
        returns, lengths = [], []
        while any(live):
            # Fixed-width batch: finished slots keep their last obs as
            # padding rows whose actions are discarded.
            batched = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *obs)
            actions = self._policy_actions(batched, deterministic=deterministic)
            for slot in range(n_slots):
                if not live[slot]:
                    continue
                o, r, terminated, truncated = self.pool.step_at(slot, actions[slot])
                obs[slot] = self._normalize(o, update=False)
                rets[slot] += r
                lens[slot] += 1
                if render and self._render_ok:
                    self.pool.render_at(slot)
                if terminated or truncated or lens[slot] >= self.config.max_ep_len:
                    returns.append(rets[slot])
                    lengths.append(lens[slot])
                    if next_ep < episodes:
                        ep_seed = None if seed is None else seed + next_ep
                        next_ep += 1
                        obs[slot] = self._normalize(
                            self.pool.reset_at(slot, seed=ep_seed), update=False
                        )
                        rets[slot], lens[slot] = 0.0, 0
                    else:
                        live[slot] = False
        return {
            "ep_ret_mean": float(np.mean(returns)),
            "ep_ret_std": float(np.std(returns)),
            "ep_len_mean": float(np.mean(lengths)),
        }
