"""Training CLI.

Surface twin of the reference ``main.py`` (ref ``main.py:113-185``):

    python -m torch_actor_critic_tpu.train --environment HalfCheetah-v5
    python -m torch_actor_critic_tpu.train --run <id>   # resume

Differences, by design:

- ``--devices`` replaces ``--cpus``: parallelism is a device mesh, not
  an ``mpirun`` re-exec (ref ``mpi_fork``, ``sac/mpi.py:10-34``).
- hyperparameters are CLI-overridable typed flags (ref hardcodes a dict,
  ``main.py:147-160``) and persist as JSON, not MLflow param strings.
- resume restores the FULL state incl. replay buffer, target critic and
  normalizer (ref drops all three, SURVEY.md §3.5).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging

from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.parallel.distributed import (
    initialize_multihost,
    is_coordinator,
)
from torch_actor_critic_tpu.resilience.preemption import (
    REQUEUE_EXIT_CODE,
    Preempted,
    PreemptionGuard,
)
from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
from torch_actor_critic_tpu.utils.config import SACConfig
from torch_actor_critic_tpu.utils.tracking import Tracker

logging.basicConfig(level=logging.INFO)
logger = logging.getLogger(__name__)


def parse_arguments(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        "Soft Actor-Critic trainer for MuJoCo/dm_control on TPU."
    )
    # Reference surface (ref main.py:113-125)
    parser.add_argument("--run", type=str, default=None, help="Run id to resume")
    parser.add_argument("--experiment", default="Default", help="Experiment name")
    parser.add_argument(
        "--disable-logging", dest="logging", action="store_false", help="Turn off logging"
    )
    parser.add_argument(
        "--render", dest="render", action="store_true", help="Render the environment"
    )
    parser.add_argument(
        "--environment", default="HalfCheetah-v5", help="Environment to use"
    )
    parser.add_argument(
        "--devices",
        type=int,
        default=None,
        help="Data-parallel width (default: all visible devices, "
        "divided by --fsdp)",
    )
    parser.add_argument(
        "--fsdp",
        type=int,
        default=1,
        help="Width of the fsdp mesh axis: parameters above the size "
        "threshold shard over it (parallel/sharding.py), composing "
        "with --devices into the dp+fsdp hybrid burst "
        "(docs/SCALING.md)",
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--profile",
        metavar="DIR",
        default=None,
        help="Capture a jax.profiler trace of the run into DIR (view with "
        "tensorboard/xprof). Profile short runs: --epochs 2 --steps-per-epoch "
        "500. The reference has no profiling at all (SURVEY.md §5).",
    )
    parser.add_argument(
        "--profile-epochs",
        metavar="A:B",
        default=None,
        help="Capture an XLA trace over the half-open epoch window A:B "
        "into <run_dir>/trace (TensorBoard/xprof-loadable); implies "
        "--telemetry true. Unlike --profile this bounds the capture to "
        "a couple of post-warmup epochs — the workflow "
        "docs/OBSERVABILITY.md describes.",
    )
    parser.add_argument(
        "--trace-export",
        metavar="PATH",
        default=None,
        help="Write a cross-plane Perfetto (chrome://tracing) trace to "
        "PATH at exit: every recorded training phase span plus XLA "
        "compile events on one timeline (docs/OBSERVABILITY.md 'Cost "
        "attribution & roofline'); implies --telemetry true.",
    )
    parser.add_argument("--runs-root", default="runs", help="Tracking root directory")
    parser.add_argument(
        "--no-save-buffer",
        dest="save_buffer",
        action="store_false",
        help="Exclude the replay buffer from checkpoints",
    )
    parser.add_argument(
        "--no-preemption-guard",
        dest="preemption_guard",
        action="store_false",
        help="Do not install SIGTERM/SIGINT handlers (default: on — a "
        "signal triggers an emergency checkpoint and exit with the "
        "requeue code %d; see docs/RESILIENCE.md)" % REQUEUE_EXIT_CODE,
    )
    parser.add_argument(
        "--precision",
        choices=("f32", "bf16"),
        default=None,
        help="Mixed-precision training policy (alias of --compute-dtype): "
        "bf16 runs the CNN trunk + MLP matmuls in bfloat16 with f32 "
        "master weights and f32 loss/target/optimizer math — "
        "loss-scale-free on TPU; f32 is the bitwise-pinned parity "
        "default (docs/SCALING.md 'Mixed precision & the pixel "
        "pipeline')",
    )
    # Every SACConfig field becomes a flag (--batch-size, --learn-alpha, ...).
    for f in dataclasses.fields(SACConfig):
        flag = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            parser.add_argument(
                flag, type=lambda s: s.lower() in ("1", "true", "yes"), default=None
            )
        elif isinstance(f.default, tuple):
            parser.add_argument(
                flag, type=lambda s: tuple(int(x) for x in s.split(",")), default=None
            )
        elif f.name == "target_entropy":
            parser.add_argument(flag, type=float, default=None)
        else:
            parser.add_argument(flag, type=type(f.default), default=None)
    parser.set_defaults(logging=True, render=False, save_buffer=True)
    return parser.parse_args(argv)


def config_from_args(args: argparse.Namespace) -> SACConfig:
    overrides = {}
    for f in dataclasses.fields(SACConfig):
        v = getattr(args, f.name, None)
        if v is not None:
            overrides[f.name] = v
    if getattr(args, "precision", None) is not None:
        alias = {"f32": "float32", "bf16": "bfloat16"}
        want = alias[args.precision]
        have = overrides.get("compute_dtype")
        if have is not None and alias.get(have, have) != want:
            raise ValueError(
                f"--precision {args.precision} conflicts with "
                f"--compute-dtype {have}; pass one"
            )
        overrides["compute_dtype"] = want
    return SACConfig(**overrides)


def main(argv=None):
    args = parse_arguments(argv)
    initialize_multihost()

    from torch_actor_critic_tpu.sac.trainer import Trainer  # jax-heavy import

    tracker = Tracker(
        experiment=args.experiment,
        run_id=args.run,
        root=args.runs_root,
        enabled=args.logging and is_coordinator(),
    )

    if args.run is not None:
        # Resume: config comes from the run's stored params
        # (ref load_session, main.py:28-51).
        stored = tracker.params()
        config = SACConfig.from_json(json.dumps(stored.get("config", {})))
        env_name = stored.get("environment", args.environment)
    else:
        config = config_from_args(args)
        env_name = args.environment
        tracker.log_params(
            {
                "environment": env_name,
                "config": json.loads(config.to_json()),
                "buffer_size": config.buffer_size,
            }
        )

    if config.compile_cache:
        # Persistent compilation cache (aot/cache.py, docs/SERVING.md
        # "Cold start"): epoch programs persist to disk, so a
        # preempted learner's `--run <id>` restart — and every spawned
        # actor process, which resolves the same directory — resumes
        # compile-free.
        from torch_actor_critic_tpu.aot import enable_persistent_cache

        enable_persistent_cache()

    mesh = make_mesh(dp=args.devices, fsdp=args.fsdp)
    checkpointer = Checkpointer(
        tracker.artifact_path("checkpoints"), save_buffer=args.save_buffer
    )
    # Telemetry (docs/OBSERVABILITY.md): built here so the CLI-only
    # --profile-epochs window reaches the recorder; a --telemetry true
    # run without a window still streams phase spans + HBM watermarks
    # to <run_dir>/telemetry.jsonl.
    from torch_actor_critic_tpu.telemetry import (
        TelemetryRecorder,
        parse_profile_epochs,
    )

    profile_window = parse_profile_epochs(args.profile_epochs)
    telemetry_rec = None
    if config.telemetry or profile_window or args.trace_export:
        telemetry_rec = TelemetryRecorder(
            run_dir=tracker.run_dir if tracker.enabled else None,
            profile_epochs=profile_window,
            sink_max_bytes=int(config.telemetry_max_mb * 1e6),
        )

    def export_trace_if_requested(extra_events=None):
        # Cross-plane Perfetto export (--trace-export): training phase
        # spans from the recorder ring, every watchdog-attributed XLA
        # compile, and any cross-process staging spans the trainer
        # collected (fleet runs: transport ingest, drain windows,
        # actor push files) — one timeline (telemetry/traceview.py).
        if args.trace_export is None or not is_coordinator():
            return
        from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog
        from torch_actor_critic_tpu.telemetry.traceview import (
            compile_events,
            export_trace,
            training_events,
        )

        spans = (
            [training_events(telemetry_rec)]
            if telemetry_rec is not None else []
        )
        if extra_events:
            spans.append(extra_events)
        summary = export_trace(
            args.trace_export, *spans,
            compile_events(get_watchdog().compile_log()),
        )
        logger.info(
            "trace exported to %s (%d train / %d compile / %d transport "
            "/ %d actor spans) — load at chrome://tracing or "
            "https://ui.perfetto.dev",
            summary["path"], summary["train_spans"],
            summary["compile_spans"], summary["transport_spans"],
            summary["actor_spans"],
        )

    if config.offline:
        # Offline training (replay/, docs/REPLAY.md): the dataset is a
        # replay disk tier — trainer spill or serve-side flywheel — and
        # there is no env, mesh sharding or replay ring in the loop.
        from torch_actor_critic_tpu.replay.offline import train_offline

        logger.info(
            "offline training from %s (reg=%s x %g, %d steps, run %s)",
            config.offline_dataset or "<unset>", config.offline_reg,
            config.offline_reg_weight, config.offline_steps,
            tracker.run_id,
        )
        metrics = train_offline(
            config, tracker=tracker, checkpointer=checkpointer,
            seed=args.seed, telemetry=telemetry_rec,
        )
        export_trace_if_requested()
        logger.info("final metrics: %s", metrics)
        return metrics
    if config.on_device:
        # Scenario workloads (scenarios/, docs/SCENARIOS.md) resolve
        # through the same on-device registry; announce their structure
        # so a run's log states which metric layout (reward_a{i} /
        # reward_t{i}) and replay layout (striped) to expect.
        from torch_actor_critic_tpu.envs.ondevice import get_on_device_env

        scenario_cls = get_on_device_env(env_name)
        if scenario_cls is not None:
            n_agents = getattr(scenario_cls, "n_agents", 1)
            n_tasks = getattr(scenario_cls, "n_tasks", 0)
            if n_agents > 1:
                logger.info(
                    "scenario workload %s: %d agents in one shared "
                    "physics state (%s critic; per-agent reward_a{i} "
                    "metrics)",
                    env_name, n_agents, config.ma_critic,
                )
            if n_tasks > 1:
                logger.info(
                    "scenario workload %s: %d tasks (%s conditioning; "
                    "per-task striped replay; reward_t{i} metrics)",
                    env_name, n_tasks,
                    f"embed[{config.task_embed_dim}]"
                    if config.task_embed_dim > 0 else "one-hot",
                )
        if config.diagnostics != "off":
            logger.warning(
                "--diagnostics is a host-Trainer feature; the fused "
                "on-device loop reports loss means only, so the "
                "in-graph diagnostic reductions would be dead code "
                "(XLA eliminates them) — running effectively at "
                "diagnostics=off"
            )
        if config.sanitize != "off":
            logger.warning(
                "--sanitize guards the host Trainer's device phases "
                "and the serving forward path; the fused on-device "
                "loop is ONE jit dispatch per epoch with no per-window "
                "host boundary to guard — running effectively at "
                "sanitize=off (the epoch drain already fetches via "
                "explicit jax.device_get)"
            )
        if config.population > 1:
            # Population-fused path: one dispatch advances N complete
            # learning curves; PBT exploit/explore events stream to
            # telemetry.jsonl when --telemetry true.
            from torch_actor_critic_tpu.sac.ondevice import (
                train_population_on_device,
            )

            logger.info(
                "population-fused on-device training: %s x %d members "
                "(run %s)",
                env_name, config.population, tracker.run_id,
            )
            metrics = train_population_on_device(
                env_name, config,
                mesh=mesh, tracker=tracker, checkpointer=checkpointer,
                seed=args.seed, telemetry=telemetry_rec,
            )
            export_trace_if_requested()
            logger.info("final metrics: %s", metrics)
            return metrics
        if profile_window:
            logger.warning(
                "--profile-epochs is a host-Trainer feature; the fused "
                "on-device loop has no host-visible phases to window — "
                "use --profile for a whole-run trace instead (per-epoch "
                "`cost` events still stream with --telemetry true)"
            )
        from torch_actor_critic_tpu.sac.ondevice import train_on_device

        logger.info(
            "on-device training: %s on mesh %s (run %s)",
            env_name, dict(mesh.shape), tracker.run_id,
        )
        metrics = train_on_device(
            env_name, config,
            mesh=mesh, tracker=tracker, checkpointer=checkpointer,
            seed=args.seed, telemetry=telemetry_rec,
        )
        export_trace_if_requested()
        logger.info("final metrics: %s", metrics)
        return metrics
    # Preemption guard (resilience/, docs/RESILIENCE.md): one SIGTERM/
    # SIGINT finishes the epoch, checkpoints, and exits with the
    # requeue code so `make`/schedulers restart with `--run <id>` for a
    # lossless resume; a second signal saves at the next update-window
    # boundary instead.
    guard = PreemptionGuard().install() if args.preemption_guard else None
    # Decoupled actor/learner split (--decoupled true, ROADMAP item 5):
    # same hardened loop, acting through the serving plane with staged
    # transitions and per-epoch publishes (docs/RESILIENCE.md
    # "Decoupled-plane failure modes"). Resume picks the class from the
    # run's stored config, so `--run <id>` restarts land on the right
    # plane automatically.
    if config.actors > 0:
        # --actors N: the supervised process fleet (decoupled/fleet.py)
        # — N ActorWorker subprocesses over the networked staging
        # transport, heartbeat-supervised with bounded restarts, on top
        # of the same decoupled learner.
        from torch_actor_critic_tpu.decoupled import FleetTrainer

        trainer_cls: type = FleetTrainer
        logger.info(
            "actor fleet: %d supervised actor processes, "
            "max_restarts=%d, heartbeat=%.2fs/%.2fs, staging=%d (%s)",
            config.actors, config.actor_max_restarts,
            config.heartbeat_interval_s, config.heartbeat_timeout_s,
            config.resolved_staging_capacity, config.staging_policy,
        )
    elif config.decoupled:
        from torch_actor_critic_tpu.decoupled import DecoupledTrainer

        trainer_cls = DecoupledTrainer
        logger.info(
            "decoupled actor/learner: serving=%s, max_actor_lag=%d, "
            "staging=%d (%s)",
            config.serve_url or "in-process", config.max_actor_lag,
            config.resolved_staging_capacity, config.staging_policy,
        )
    else:
        trainer_cls = Trainer
    trainer = trainer_cls(
        env_name,
        config,
        mesh=mesh,
        tracker=tracker,
        checkpointer=checkpointer,
        seed=args.seed,
        render=args.render,
        preemption=guard,
        telemetry=telemetry_rec,
    )
    if args.run is not None and checkpointer.latest_epoch() is not None:
        start = trainer.restore()
        logger.info("resumed run %s at epoch %d", tracker.run_id, start)

    logger.info(
        "training %s on mesh %s (run %s)", env_name, dict(mesh.shape), tracker.run_id
    )
    try:
        if args.profile:
            import jax

            with jax.profiler.trace(args.profile):
                metrics = trainer.train(render=args.render)
            logger.info("profiler trace written to %s", args.profile)
        else:
            metrics = trainer.train(render=args.render)
    except Preempted as p:
        logger.warning(
            "%s — resume with: python -m torch_actor_critic_tpu.train "
            "--run %s --runs-root %s",
            p, tracker.run_id, args.runs_root,
        )
        raise SystemExit(p.exit_code)
    finally:
        # Export BEFORE close: a fleet trainer's staging span buffers
        # (and actor span files) are still attached; the finally also
        # runs on Preempted, so a SIGTERM'd run still gets its
        # timeline.
        export_trace_if_requested(
            trainer.extra_trace_events() if args.trace_export else None
        )
        trainer.close()
        if guard is not None:
            guard.uninstall()
        if (
            telemetry_rec is not None
            and telemetry_rec.epochs_recorded
            and is_coordinator()
        ):
            logger.info("%s", telemetry_rec.summary())
    logger.info("final metrics: %s", metrics)
    return metrics


if __name__ == "__main__":
    main()
