"""Evaluation / rollout CLI.

Surface twin of the reference ``run_agent.py`` (ref ``run_agent.py:51-82``):

    python -m torch_actor_critic_tpu.run_agent --run <id> [--episodes N]
        [--headless] [--random]

Loads the actor from the run's latest Orbax checkpoint (the reference
unpickles an mlflow-logged torch module, ref ``run_agent.py:74-76``),
reads the env name from the run params with the same legacy fallback
(ref ``run_agent.py:71``), and rolls out with deterministic or
stochastic actions (ref ``--random`` flag, ``run_agent.py:58``).
"""

from __future__ import annotations

import argparse
import json
import logging

from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
from torch_actor_critic_tpu.utils.config import SACConfig
from torch_actor_critic_tpu.utils.tracking import Tracker

logging.basicConfig(level=logging.INFO)
logger = logging.getLogger(__name__)


def parse_arguments(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser("Soft Actor-Critic evaluation for MuJoCo.")
    parser.add_argument("--run", type=str, required=True, help="Run id to evaluate")
    parser.add_argument("--experiment", default="Default", help="Experiment name")
    parser.add_argument("--runs-root", default="runs")
    parser.add_argument(
        "--episodes", type=int, default=100, help="Number of test episodes"
    )
    parser.add_argument(
        "--headless", action="store_false", dest="render", help="Disable rendering"
    )
    parser.add_argument(
        "--random", action="store_false", dest="deterministic", help="Stochastic policy"
    )
    parser.add_argument(
        "--seed", type=int, default=None,
        help="Seed episode resets (episode i uses seed+i) and the acting "
        "PRNG; two invocations with the same seed produce identical "
        "returns",
    )
    parser.set_defaults(render=True, deterministic=True)
    return parser.parse_args(argv)


def main(argv=None):
    args = parse_arguments(argv)
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer

    tracker = Tracker.load(args.run, experiment=args.experiment, root=args.runs_root)
    params = tracker.params()
    # Legacy fallback mirrors ref run_agent.py:71.
    env_name = params.get("environment", "Humanoid-v5")
    config = SACConfig.from_json(json.dumps(params.get("config", {})))

    checkpointer = Checkpointer(tracker.artifact_path("checkpoints"))
    # Render handling (display detection, gymnasium's construction-time
    # render_mode) lives in the Trainer, shared with the train CLI.
    trainer = Trainer(
        env_name, config, mesh=make_mesh(dp=1), checkpointer=checkpointer,
        render=args.render,
    )
    try:
        trainer.restore(include_buffer=False)
        logger.info("evaluating run %s on %s", args.run, env_name)
        metrics = trainer.evaluate(
            episodes=args.episodes,
            deterministic=args.deterministic,
            render=args.render,
            seed=args.seed,
        )
    finally:
        trainer.close()
    logger.info("eval metrics: %s", metrics)
    print(json.dumps(metrics))
    return metrics


if __name__ == "__main__":
    main()
