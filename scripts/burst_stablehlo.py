"""Whether a change left a trunk cell's update burst the program it was.

Lowers the burst as the cell's driver builds it, at the cell's own sizes, for
a described v5e (no chip, nothing compiled, abstract arguments: a minute or
two and a few GiB a cell), from the checkout given, and prints the length and
the sha256 of its StableHLO with the Mosaic kernels' serialized bodies
blanked: a body carries its source file's path and line numbers, so two
checkouts never share one (PERF.md section 6, PR 31), and the kernels' own
text is held by their jaxprs. With ``--against`` it does the same from a
second checkout (a ``git archive`` of the parent commit, say) in a process
of its own and exits 1 where the two differ:

    python scripts/burst_stablehlo.py --cells sdar30b_a3b_trunk_burst,nemotron3_super_trunk_burst \\
        --against .parent0 [--keep /root/scratch]

A second checkout older than a cell's files refuses that cell; give it cells
both sides have.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import subprocess
import sys

BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+')


def lowered(root: str, cell_name: str) -> str:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    v5e = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    jax.default_backend = lambda: "tpu"  # what the kernels' ``auto`` asks at trace time

    from benchmark.drivers import trunkburst
    from benchmark.harness import registry, spans

    import torch_actor_critic_tpu
    from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
    from torch_actor_critic_tpu.core.types import BufferState
    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC
    from torch_actor_critic_tpu.parallel.mesh import make_mesh
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    assert torch_actor_critic_tpu.__file__.startswith(root), torch_actor_critic_tpu.__file__
    _, cell, config = registry.resolve(cell_name, root)
    driver = registry.load_driver(cell["driver"], os.path.join(root, "benchmark"))(
        cell, config, 1, spans.Spans(), {"rehearsal": False}
    )
    cfg, env = driver.sac_config(), trunkburst.Spec(driver.model)
    sac = make_learner(cfg, *build_models(cfg, env), env.act_dim)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=v5e[:1]))
    state = jax.eval_shape(sac.init_state, jax.random.key(0), env.example_obs())

    def rows(n):
        one = jax.eval_shape(lambda: init_replay_buffer(n, env.obs_spec, env.act_dim).data)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), one
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(data=rows(cell["traffic"]["ring_rows"]), ptr=index, size=index)
    chunk = rows(cfg.update_every)
    burst = learner._build_burst(cfg.update_every, state, ring, chunk)
    return burst.lower(state, ring, chunk).as_text()


def account(root: str, cells: list[str], keep: str | None) -> dict:
    out = {}
    for cell in cells:
        text = BODY.sub(r"\1", lowered(root, cell))
        if keep:
            name = f"stablehlo.{os.path.basename(root)}.{cell}.txt"
            with open(os.path.join(keep, name), "w") as f:
                f.write(text)
        out[cell] = [len(text), hashlib.sha256(text.encode()).hexdigest()]
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--cells", required=True)
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    parser.add_argument("--against")
    parser.add_argument("--keep")
    args = parser.parse_args()
    root, cells = os.path.abspath(args.root), args.cells.split(",")
    if args.against:  # each checkout imports its own package: a process each
        sides = {}
        for side in (root, os.path.abspath(args.against)):
            cmd = [sys.executable, os.path.abspath(__file__), "--root", side, "--cells", args.cells]
            if args.keep:
                cmd += ["--keep", args.keep]
            env = {**os.environ, "JAX_PLATFORMS": "cpu"}
            done = subprocess.run(cmd, cwd=side, env=env, capture_output=True, text=True)
            if done.returncode:
                sys.stderr.write(done.stderr[-2000:])
                return 2
            sides[side] = json.loads(done.stdout.strip().splitlines()[-1])
            print(side, json.dumps(sides[side]))
        differ = [c for c in cells if len({json.dumps(s[c]) for s in sides.values()}) > 1]
        print("differ:", differ or "none")
        return 1 if differ else 0
    sys.path.insert(0, root)
    os.chdir(root)
    print(json.dumps(account(root, cells, args.keep)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
