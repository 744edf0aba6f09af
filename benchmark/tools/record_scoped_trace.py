"""Record the small scoped trace the tests reduce: a few windows of the
program's own update burst at a tiny size on the chip, with the harness's
``bench/window`` spans and the recorder's ``tac/host/<phase>`` annotations
around them, and the burst's scope table beside it.  Run on the chip:

    python3 benchmark/tools/record_scoped_trace.py chiprun_out/scoped_trace

and keep what it names as ``benchmark/data/small_v5e_scoped.xplane.pb`` and
``benchmark/data/small_v5e_scoped.table.json``.  Every window also runs one
operation of another program, so that the reduction's "other program" case is
in the trace.  The scan is not unrolled (one body, few distinct instructions:
the file stays small) and the layers are wide enough that the device's time
is in its operations, not between them.
"""

import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

UPDATES = 10
MODEL = {"family": "mlp", "obs_dim": 17, "act_dim": 6, "act_limit": 1.0}


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers import _common
    from benchmark.harness import scopes, trace
    from benchmark.harness import spans as spans_mod
    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.parallel.dp import (
        DataParallelSAC, init_sharded_buffer, shard_chunk_from_local,
    )
    from torch_actor_critic_tpu.parallel.mesh import make_mesh
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
    from torch_actor_critic_tpu.telemetry.recorder import PHASES, TelemetryRecorder
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.sync import drain

    out = sys.argv[1]
    shutil.rmtree(out, ignore_errors=True)
    cfg = SACConfig(
        hidden_sizes=(2048, 2048), batch_size=2048, buffer_size=65536,
        update_every=UPDATES, burst_unroll=1,
    )
    env = _common.EnvSpec(MODEL)
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    dp = DataParallelSAC(make_learner(cfg, *build_models(cfg, env), env.act_dim), mesh)
    state = dp.init_state(jax.random.key(0), env.example_obs())
    buffer = init_sharded_buffer(cfg.buffer_size, env.obs_spec, env.act_dim, mesh)
    rng = np.random.default_rng(0)
    rows = lambda *shape: rng.standard_normal((1, UPDATES) + shape).astype(np.float32)  # noqa: E731
    local = Batch(
        states=rows(MODEL["obs_dim"]), actions=rows(MODEL["act_dim"]), rewards=rows(),
        next_states=rows(MODEL["obs_dim"]), done=np.zeros((1, UPDATES), np.float32),
    )
    other = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)

    rec = TelemetryRecorder(run_dir=None)
    phase = {name: i for i, name in enumerate(PHASES)}
    spans = spans_mod.Spans(annotate=True)

    def window():
        with spans.span("window"):
            rec.begin(phase["place_chunk"])
            chunk = shard_chunk_from_local(local, mesh, sp=1)
            rec.begin(phase["burst_dispatch"])
            out_ = dp.update_burst(state, buffer, chunk, UPDATES)
            y = other(x)
            rec.window += 1
            rec.begin(phase["drain"])
            drain(out_[2]["loss_q"])
            drain(y)
            rec.end()
        return out_[0], out_[1]

    state, buffer = window()  # compiles
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    rec.epoch_begin(0)
    for _ in range(2):
        state, buffer = window()
    jax.profiler.stop_trace()

    path = trace.find_xplane(out)
    table_path = os.path.join(out, "table.json")
    scoped = dp.burst_scope_table()
    with open(table_path, "w") as f:
        json.dump(scoped, f)
    summary = trace.reduce(trace.load(path))
    reduced = scopes.reduce(scopes.load(path), scoped, summary["window"])
    print(path, os.path.getsize(path), "bytes;", table_path, os.path.getsize(table_path), "bytes")
    print({k: summary[k] for k in ("window_s", "busy_s", "n_devices")})
    print({k: reduced[k] for k in ("device", "leaf_s", "unscoped_reasons", "host", "host_spans")})
    print("identity gap", scopes.identity_gap(reduced, summary["busy_s"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
