"""Record the small trace the window-span tests reduce: a few windows of the
program's own update burst at a tiny size on the chip, each made by the four
functions that open the window's spans themselves (``Trainer._build_chunk``,
``shard_chunk_from_local``, ``DataParallelSAC.update_burst``,
``utils.sync.drain``), with the harness's ``bench/window`` span around it and
nothing else.  Run on the chip:

    python3 benchmark/tools/record_window_trace.py chiprun_out/window_trace

and keep what it names as ``benchmark/data/small_v5e_windows.xplane.pb``.  The
burst is two updates of a wide MLP, so that the device works a few
milliseconds a window and idles about as long while the host makes the next
one ready: every owner of idle time is in the trace, and the file stays small.
"""

import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

UPDATES = 2
WINDOWS = 4
MODEL = {"family": "mlp", "obs_dim": 17, "act_dim": 6, "act_limit": 1.0}


def main() -> int:
    import jax
    import numpy as np

    from benchmark.drivers import _common
    from benchmark.harness import spans as spans_mod
    from benchmark.harness import trace, window_spans
    from torch_actor_critic_tpu.parallel.dp import (
        DataParallelSAC, init_sharded_buffer, shard_chunk_from_local,
    )
    from torch_actor_critic_tpu.parallel.mesh import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer, build_models, make_learner
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
    rows = lambda *shape: rng.standard_normal((1,) + shape).astype(np.float32)  # noqa: E731
    staged = [  # one env's steps, as the Trainer stages them
        (rows(MODEL["obs_dim"]), rows(MODEL["act_dim"]), rows(), rows(MODEL["obs_dim"]),
         np.zeros((1,), np.float32))
        for _ in range(UPDATES)
    ]
    spans = spans_mod.Spans(annotate=True)

    def window(state, buffer):
        with spans.span("window"):
            local = Trainer._build_chunk(None, staged)
            chunk = shard_chunk_from_local(local, mesh, sp=1)
            state, buffer, m = dp.update_burst(state, buffer, chunk, UPDATES)
            drain(m["loss_q"])
        return state, buffer

    for _ in range(2):  # compiles, then settles the placements
        state, buffer = window(state, buffer)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(WINDOWS):
        state, buffer = window(state, buffer)
    jax.profiler.stop_trace()

    path = trace.find_xplane(out)
    summary = trace.reduce(trace.load(path))
    reduced = window_spans.reduce(window_spans.load(path), summary["gaps"], summary["window"])
    print(path, os.path.getsize(path), "bytes")
    print({k: summary[k] for k in ("window_s", "busy_s", "n_devices")})
    print(json.dumps(window_spans.printable(reduced) if reduced else None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
