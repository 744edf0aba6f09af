"""Record the small trace the tests reduce: a few windows of a tiny jitted
program on the chip, with the harness's spans around them.  Run on the chip:

    python3 benchmark/tools/record_small_trace.py chiprun_out/small_trace

and keep the ``.xplane.pb`` it names as ``benchmark/data/small_v5e.xplane.pb``.
"""

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.harness import spans as spans_mod
    from benchmark.harness import trace

    out = sys.argv[1]
    shutil.rmtree(out, ignore_errors=True)
    n = len(jax.devices())
    x = jnp.ones((n, 512, 512), jnp.float32)

    step = jax.pmap(lambda a: jax.lax.pmean(jnp.tanh(a @ a) * 0.01 + jnp.tanh(a.T @ a), "i"), axis_name="i")
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    spans = spans_mod.Spans(annotate=True)
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(3):
        with spans.span("window"):
            with spans.span("stage"):
                time.sleep(0.002)
            with spans.span("burst_dispatch"):
                y = step(x)
            with spans.span("drain"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(out)
    summary = trace.reduce(trace.load(path))
    print(path, os.path.getsize(path), "bytes")
    print({k: summary[k] for k in ("window_s", "busy_s", "n_devices", "by_kind", "collective_s", "collective_exposed_s")})
    print(trace.breakdown(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
