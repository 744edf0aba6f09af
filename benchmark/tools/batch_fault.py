"""The upper reading for a trunk cell's two loss limits: a part of the batch
left out.

The float8 control hardly moves a trunk cell's losses, so their limits need
another fault to stand under (``README.md``, "How a limit and a bound are
set").  For each seed this runs the cell's program as ``control.py`` does
(set-up, ``--windows`` windows, the sound comparison against the reference)
and then the reference once more, at the same mode, on the first call's rows
with the later half of every update's batch replaced by the earlier half:
what a step that leaves half of its batch out computes.  It prints the sound
readings and, under ``half_batch:<name>``, that account against the sound
reference's.  Run on the chip at the cell's own size:

    python3 benchmark/tools/batch_fault.py --workload <trunk cell> --seeds 1,2 [--windows 2]

The benchmark's own runs never call this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def first_half_twice(x, axis: int):
    """``x`` with the later half of ``axis`` replaced by the earlier half."""
    import jax.numpy as jnp

    half = x.shape[axis] // 2
    first = jnp.take(x, jnp.arange(half), axis=axis)
    return jnp.concatenate([first, first], axis=axis)


def readings(cell, config, seed, windows, overrides=None, bench_dir=None):
    import jax

    from benchmark.harness import registry, spans

    driver = registry.load_driver(cell["driver"], bench_dir or registry.BENCH_DIR)(
        cell, config, seed, spans.Spans(), dict(overrides or {})
    )
    driver.setup()
    for _ in range(windows):
        driver.window()
    driver.free()
    mode = config.get("reference_mode", "highest")
    out = {c.name: c.value for c in driver.check(mode)}
    sound = driver._follow(mode)
    # every leaf, the noise too: (updates, shard, batch, ...)
    driver._rows, driver.eps_q, driver.eps_pi = jax.tree_util.tree_map(
        lambda x: first_half_twice(x, 2), (driver._rows, driver.eps_q, driver.eps_pi)
    )
    driver._followed = {}
    got = driver._follow(mode)
    out.update({
        f"half_batch:{c.name}": c.value for c in driver._compare(got, sound, got["choices"])
    })
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--windows", type=int, default=2)
    args = parser.parse_args(argv)
    from benchmark.harness import registry

    _, cell, config = registry.resolve(args.workload)
    import jax

    from torch_actor_critic_tpu.aot.cache import enable_persistent_cache

    enable_persistent_cache()
    print(json.dumps({"device": jax.devices()[0].device_kind, "workload": cell["name"]}))
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            values = readings(cell, config, seed, args.windows)
        except Exception as e:  # noqa: BLE001
            values = {"error": repr(e)[:300]}
        print(json.dumps({"seed": seed, **values}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
