"""Readings for the limits of ``correct``, on several seeds in one process.

For each seed: the program as configured against the reference (``sound``),
and the control, which is the reference put in the program's place one
precision lower (the configuration file's ``control.reference_mode``) against
the reference, on the same recorded updates.  ``--variants sound,program_low``
adds the program's own lower-precision path (``control.program``).  Run on the chip at the cell's own size:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--windows 4]

Prints one JSON line for each seed and variant with every number compared.
The benchmark's own runs never call this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def readings(cell, config, seed, overrides, windows, bench_dir=None, low=None):
    from benchmark.harness import registry, spans

    driver = registry.load_driver(cell["driver"], bench_dir or registry.BENCH_DIR)(
        cell, config, seed, spans.Spans(), overrides
    )
    driver.setup()
    for _ in range(windows):
        driver.window()
    driver.free()
    mode = config.get("reference_mode", "highest")
    out = {c.name: c.value for c in driver.check(mode)}
    if low:
        out.update({f"{low}:{c.name}": c.value for c in driver.control(low, mode)})
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--windows", type=int, default=4)
    parser.add_argument("--variants", default="sound")
    args = parser.parse_args()
    from benchmark.harness import registry

    _, cell, config = registry.resolve(args.workload)
    import jax

    from torch_actor_critic_tpu.aot.cache import enable_persistent_cache

    enable_persistent_cache()
    print(json.dumps({"device": jax.devices()[0].device_kind, "workload": cell["name"]}))
    for seed in (int(s) for s in args.seeds.split(",")):
        for variant in args.variants.split(","):
            overrides = config["control"].get("program") if variant == "program_low" else None
            try:
                values = readings(
                    cell, config, seed, overrides, args.windows,
                    low=config["control"]["reference_mode"] if variant == "sound" else None,
                )
            except Exception as e:  # noqa: BLE001 — a control that crashes has failed
                values = {"error": repr(e)[:300]}
            print(json.dumps({"seed": seed, "variant": variant, **values}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
