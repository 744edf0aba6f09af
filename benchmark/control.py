"""Readings for the limits of ``correct``, on several seeds in one process.

For each seed: the program as configured against the reference (``sound``),
and the control, which is the reference put in the program's place one
precision lower (the configuration file's ``control.reference_mode``) against
the reference, on the same recorded updates.  ``--variants sound,program_low``
adds the program's own lower-precision path (``control.program``);
``--sound-only`` leaves the control's run out, so that topping a table of
sound seeds up costs one reference run a seed and not two.  Run on the chip at
the cell's own size:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--windows 4] [--sound-only]

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


def seed_lines(
    cell, config, seeds, variants=("sound",), windows=4, sound_only=False, overrides=None
):
    """One dict for each seed and variant: the sound readings and, unless
    ``sound_only``, the control's under ``<control's mode>:<name>``.  A run
    that crashes gives its error: a control that crashes has failed."""
    for seed in seeds:
        for variant in variants:
            over = dict(overrides or {})
            if variant == "program_low":
                over.update(config["control"].get("program") or {})
            low = None
            if variant == "sound" and not sound_only:
                low = config["control"]["reference_mode"]
            try:
                values = readings(cell, config, seed, over, windows, low=low)
            except Exception as e:  # noqa: BLE001
                values = {"error": repr(e)[:300]}
            yield {"seed": seed, "variant": variant, **values}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--windows", type=int, default=4)
    parser.add_argument("--variants", default="sound")
    parser.add_argument("--sound-only", action="store_true")
    args = parser.parse_args(argv)
    from benchmark.harness import registry

    _, cell, config = registry.resolve(args.workload)
    import jax

    from torch_actor_critic_tpu.aot.cache import enable_persistent_cache

    enable_persistent_cache()
    print(json.dumps({"device": jax.devices()[0].device_kind, "workload": cell["name"]}))
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in seed_lines(
        cell, config, seeds, args.variants.split(","), args.windows, args.sound_only
    ):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
