"""Cut sizes for the CPU rehearsals of the benchmark's cells.

The real cells fill a quarter of a chip; a test holds a ring of a thousand
rows.  Widths are cut here and nowhere else: a number from such a run is a
rehearsal of the control flow, never a device metric.
"""

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import registry  # noqa: E402

# what BENCHMARK.json lists first, then the parked cells (benchmark/parked/): a
# run never sees those, a rehearsal keeps their files working
CELLS = [w["name"] for w in registry.load_benchmark(parked=True)["workloads"]]


def cut(cell_name: str, chips: int | None = None):
    """(benchmark, cell, configuration) of ``cell_name`` at a size a test run
    can hold.  On the CPU the program multiplies in true float32, so the
    reference it is held to is the ``highest`` one."""
    bench, cell, config = registry.resolve(cell_name, parked=True)
    cell, config = copy.deepcopy(cell), copy.deepcopy(config)
    config["reference_mode"] = "highest"
    cell["limits"] = {k: 1e-4 for k in cell["limits"]}
    config["model"]["hidden_sizes"] = [32, 32]
    config["sac"].update(batch_size=8, update_every=10)
    if config["model"]["family"] == "visual":
        # 44x44 is the smallest frame whose last conv layer is not 1x1.
        config["model"].update(
            frame=[44, 44, 3], cnn_dense_size=64, feature_dim=12, act_dim=5
        )
        cell["traffic"].update(ring_rows=512, pool_windows=2, fill_slab_rows=100)
    else:
        config["sac"]["population"] = 3
        cell["traffic"].update(ring_rows=1000)
        if cell["driver"] == "fused":
            cell["traffic"].update(n_envs=4, steps_per_dispatch=30)
        else:
            cell["traffic"].update(steps_per_epoch=40, windows_per_call=4)
    cell["traffic"]["trace_seconds"] = 1
    if chips is not None:
        cell["chips"] = chips
    return bench, cell, config
