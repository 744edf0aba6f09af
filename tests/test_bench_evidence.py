"""bench.py's stated geometry (what it measures is what the env defines).

The chip-evidence splice this file once pinned is gone with the
fallbacks it served; what bench.py does without a chip is pinned in
tests/test_shell.py.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def test_visual_bench_geometry_matches_wall_runner_spec():
    """bench_visual's 'exact wall-runner geometry' claim (BASELINE
    config 5): the bench imports the env module's constants (single
    source of truth), and those constants ARE the reference's spaces
    (ref environments/wall_runner.py:20-21) — pin both facts."""
    import inspect

    from torch_actor_critic_tpu.envs import wall_runner

    src = inspect.getsource(bench.bench_visual)
    for name in ("FEATURE_DIM", "FRAME_SHAPE", "ACT_DIM"):
        assert name in src, f"bench_visual no longer uses {name}"
    assert wall_runner.FEATURE_DIM == 168
    assert wall_runner.FRAME_SHAPE == (64, 64, 3)
    assert wall_runner.ACT_DIM == 56
