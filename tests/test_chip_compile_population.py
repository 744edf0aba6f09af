"""The chip's compiler, asked in the sandbox (``test_chip_compile.py`` says
how): the population's burst and fused epoch and the visual cell's burst,
compiled for the described v5e at the cells' sizes, and what passes over a
ring leaf in them."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile_helpers import (  # noqa: F401  (``v5e`` and ``chip_compiler`` are fixtures)
    _chunk_of,
    _on,
    _ring_scatters,
    chip_compiler,
    v5e,
)

from torch_actor_critic_tpu.buffer.replay import init_replay_buffer, nbytes
from torch_actor_critic_tpu.core.types import Batch, BufferState
from torch_actor_critic_tpu.parallel import DataParallelSAC, make_mesh
from torch_actor_critic_tpu.utils.config import SACConfig

# What may carry a whole ring leaf through a program without passing over
# it: names for a buffer, and an update in place.
_NO_PASS = {
    "parameter", "get-tuple-element", "tuple", "while", "bitcast",
    "dynamic-update-slice",
}


def _whole_leaf_passes(hlo_text, rows):
    """The instructions of an optimized program (fused ones too) whose
    result has a dimension of at least ``rows``, a ring's row count, and
    that read or write all of it: everything but parameters, tuples and
    their elements, loops, bitcasts and in-place updates (a
    ``dynamic-update-slice``, alone or as the root of a fusion)."""
    root_of, name = {}, None
    for line in hlo_text.splitlines():
        header = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if header:
            name = header.group(1)
        root = re.match(r"\s*ROOT .*? = .*? ([a-z][a-z0-9\-]*)\(", line)
        if root and name is not None:
            root_of[name] = root.group(1)
    found = []
    for line in hlo_text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][a-z0-9\-]*)\((.*)", line
        )
        if not m:
            continue
        result, op, rest = m.groups()
        shapes = [
            [int(d) for d in dims.split(",") if d]
            for dims in re.findall(r"\[([\d,]*)\]", result)
        ]
        largest = max(shapes, key=lambda dims: np.prod(dims), default=[])
        if not largest or max(largest) < rows or op in _NO_PASS:
            continue
        if op.endswith("-start"):  # holds its operand too; its -done is judged
            continue
        callee = re.search(r"calls=%?([\w.\-]+)", rest)
        if op == "fusion" and callee and (
            root_of.get(callee.group(1)) == "dynamic-update-slice"
        ):
            continue
        found.append((f"{op} {result.split('{')[0]}", int(np.prod(largest))))
    return found


def _visual_burst_passes_over_no_frame_leaf(devices):
    """ISSUE 30: ``wallrunner_cnn_burst``'s burst at the cell's sizes. The
    frame ring rests tile by tile (``buffer/replay.py::stored_row_shape``):
    push and gather work on it as it rests and nothing passes over a frame
    leaf (two copies a window before: 98% of the ring's elements). What
    still passes over a leaf, as it did: the feature and action leaves on
    their way to the gather (bfloat16, row-major) and the prefetch of the
    scalar ones, 2% of the ring (PERF.md section 7)."""
    from benchmark.drivers import _common
    from benchmark.harness import registry
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    _, cell, config = registry.resolve("wallrunner_cnn_burst")
    rows = cell["traffic"]["ring_rows"]
    cfg = _common.sac_config(config, cell)
    env = _common.EnvSpec(config["model"])
    sac = make_learner(cfg, *build_models(cfg, env), env.act_dim)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=devices[:1]))
    state = jax.eval_shape(sac.init_state, jax.random.key(0), env.example_obs())

    def on_dp(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), tree
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(
        data=on_dp(jax.eval_shape(
            lambda: init_replay_buffer(rows, env.obs_spec, env.act_dim).data
        )),
        ptr=index, size=index,
    )
    frame = ring.data.states.frame
    assert frame.shape == (1, rows, 96, 128)
    # The chunk as the Trainer stages it: rows in a transition's shape.
    # (One in the stored shape hides the fault: the compiler carries a
    # picture's own layout through push's reshape onto the ring.)
    n = cfg.update_every
    obs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((n,) + tuple(x.shape), x.dtype),
        env.obs_spec,
    )
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    chunk = on_dp(Batch(
        states=obs, actions=f32((n, env.act_dim)), rewards=f32((n,)),
        next_states=obs, done=f32((n,)),
    ))
    compiled = learner._build_burst(n, state, ring, chunk).lower(
        state, ring, chunk
    ).compile()
    passes = _whole_leaf_passes(compiled.as_text(), rows)
    assert [name for name, size in passes if size >= frame.size // 4] == []
    ring_elements = sum(x.size for x in jax.tree_util.tree_leaves(ring.data))
    assert sum(size for _, size in passes) < 0.025 * ring_elements
    # the two padded frame copies were 9.4 GiB of scratch
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def _population_epoch_keeps_its_three_passes(devices):
    """ISSUE 30: ``cheetah_pop32_fused``'s epoch at the cell's sizes. The
    population's rings rest as before: each of the three leaves wider than
    a scalar is still converted to bfloat16 and relaid once a window; no
    other pass may join those (PERF.md section 7 says what was tried)."""
    from benchmark.drivers import _common
    from benchmark.harness import registry
    from torch_actor_critic_tpu.envs.ondevice import get_on_device_env
    from torch_actor_critic_tpu.sac.ondevice import (
        PopulationOnDeviceLoop,
        _wrap_and_build,
    )

    _, cell, config = registry.resolve("cheetah_pop32_fused")
    traffic = cell["traffic"]
    cfg = _common.sac_config(config, cell)
    env_cls, sac = _wrap_and_build(get_on_device_env(traffic["env"]), cfg)
    loop = PopulationOnDeviceLoop(
        sac, env_cls, n_members=cfg.population, n_envs=traffic["n_envs"]
    )
    state, ring, envs, keys, _ = jax.eval_shape(
        lambda k: loop.init(k, buffer_capacity=traffic["ring_rows"]),
        jax.random.key(0),
    )
    compiled = loop._build_epoch(
        traffic["steps_per_dispatch"], cfg.update_every, False
    ).lower(*_on(devices[0], (state, ring, envs, keys))).compile()
    passes = _whole_leaf_passes(compiled.as_text(), traffic["ring_rows"])
    assert sorted(name for name, _ in passes) == [
        "copy bf16[32,1000000,17]", "copy bf16[32,1000000,17]",
        "copy bf16[32,1000000,6]",
    ]


def _population_programs(devices, members):
    """The population burst and the fused population epoch at the
    reference configuration: neither holds a scatter over a ring."""
    from torch_actor_critic_tpu.envs.ondevice import get_on_device_env
    from torch_actor_critic_tpu.parallel.population import PopulationLearner
    from torch_actor_critic_tpu.sac.ondevice import (
        PopulationOnDeviceLoop,
        _wrap_and_build,
    )

    cfg = SACConfig()
    env_cls, sac = _wrap_and_build(get_on_device_env("cheetah-run-jax"), cfg)
    loop = PopulationOnDeviceLoop(sac, env_cls, n_members=members, n_envs=16)
    state, ring, env_states, act_keys, _ = jax.eval_shape(
        lambda: loop.init(jax.random.key(0), cfg.buffer_size)
    )
    chunk = _chunk_of(ring, cfg.update_every)
    burst = PopulationLearner(sac, members)._build_burst(cfg.update_every)
    epoch = loop._build_epoch(2 * cfg.update_every, cfg.update_every, False)
    for program, args in (
        (burst, (state, ring, chunk)),
        (epoch, (state, ring, env_states, act_keys)),
    ):
        compiled = program.lower(*_on(devices[0], args)).compile()
        assert compiled.memory_analysis().alias_size_in_bytes >= nbytes(ring)
        assert _ring_scatters(compiled.as_text(), cfg.buffer_size) == []


CASES = [
    pytest.param(_population_programs, (8,), id="population-burst-and-epoch"),
    pytest.param(
        _visual_burst_passes_over_no_frame_leaf, (),
        id="no-whole-leaf-pass-visual-burst",
    ),
    pytest.param(
        _population_epoch_keeps_its_three_passes, (),
        id="no-whole-leaf-pass-population-epoch",
    ),
]

@pytest.mark.parametrize("compile_case, args", CASES)
def test_compiles_for_v5e(v5e, compile_case, args):
    compile_case(v5e, *args)
