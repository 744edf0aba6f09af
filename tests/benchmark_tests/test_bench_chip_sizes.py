"""The cells' programs compiled for a described v5e at the cells' own sizes
(on-chip-measurement guide, section 2): what the chip's compiler would refuse
for want of memory is refused here, on every later PR, at no chip time.
Nothing runs; a compile that passes is not a chip run."""

import os

import jax
import jax.numpy as jnp
import pytest
from bench_cut import ROOT  # noqa: F401

from benchmark.harness import flops, registry

GIB = 2**30


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"the v5e:2x2 topology cannot be described here: {e!r}")
    return topo.devices


@pytest.fixture
def chip_compiler(monkeypatch):
    """Cache off (a described chip's entries cannot be read back), and the
    program's trace-time guards told that the target is a TPU."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(device, tree):
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def test_burst_cell_fits_a_v5e(v5e, chip_compiler):
    from benchmark.drivers import _common
    from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
    from torch_actor_critic_tpu.core.types import BufferState
    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC
    from torch_actor_critic_tpu.parallel.mesh import make_mesh
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    _, cell, config = registry.resolve("wallrunner_cnn_burst")
    cfg = _common.sac_config(config, cell)
    env = _common.EnvSpec(config["model"])
    actor_def, critic_def = build_models(cfg, env)
    sac = make_learner(cfg, actor_def, critic_def, env.act_dim)
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
    compiled = learner._build_burst(cfg.update_every, state, ring, chunk).lower(
        state, ring, chunk
    ).compile()  # the compiler raises RESOURCE_EXHAUSTED on what does not fit
    mem = compiled.memory_analysis()
    at_rest = cell["traffic"]["ring_rows"] * flops.row_bytes(config["model"])
    assert mem.argument_size_in_bytes >= at_rest >= 4 * GIB
    assert mem.alias_size_in_bytes >= at_rest  # the ring is updated in place


def test_fused_cell_fits_a_v5e(v5e, chip_compiler):
    from benchmark.drivers import _common
    from torch_actor_critic_tpu.envs.ondevice import get_on_device_env
    from torch_actor_critic_tpu.sac.ondevice import PopulationOnDeviceLoop, _wrap_and_build

    _, cell, config = registry.resolve("cheetah_pop32_fused")
    traffic = cell["traffic"]
    cfg = _common.sac_config(config, cell)
    env_cls, sac = _wrap_and_build(get_on_device_env(traffic["env"]), cfg)
    loop = PopulationOnDeviceLoop(
        sac, env_cls, n_members=cfg.population, n_envs=traffic["n_envs"]
    )
    state, ring, envs, keys, _ = jax.eval_shape(
        lambda k: loop.init(k, buffer_capacity=traffic["ring_rows"]), jax.random.key(0)
    )
    args = _on(v5e[0], (state, ring, envs, keys))
    compiled = loop._build_epoch(
        traffic["steps_per_dispatch"], cfg.update_every, False
    ).lower(*args).compile()
    mem = compiled.memory_analysis()
    at_rest = cfg.population * traffic["ring_rows"] * flops.row_bytes(config["model"])
    assert mem.argument_size_in_bytes >= at_rest >= 4 * GIB
    assert mem.alias_size_in_bytes >= at_rest
