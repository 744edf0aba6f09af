"""Test configuration: run everything on a virtual 8-device CPU mesh.

This is the capability the reference's test suite lacks entirely (its
MPI path silently degrades to no-ops when ``num_procs()==1``, ref
``sac/mpi.py:79-80,94-95``, so no distributed code is ever exercised in
CI — SURVEY.md §4). Forcing 8 XLA host devices gives real
``shard_map``/``psum`` collective semantics to every distributed test
without TPU hardware.

Must set env vars before jax is imported anywhere.
"""

import os
import re

os.environ["JAX_PLATFORMS"] = "cpu"
# No GL stack in this container: mujoco's default EGL probe dies with an
# opaque AttributeError at dm_control import. Physics needs no renderer;
# tests that render go through paths that tolerate a disabled backend.
os.environ.setdefault("MUJOCO_GL", "disabled")
# The suite assumes exactly 8 virtual devices; strip any externally-set
# device-count flag rather than half-honoring it and failing later.
_flags = re.sub(
    r"--xla_force_host_platform_device_count=\d+",
    "",
    os.environ.get("XLA_FLAGS", ""),
)
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

assert jax.device_count() == 8, jax.devices()


import contextlib  # noqa: E402

import pytest  # noqa: E402


@contextlib.contextmanager
def _compile_cache_at(path):
    """What a process started with ``JAX_COMPILATION_CACHE_DIR=path``
    sees. jax reads the variable when it is imported, so a test that
    wants the persistent cache somewhere of its own sets the variable
    AND the config value it would have produced; the program under test
    (aot/cache.py) then finds the variable set and writes no directory
    itself. Everything is put back on exit, cache off included."""
    from jax.experimental.compilation_cache import compilation_cache

    path = str(path)
    os.makedirs(path, exist_ok=True)
    prev_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    prev_cfg = jax.config.jax_compilation_cache_dir
    prev_min = (
        jax.config.jax_persistent_cache_min_compile_time_secs,
        jax.config.jax_persistent_cache_min_entry_size_bytes,
    )
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache.reset_cache()
    try:
        yield path
    finally:
        if prev_env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = prev_env
        jax.config.update("jax_compilation_cache_dir", prev_cfg)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min[0]
        )
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", prev_min[1]
        )
        compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def compile_cache_at():
    """The :func:`_compile_cache_at` context manager, for tests of the
    persistent compilation cache (session-scoped so module fixtures may
    use it)."""
    return _compile_cache_at
