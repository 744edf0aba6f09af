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
import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# The longest test takes 165 s under the driver's load (ROADMAP.md, "Tier-1
# verify"). One that waits on something that never comes costs itself and
# these ten minutes, not the run's clock and every test behind it.
TEST_LIMIT_SECONDS = 600


@contextlib.contextmanager
def limited():
    """Fail what runs inside once ``TEST_LIMIT_SECONDS`` of wall-clock have
    passed (``SIGALRM`` from the real-time timer: a wait in Python, a sleep,
    a child's ``wait`` or a socket is interrupted, a call that never comes
    back from native code is not). The timer is off and the handler there
    was is back afterwards. Signals reach the main thread alone, so off it
    this arms nothing; pytest-xdist runs a worker's tests on its main thread."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    seconds = TEST_LIMIT_SECONDS

    def too_long(signum, frame):
        pytest.fail(f"still running at the limit of {seconds:g} s a test", pytrace=False)

    previous = signal.signal(signal.SIGALRM, too_long)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def within_the_limit():
    with limited():
        yield


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
