"""The persistent XLA compilation cache: one directory, placed from outside.

JAX's persistent compilation cache keys a compiled executable on the
program HLO + compile options + backend identity, so two *processes*
compiling the same jit program share one entry: a fleet worker spawned
after the first one, a learner restarted after preemption, or the next
run of the same command retrieves the executable from disk instead of
re-running XLA. The directory is part of how an entry is found, so it
never moves:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself read it at
  import and the program uses that directory — nothing here or anywhere
  else writes ``jax_compilation_cache_dir``;
- where it is not, the directory is ``<checkout>/.jax_cache``
  (git-ignored), never a temp name, pid or timestamp.

:func:`cache_dir` is the one place that decides; ``train.py``,
``serve.py``, ``chip_smoke.py`` and every process they spawn go through
:func:`enable_persistent_cache`, so parent and children agree without
handing a path down.

One setting rides along, identical in every process because a
cache-affecting knob that differs between writer and reader silently
forks the key space: compiles are persisted **unthresholded** (the jax
defaults skip programs that compile in under a second, which is most
serve-bucket programs).

Hit/miss counters ride the watchdog
(:mod:`~torch_actor_critic_tpu.diagnostics.watchdog` listens for the
``/jax/compilation_cache/cache_{hits,misses}`` monitoring events) onto
``/metrics`` and metrics.jsonl.
"""

from __future__ import annotations

import logging
import os

from torch_actor_critic_tpu.utils.procenv import PACKAGE_ROOT

logger = logging.getLogger(__name__)

__all__ = [
    "CACHE_DIR_ENV",
    "cache_dir",
    "cache_entries",
    "enable_persistent_cache",
]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """Where this checkout's compiled programs are kept: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
    Touches no backend — the parent of chip-using children may ask."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(
        PACKAGE_ROOT, ".jax_cache"
    )


def enable_persistent_cache() -> str:
    """Turn the persistent compilation cache on at :func:`cache_dir`
    and arm the watchdog's hit/miss counters. Returns the directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

    path = cache_dir()
    if not os.environ.get(CACHE_DIR_ENV):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax decides once per process whether the cache is in use; a
    # process that already compiled something must be asked again.
    compilation_cache.reset_cache()
    # Counters must be live before the first compile probes the cache.
    get_watchdog().install()
    logger.info("persistent compilation cache: %s", path)
    return path


def cache_entries() -> int:
    """Number of persisted executables under :func:`cache_dir` (0 for a
    missing directory) — the bundle builder's check that its warmup
    really was written."""
    path = cache_dir()
    if not os.path.isdir(path):
        return 0
    return sum(
        1 for name in os.listdir(path)
        if os.path.isfile(os.path.join(path, name))
        and not name.endswith("-atime")
    )
