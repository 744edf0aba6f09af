"""aot/: kill the cold start — compilation as a build artifact.

Every spawned serve worker, hot-reload to a new bucket, restarted
learner and respawned actor used to pay live XLA compiles (the
diagnostics/ watchdog counts them; serve warmup only hides them behind
wall-clock). This subsystem makes compilation a **build artifact**:

- :mod:`~torch_actor_critic_tpu.aot.manifest` — the set of programs to
  pre-compile, derived from the checked
  ``reachability.ENTRY_POINTS`` / ``contracts.ENTRY_POINT_CONTRACTS``
  tables plus the serve bucket ladder. The tables ARE the manifest; a
  new entry point cannot ship without declaring its bundleability
  (`stale-bundle-manifest` lint).
- :mod:`~torch_actor_critic_tpu.aot.bundle` — a ``warm_start`` bundle
  next to the Orbax checkpoint: ``jax.export``-serialized programs,
  built while populating the persistent compilation cache and stamped
  with a compatibility fingerprint. A mismatched bundle is rejected loudly
  and counted; serving falls back to live compile.
- :mod:`~torch_actor_critic_tpu.aot.cache` — the persistent
  compilation cache shared by fleet workers and restarted learners
  (one directory per checkout, placed from outside by
  ``JAX_COMPILATION_CACHE_DIR``), hit/miss counters surfaced through the watchdog onto ``/metrics``
  and metrics.jsonl.
- :mod:`~torch_actor_critic_tpu.aot.prefork` — a pre-forked warm
  worker pool for the fleet router (``serve.py --warm-pool N``):
  scale-up and kill-replacement draw an already-warm process instead
  of paying spawn+compile.

Success metric: time-to-first-act for a fresh worker with vs without
a bundle (not measured on the chip yet: ROADMAP W5), and
``live_compiles == 0`` through a full chaos flood (docs/SERVING.md
"Cold start & warm-start bundles").
"""

from torch_actor_critic_tpu.aot.bundle import (
    BundleMismatchError,
    WarmStartBundle,
    build_bundle,
    default_bundle_dir,
    emit_bundle,
    load_bundle,
)
from torch_actor_critic_tpu.aot.cache import (
    cache_dir,
    enable_persistent_cache,
)
from torch_actor_critic_tpu.aot.manifest import (
    ManifestError,
    bundled_entry_points,
    entry_point_table,
    serve_programs,
)
from torch_actor_critic_tpu.aot.prefork import WarmPool

__all__ = [
    "BundleMismatchError",
    "WarmStartBundle",
    "build_bundle",
    "default_bundle_dir",
    "emit_bundle",
    "load_bundle",
    "cache_dir",
    "enable_persistent_cache",
    "ManifestError",
    "bundled_entry_points",
    "entry_point_table",
    "serve_programs",
    "WarmPool",
]
