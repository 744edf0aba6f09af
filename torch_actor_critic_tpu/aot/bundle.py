"""warm_start bundles: compilation as a checkpoint-adjacent artifact.

A bundle is a directory shipped **next to the Orbax checkpoint**
(``<run>/checkpoints`` -> ``<run>/warm_start``) holding everything a
fresh worker needs to answer its first request without a live compile:

``MANIFEST.json``
    Format version, the environment **fingerprint** (jaxlib version,
    backend, device kind/count, mesh shape), the bucket ladder, and a
    per-program index with each program's abstract input avals.
``programs/*.jexp``
    One ``jax.export``-serialized program per manifest entry — the
    portable, *verifiable* half of the bundle. Consumers do not serve
    through ``Exported.call`` (that would re-trace a second program and
    break the bitwise pin between warmup and live dispatch); they
    deserialize to check avals against their own jit programs, and the
    round-trip test proves bitwise agreement with the live compile.

The compiled executables themselves are not in the bundle: building one
runs the REAL ``PolicyEngine`` warmup with the persistent compilation
cache on (:mod:`~torch_actor_critic_tpu.aot.cache` — one directory per
checkout, placed by ``JAX_COMPILATION_CACHE_DIR``), so every consumer on
the same cache that dispatches the same jit programs gets disk hits
instead of XLA runs. That is the mechanism that delivers
``live_compiles == 0``; the bundle is the verified index of what was
put there.

A bundle whose fingerprint or avals disagree with the consuming
process is **rejected loudly** (:class:`BundleMismatchError`), counted
on the watchdog (``bundle_rejected``), and the worker falls back to a
plain live-compile warmup — a stale bundle may cost the cold start
back, never correctness.
"""

from __future__ import annotations

import json
import logging
import os
import pathlib
import typing as t

logger = logging.getLogger(__name__)

__all__ = [
    "BUNDLE_FORMAT",
    "BundleMismatchError",
    "WarmStartBundle",
    "build_bundle",
    "default_bundle_dir",
    "emit_bundle",
    "environment_fingerprint",
    "load_bundle",
]

BUNDLE_FORMAT = 1

_MANIFEST = "MANIFEST.json"
_PROGRAMS = "programs"


class BundleMismatchError(RuntimeError):
    """The bundle does not fit this process (wrong jaxlib / devices /
    avals / missing program). Callers catch this, count it on the
    watchdog, and fall back to live compile."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def environment_fingerprint(
    mesh_shape: t.Sequence[int] | None = None,
) -> t.Dict[str, t.Any]:
    """What must match between the process that built a bundle and the
    process consuming it for the serialized programs (and the
    persistent-cache keys behind them) to be valid."""
    import jax
    import jaxlib

    return {
        "format": BUNDLE_FORMAT,
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "unknown"),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "mesh_shape": list(mesh_shape) if mesh_shape else None,
    }


def check_fingerprint(
    stored: t.Mapping[str, t.Any],
    mesh_shape: t.Sequence[int] | None = None,
) -> None:
    """Raise :class:`BundleMismatchError` naming every field on which
    ``stored`` disagrees with this process's fingerprint."""
    current = environment_fingerprint(mesh_shape)
    mismatched = [
        f"{key}: bundle={stored.get(key)!r} here={current[key]!r}"
        for key in current
        if stored.get(key) != current[key]
    ]
    if mismatched:
        raise BundleMismatchError(
            "warm-start bundle fingerprint mismatch — "
            + "; ".join(mismatched)
        )


def default_bundle_dir(ckpt_dir: str | os.PathLike) -> pathlib.Path:
    """Where a bundle lives relative to its Orbax checkpoint directory:
    a ``warm_start/`` sibling (``<run>/checkpoints`` ->
    ``<run>/warm_start``)."""
    return pathlib.Path(ckpt_dir).absolute().parent / "warm_start"


def _aval_sig(x: t.Any) -> t.List[t.Any]:
    """JSON-able (shape, dtype) signature of one abstract value."""
    return [list(int(d) for d in x.shape), str(x.dtype)]


def _flat_avals(*args: t.Any) -> t.List[t.List[t.Any]]:
    """Flattened (shape, dtype) signatures of a call's arguments, in
    ``jax.export`` flattening order (tree_leaves of the args tuple)."""
    import jax

    return [_aval_sig(leaf) for leaf in jax.tree_util.tree_leaves(args)]


class WarmStartBundle:
    """A loaded (but not yet verified) bundle directory."""

    def __init__(self, root: pathlib.Path, manifest: t.Dict[str, t.Any]):
        self.root = pathlib.Path(root)
        self.manifest = manifest

    # ----------------------------------------------------------- layout

    @property
    def fingerprint(self) -> t.Dict[str, t.Any]:
        return dict(self.manifest.get("fingerprint", {}))

    @property
    def buckets(self) -> t.Tuple[int, ...]:
        return tuple(int(b) for b in self.manifest.get("buckets", ()))

    @property
    def deterministic_only(self) -> bool:
        return bool(self.manifest.get("deterministic_only", False))

    def programs(self) -> t.Dict[str, t.Dict[str, t.Any]]:
        return dict(self.manifest.get("programs", {}))

    # ------------------------------------------------------------ checks

    def check(self, mesh_shape: t.Sequence[int] | None = None) -> None:
        """Environment-level compatibility gate (cheap, no
        deserialization). Per-program aval checks happen in the
        engine's bundle-armed warmup."""
        check_fingerprint(self.fingerprint, mesh_shape)

    def program_avals(self, name: str) -> t.List[t.List[t.Any]]:
        entry = self.manifest.get("programs", {}).get(name)
        if entry is None:
            raise BundleMismatchError(
                f"warm-start bundle has no program {name!r} "
                f"(bundled: {sorted(self.manifest.get('programs', {}))})"
            )
        return entry["in_avals"]

    def load_program(self, name: str):
        """Deserialize one program back to a ``jax.export.Exported``.
        Raises :class:`BundleMismatchError` for a missing or
        undeserializable entry."""
        from jax import export as jax_export

        entry = self.manifest.get("programs", {}).get(name)
        if entry is None:
            raise BundleMismatchError(
                f"warm-start bundle has no program {name!r} "
                f"(bundled: {sorted(self.manifest.get('programs', {}))})"
            )
        path = self.root / _PROGRAMS / entry["file"]
        try:
            data = path.read_bytes()
        except OSError as exc:
            raise BundleMismatchError(
                f"warm-start bundle program file missing: {path} ({exc})"
            ) from exc
        try:
            return jax_export.deserialize(data)
        except Exception as exc:  # noqa: BLE001 — any corruption shape
            raise BundleMismatchError(
                f"warm-start bundle program {name!r} failed to "
                f"deserialize ({type(exc).__name__}: {exc})"
            ) from exc

    def verify_program(
        self, name: str, *call_args: t.Any
    ):
        """Deserialize ``name`` and check its input avals against the
        avals of ``call_args`` (the exact arguments the consumer's jit
        program will be dispatched with). Returns the ``Exported`` on
        success; raises :class:`BundleMismatchError` otherwise."""
        exported = self.load_program(name)
        expected = _flat_avals(*call_args)
        got = [_aval_sig(a) for a in exported.in_avals]
        if got != expected:
            raise BundleMismatchError(
                f"warm-start bundle program {name!r} aval mismatch — "
                f"bundle={got} here={expected} (model/obs-spec/bucket "
                "drift since the bundle was built)"
            )
        return exported


def load_bundle(bundle_dir: str | os.PathLike) -> WarmStartBundle:
    """Read a bundle directory's manifest. Raises ``FileNotFoundError``
    when there is no bundle there, :class:`BundleMismatchError` when
    there is one but it is unreadable or a future format."""
    root = pathlib.Path(bundle_dir)
    manifest_path = root / _MANIFEST
    if not manifest_path.is_file():
        raise FileNotFoundError(
            f"no warm-start bundle at {root} (missing {_MANIFEST})"
        )
    try:
        manifest = json.loads(manifest_path.read_text())
    except (OSError, ValueError) as exc:
        raise BundleMismatchError(
            f"warm-start bundle manifest unreadable: {manifest_path} "
            f"({exc})"
        ) from exc
    fmt = manifest.get("format")
    if fmt != BUNDLE_FORMAT:
        raise BundleMismatchError(
            f"warm-start bundle format {fmt!r} != supported "
            f"{BUNDLE_FORMAT} — rebuild the bundle with this tree"
        )
    return WarmStartBundle(root, manifest)


def build_bundle(
    bundle_dir: str | os.PathLike,
    actor_def: t.Any,
    obs_spec: t.Any,
    params: t.Any,
    max_batch: int = 64,
    buckets: t.Sequence[int] | None = None,
    deterministic_only: bool = False,
) -> WarmStartBundle:
    """Build a warm-start bundle at ``bundle_dir``.

    Instantiates a real :class:`~torch_actor_critic_tpu.serve.engine
    .PolicyEngine`, turns the persistent compilation cache on (at the
    checkout's one directory, where it stays on) and runs the engine's
    own warmup — so the cache entries are keyed by the *exact* jit
    programs every consumer dispatches — then ``jax.export``-serializes
    each manifest program for fingerprinting and bitwise verification.
    """
    import jax
    import numpy as np
    from jax import export as jax_export

    from torch_actor_critic_tpu.aot import cache as aot_cache
    from torch_actor_critic_tpu.aot.manifest import (
        entry_point_table,
        program_filename,
        serve_programs,
    )
    from torch_actor_critic_tpu.serve.engine import PolicyEngine

    root = pathlib.Path(bundle_dir)
    (root / _PROGRAMS).mkdir(parents=True, exist_ok=True)

    engine = PolicyEngine(
        actor_def, obs_spec, max_batch=max_batch, buckets=buckets,
    )

    aot_cache.enable_persistent_cache()
    # The warmup below IS the cache-population pass: every
    # (bucket, deterministic) jit program compiles once and is
    # persisted unthresholded (aot/cache.py).
    engine.warmup(params, deterministic_only=deterministic_only)

    programs: t.Dict[str, t.Dict[str, t.Any]] = {}
    # jax.export cannot serialize typed-PRNG-key avals (no
    # flatbuffer dtype kind for key<fry>), so the sampled programs
    # are exported through a raw-uint32 wrapper: the serialized
    # program takes jax.random.key_data(key) and re-wraps inside.
    # Bitwise identical to the engine's typed-key program — only
    # the calling convention of the ARTIFACT differs (the engine's
    # own jit path, which the compile cache serves, is untouched).
    key_data = jax.random.key_data(jax.random.key(0))

    def sampled_raw(params_, obs_, key_data_):
        return engine._fwd[False](
            params_, obs_, jax.random.wrap_key_data(key_data_)
        )

    sampled_raw_jit = jax.jit(sampled_raw)

    for spec in serve_programs(engine.buckets, deterministic_only):
        zero_obs = jax.tree_util.tree_map(
            lambda s: np.zeros(
                (spec.bucket,) + tuple(s.shape), s.dtype
            ),
            obs_spec,
        )
        if spec.deterministic:
            call_args: t.Tuple[t.Any, ...] = (params, zero_obs)
            fn = engine._fwd[True]
        else:
            call_args = (params, zero_obs, key_data)
            fn = sampled_raw_jit
        exported = jax_export.export(fn)(*call_args)
        fname = program_filename(spec.name)
        (root / _PROGRAMS / fname).write_bytes(exported.serialize())
        programs[spec.name] = {
            "file": fname,
            "identity": spec.identity,
            "bucket": spec.bucket,
            "deterministic": spec.deterministic,
            "in_avals": _flat_avals(*call_args),
        }

    entries = aot_cache.cache_entries()
    if entries == 0:
        logger.warning(
            "warm-start bundle %s: the compile cache at %s is EMPTY "
            "after warmup — persistent-cache writes are being skipped "
            "on this backend; consumers will fall back to live "
            "compiles", root, aot_cache.cache_dir(),
        )
    manifest = {
        "format": BUNDLE_FORMAT,
        "fingerprint": environment_fingerprint(),
        "buckets": [int(b) for b in engine.buckets],
        "max_batch": int(engine.max_batch),
        "deterministic_only": bool(deterministic_only),
        "entry_points": entry_point_table(),
        "cache_entries": entries,
        "programs": programs,
    }
    (root / _MANIFEST).write_text(json.dumps(manifest, indent=2))
    logger.info(
        "warm-start bundle built: %s (%d programs, %d cache entries)",
        root, len(programs), entries,
    )
    return WarmStartBundle(root, manifest)


def emit_bundle(
    ckpt_dir: str | os.PathLike,
    actor_def: t.Any,
    obs_spec: t.Any,
    params: t.Any,
    **kwargs: t.Any,
) -> WarmStartBundle:
    """Build the bundle at its checkpoint-adjacent default location
    (the learner's ``--emit-bundle`` path)."""
    return build_bundle(
        default_bundle_dir(ckpt_dir), actor_def, obs_spec, params,
        **kwargs,
    )
