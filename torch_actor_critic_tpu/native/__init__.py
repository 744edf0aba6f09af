"""ctypes loader for the native runtime (``libtacrt.so``).

The library is one translation unit with no dependencies and is never
committed: it is built from ``tac_runtime.cpp`` with ``g++`` into the
package directory (~1 s) whenever it is missing or older than its
source, so what runs is always what git holds. Set ``TAC_NATIVE_LIB``
to load a specific build instead (the ASan variant from
``make native-asan``).

A library that cannot be built or loaded is a
:class:`NativeRuntimeError` on the path that asked for it
(``parallel_envs``) — there is no silent Python path behind it.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sys
import tempfile
import threading
from pathlib import Path

logger = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).parent
_LOCK = threading.Lock()
_CACHE: dict = {}

SOURCES = [_NATIVE_DIR / "tac_runtime.cpp"]


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.tac_store_wake.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.tac_store_wake.restype = None
    lib.tac_load.argtypes = [ctypes.c_void_p]
    lib.tac_load.restype = ctypes.c_int32
    lib.tac_wait_ne.argtypes = [ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64]
    lib.tac_wait_ne.restype = ctypes.c_int
    lib.tac_wait_all_eq.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int64,
        ctypes.c_int64,
    ]
    lib.tac_wait_all_eq.restype = ctypes.c_int
    return lib


class NativeRuntimeError(RuntimeError):
    """The native runtime could not be built or loaded."""


def _stale(lib: Path) -> bool:
    return not lib.exists() or any(
        src.stat().st_mtime > lib.stat().st_mtime for src in SOURCES
    )


def _build(out: Path) -> None:
    # Build to a temp file then rename: concurrent builders (spawned
    # env workers racing the parent) each land a complete .so.
    with tempfile.NamedTemporaryFile(
        dir=out.parent, suffix=".so.tmp", delete=False
    ) as tmp:
        tmp_path = Path(tmp.name)
    cmd = [
        os.environ.get("CXX", "g++"), "-O2", "-Wall", "-fPIC",
        "-std=c++17", "-shared", "-o", str(tmp_path),
        *[str(s) for s in SOURCES],
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp_path, out)
    except (OSError, subprocess.SubprocessError) as e:
        tmp_path.unlink(missing_ok=True)
        detail = getattr(e, "stderr", b"") or b""
        raise NativeRuntimeError(
            f"building {out.name} failed ({e}): "
            f"{detail.decode(errors='replace')[-500:]}"
        ) from e


def load_runtime() -> ctypes.CDLL:
    """Load the native runtime, building it first when the ``.so`` is
    missing or older than its source. Raises
    :class:`NativeRuntimeError` when that is impossible."""
    with _LOCK:
        if "lib" in _CACHE:
            return _CACHE["lib"]
        if not sys.platform.startswith("linux"):
            raise NativeRuntimeError(
                f"the native runtime is futex-based (Linux only); this "
                f"is {sys.platform}"
            )
        override = os.environ.get("TAC_NATIVE_LIB")
        path = Path(override) if override else _NATIVE_DIR / "libtacrt.so"
        if not override and _stale(path):
            _build(path)
        try:
            _CACHE["lib"] = _declare(ctypes.CDLL(str(path)))
        except OSError as e:
            raise NativeRuntimeError(f"loading {path} failed: {e}") from e
        return _CACHE["lib"]
