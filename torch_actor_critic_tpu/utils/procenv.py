"""Environments for child processes: one process for each chip.

A chip belongs to one process at a time, and JAX reads ``JAX_PLATFORMS``
when it is imported — so which device a child may touch is decided by
the environment it *starts* with, never by a call it makes later.

- Host-side helpers (env workers, actor processes, CPU stages) start
  held to the CPU — :func:`cpu_env` for a ``subprocess``,
  :func:`spawning_on_cpu` around a ``multiprocessing`` ``start()`` —
  and can never reach for a chip their parent holds.
- Replicas that each serve from their own chip start under
  :func:`chip_env`.

Nothing here touches a backend: the parent of chip-using children may
call it.
"""

from __future__ import annotations

import contextlib
import os
import typing as t

__all__ = ["PACKAGE_ROOT", "chip_env", "cpu_env", "spawning_on_cpu"]

# The checkout (parent of the package directory): spawn children boot a
# fresh interpreter that does not inherit sys.path, so a source checkout
# reaches them through PYTHONPATH.
PACKAGE_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_CPU_ONLY = {"JAX_PLATFORMS": "cpu"}


def cpu_env() -> t.Dict[str, str]:
    """This process's environment with JAX held to the CPU."""
    return {**os.environ, **_CPU_ONLY}


def chip_env(chip: int) -> t.Dict[str, str]:
    """This process's environment with libtpu shown local chip ``chip``
    and no other, as a one-chip process of its own (the bounds variables
    are what let several libtpu loads share one host)."""
    return {
        **os.environ,
        "TPU_VISIBLE_CHIPS": str(int(chip)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


@contextlib.contextmanager
def spawning_on_cpu():
    """Hold ``multiprocessing`` children started inside to the CPU, with
    the checkout on their ``PYTHONPATH``: they snapshot ``os.environ``
    at ``start()``, so it is changed for that long and then put back."""
    overrides = {
        **_CPU_ONLY,
        "PYTHONPATH": PACKAGE_ROOT + (
            os.pathsep + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH") else ""
        ),
    }
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
