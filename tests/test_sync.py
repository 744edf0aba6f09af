"""utils/sync.drain: the host-fetch barrier every timing site uses.

``drain`` waits for the device by demanding a *value* — bytes that
cannot exist before the producer ran — and returns it. The second half
of this file holds it to that on a stub backend whose arrays execute
only when their value is asked for: whatever else a backend offers for
waiting, ``drain`` must fetch.
"""

import types

import numpy as np


def test_drain_returns_the_reduced_value():
    """drain() returns the reduced value of what it waited for."""
    import jax.numpy as jnp

    from torch_actor_critic_tpu.utils.sync import drain

    x = jnp.arange(8.0)
    assert drain(x) == 28.0
    assert drain(jnp.float32(3.5)) == 3.5
    assert drain(2) == 2.0


class LazyBackendArray:
    """An array on a stub backend where execution is deferred until the
    array's value is demanded (``__array__``); its ``block_until_ready``
    only counts its calls. ``drain`` is a value fetch, so it must run
    the producer here without ever touching that method."""

    def __init__(self, values):
        self._values = np.asarray(values, np.float32)
        self._result = None
        self.block_until_ready_calls = 0
        self.is_fully_addressable = True

    @property
    def executed(self) -> bool:
        return self._result is not None

    def block_until_ready(self):
        self.block_until_ready_calls += 1
        return self

    def __array__(self, dtype=None, copy=None):
        if self._result is None:
            self._result = self._values  # "executes" the producer
        return np.asarray(self._result, dtype=dtype)


def _install_lazy_backend(monkeypatch):
    """Point utils.sync at the lazy backend: isinstance dispatch sees
    LazyBackendArray as the device array type, and the reduction is a
    host-side value fetch (what jnp.sum + float() amounts to on a real
    backend once the bytes must cross the wire)."""
    from torch_actor_critic_tpu.utils import sync

    monkeypatch.setattr(
        sync,
        "jax",
        types.SimpleNamespace(
            Array=LazyBackendArray,
            # drain fetches through the EXPLICIT transfer API (legal
            # under the --sanitize transfer guard); on this backend a
            # device_get is a value fetch like __array__ — it demands
            # bytes, so it runs the producer.
            device_get=lambda x: np.asarray(x),
        ),
    )
    monkeypatch.setattr(
        sync,
        "jnp",
        types.SimpleNamespace(
            sum=lambda x, dtype=None: np.sum(np.asarray(x), dtype=dtype),
            float32=np.float32,
        ),
    )
    return sync


def test_drain_fetches_the_value(monkeypatch):
    sync = _install_lazy_backend(monkeypatch)
    x = LazyBackendArray([1.0, 2.0, 3.0])
    assert not x.executed
    assert sync.drain(x) == 6.0
    # By the time drain returns, the producer HAS run — because the
    # value was fetched, not because some other wait was called.
    assert x.executed
    assert x.block_until_ready_calls == 0


def test_drain_reduces_in_f32():
    """The returned value is usable as a checksum: the reduction runs
    in float32 whatever the array's dtype (a bf16 sum stops counting
    exactly at 256, a uint8 sum wraps)."""
    import jax.numpy as jnp

    from torch_actor_critic_tpu.utils.sync import drain

    assert drain(jnp.ones((1000,), jnp.bfloat16)) == 1000.0
    assert drain(jnp.full((4,), 200, jnp.uint8)) == 800.0


def test_drain_multihost_shard_fetch_also_executes(monkeypatch):
    """The not-fully-addressable branch drains via a local-shard fetch,
    which must equally demand bytes (run the producer)."""
    sync = _install_lazy_backend(monkeypatch)
    shard = LazyBackendArray([4.0, 5.0])
    x = LazyBackendArray([0.0])  # container; only shards are fetched
    x.is_fully_addressable = False
    x.addressable_shards = [types.SimpleNamespace(data=shard)]
    assert sync.drain(x) == 9.0
    assert shard.executed
    assert not x.executed  # only the local shard crosses the wire
    assert shard.block_until_ready_calls == 0
