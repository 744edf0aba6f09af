"""Serving subsystem tests: engine bucketing, micro-batcher edge cases,
checkpoint hot-reload under fire, and the HTTP frontend.

All CPU (conftest pins JAX_PLATFORMS=cpu), all against the in-process
stack; the only sockets are the HTTP round-trip test's loopback.
"""

import json
import shutil
import threading
import time
from urllib import request as urlreq

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.core.types import MultiObservation
from torch_actor_critic_tpu.models import Actor, VisualActor
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.serve import (
    MicroBatcher,
    ModelRegistry,
    PolicyServer,
)
from torch_actor_critic_tpu.serve.engine import PolicyEngine, default_buckets
from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
from torch_actor_critic_tpu.utils.config import SACConfig

OBS_DIM, ACT_DIM = 17, 6


def make_actor_and_params(seed=0, act_dim=ACT_DIM, hidden=(32, 32)):
    actor = Actor(act_dim=act_dim, hidden_sizes=hidden)
    params = actor.init(
        jax.random.key(seed), jnp.zeros((OBS_DIM,)), jax.random.key(1)
    )
    return actor, params


def flat_spec():
    return jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)


def assert_matches_unbatched(got, want):
    """A padded bucket forward (a gemm) against the unbatched model
    apply (a matvec): two differently batched computations of the same
    numbers reduce in different orders, and XLA promises no bitwise
    equality between them (the drift on jaxlib 0.9.0 is ~1e-7
    relative). They agree to float32 round-off; what IS pinned bitwise
    in this file is computed at equal shapes."""
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-7
    )


def make_registry(max_batch=8, warmup=False, **kw):
    actor, params = make_actor_and_params(**kw)
    reg = ModelRegistry()
    reg.register(
        "default", actor, flat_spec(), params=params,
        max_batch=max_batch, warmup=warmup,
    )
    return reg, actor, params


# ------------------------------------------------------------------ engine


def test_default_buckets_power_of_two():
    assert default_buckets(64) == (2, 4, 8, 16, 32, 64)
    # the ladder always starts at 2 — a max_batch=1 engine pads its
    # lone request up to the 2-row bucket so responses stay
    # batch-shape invariant (batch-1 matvec vs gemm last-bit drift)
    assert default_buckets(1) == (2,)
    assert default_buckets(2) == (2,)
    # non-power-of-two max rounds the top bucket up, never down
    assert default_buckets(48)[-1] == 64


def test_engine_bucket_padding_bitwise_matches_unbatched_forward():
    """The acceptance bar: a padded bucket forward returns, row for
    row, what the unbatched model apply returns (to float32 round-off:
    the shapes differ), and — at equal shapes, bitwise — the same rows
    whatever else shares their bucket: the ops are row-wise, so padding
    rows cannot leak into real rows."""
    actor, params = make_actor_and_params()
    eng = PolicyEngine(actor, flat_spec(), max_batch=16)
    obs = np.random.default_rng(0).standard_normal((5, OBS_DIM)).astype(
        np.float32
    )
    batched = eng.act(params, obs, deterministic=True)  # bucket 8, pad 3
    assert eng.bucket_for(5) == 8
    for i in range(5):
        single, _ = actor.apply(
            params, jnp.asarray(obs[i]), None,
            deterministic=True, with_logprob=False,
        )
        assert_matches_unbatched(batched[i], single)
    # Same bucket (8), other company: 2 more real rows where the
    # padding was. Same program, same shape — the first 5 rows must not
    # move by a bit.
    crowded = np.concatenate([obs, 7.0 * np.ones((2, OBS_DIM), np.float32)])
    assert eng.bucket_for(7) == 8
    np.testing.assert_array_equal(
        eng.act(params, crowded, deterministic=True)[:5], batched
    )


def test_engine_visual_pytree_obs():
    """VisualActor (MultiObservation pytree) serves through the same
    engine; padded rows match the unbatched forward to float32
    round-off (XLA convs reduce in batch-shape-dependent order, so
    exact bitwise holds only for the flat MLP stack)."""
    actor = VisualActor(
        act_dim=4, hidden_sizes=(32, 32), filters=(8, 16),
        kernel_sizes=(4, 3), strides=(2, 1), cnn_dense_size=32,
    )
    spec = MultiObservation(
        features=jax.ShapeDtypeStruct((7,), jnp.float32),
        frame=jax.ShapeDtypeStruct((24, 24, 3), jnp.uint8),
    )
    zero = MultiObservation(
        features=np.zeros((7,), np.float32),
        frame=np.zeros((24, 24, 3), np.uint8),
    )
    params = actor.init(jax.random.key(0), zero, jax.random.key(1))
    eng = PolicyEngine(actor, spec, max_batch=8)
    rng = np.random.default_rng(1)
    obs = MultiObservation(
        features=rng.standard_normal((3, 7)).astype(np.float32),
        frame=rng.integers(0, 256, (3, 24, 24, 3), dtype=np.uint8),
    )
    batched = eng.act(params, obs, deterministic=True)
    assert batched.shape == (3, 4)
    for i in range(3):
        single, _ = actor.apply(
            params,
            MultiObservation(
                features=jnp.asarray(obs.features[i]),
                frame=jnp.asarray(obs.frame[i]),
            ),
            None, deterministic=True, with_logprob=False,
        )
        np.testing.assert_allclose(
            batched[i], np.asarray(single), rtol=1e-5, atol=1e-6
        )


def test_engine_warmup_compiles_every_bucket():
    actor, params = make_actor_and_params()
    eng = PolicyEngine(actor, flat_spec(), max_batch=4)
    warmed = eng.warmup(params)
    assert set(warmed) == {(b, d) for b in (2, 4) for d in (True, False)}
    assert eng.compiled_buckets() == frozenset(warmed)


def test_engine_rejects_oversized_batch():
    actor, params = make_actor_and_params()
    eng = PolicyEngine(actor, flat_spec(), max_batch=4)
    with pytest.raises(ValueError, match="split"):
        eng.act(params, np.zeros((5, OBS_DIM), np.float32))


# ----------------------------------------------------------------- batcher


def test_deadline_flush_single_request():
    """One lone request must come back after ~max_wait_ms, not hang
    waiting for a full batch; its batch has occupancy 1 row."""
    reg, actor, params = make_registry(max_batch=8)
    with MicroBatcher(reg, max_batch=8, max_wait_ms=10.0) as mb:
        obs = np.ones((OBS_DIM,), np.float32)
        t0 = time.perf_counter()
        res = mb.act(obs, timeout=30.0)
        elapsed = time.perf_counter() - t0
        assert res.action.shape == (ACT_DIM,)
        # generous ceiling: compile happens on first call (no warmup
        # here); the point is that it returns at all without a second
        # request arriving.
        assert elapsed < 30.0
        snap = mb.metrics.snapshot()
        assert snap["batches_total"] == 1
        assert snap["responses_total"] == 1


def test_oversized_request_splits_and_reassembles():
    """A single request with rows > max_batch is split across engine
    calls and reassembled in order, bitwise-equal to the unbatched
    forwards (to float32 round-off: see assert_matches_unbatched)."""
    reg, actor, params = make_registry(max_batch=4)
    n = 4 * 3 + 1  # 13 rows -> chunks of 4,4,4,1
    obs = np.random.default_rng(2).standard_normal((n, OBS_DIM)).astype(
        np.float32
    )
    with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0) as mb:
        res = mb.act(obs, timeout=60.0)
        assert res.action.shape == (n, ACT_DIM)
        snap = mb.metrics.snapshot()
        assert snap["batches_total"] == 4  # ceil(13/4)
    for i in range(n):
        single, _ = actor.apply(
            params, jnp.asarray(obs[i]), None,
            deterministic=True, with_logprob=False,
        )
        assert_matches_unbatched(res.action[i], single)


def test_concurrent_requests_coalesce_and_multiple_buckets():
    """Concurrent callers coalesce into shared forwards; across the
    run, >= 2 distinct bucket sizes get exercised through ONE engine,
    and every response matches its own unbatched forward."""
    reg, actor, params = make_registry(max_batch=8)
    engine, _, _ = reg.acquire("default")
    # Continuous batching admits whatever queued up DURING the previous
    # forward, so on a fast host a herd of tiny forwards may never
    # coalesce. Give each forward a floor so the herd does queue behind
    # it — the coalescing is then by construction, not by luck.
    fast_act = engine.act

    def act(*args, **kwargs):
        time.sleep(0.02)
        return fast_act(*args, **kwargs)

    engine.act = act
    rng = np.random.default_rng(3)
    all_obs = rng.standard_normal((24, OBS_DIM)).astype(np.float32)
    results = {}
    with MicroBatcher(reg, max_batch=8, max_wait_ms=20.0) as mb:
        # Phase 1: a lone request (deadline flush -> bucket 1).
        results[0] = mb.act(all_obs[0], timeout=60.0)
        # Phase 2: a thread herd (coalesces -> larger buckets).
        def call(i):
            results[i] = mb.act(all_obs[i], timeout=60.0)
        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(1, 24)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        snap = mb.metrics.snapshot()
    assert len(results) == 24
    buckets_used = {b for b, _ in engine.compiled_buckets()}
    assert len(buckets_used) >= 2, buckets_used
    assert snap["responses_total"] == 24
    assert snap["errors_total"] == 0
    # mean occupancy is meaningful and in range
    assert 0 < snap["mean_batch_occupancy"] <= 1.0
    for i, res in results.items():
        single, _ = actor.apply(
            params, jnp.asarray(all_obs[i]), None,
            deterministic=True, with_logprob=False,
        )
        assert_matches_unbatched(res.action, single)


def test_sampled_actions_need_key_and_vary():
    reg, actor, params = make_registry(max_batch=4)
    obs = np.ones((OBS_DIM,), np.float32)
    with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0, seed=7) as mb:
        a1 = mb.act(obs, deterministic=False, timeout=60.0).action
        a2 = mb.act(obs, deterministic=False, timeout=60.0).action
        d = mb.act(obs, deterministic=True, timeout=60.0).action
    assert not np.array_equal(a1, a2)  # fresh key per forward
    assert not np.array_equal(a1, d)


def test_unknown_slot_raises_immediately():
    reg, _, _ = make_registry()
    with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0) as mb:
        with pytest.raises(KeyError, match="unknown model slot"):
            mb.act(np.ones((OBS_DIM,), np.float32), slot="nope")


def test_batcher_chunks_at_engine_max_batch():
    """A slot registered with a SMALLER max_batch than the batcher's
    must still serve full-size requests: chunks honor the engine's own
    bucket ceiling, not just the batcher's."""
    reg, actor, params = make_registry(max_batch=4)
    n = 10  # > engine max_batch, < batcher max_batch
    obs = np.random.default_rng(5).standard_normal((n, OBS_DIM)).astype(
        np.float32
    )
    with MicroBatcher(reg, max_batch=16, max_wait_ms=1.0) as mb:
        res = mb.act(obs, timeout=60.0)
        assert res.action.shape == (n, ACT_DIM)
        snap = mb.metrics.snapshot()
        assert snap["batches_total"] == 3  # ceil(10/4)
        assert snap["errors_total"] == 0
    for i in range(n):
        single, _ = actor.apply(
            params, jnp.asarray(obs[i]), None,
            deterministic=True, with_logprob=False,
        )
        assert_matches_unbatched(res.action[i], single)


def test_duplicate_slot_registration_raises_unless_replace():
    reg, actor, params = make_registry(max_batch=4)
    with pytest.raises(ValueError, match="already registered"):
        reg.register(
            "default", actor, flat_spec(), params=params,
            max_batch=4, warmup=False,
        )
    info = reg.register(
        "default", actor, flat_spec(), params=params,
        max_batch=4, warmup=False, replace=True,
    )
    assert info["generation"] == 0


def test_metrics_idle_window_reports_zero_rate():
    """After the first snapshot, an idle inter-snapshot window reports
    requests_per_sec == 0.0 — not a stale lifetime rate."""
    reg, _, _ = make_registry(max_batch=4)
    with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0) as mb:
        mb.act(np.ones((OBS_DIM,), np.float32), timeout=60.0)
        first = mb.metrics.snapshot()  # lifetime fallback: saw traffic
        assert first["requests_per_sec"] > 0
        time.sleep(0.01)  # idle window
        idle = mb.metrics.snapshot()
        assert idle["requests_per_sec"] == 0.0


# -------------------------------------------------------------- hot reload


def _save_checkpoint(ckpt_dir, epoch, seed):
    """Write a real TrainState checkpoint (what the trainer writes) and
    return its actor params."""
    from torch_actor_critic_tpu.models import DoubleCritic

    cfg = SACConfig(hidden_sizes=(32, 32))
    sac = SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32)),
        DoubleCritic(hidden_sizes=(32, 32)),
        ACT_DIM,
    )
    state = sac.init_state(jax.random.key(seed), jnp.zeros((OBS_DIM,)))
    ck = Checkpointer(ckpt_dir, save_buffer=False)
    try:
        ck.save(epoch, state, extra={"config": cfg.to_json()}, wait=True)
    finally:
        ck.close()
    return state.actor_params


def test_hot_reload_swaps_generation_with_inflight_requests(tmp_path):
    """The acceptance bar: a checkpoint hot-reload completes while
    requests are in flight with ZERO dropped/errored requests; the
    generation counter steps, post-swap responses match the new
    weights, and every response's generation maps it to exactly one
    params version."""
    ckpt_dir = tmp_path / "ckpts"
    params0 = _save_checkpoint(ckpt_dir, 0, seed=0)
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32))
    reg = ModelRegistry()
    info = reg.register(
        "default", actor, flat_spec(), ckpt_dir=str(ckpt_dir),
        max_batch=8, warmup=True,
    )
    assert info["epoch"] == 0
    obs = np.random.default_rng(4).standard_normal((OBS_DIM,)).astype(
        np.float32
    )
    expected = {}
    for gen, params in ((0, params0),):
        a, _ = actor.apply(
            params, jnp.asarray(obs), None,
            deterministic=True, with_logprob=False,
        )
        expected[gen] = np.asarray(a)

    stop = threading.Event()
    results, errors = [], []

    def hammer():
        with_mb_timeout = 60.0
        while not stop.is_set():
            try:
                results.append(mb.act(obs, timeout=with_mb_timeout))
            except Exception as e:  # noqa: BLE001 — the assertion below
                errors.append(e)

    with MicroBatcher(reg, max_batch=8, max_wait_ms=1.0) as mb:
        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        # let traffic flow on generation 0
        deadline = time.time() + 20.0
        while not any(r.generation == 0 for r in results):
            assert time.time() < deadline, "no gen-0 traffic"
            time.sleep(0.01)
        # write epoch 1 with different weights and hot-reload
        params1 = _save_checkpoint(ckpt_dir, 1, seed=123)
        a1, _ = actor.apply(
            params1, jnp.asarray(obs), None,
            deterministic=True, with_logprob=False,
        )
        expected[1] = np.asarray(a1)
        out = reg.reload()
        assert out["default"]["reloaded"] is True
        assert out["default"]["generation"] == 1
        assert out["default"]["epoch"] == 1
        # traffic must reach generation 1
        deadline = time.time() + 20.0
        while not any(r.generation == 1 for r in results):
            assert time.time() < deadline, "no gen-1 traffic after reload"
            time.sleep(0.01)
        stop.set()
        for t in threads:
            t.join(timeout=60.0)

    assert not errors, errors[:3]
    gens = {r.generation for r in results}
    assert gens == {0, 1}, gens  # both param versions actually served
    # every response is consistent with the params of ITS generation —
    # no torn reads, no half-swapped weights.
    assert not np.array_equal(expected[0], expected[1])
    for r in results:
        assert_matches_unbatched(r.action, expected[r.generation])
    # a second reload with no new checkpoint is a no-op
    again = reg.reload()
    assert again["default"]["reloaded"] is False
    reg.close()


def test_reload_poller_picks_up_new_epoch(tmp_path):
    ckpt_dir = tmp_path / "ckpts"
    _save_checkpoint(ckpt_dir, 0, seed=0)
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32))
    reg = ModelRegistry()
    reg.register(
        "default", actor, flat_spec(), ckpt_dir=str(ckpt_dir),
        max_batch=4, warmup=False,
    )
    reg.start_polling(interval_s=0.1)
    try:
        _save_checkpoint(ckpt_dir, 3, seed=9)
        deadline = time.time() + 30.0
        while reg.slots()["default"]["generation"] < 1:
            assert time.time() < deadline, "poller never reloaded"
            time.sleep(0.05)
        assert reg.slots()["default"]["epoch"] == 3
    finally:
        reg.close()


# -------------------------------------------------------------------- HTTP


def test_http_act_healthz_metrics_reload_roundtrip():
    reg, actor, params = make_registry(max_batch=4)
    with PolicyServer(reg, port=0, max_batch=4, max_wait_ms=1.0) as srv:
        srv.start()

        def get(path):
            return json.loads(
                urlreq.urlopen(srv.address + path, timeout=30).read()
            )

        def post(path, payload):
            req = urlreq.Request(
                srv.address + path,
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            return json.loads(urlreq.urlopen(req, timeout=30).read())

        health = get("/healthz")
        assert health["status"] == "ok"
        assert "default" in health["slots"]

        obs = np.random.default_rng(5).standard_normal(OBS_DIM).astype(
            np.float32
        )
        out = post("/act", {"obs": obs.tolist()})
        expected, _ = actor.apply(
            params, jnp.asarray(obs), None,
            deterministic=True, with_logprob=False,
        )
        np.testing.assert_allclose(
            np.asarray(out["action"], np.float32),
            np.asarray(expected),
            rtol=1e-6, atol=1e-7,  # float -> JSON decimal -> float
        )
        assert out["generation"] == 0

        snap = get("/metrics")
        assert snap["responses_total"] >= 1
        assert "p50_ms" in snap

        rel = post("/reload", {})
        assert rel["reload"]["default"]["reloaded"] is False

        # error paths stay structured
        with pytest.raises(urlreq.HTTPError) as e:
            post("/act", {"nope": 1})
        assert e.value.code == 400
        with pytest.raises(urlreq.HTTPError) as e:
            post("/act", {"obs": obs.tolist(), "model": "ghost"})
        assert e.value.code == 404


def test_batcher_timeout_maps_to_503_with_retry_after():
    """Resilience satellite (ISSUE 2): a stalled policy backend must
    answer 503 + Retry-After (back off and retry), not a generic 500
    (broken, page someone) — and every connection carries a socket
    timeout so a stalled client cannot wedge a handler thread forever.
    The stall is a real one: the engine forward blocks on an event the
    test controls, so the batcher future deterministically exceeds the
    server's act deadline. No sleeps, no races."""
    reg, actor, params = make_registry(max_batch=4)
    engine, _, _ = reg.acquire("default")
    release = threading.Event()
    real_act = engine.act

    def stalled_act(*args, **kwargs):
        release.wait(30.0)
        return real_act(*args, **kwargs)

    engine.act = stalled_act
    try:
        with PolicyServer(
            reg, port=0, max_batch=4, max_wait_ms=1.0,
            request_timeout_s=12.5, act_timeout_s=0.2,
        ) as srv:
            srv.start()
            # The per-connection socket timeout reaches the stdlib
            # handler (applied via connection.settimeout in setup()).
            assert srv._httpd.RequestHandlerClass.timeout == 12.5
            req = urlreq.Request(
                srv.address + "/act",
                data=json.dumps({"obs": [0.0] * OBS_DIM}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urlreq.HTTPError) as e:
                urlreq.urlopen(req, timeout=30)
            assert e.value.code == 503
            assert e.value.headers["Retry-After"] == "1"
            assert "timed out" in json.loads(e.value.read())["error"]
            release.set()  # unblock the dispatcher before shutdown
    finally:
        release.set()
        engine.act = real_act


def test_http_batched_obs():
    reg, actor, params = make_registry(max_batch=4)
    with PolicyServer(reg, port=0, max_batch=4, max_wait_ms=1.0) as srv:
        srv.start()
        obs = np.zeros((3, OBS_DIM), np.float32)
        req = urlreq.Request(
            srv.address + "/act",
            data=json.dumps({"obs": obs.tolist()}).encode(),
            headers={"Content-Type": "application/json"},
        )
        out = json.loads(urlreq.urlopen(req, timeout=30).read())
        assert np.asarray(out["action"]).shape == (3, ACT_DIM)
