"""Overload & degradation tests (docs/SERVING.md): admission control,
deadline purge, the engine circuit breaker, sentinel-validated
hot-reload, and graceful drain.

Determinism rules carried over from tests/test_resilience.py: no
wall-clock sleeps in assertions — engine stalls are real Events the
test controls, breaker time is a fake injected clock, and drain
completion is observed through the API, not timed.
"""

import json
import os
import signal
import threading
import time
from urllib import request as urlreq

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.resilience.faultinject import (
    FaultyEngine,
    corrupt_checkpoint,
    flood,
    nan_params,
)
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.serve import (
    BreakerOpenError,
    CircuitBreaker,
    MicroBatcher,
    ModelRegistry,
    NonFiniteActionError,
    PolicyServer,
    ShedError,
    install_drain_handler,
)
from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
from torch_actor_critic_tpu.utils.config import SACConfig

OBS_DIM, ACT_DIM = 17, 6


def make_actor_and_params(seed=0):
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32))
    params = actor.init(
        jax.random.key(seed), jnp.zeros((OBS_DIM,)), jax.random.key(1)
    )
    return actor, params


def flat_spec():
    return jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)


def make_registry(max_batch=4, warmup=True, breaker=None):
    actor, params = make_actor_and_params()
    reg = ModelRegistry()
    reg.register(
        "default", actor, flat_spec(), params=params,
        max_batch=max_batch, warmup=warmup, breaker=breaker,
    )
    return reg, actor, params


def stall_engine(reg, slot="default"):
    """Replace the slot engine's act with one that blocks on an Event
    the test controls; returns (release_event, restore_fn)."""
    engine, _, _ = reg.acquire(slot)
    release = threading.Event()
    real_act = engine.act

    def stalled_act(*args, **kwargs):
        release.wait(30.0)
        return real_act(*args, **kwargs)

    engine.act = stalled_act
    return release, lambda: setattr(engine, "act", real_act)


OBS = np.ones((OBS_DIM,), np.float32)


# -------------------------------------------------------- admission control


def test_queue_full_sheds_with_structured_error():
    """Submits past capacity raise ShedError(queue_full) instead of
    growing the queue; the queue depth never exceeds the bound."""
    reg, _, _ = make_registry()
    release, restore = stall_engine(reg)
    try:
        with MicroBatcher(
            reg, max_batch=4, max_wait_ms=1.0, capacity=3
        ) as mb:
            # The dispatcher takes the first request out of the queue
            # and stalls in the engine; then fill the queue to the
            # bound and observe rejection.
            first = mb.submit(OBS)
            deadline = time.time() + 30.0
            while mb.queue_depth() > 0:  # dispatcher picked it up
                assert time.time() < deadline
                time.sleep(0.001)
            futures, sheds = flood(mb.submit, OBS, 10)
            assert len(futures) == 3  # exactly the capacity
            assert len(sheds) == 7
            assert all(e.reason == "queue_full" for e in sheds)
            assert all(e.retry_after_s > 0 for e in sheds)
            assert sheds[0].detail["capacity"] == 3
            assert mb.queue_depth() <= 3
            snap = mb.metrics.snapshot()
            assert snap["sheds_total"] == 7
            assert snap["shed_by_reason"]["queue_full"] == 7
            release.set()
            # every ACCEPTED request is answered
            assert first.result(timeout=30.0).action.shape == (ACT_DIM,)
            for f in futures:
                assert f.result(timeout=30.0).action.shape == (ACT_DIM,)
    finally:
        release.set()
        restore()


def test_expired_request_purged_never_dispatched():
    """Satellite: a request whose deadline passes while queued is
    purged at group-collection time — its future fails with
    ShedError(expired), the engine never runs it, and it is counted in
    shed_expired_total."""
    reg, _, _ = make_registry()
    engine, _, _ = reg.acquire("default")
    faulty = FaultyEngine(engine)  # used only for its call counter
    reg._slots["default"].engine = faulty
    release, _ = stall_engine(reg)
    try:
        with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0) as mb:
            # Group 1 occupies the (stalled) engine...
            blocker = mb.submit(OBS)
            deadline = time.time() + 30.0
            while mb.queue_depth() > 0:
                assert time.time() < deadline
                time.sleep(0.001)
            # ...while this request's deadline expires in the queue.
            doomed = mb.submit(OBS, deadline_s=0.01)
            time.sleep(0.05)  # the deadline lapses; the engine is
            # still stalled, so the purge deterministically happens at
            # the NEXT group collection, after release below
            release.set()
            with pytest.raises(ShedError, match="purged") as e:
                doomed.result(timeout=30.0)
            assert e.value.reason == "expired"
            assert blocker.result(timeout=30.0).generation == 0
            calls_after_blocker = faulty.calls_total
            snap = mb.metrics.snapshot()
        assert snap["shed_expired_total"] == 1
        # the purged request never reached the engine: only the
        # blocker's forward ran
        assert calls_after_blocker == 1
    finally:
        release.set()


def test_act_timeout_doubles_as_deadline():
    """The timed-out-client leak fix: act(timeout=T) attaches deadline
    T, so an abandoned call's queued request is purged instead of
    burning a forward."""
    reg, _, _ = make_registry()
    release, _ = stall_engine(reg)
    try:
        with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0) as mb:
            mb.submit(OBS)  # stalls the dispatcher
            deadline = time.time() + 30.0
            while mb.queue_depth() > 0:
                assert time.time() < deadline
                time.sleep(0.001)
            with pytest.raises(Exception):  # noqa: B017 — Future
                # timeout or the purge's ShedError, whichever wins the
                # race; the point is the queue-side cleanup below
                mb.act(OBS, timeout=0.01)
            release.set()
            deadline = time.time() + 30.0
            while mb.metrics.snapshot()["shed_expired_total"] < 1:
                assert time.time() < deadline, "request never purged"
                time.sleep(0.005)
    finally:
        release.set()


def test_deadline_infeasible_shed_at_submit():
    """Once the service-rate EMA is warm, a deadline that provably
    cannot be met at the current backlog is rejected at submit time."""
    reg, _, _ = make_registry()
    with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0) as mb:
        for _ in range(4):  # warm the EMA (>= 3 samples)
            mb.act(OBS, timeout=30.0)
        release, restore = stall_engine(reg)
        try:
            mb.submit(OBS)
            deadline = time.time() + 30.0
            while mb.queue_depth() > 0:
                assert time.time() < deadline
                time.sleep(0.001)
            # Huge backlog (500 queued rows) vs a microscopic deadline:
            # est_wait = rows * ema must exceed it deterministically.
            big = np.ones((100, OBS_DIM), np.float32)
            for _ in range(5):
                mb.submit(big)
            with pytest.raises(ShedError) as e:
                mb.submit(OBS, deadline_s=1e-9)
            assert e.value.reason == "deadline_infeasible"
            assert e.value.detail["estimated_wait_s"] > 0
        finally:
            release.set()
            restore()


def test_http_queue_full_maps_to_429_with_retry_after():
    reg, _, _ = make_registry()
    release, restore = stall_engine(reg)
    try:
        with PolicyServer(
            reg, port=0, max_batch=4, max_wait_ms=1.0,
            act_timeout_s=30.0, capacity=1,
        ) as srv:
            srv.start()
            # Occupy the engine + fill the 1-slot queue via the
            # in-process client (same batcher the HTTP path uses).
            blocker = srv.client.act_async(OBS)
            deadline = time.time() + 30.0
            while srv.batcher.queue_depth() > 0:
                assert time.time() < deadline
                time.sleep(0.001)
            queued = srv.client.act_async(OBS)
            req = urlreq.Request(
                srv.address + "/act",
                data=json.dumps({"obs": OBS.tolist()}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urlreq.HTTPError) as e:
                urlreq.urlopen(req, timeout=30)
            assert e.value.code == 429
            assert int(e.value.headers["Retry-After"]) >= 1
            body = json.loads(e.value.read())
            assert body["reason"] == "queue_full"
            release.set()
            assert blocker.result(timeout=30.0).action.shape == (ACT_DIM,)
            assert queued.result(timeout=30.0).action.shape == (ACT_DIM,)
    finally:
        release.set()
        restore()


# ---------------------------------------------------------- circuit breaker


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_breaker_trip_half_open_recovery():
    """The full state machine through the REAL serving path: NaN params
    trip the breaker via the engine's in-graph finiteness check,
    requests fail fast while open, the fake clock drives the half-open
    transition, a failing probe re-opens, and a good probe closes."""
    clock = FakeClock()
    breaker = CircuitBreaker(
        fail_threshold=2, cooldown_s=10.0, clock=clock
    )
    reg, actor, good_params = make_registry(breaker=breaker)
    poisoned = nan_params(good_params)
    with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0) as mb:
        assert mb.act(OBS, timeout=30.0).generation == 0  # healthy
        reg.swap("default", poisoned, validate=False)  # fault injection

        # Two consecutive non-finite forwards trip the breaker.
        for _ in range(2):
            with pytest.raises(NonFiniteActionError):
                mb.act(OBS, timeout=30.0)
        assert breaker.state == "open"
        assert breaker.trips_total == 1

        # Open: shed at submit, no engine work.
        with pytest.raises(BreakerOpenError) as e:
            mb.act(OBS, timeout=30.0)
        assert e.value.reason == "breaker_open"
        assert 0 < e.value.retry_after_s <= 10.0

        # Cooldown elapses -> half-open; the probe still fails (params
        # are still poisoned) -> re-open.
        clock.advance(10.0)
        assert breaker.admits()
        with pytest.raises(NonFiniteActionError):
            mb.act(OBS, timeout=30.0)
        assert breaker.state == "open"
        assert breaker.trips_total == 2

        # Fix the engine (sentinel-validated swap), next probe closes.
        clock.advance(10.0)
        gen = reg.swap("default", good_params)
        res = mb.act(OBS, timeout=30.0)
        assert res.generation == gen
        assert breaker.state == "closed"
        assert breaker.probes_total >= 2
        # transitions landed in the registry's telemetry event log
        events = [e["event"] for e in reg.breaker_events()]
        assert "breaker_open" in events
        assert "breaker_half_open" in events
        assert "breaker_close" in events
    reg.close()


def test_breaker_trips_on_forward_failures_and_flushes_queued():
    """Forward exceptions (injected via FaultyEngine) count toward the
    trip, and requests already queued behind the trip fail fast with
    BreakerOpenError rather than running the engine."""
    clock = FakeClock()
    breaker = CircuitBreaker(
        fail_threshold=2, cooldown_s=5.0, clock=clock
    )
    reg, _, _ = make_registry(breaker=breaker)
    engine, _, _ = reg.acquire("default")
    faulty = FaultyEngine(engine).fail_next(100)
    reg._slots["default"].engine = faulty
    with MicroBatcher(reg, max_batch=4, max_wait_ms=5.0) as mb:
        # Two failing groups trip it; queue a burst in one group so the
        # remaining requests observe the open breaker at dispatch.
        for _ in range(2):
            with pytest.raises(RuntimeError, match="injected"):
                mb.act(OBS, timeout=30.0)
        assert breaker.state == "open"
        snap_before = faulty.calls_total
        futures, sheds = flood(mb.submit, OBS, 5)
        # submit-time fail-fast: the open breaker sheds everything
        assert len(futures) == 0 and len(sheds) == 5
        assert all(isinstance(e, BreakerOpenError) for e in sheds)
        assert faulty.calls_total == snap_before  # zero engine work
        snap = mb.metrics.snapshot()
        assert snap["shed_by_reason"]["breaker_open"] == 5
    reg.close()


def test_metrics_exports_breaker_state():
    clock = FakeClock()
    breaker = CircuitBreaker(fail_threshold=1, cooldown_s=5.0, clock=clock)
    reg, _, good = make_registry(breaker=breaker)
    with PolicyServer(reg, port=0, max_batch=4, max_wait_ms=1.0) as srv:
        srv.start()
        reg.swap("default", nan_params(good), validate=False)
        req = urlreq.Request(
            srv.address + "/act",
            data=json.dumps({"obs": OBS.tolist()}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urlreq.HTTPError) as e:
            urlreq.urlopen(req, timeout=30)
        assert e.value.code == 500  # the tripping request itself
        with pytest.raises(urlreq.HTTPError) as e:
            urlreq.urlopen(req, timeout=30)
        assert e.value.code == 503  # breaker now open -> fail fast
        assert int(e.value.headers["Retry-After"]) >= 1
        snap = json.loads(
            urlreq.urlopen(srv.address + "/metrics", timeout=30).read()
        )
        assert snap["breakers"]["slots"]["default"]["state"] == "open"
        assert snap["breakers"]["trips_total"] == 1
        assert snap["breakers"]["open_slots"] == ["default"]
        assert snap["queue_capacity"] == srv.batcher.capacity
        health = json.loads(
            urlreq.urlopen(srv.address + "/healthz", timeout=30).read()
        )
        assert health["slots"]["default"]["breaker"] == "open"


# ----------------------------------------------------- validated hot-reload


def _save_checkpoint(ckpt_dir, epoch, seed):
    from torch_actor_critic_tpu.models import DoubleCritic as DC

    cfg = SACConfig(hidden_sizes=(32, 32))
    sac = SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32)),
        DC(hidden_sizes=(32, 32)),
        ACT_DIM,
    )
    state = sac.init_state(jax.random.key(seed), jnp.zeros((OBS_DIM,)))
    ck = Checkpointer(ckpt_dir, save_buffer=False)
    try:
        ck.save(epoch, state, extra={"config": cfg.to_json()}, wait=True)
    finally:
        ck.close()
    return state.actor_params


def test_reload_rejects_nan_checkpoint_keeps_last_good(tmp_path):
    """Acceptance bar: a reload of a NaN-corrupted checkpoint is
    REJECTED by the all-finite sentinel — the previous generation keeps
    serving bitwise-identical responses, and a later good epoch still
    reloads."""
    ckpt_dir = tmp_path / "ckpts"
    params0 = _save_checkpoint(ckpt_dir, 0, seed=0)
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32))
    reg = ModelRegistry()
    reg.register(
        "default", actor, flat_spec(), ckpt_dir=str(ckpt_dir),
        max_batch=4, warmup=False,
    )
    obs = np.random.default_rng(7).standard_normal(OBS_DIM).astype(
        np.float32
    )
    expected0, _ = actor.apply(
        params0, jnp.asarray(obs), None,
        deterministic=True, with_logprob=False,
    )
    with MicroBatcher(reg, max_batch=4, max_wait_ms=1.0) as mb:
        before = mb.act(obs, timeout=30.0)
        # Against the unbatched apply: float32 round-off (a padded
        # bucket forward and a matvec are differently batched
        # computations; XLA promises no bitwise equality there). The
        # bitwise pin is below, at equal shapes: before == after.
        np.testing.assert_allclose(
            before.action, np.asarray(expected0), rtol=2e-6, atol=2e-7
        )

        _save_checkpoint(ckpt_dir, 1, seed=99)
        corrupt_checkpoint(ckpt_dir, 1, mode="nan-params")
        out = reg.reload()
        assert out["default"]["status"] == "rejected"
        assert out["default"]["reloaded"] is False
        assert out["default"]["generation"] == 0
        assert "non-finite" in out["default"]["reason"]
        assert reg.slots()["default"]["reload_rejected_total"] == 1

        # still serving the last-good generation, bit for bit
        after = mb.act(obs, timeout=30.0)
        assert after.generation == 0
        np.testing.assert_array_equal(after.action, before.action)

        # a subsequent GOOD epoch reloads normally
        _save_checkpoint(ckpt_dir, 2, seed=5)
        out = reg.reload()
        assert out["default"]["status"] == "ok"
        assert out["default"]["epoch"] == 2
        assert out["default"]["generation"] == 1
        assert mb.act(obs, timeout=30.0).generation == 1
    reg.close()


def test_reload_multi_slot_isolation(tmp_path):
    """Satellite: one slot's restore failure must not abort reloading
    the remaining slots — per-slot {ok|rejected|error} statuses."""
    dir_a, dir_b = tmp_path / "a", tmp_path / "b"
    _save_checkpoint(dir_a, 0, seed=0)
    _save_checkpoint(dir_b, 0, seed=1)
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32))
    reg = ModelRegistry()
    reg.register("a", actor, flat_spec(), ckpt_dir=str(dir_a),
                 max_batch=4, warmup=False)
    reg.register("b", actor, flat_spec(), ckpt_dir=str(dir_b),
                 max_batch=4, warmup=False)
    # slot a's next epoch is structurally corrupt (unreadable); slot
    # b's is fine. NOTE: epoch-1 corruption makes the checkpointer fall
    # back to epoch 0 (already loaded) => slot a reports noop, slot b
    # must still reload.
    _save_checkpoint(dir_a, 1, seed=2)
    corrupt_checkpoint(dir_a, 1, mode="drop-meta")
    _save_checkpoint(dir_b, 1, seed=3)
    out = reg.reload()
    assert set(out) == {"a", "b"}
    assert out["b"]["status"] == "ok"
    assert out["b"]["epoch"] == 1
    assert out["a"]["status"] in ("noop", "error")  # never raised
    assert out["a"]["reloaded"] is False
    assert reg.slots()["a"]["generation"] == 0
    assert reg.slots()["b"]["generation"] == 1
    reg.close()


def test_swap_validates_unless_told_not_to():
    reg, _, good = make_registry(warmup=False)
    bad = nan_params(good)
    with pytest.raises(ValueError, match="non-finite"):
        reg.swap("default", bad)
    assert reg.slots()["default"]["generation"] == 0
    assert reg.swap("default", bad, validate=False) == 1  # harness path
    reg.close()


def test_register_rejects_nan_params():
    actor, params = make_actor_and_params()
    reg = ModelRegistry()
    with pytest.raises(ValueError, match="non-finite"):
        reg.register(
            "default", actor, flat_spec(),
            params=nan_params(params), max_batch=4, warmup=False,
        )


# ------------------------------------------------------------ graceful drain


def test_sigterm_drain_answers_all_accepted_requests():
    """Acceptance bar: SIGTERM stops admissions (503 + Retry-After,
    /healthz flips to draining) and every request accepted before the
    signal is answered."""
    reg, _, _ = make_registry()
    srv = PolicyServer(reg, port=0, max_batch=4, max_wait_ms=20.0)
    srv.start()
    trigger = install_drain_handler(srv, flush_timeout_s=30.0)
    try:
        # A backlog of accepted requests...
        futures = [srv.client.act_async(OBS) for _ in range(12)]
        # ...then SIGTERM. The handler spawns the drain thread; the
        # direct trigger is the same code path and keeps the test
        # signal-safe under pytest-xdist-less CI too.
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 30.0
        while not srv.draining:
            assert time.time() < deadline, "SIGTERM never started drain"
            time.sleep(0.005)
        # new work is refused while draining
        deadline = time.time() + 30.0
        while True:
            try:
                req = urlreq.Request(
                    srv.address + "/act",
                    data=json.dumps({"obs": OBS.tolist()}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                urlreq.urlopen(req, timeout=30)
            except urlreq.HTTPError as e:
                assert e.code == 503
                assert e.headers["Retry-After"] is not None
                break
            except OSError:
                break  # HTTP loop already released post-drain
            else:
                # raced ahead of the draining flag; retry until refused
                assert time.time() < deadline
                time.sleep(0.005)
        # every ACCEPTED request is answered — zero drops
        for f in futures:
            assert f.result(timeout=30.0).action.shape == (ACT_DIM,)
        # healthz reports draining with 503 (until the loop exits)
        try:
            urlreq.urlopen(srv.address + "/healthz", timeout=5)
            raise AssertionError("healthz should answer 503 draining")
        except urlreq.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "draining"
        except OSError:
            pass  # server loop already fully shut down — also fine
        _ = trigger  # direct trigger unused: the signal did the work
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        srv.close()


def test_drain_is_idempotent_and_reports():
    reg, _, _ = make_registry()
    with PolicyServer(reg, port=0, max_batch=4, max_wait_ms=1.0) as srv:
        srv.start()
        assert srv.client.act(OBS).action.shape == (ACT_DIM,)
        info = srv.drain(flush_timeout_s=10.0)
        assert info["drained"] is True
        assert info["queued_at_exit"] == 0
        assert info["responses_total"] >= 1
        # a second drain is a no-op, not an error
        assert srv.drain(flush_timeout_s=1.0)["drained"] is True
        # post-drain submits shed with ShedError(draining)
        with pytest.raises(ShedError) as e:
            srv.batcher.submit(OBS)
        assert e.value.reason == "draining"


def test_close_surfaces_leaked_server_thread(caplog):
    """Satellite: close() must not silently leak a wedged server
    thread — it logs a warning with the thread state and reports it in
    the close result."""
    reg, _, _ = make_registry(warmup=False)
    srv = PolicyServer(reg, port=0, max_batch=4, max_wait_ms=1.0)
    srv.start()
    result = srv.close()
    assert result["server_thread_stopped"] is True

    # Simulate the wedged-thread case with a thread that outlives the
    # join budget.
    reg2, _, _ = make_registry(warmup=False)
    srv2 = PolicyServer(reg2, port=0, max_batch=4, max_wait_ms=1.0)
    srv2.start()
    wedge = threading.Event()
    stuck = threading.Thread(
        target=wedge.wait, args=(30.0,), name="wedged-handler", daemon=True
    )
    stuck.start()
    srv2._thread = stuck
    with caplog.at_level("WARNING"):
        result = srv2.close(thread_join_timeout_s=0.05)
    try:
        assert result["server_thread_stopped"] is False
        assert result["server_thread"]["name"] == "wedged-handler"
        assert any(
            "still alive" in r.message for r in caplog.records
        )
    finally:
        wedge.set()
        stuck.join(timeout=10.0)
