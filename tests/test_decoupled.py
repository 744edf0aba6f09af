"""Decoupled actor/learner tests: every link's failure mode proven.

The decoupled plane's contract (docs/RESILIENCE.md "Decoupled-plane
failure modes") asserted end-to-end on CPU, with the determinism
discipline of tests/test_resilience.py — injections key off exact step
or call counts, clocks/sleeps are injected, nothing is timing-flaky:

- StagingBuffer: backpressure policies counted, bounded-staleness gate
  drops + bounds the lag histogram, conservation invariant, pause/
  resume, checkpoint array round-trip.
- PolicyClient: the in-process retry/backoff is bounded, deadline-aware
  and classification-preserving (transport parity with PR-9's HTTP mode).
- ActorWorker: degrade-to-snapshot on serving loss (no stalled envs),
  probe-and-re-home, idle-spin against a paused staging buffer.
- DecoupledTrainer: acting through the real serving stack, per-epoch
  validated publish (NaN rejected, last-good keeps serving), SIGTERM →
  requeue → BITWISE resume including the staged-transition tail and
  the serving plane's PRNG stream.
"""

import os
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.decoupled import (
    ActorWorker,
    DecoupledTrainer,
    StagingBuffer,
    StagingUnavailable,
)
from torch_actor_critic_tpu.diagnostics import EarlyWarningMonitor
from torch_actor_critic_tpu.models import Actor
from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.resilience import (
    REQUEUE_EXIT_CODE,
    Preempted,
    PreemptionGuard,
)
from torch_actor_critic_tpu.resilience.faultinject import (
    FaultyEnvPool,
    LossyLink,
    nan_params,
)
from torch_actor_critic_tpu.serve import (
    ModelRegistry,
    PolicyClient,
    PolicyServer,
    ShedError,
)
from torch_actor_critic_tpu.serve.batcher import ActResult
from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
from torch_actor_critic_tpu.utils.config import SACConfig

TINY = dict(
    hidden_sizes=(16, 16),
    batch_size=16,
    epochs=3,
    steps_per_epoch=40,
    start_steps=10,
    update_after=10,
    update_every=10,
    buffer_size=500,
    max_ep_len=100,
    save_every=1,
    decoupled=True,
    max_actor_lag=4,
)


def make_trainer(ckpt_dir, seed=7, preemption=None, client=None, **over):
    cfg = SACConfig(**{**TINY, **over})
    ck = (
        Checkpointer(ckpt_dir, retry_backoff_s=0.0)
        if ckpt_dir is not None
        else None
    )
    return DecoupledTrainer(
        "Pendulum-v1",
        cfg,
        mesh=make_mesh(dp=1),
        checkpointer=ck,
        seed=seed,
        preemption=preemption,
        client=client,
    )


def comparable_state(tr):
    """Every array that defines the learner: full TrainState (PRNG key
    as raw uint32) + the replay ring and its cursors (the pattern of
    tests/test_resilience.py)."""
    s = tr.state
    trees = {
        "actor": s.actor_params,
        "critic": s.critic_params,
        "target": s.target_critic_params,
        "pi_opt": s.pi_opt_state,
        "q_opt": s.q_opt_state,
        "log_alpha": s.log_alpha,
        "alpha_opt": s.alpha_opt_state,
        "step": s.step,
        "rng": jax.random.key_data(s.rng),
        "buffer": tr.buffer.data,
        "ptr": tr.buffer.ptr,
        "size": tr.buffer.size,
    }
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(trees)]


def txn(i, n_envs=1, obs_dim=3, act_dim=1):
    """A tiny distinguishable batched transition."""
    return (
        np.full((n_envs, obs_dim), float(i), np.float32),
        np.full((n_envs, act_dim), float(i), np.float32),
        np.full((n_envs,), float(i), np.float32),
        np.full((n_envs, obs_dim), float(i) + 0.5, np.float32),
        np.zeros((n_envs,), np.float32),
    )


# ------------------------------------------------------------ staging unit


def test_staging_backpressure_shed_and_drop_oldest_counted():
    shed = StagingBuffer(capacity=2, policy="shed")
    assert shed.put(txn(0)) and shed.put(txn(1))
    assert not shed.put(txn(2))  # refused, counted
    assert shed.shed_total == 1 and shed.staged_total == 2
    assert shed.conservation_holds()

    drop = StagingBuffer(capacity=2, policy="drop_oldest")
    assert drop.put(txn(0)) and drop.put(txn(1)) and drop.put(txn(2))
    assert drop.dropped_backpressure_total == 1
    assert drop.staged_total == 3 and drop.depth() == 2
    # Oldest evicted: the queue now holds txns 1 and 2.
    out = drop.pop_window(2)
    assert [int(e.transition[0][0, 0]) for e in out] == [1, 2]
    assert drop.conservation_holds()


def test_staging_block_policy_is_bounded_not_a_deadlock():
    st = StagingBuffer(capacity=1, policy="block", block_timeout_s=0.01)
    assert st.put(txn(0))
    # No consumer: the bounded wait expires and the put is SHED (and
    # counted), never a hang.
    assert not st.put(txn(1))
    assert st.blocked_total == 1 and st.shed_total == 1
    assert st.conservation_holds()


def test_staging_block_policy_wakes_on_drain():
    st = StagingBuffer(capacity=1, policy="block", block_timeout_s=30.0)
    assert st.put(txn(0))
    accepted = []
    done = threading.Event()

    def producer():
        accepted.append(st.put(txn(1)))
        done.set()

    thr = threading.Thread(target=producer, daemon=True)
    thr.start()
    # The producer is parked on backpressure; draining frees a slot.
    assert st.pop_window(1) is not None
    assert done.wait(10.0)
    thr.join(10.0)
    assert accepted == [True]
    assert st.depth() == 1 and st.conservation_holds()


def test_staging_pop_window_is_exact_size_or_none():
    st = StagingBuffer(capacity=10)
    for i in range(3):
        st.put(txn(i))
    assert st.pop_window(4) is None  # partial windows never drain
    assert st.depth() == 3
    out = st.pop_window(3)
    assert [int(e.transition[0][0, 0]) for e in out] == [0, 1, 2]
    with pytest.raises(ValueError):
        st.pop_window(0)


def test_staging_stale_gate_drops_and_bounds_histogram():
    st = StagingBuffer(capacity=16, max_lag=2)
    st.put(txn(0), generation=1, epoch=0)   # lag 5 at epoch 5: stale
    st.put(txn(1), generation=3, epoch=4)   # lag 1: fresh
    st.put(txn(2), generation=4, epoch=5)   # lag 0: fresh
    st.put(txn(3))                          # untagged (warmup): lag 0
    out = st.pop_window(3, current_epoch=5)
    assert [int(e.transition[0][0, 0]) for e in out] == [1, 2, 3]
    assert st.dropped_stale_total == 1
    assert st.conservation_holds()
    # Every recorded lag respects the knob — the acceptance bound.
    snap = st.snapshot()
    assert snap["actor_lag"]["actor_lag_max"] <= 2
    assert snap["actor_lag"]["actor_lag_count"] == 3


def test_staging_pause_blocks_puts_until_resume():
    st = StagingBuffer(capacity=4)
    st.put(txn(0))
    st.pause()
    with pytest.raises(StagingUnavailable):
        st.put(txn(1))
    assert st.depth() == 1  # staged contents survive the pause
    st.resume()
    assert st.put(txn(1))
    assert st.staged_total == 2


def test_staging_drop_oldest_conserves_under_pause_resume_race():
    """Conservation under the worst interleaving: drop_oldest evictions
    racing pause()/resume() flips and a concurrent drainer. Every
    accepted transition must land in exactly one counted outcome —
    drained, dropped_backpressure, or still queued — with no path
    (eviction inside put, StagingUnavailable on a paused buffer,
    pop_window mid-flip) losing or double-counting a row."""
    st = StagingBuffer(capacity=4, policy="drop_oldest")
    n_producers, puts_each = 4, 60
    accepted = [0] * n_producers
    stop_flipping = threading.Event()

    def producer(slot):
        for i in range(puts_each):
            while True:
                try:
                    assert st.put(txn(i))  # drop_oldest always admits
                    accepted[slot] += 1
                    break
                except StagingUnavailable:
                    # Paused mid-run: retry the SAME transition (the
                    # documented actor contract).
                    pass

    def flipper():
        while not stop_flipping.is_set():
            st.pause()
            st.resume()

    drained_windows = [0]
    producers_done = threading.Event()

    def drainer():
        while not (producers_done.is_set() and st.depth() < 2):
            if st.pop_window(2) is not None:
                drained_windows[0] += 1

    threads = [
        threading.Thread(target=producer, args=(s,), daemon=True)
        for s in range(n_producers)
    ]
    threads += [
        threading.Thread(target=flipper, daemon=True),
        threading.Thread(target=drainer, daemon=True),
    ]
    for thr in threads:
        thr.start()
    for thr in threads[:n_producers]:
        thr.join(30.0)
    producers_done.set()
    stop_flipping.set()
    for thr in threads[n_producers:]:
        thr.join(30.0)
    assert all(not thr.is_alive() for thr in threads)

    assert accepted == [puts_each] * n_producers
    assert st.staged_total == n_producers * puts_each
    assert st.drained_total == 2 * drained_windows[0]
    assert not st.paused  # resume() was the flipper's last word
    # The invariant the whole module exists for:
    assert st.conservation_holds()
    snap = st.snapshot()
    assert snap["staged_total"] == (
        snap["drained_total"]
        + snap["dropped_backpressure_total"]
        + snap["depth"]
    )


def test_staging_checkpoint_arrays_roundtrip_is_bitwise():
    st = StagingBuffer(capacity=8, max_lag=3)
    st.put(txn(0), generation=2, epoch=1)
    st.put(txn(1), generation=3, epoch=2)
    st.put(txn(2))  # untagged
    st.pop_window(1, current_epoch=2)  # make the counters non-trivial
    arrays = st.export_arrays()
    meta = st.meta_state()
    assert meta["count"] == 2

    st2 = StagingBuffer(capacity=8, max_lag=3)
    st2.load_meta(meta)
    assert st2.import_arrays(arrays) == 2
    assert st2.staged_total == st.staged_total
    assert st2.drained_total == st.drained_total
    assert st2.lag_hist.count == st.lag_hist.count
    a = list(st._q)
    b = list(st2._q)
    assert len(a) == len(b) == 2
    for ea, eb in zip(a, b):
        assert ea.generation == eb.generation
        assert ea.epoch == eb.epoch
        for xa, xb in zip(ea.transition, eb.transition):
            np.testing.assert_array_equal(xa, xb)
    # An empty buffer exports no arrays item at all.
    empty = StagingBuffer(capacity=2)
    assert empty.export_arrays() is None


# --------------------------------------------- in-process client retry


class _ScriptedBatcher:
    """Raises a scripted exception sequence from act(), then succeeds."""

    def __init__(self, errors):
        self.errors = list(errors)
        self.calls = 0
        self.timeouts = []

    def act(self, obs, deterministic=True, slot="default", timeout=None,
            request_id=None):
        self.calls += 1
        self.timeouts.append(timeout)
        if self.errors:
            raise self.errors.pop(0)
        return ActResult(np.zeros((1, 2), np.float32), 5, 9)


def test_inprocess_client_retries_sheds_with_backoff_and_hint():
    sleeps = []
    batcher = _ScriptedBatcher([
        ShedError("queue_full", "full", retry_after_s=0.5),
        ShedError("breaker_open", "open", retry_after_s=0.0),
    ])
    client = PolicyClient(
        ModelRegistry(), batcher, retries=3, backoff_s=0.25,
        sleep=sleeps.append,
    )
    res = client.act(np.zeros(2), timeout=60.0)
    assert res.generation == 5 and res.epoch == 9
    assert batcher.calls == 3
    assert client.retries_total == 2
    # Delay honors max(hint, backoff*2^n) with <=25% jitter — exactly
    # the HTTP-mode ladder.
    assert 0.5 <= sleeps[0] <= 0.5 * 1.25
    assert 0.5 <= sleeps[1] <= 0.5 * 1.25  # backoff 0.25*2 vs hint 0
    # The per-attempt timeout shrinks toward the caller's deadline.
    assert all(t_ is not None and t_ <= 60.0 for t_ in batcher.timeouts)


def test_inprocess_client_retry_is_bounded_and_classification_preserved():
    batcher = _ScriptedBatcher([
        ShedError("queue_full", "full", retry_after_s=0.0)
        for _ in range(10)
    ])
    client = PolicyClient(
        ModelRegistry(), batcher, retries=2, backoff_s=0.0,
        sleep=lambda s: None,
    )
    with pytest.raises(ShedError) as ei:
        client.act(np.zeros(2), timeout=60.0)
    assert ei.value.reason == "queue_full"  # the LAST rejection, intact
    assert batcher.calls == 3  # 1 + retries, never more


def test_inprocess_client_never_retries_past_the_deadline():
    sleeps = []
    batcher = _ScriptedBatcher([
        ShedError("queue_full", "full", retry_after_s=500.0),
    ])
    client = PolicyClient(
        ModelRegistry(), batcher, retries=5, backoff_s=0.25,
        sleep=sleeps.append,
    )
    with pytest.raises(ShedError) as ei:
        client.act(np.zeros(2), timeout=0.2)
    # The 500s Retry-After cannot fit a 0.2s budget: the rejection is
    # raised immediately, with zero sleeping past the deadline.
    assert ei.value.reason == "queue_full"
    assert sleeps == []
    assert batcher.calls == 1


def test_inprocess_client_does_not_retry_request_errors():
    batcher = _ScriptedBatcher([ValueError("bad obs shape")])
    client = PolicyClient(
        ModelRegistry(), batcher, retries=5, sleep=lambda s: None
    )
    with pytest.raises(ValueError):
        client.act(np.zeros(2), timeout=5.0)
    assert batcher.calls == 1


# -------------------------------------------------- actor worker / link


class _FakeClient:
    def __init__(self):
        self.fail_left = 0
        self.calls = 0
        self.retries_total = 0

    def act(self, obs, deterministic=True, slot="default", timeout=None,
            request_id=None):
        self.calls += 1
        if self.fail_left:
            self.fail_left -= 1
            raise ConnectionError("injected connection loss")
        return ActResult(np.asarray(obs) * 0.0, 7, 3)


def _fallback(obs, deterministic):
    return np.asarray(obs) * 0.0 + 1.0, 2, 1


def test_actor_degrades_probes_and_rehomes():
    client = _FakeClient()
    staging = StagingBuffer(capacity=8)
    actor = ActorWorker(
        client, staging, fallback=_fallback, probe_every=3,
        sleep=lambda s: None,
    )
    obs = np.zeros((1, 3), np.float32)
    client.fail_left = 4
    # First failure: degrade, stamped with the SNAPSHOT's tags.
    actions, gen, epoch, src = actor.act(obs)
    assert src == "fallback" and (gen, epoch) == (2, 1)
    assert actor.degraded and actor.degradations_total == 1
    # While degraded, only every probe_every-th call touches serving.
    calls_before = client.calls
    assert actor.act(obs)[3] == "fallback"
    assert actor.act(obs)[3] == "fallback"
    assert client.calls == calls_before  # no serving attempts between probes
    probe = actor.act(obs)  # 3rd degraded step: probe (fails, 3 left->2)
    assert probe[3] == "fallback" and actor.probes_total == 1
    actor.act(obs), actor.act(obs)
    rehomed = actor.act(obs)  # next probe: fail budget spent -> success
    # fail_left was 4: initial + first probe consumed 2... walk until
    # re-homed to stay robust to the exact probe arithmetic:
    for _ in range(12):
        if not actor.degraded:
            break
        rehomed = actor.act(obs)
    assert not actor.degraded
    assert actor.rehomes_total == 1
    assert rehomed[3] == "serving" and rehomed[1] == 7 and rehomed[2] == 3
    assert actor.fallback_actions_total >= 4


def test_actor_without_fallback_surfaces_the_failure():
    client = _FakeClient()
    client.fail_left = 1
    actor = ActorWorker(client, StagingBuffer(capacity=2), fallback=None)
    with pytest.raises(ConnectionError):
        actor.act(np.zeros((1, 3), np.float32))


def test_actor_idle_spins_while_paused_and_reconnects():
    staging = StagingBuffer(capacity=8)
    actor = ActorWorker(
        _FakeClient(), staging, fallback=_fallback,
        idle_backoff_s=0.0, sleep=lambda s: None,
    )
    staging.pause()
    stop = threading.Event()
    done = threading.Event()
    result = []

    def worker():
        result.append(actor.stage(txn(0), generation=1, epoch=0, stop=stop))
        done.set()

    thr = threading.Thread(target=worker, daemon=True)
    thr.start()
    # The actor is spinning against the paused buffer, losing nothing.
    import time as _time

    t_end = _time.monotonic() + 10.0
    while actor.idle_spins_total == 0 and _time.monotonic() < t_end:
        _time.sleep(0)  # yield to the spinning thread
    assert actor.idle_spins_total >= 1
    assert not done.is_set()
    staging.resume()
    assert done.wait(10.0)
    thr.join(10.0)
    assert result == [True]
    assert staging.depth() == 1  # the SAME transition arrived post-resume
    assert actor.idle_spins_total >= 1


def test_lossy_link_injects_latency_and_drops_standalone():
    class _Echo:
        def act(self, obs, **kw):
            return ActResult(np.asarray(obs), 1, None)

    slept = []
    link = LossyLink(_Echo(), latency_s=0.25, sleep=slept.append)
    link.drop_next(2)
    with pytest.raises(OSError):
        link.act(np.zeros(2))
    with pytest.raises(OSError):
        link.act(np.zeros(2))
    out = link.act(np.ones(2))
    assert out.generation == 1
    assert link.calls_total == 3 and link.drops_injected == 2
    assert slept == [0.25, 0.25, 0.25]  # every call pays the link latency
    # Probabilistic mode is seedable (deterministic under a fixed rng).
    import random

    link2 = LossyLink(
        _Echo(), drop_rate=1.0, rng=random.Random(0), sleep=lambda s: None
    )
    with pytest.raises(OSError):
        link2.act(np.zeros(2))
    with pytest.raises(ValueError):
        LossyLink(_Echo(), drop_rate=1.5)


def test_lag_drift_feeds_early_warning_monitor():
    mon = EarlyWarningMonitor(warmup=2)
    fired = []
    for lag in (1.0, 1.0, 1.0, 1.0, 40.0):
        fired += mon.update({"decoupled/actor_lag_mean": lag})
    assert any(w["kind"] == "actor_lag_drift" for w in fired)


# ----------------------------------------------- epoch on the wire


def test_actresult_carries_publish_epoch_inprocess_and_http():
    actor = Actor(act_dim=2, hidden_sizes=(8, 8))
    params = actor.init(
        jax.random.key(0), jnp.zeros((3,)), jax.random.key(1)
    )
    reg = ModelRegistry()
    reg.register(
        "default", actor, jax.ShapeDtypeStruct((3,), jnp.float32),
        params=params, max_batch=2,
    )
    staging = StagingBuffer(capacity=4, max_lag=2)
    staging.put(txn(0), generation=1, epoch=7)
    srv = PolicyServer(
        reg, port=0,
        extra_snapshot=lambda: {"decoupled": staging.snapshot()},
    ).start()
    try:
        # Directly-seeded slot: no epoch yet.
        res = srv.client.act(np.zeros(3, np.float32))
        assert res.epoch is None and res.generation == 0
        # A publish stamps every subsequent response, both transports.
        reg.swap("default", params, epoch=7)
        res = srv.client.act(np.zeros(3, np.float32))
        assert res.epoch == 7 and res.generation == 1
        http = PolicyClient(url=srv.address, retries=0)
        res = http.act(np.zeros(3, np.float32))
        assert res.epoch == 7 and res.generation == 1
        # The staging snapshot rides /metrics via extra_snapshot: the
        # actor-lag histogram is observable next to serving metrics.
        import json
        from urllib import request as urlreq

        with urlreq.urlopen(f"{srv.address}/metrics", timeout=10) as r:
            snap = json.loads(r.read())
        assert "decoupled" in snap
        assert "actor_lag_count" in snap["decoupled"]["actor_lag"]
    finally:
        srv.close()


# ------------------------------------------------- trainer end-to-end


def test_decoupled_trainer_trains_through_the_serving_plane(tmp_path):
    tr = make_trainer(tmp_path / "ck", epochs=2)
    try:
        m = tr.train()
        assert np.isfinite(m["loss_q"])
        # Every policy action post-warmup went through the serving
        # stack and every transition is accounted for.
        assert tr.actor.serving_actions_total > 0
        assert m["decoupled/staged_total"] == 80
        assert tr.staging.conservation_holds()
        assert m["decoupled/actor_lag_max"] <= TINY["max_actor_lag"]
        # One validated publish per epoch; the slot tracks the epoch.
        assert m["decoupled/published_generation"] == 2
        assert tr.registry.epoch_of("default") == 1
    finally:
        tr.close()


def test_stale_gate_drops_in_the_real_loop(tmp_path):
    # max_actor_lag=0: after the first publish every transition is one
    # epoch stale at drain time, so the gate drops them and windows are
    # SKIPPED (shape-stable) — off-policy drift as a hard knob.
    tr = make_trainer(tmp_path / "ck", epochs=3, max_actor_lag=0)
    try:
        m = tr.train()
        assert np.isfinite(m["loss_q"])
        assert m["decoupled/dropped_stale_total"] > 0
        assert tr.staging.conservation_holds()
        assert m["decoupled/actor_lag_max"] == 0.0
    finally:
        tr.close()


def test_serving_loss_degrades_and_run_completes(tmp_path):
    tr = make_trainer(tmp_path / "ck", epochs=2)
    # Sever the actor↔serving link from lockstep step 20 on: the link
    # drops every later call, actors degrade to the local snapshot and
    # envs never stall.
    link = LossyLink(tr.client).drop_next(10_000)
    tr.pool = FaultyEnvPool(tr.pool).call_at(
        20, lambda: setattr(tr.actor, "client", link)
    )
    try:
        m = tr.train()
        assert np.isfinite(m["loss_q"])
        assert tr.actor.degradations_total >= 1
        assert m["decoupled/fallback_actions_total"] > 0
        assert m["decoupled/degraded"] == 1.0
        # Degraded transitions are stamped with the published epoch, so
        # staleness stays bounded (the learner keeps publishing).
        assert m["decoupled/actor_lag_max"] <= TINY["max_actor_lag"]
        assert tr.staging.conservation_holds()
    finally:
        tr.close()


def test_nan_publish_is_rejected_and_last_good_serves(tmp_path):
    tr = make_trainer(None, sentinel=False)
    try:
        host = tr._fetch_params_single_transfer()
        gen0 = tr.registry.swap("default", host, epoch=0)
        tr._published_generation = 1
        # Poison the learner's actor params (the state a NaN epoch
        # would publish) and run the publish path.
        tr.state = tr.state.replace(
            actor_params=jax.tree_util.tree_map(
                jnp.asarray, nan_params(host)
            )
        )
        tr._host_params = None
        tr._publish_epoch(1, saved=False)
        assert tr._publish_rejected_total == 1
        assert tr._published_generation == 1  # no new generation
        # The slot still serves the last-good params/epoch.
        _, params, gen = tr.registry.acquire("default")
        assert gen == gen0
        assert tr.registry.epoch_of("default") == 0
        assert all(
            np.isfinite(np.asarray(x)).all()
            for x in jax.tree_util.tree_leaves(params)
        )
    finally:
        tr.close()


def test_decoupled_sigterm_resume_is_bitwise_including_staging(tmp_path):
    """The acceptance bitwise proof: SIGTERM mid-epoch-1, requeue exit,
    resume — the final learner state AND replay stream are bitwise
    identical to an uninterrupted run. steps_per_epoch=44 leaves the
    epoch-1 boundary (step 88) 8 transitions past the last window
    drain (step 80), so the checkpointed staging tail (and the serving
    plane's PRNG stream) is part of what must round-trip."""
    over = dict(epochs=3, steps_per_epoch=44, save_every=10)

    tra = make_trainer(tmp_path / "a", **over)
    try:
        tra.train()
        ref = comparable_state(tra)
        ref_staged = tra.staging.staged_total
    finally:
        tra.close()

    guard = PreemptionGuard().install()
    trb = make_trainer(tmp_path / "b", preemption=guard, **over)
    trb.pool = FaultyEnvPool(trb.pool).call_at(
        50, lambda: os.kill(os.getpid(), signal.SIGTERM)
    )
    try:
        with pytest.raises(Preempted) as ei:
            trb.train()
    finally:
        guard.uninstall()
        trb.close()
    assert ei.value.exit_code == REQUEUE_EXIT_CODE
    meta = trb.checkpointer.peek_meta()
    assert meta["epoch"] == 1
    dec = meta["decoupled"]
    assert dec["staging"]["count"] == 8  # the undrained tail is saved
    assert dec["batcher_key"]  # the serving PRNG stream is part of it

    trc = make_trainer(tmp_path / "b", **{**over, "epochs": 1})
    try:
        assert trc.restore() == 2
        assert trc.staging.depth() == 8  # zero accepted transitions lost
        trc.train()
        got = comparable_state(trc)
        assert trc.staging.staged_total == ref_staged
        assert trc.staging.conservation_holds()
    finally:
        trc.close()
    for x, y in zip(ref, got, strict=True):
        np.testing.assert_array_equal(x, y)
