"""Actor-process fleet: networked staging transport + supervision.

What must hold (docs/RESILIENCE.md "Decoupled-plane failure modes"):

- the wire codec round-trips transitions **bitwise** (flat and visual
  observations), and a malformed/garbage push is rejected with 400
  leaving EVERY conservation counter untouched (the poison-push
  regression);
- ingestion is **idempotent**: per-actor monotonic sequence numbers
  dedup retried pushes — a response lost in flight is retried with the
  same seq and answered ``duplicate``, never double-staged; a reaped
  actor's zombie incarnation is 410-fenced even when its push was in
  flight across the retire;
- the cross-process conservation invariant ``staged == drained +
  dropped_stale + dropped_backpressure + dropped_dead_actor + depth``
  holds through accepts, sheds, pauses, purges, and checkpoints;
- the supervisor declares death on process exit or heartbeat-deadline
  miss, SIGKILL-reaps, purges, and restarts with jittered exponential
  backoff up to the budget (fake clock/procs — deterministic);
- a FleetTrainer with live (thread-backed) actors trains through an
  actor death with the invariant intact and the restart counted, and a
  restored learner carries the dedup watermarks so reconnecting actors
  resume exactly (the process-level chaos version runs in
  ``make decouple-smoke``).

Determinism rules as in tests/test_resilience.py: injectable clocks,
rngs, sleeps and kill callables; nothing waits on wall-clock where a
fake clock can drive the schedule. The trainer-level tests use
thread-backed actor "processes" (real subprocesses pay a jax import
each — that cost belongs to the smoke, not tier-1); the supervisor
cannot tell the difference because it only sees the process protocol
(``pid``/``is_alive``/``join``).
"""

import itertools
import json
import signal
import threading
import time
from urllib import error as urlerr
from urllib import request as urlreq

import jax
import numpy as np
import pytest

from torch_actor_critic_tpu.core.types import MultiObservation
from torch_actor_critic_tpu.decoupled import (
    FleetSupervisor,
    FleetTrainer,
    RemoteStagingClient,
    StagingBuffer,
    StagingTransportServer,
    StagingUnavailable,
)
from torch_actor_critic_tpu.decoupled.fleet import _actor_loop
from torch_actor_critic_tpu.decoupled.transport import (
    canonical_transition,
    decode_transition,
    encode_transition,
)
from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.resilience.faultinject import (
    FlakyTransport,
    kill_actor,
)
from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
from torch_actor_critic_tpu.utils.config import SACConfig


class _Spec:
    """Minimal array obs-spec (shape + dtype), like envs expose."""

    def __init__(self, shape, dtype=np.float32):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)


SPEC = _Spec((3,))
N_ENVS = 2
ACT_DIM = 1


def txn(i, n_envs=N_ENVS, obs_dim=3, act_dim=ACT_DIM):
    rng = np.random.default_rng(i)
    return (
        rng.standard_normal((n_envs, obs_dim)).astype(np.float32),
        rng.standard_normal((n_envs, act_dim)).astype(np.float32),
        rng.standard_normal((n_envs,)).astype(np.float32),
        rng.standard_normal((n_envs, obs_dim)).astype(np.float32),
        np.zeros((n_envs,), np.float32),
    )


def make_server(staging=None, spec=SPEC, act=None, **kw):
    staging = staging if staging is not None else StagingBuffer(
        8, policy="shed"
    )
    return StagingTransportServer(
        staging, spec, n_envs=N_ENVS, act_dim=ACT_DIM, act=act, **kw
    )


def stage_body(i, actor_id=0, incarnation=0, seq=None, generation=1,
               epoch=0, transition=None):
    return {
        "actor_id": actor_id,
        "incarnation": incarnation,
        "seq": seq if seq is not None else i,
        "generation": generation,
        "epoch": epoch,
        "transition": encode_transition(
            transition if transition is not None else txn(i)
        ),
    }


def assert_conserved(staging):
    assert staging.conservation_holds(), staging.snapshot()


def _no_sleep(_s):
    pass


# ------------------------------------------------------------- wire codec


def test_codec_roundtrip_bitwise_flat():
    tr = canonical_transition(txn(3), SPEC)
    out = decode_transition(
        encode_transition(tr), SPEC, N_ENVS, ACT_DIM
    )
    for a, b in zip(tr, out):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))
    # Decoded arrays are owned + writable (frombuffer views are not).
    out[1][0, 0] = 7.0


def test_codec_roundtrip_bitwise_multiobs():
    spec = MultiObservation(
        features=_Spec((3,)), frame=_Spec((4, 4, 1), np.uint8)
    )
    rng = np.random.default_rng(0)
    obs = MultiObservation(
        features=rng.standard_normal((N_ENVS, 3)).astype(np.float32),
        frame=rng.integers(0, 255, (N_ENVS, 4, 4, 1), dtype=np.uint8),
    )
    tr = (
        obs,
        np.zeros((N_ENVS, ACT_DIM), np.float32),
        np.zeros((N_ENVS,), np.float32),
        obs,
        np.zeros((N_ENVS,), np.float32),
    )
    out = decode_transition(encode_transition(tr), spec, N_ENVS, ACT_DIM)
    np.testing.assert_array_equal(out[0].features, obs.features)
    np.testing.assert_array_equal(out[0].frame, obs.frame)
    assert out[0].frame.dtype == np.uint8


# ------------------------------------------- idempotent ingestion (server)


def test_stage_accept_dedup_and_seq_audit():
    srv = make_server()
    assert srv.handle_stage(stage_body(0))[0] == 200
    assert srv.handle_stage(stage_body(1))[0] == 200
    # Retried push whose response was lost: same seq, answered
    # duplicate, nothing staged twice.
    code, payload, _ = srv.handle_stage(stage_body(1))
    assert code == 200 and payload["duplicate"] is True
    snap = srv.snapshot()
    assert snap["accepted_total"] == 2
    assert snap["duplicate_pushes_total"] == 1
    assert srv.staging.staged_total == 2 == srv.staging.depth()
    # The audit is exact: watermark == last accepted seq, accepted ==
    # watermark + 1 for a gapless stream.
    assert snap["actors"]["0"]["seq"] == 1
    assert snap["actors"]["0"]["accepted_total"] == 2
    assert_conserved(srv.staging)


def test_zombie_incarnation_fenced_and_purged():
    srv = make_server()
    for i in range(3):
        assert srv.handle_stage(stage_body(i))[0] == 200
    assert srv.handle_stage(stage_body(0, actor_id=1))[0] == 200
    # Supervisor declares actor 0 dead: watermark bumps first, then the
    # staged tail purges — conservation picks up the dead-actor term.
    assert srv.retire_actor(0, incarnation=0) == 3
    assert srv.staging.dropped_dead_actor_total == 3
    assert srv.staging.depth() == 1  # actor 1's transition survives
    assert_conserved(srv.staging)
    # Zombie push from the reaped incarnation: 410, nothing staged.
    assert srv.handle_stage(stage_body(9, seq=9))[0] == 410
    assert srv.staging.depth() == 1
    # The respawned incarnation starts a fresh seq space.
    code, payload, _ = srv.handle_stage(
        stage_body(5, seq=0, incarnation=1)
    )
    assert code == 200 and payload["duplicate"] is False
    assert srv.snapshot()["rejected_zombie_total"] == 1
    assert_conserved(srv.staging)


def test_pause_maps_to_503_shed_to_429():
    srv = make_server(staging=StagingBuffer(2, policy="shed"))
    srv.staging.pause()
    code, _, headers = srv.handle_stage(stage_body(0))
    assert code == 503 and "Retry-After" in headers
    srv.staging.resume()
    assert srv.handle_stage(stage_body(0))[0] == 200
    assert srv.handle_stage(stage_body(1))[0] == 200
    # Full buffer, shed policy: counted 429 — a terminal outcome, not
    # a retry (the client advances its seq past a shed push).
    code, _, headers = srv.handle_stage(stage_body(2))
    assert code == 429 and "Retry-After" in headers
    snap = srv.snapshot()
    assert snap["unavailable_503_total"] == 1
    assert snap["shed_429_total"] == 1
    assert snap["accepted_total"] == 2
    assert_conserved(srv.staging)


# ------------------------------------------------- poison-push regression


def test_poison_push_cannot_corrupt_conservation():
    srv = make_server().start()
    try:
        assert srv.handle_stage(stage_body(0))[0] == 200
        before = srv.staging.snapshot()
        good = stage_body(1)
        poisons = []
        # Field-level garbage.
        for key, val in [
            ("actor_id", "zero"), ("actor_id", -1), ("seq", None),
            ("seq", True), ("generation", "g"), ("epoch", "now"),
            ("transition", None), ("transition", [1, 2, 3]),
        ]:
            b = dict(good)
            b[key] = val
            poisons.append(b)
        # Leaf-level garbage: wrong dtype, wrong shape, truncated
        # bytes, invalid base64, missing field.
        for mutate in [
            lambda tr: tr["actions"].update(dtype="float64"),
            lambda tr: tr["rewards"].update(shape=[N_ENVS, 1]),
            lambda tr: tr["done"].update(data=tr["done"]["data"][:-8]),
            lambda tr: tr["obs"].update(data="!!not-base64!!"),
            lambda tr: tr.pop("next_obs"),
        ]:
            b = stage_body(1)
            mutate(b["transition"])
            poisons.append(b)
        for b in poisons:
            code, payload, _ = srv.handle_stage(b)
            assert code == 400, (sorted(b), payload)
        # Raw bad JSON through the real HTTP stack.
        req = urlreq.Request(
            srv.address + "/stage", data=b"{not json",
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urlerr.HTTPError) as ei:
            urlreq.urlopen(req, timeout=5.0)
        assert ei.value.code == 400
        # THE regression: every staging counter and the depth are
        # untouched — a poison push cannot move the invariant.
        assert srv.staging.snapshot() == before
        assert_conserved(srv.staging)
        snap = srv.snapshot()
        assert snap["rejected_malformed_total"] == len(poisons) + 1
        assert snap["accepted_total"] == 1
        # And the actor's dedup watermark did not move either.
        assert snap["actors"]["0"]["seq"] == 0
    finally:
        srv.close()


# --------------------------------------------------- client retry contract


def test_client_retries_lost_response_and_dedups():
    srv = make_server()
    calls = {"n": 0}

    def lossy_post(path, payload, timeout_s):
        # Request DELIVERED, response lost in flight: the ambiguous
        # failure only sequence numbers make safe to retry.
        status, out, _ = srv.handle_stage(payload)
        calls["n"] += 1
        if calls["n"] == 1:
            raise ConnectionError("response lost in flight")
        return status, out

    cli = RemoteStagingClient(
        "http://unused", actor_id=0, backoff_s=0.0001,
        sleep=_no_sleep, post=lossy_post,
    )
    assert cli.put(canonical_transition(txn(0), SPEC), generation=1,
                   epoch=0) is True
    stats = cli.stats()
    assert stats["duplicates_total"] == 1  # retry hit the dedup path
    assert stats["accepted_total"] == 0
    assert srv.snapshot()["accepted_total"] == 1
    assert srv.staging.staged_total == 1  # never double-ingested
    assert_conserved(srv.staging)
    # The next push proceeds in the advanced seq space.
    assert cli.put(canonical_transition(txn(1), SPEC), generation=1,
                   epoch=0) is True
    assert srv.staging.staged_total == 2


def test_client_budget_exhaustion_keeps_seq_for_retry():
    def dead_post(path, payload, timeout_s):
        raise ConnectionError("connection refused")

    cli = RemoteStagingClient(
        "http://unused", actor_id=0, retry_budget_s=0.05,
        backoff_s=0.001, sleep=_no_sleep, post=dead_post,
    )
    tr = canonical_transition(txn(0), SPEC)
    with pytest.raises(StagingUnavailable):
        cli.put(tr, generation=1, epoch=0)
    seq_before = cli.stats()["next_seq"]
    # The ActorWorker idle-spin retries the SAME transition: same seq,
    # so whatever the dead window actually landed is deduplicated once
    # the learner is back.
    srv = make_server()
    cli._post = lambda p, b, t: srv.handle_stage(b)[:2]
    assert cli.put(tr, generation=1, epoch=0) is True
    assert cli.stats()["next_seq"] == seq_before + 1
    assert srv.staging.staged_total == 1
    assert_conserved(srv.staging)


def test_flaky_transport_drops_then_delivers_exactly_once():
    srv = make_server()
    flaky = FlakyTransport(
        lambda p, b, t: srv.handle_stage(b)[:2], sleep=_no_sleep
    )
    cli = RemoteStagingClient(
        "http://unused", actor_id=3, retry_budget_s=30.0,
        backoff_s=0.0001, sleep=_no_sleep, post=flaky,
    )
    flaky.drop_next(2)
    assert cli.put(canonical_transition(txn(0), SPEC), generation=1,
                   epoch=0) is True
    assert flaky.drops_injected == 2
    assert flaky.calls_total == 3
    assert cli.stats()["retries_total"] == 2
    assert srv.staging.staged_total == 1  # exactly once through the flap
    assert srv.snapshot()["actors"]["3"]["accepted_total"] == 1
    assert_conserved(srv.staging)


def test_client_410_means_superseded():
    srv = make_server()
    srv.retire_actor(0, incarnation=0)
    cli = RemoteStagingClient(
        "http://unused", actor_id=0, incarnation=0, sleep=_no_sleep,
        post=lambda p, b, t: srv.handle_stage(b)[:2],
    )
    with pytest.raises(RuntimeError, match="superseded"):
        cli.put(canonical_transition(txn(0), SPEC))


def test_heartbeat_over_http_feeds_liveness_and_fences_zombies():
    srv = make_server().start()
    try:
        cli = RemoteStagingClient(srv.address, actor_id=2, incarnation=5)
        assert cli.heartbeat(pid=4242, steps=17) is True
        live = srv.liveness()
        assert live[2]["pid"] == 4242
        assert live[2]["incarnation"] == 5
        assert live[2]["steps"] == 17
        assert live[2]["age_s"] < 60.0
        srv.retire_actor(2, incarnation=5)
        with pytest.raises(RuntimeError, match="superseded"):
            cli.heartbeat(pid=4242, steps=18)
        # Heartbeat delivery failure is counted, never raised: loss IS
        # the supervisor's signal, the actor must not die of it.
        dead = RemoteStagingClient("http://127.0.0.1:1", actor_id=9)
        assert dead.heartbeat(pid=1, steps=0) is False
        assert dead.stats()["heartbeat_failures_total"] == 1
    finally:
        srv.close()


# ------------------------------------------------------ checkpoint bridge


def test_staged_tail_and_watermarks_roundtrip():
    srv = make_server(staging=StagingBuffer(8, policy="shed"))
    for i in range(3):
        assert srv.handle_stage(
            stage_body(i, actor_id=i % 2, incarnation=0, seq=i // 2)
        )[0] == 200
    arrays = srv.staging.export_arrays()
    assert [int(a) for a in arrays["actor_id"]] == [0, 1, 0]
    st2 = StagingBuffer(8, policy="shed")
    st2.load_meta(srv.staging.meta_state())
    assert st2.import_arrays(arrays) == 3
    assert st2.snapshot() == srv.staging.snapshot()
    assert_conserved(st2)
    # Restored entries keep their producer tag: purging actor 0 in the
    # restored buffer drops exactly its two transitions.
    assert st2.purge_actor(0) == 2
    assert_conserved(st2)
    # Pre-fleet checkpoints (no actor_id array) restore as untagged.
    legacy = {k: v for k, v in arrays.items() if k != "actor_id"}
    st3 = StagingBuffer(8, policy="shed")
    assert st3.import_arrays(legacy) == 3
    assert st3.purge_actor(0) == 0
    assert st3.purge_actor(-1) == 3
    # Watermarks survive the JSON round trip and keep deduping.
    srv2 = make_server()
    srv2.load_watermarks(json.loads(json.dumps(srv.watermarks())))
    code, payload, _ = srv2.handle_stage(
        stage_body(0, actor_id=0, incarnation=0, seq=0)
    )
    assert code == 200 and payload["duplicate"] is True
    assert srv2.staging.staged_total == 0


# ------------------------------------------------------------- supervisor


class _FakeProc:
    def __init__(self, pid):
        self.pid = pid
        self.alive = True
        self.exitcode = None

    def is_alive(self):
        return self.alive

    def join(self, timeout=None):
        pass


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _make_supervisor(clock, liveness, max_restarts=2, **kw):
    import random

    spawned, kills, retired = [], [], []

    def spawn(aid, inc):
        proc = _FakeProc(pid=5000 + 100 * aid + inc)
        spawned.append((aid, inc, proc))
        return proc

    def on_death(aid, inc):
        retired.append((aid, inc))
        return 1

    sup = FleetSupervisor(
        spawn, n_actors=2, liveness=liveness, on_death=on_death,
        heartbeat_timeout_s=3.0, max_restarts=max_restarts,
        backoff_s=0.5, clock=clock,
        kill=lambda pid, sig: kills.append((pid, sig)),
        rng=random.Random(0), **kw,
    )
    # Seed the slots by hand (the monitor thread stays off: tests
    # drive poll_once against the fake clock).
    with sup._lock:
        for aid in range(sup.n_actors):
            sup._incarnation[aid] = 0
            sup._restarts[aid] = 0
            sup._procs[aid] = sup._spawn(aid, 0)
            sup._spawned_at[aid] = clock()
    return sup, spawned, retired, kills


def test_supervisor_restarts_dead_process_with_backoff():
    clock = _Clock()
    sup, spawned, retired, kills = _make_supervisor(clock, lambda: {})
    assert len(spawned) == 2
    spawned[0][2].alive = False  # actor 0's process dies
    sup.poll_once()
    assert retired == [(0, 0)]  # watermark bump + purge ran
    assert kills == [(5000, signal.SIGKILL)]
    st = sup.stats()
    assert st["deaths_total"] == 1
    assert st["restarts_total"] == 0  # backoff pending
    # Before the backoff expires: no respawn.
    clock.t += 0.1
    sup.poll_once()
    assert len(spawned) == 2
    # Past the jittered backoff (0.5s x [1, 1.5]): respawned as the
    # next incarnation.
    clock.t += 0.8
    sup.poll_once()
    assert len(spawned) == 3
    assert spawned[-1][:2] == (0, 1)
    st = sup.stats()
    assert st["restarts_total"] == 1
    assert st["purged_on_death_total"] == 1
    assert st["actors"][0]["incarnation"] == 1
    assert st["actors"][1]["incarnation"] == 0  # bystander untouched


def test_supervisor_heartbeat_deadline_and_grace():
    clock = _Clock()
    live = {}
    sup, spawned, retired, _kills = _make_supervisor(
        clock, lambda: live, grace_s=60.0
    )
    # No heartbeat yet but inside the grace window: alive (process
    # start + imports are not a liveness failure).
    clock.t += 10.0
    sup.poll_once()
    assert retired == []
    # Heartbeats flowing, stale-but-within-deadline: alive.
    live[0] = {"age_s": 2.0, "incarnation": 0, "pid": 1, "steps": 5}
    live[1] = {"age_s": 0.1, "incarnation": 0, "pid": 2, "steps": 5}
    sup.poll_once()
    assert retired == []
    # Heartbeat past the deadline: declared dead even though the
    # process object still claims alive (wedged, not exited).
    live[0]["age_s"] = 3.5
    sup.poll_once()
    assert retired == [(0, 0)]
    # A heartbeat from the STALE incarnation does not vouch for the
    # successor: past the grace window with no fresh-incarnation beat,
    # it is declared dead too.
    clock.t += 1.0
    sup.poll_once()  # respawn as incarnation 1
    assert spawned[-1][:2] == (0, 1)
    clock.t += 61.0
    live[1]["age_s"] = 0.1  # actor 1 keeps beating
    sup.poll_once()
    assert retired[-1] == (0, 1)
    assert all(aid == 0 for aid, _inc in retired)


def test_supervisor_gives_up_after_max_restarts():
    clock = _Clock()
    sup, spawned, _retired, _k = _make_supervisor(
        clock, lambda: {}, max_restarts=1
    )
    for _ in range(2):
        # Kill actor 0's latest incarnation each round.
        next(
            p for a, _i, p in reversed(spawned) if a == 0
        ).alive = False
        sup.poll_once()
        clock.t += 2.0
        sup.poll_once()
    st = sup.stats()
    assert st["gave_up"] == [0]
    assert st["restarts_total"] == 1
    assert st["deaths_total"] == 2
    # An abandoned slot stays abandoned; the survivor keeps running.
    clock.t += 10.0
    sup.poll_once()
    assert len(spawned) == 3  # initial 2 + the one allowed restart
    assert sup.stats()["actors"][1]["alive"] is True


def test_kill_actor_raw_pid_and_supervisor_slot():
    import multiprocessing as mp

    # spawn, not fork: jax is multithreaded and fork-unsafe.
    ctx = mp.get_context("spawn")
    # Raw-pid mode (the smoke killing across a process boundary).
    p1 = ctx.Process(target=time.sleep, args=(60,), daemon=True)
    p1.start()
    assert kill_actor(p1.pid) == p1.pid
    p1.join(timeout=10.0)
    assert not p1.is_alive() and p1.exitcode == -signal.SIGKILL
    # Supervisor-slot mode: kill by actor index, joined before return.
    p2 = ctx.Process(target=time.sleep, args=(60,), daemon=True)
    p2.start()
    sup, _s, _r, _k = _make_supervisor(_Clock(), lambda: {})
    with sup._lock:
        sup._procs[1] = p2
    assert kill_actor(sup, idx=1) == p2.pid
    assert not p2.is_alive() and p2.exitcode == -signal.SIGKILL
    with pytest.raises(ValueError, match="no live actor"):
        kill_actor(sup, idx=7)


# ------------------------------------------------- FleetTrainer end-to-end


TINY_FLEET = dict(
    hidden_sizes=(16, 16),
    batch_size=16,
    epochs=2,
    steps_per_epoch=40,
    start_steps=10,
    update_after=10,
    update_every=10,
    buffer_size=500,
    max_ep_len=100,
    save_every=1,
    actors=2,
    # shed (not block): a full buffer must never wedge a transport
    # handler thread under test timing.
    staging_policy="shed",
    max_actor_lag=4,
    heartbeat_interval_s=0.1,
    heartbeat_timeout_s=30.0,  # thread actors: no liveness churn
)


class _ThreadProc:
    """Thread-backed stand-in satisfying the supervisor's process
    protocol. The fake pid guarantees os.kill raises ProcessLookupError
    (handled as already-reaped); join() doubles as the stop signal so
    SIGTERM-less shutdown still rolls the actor down."""

    _pids = itertools.count(2 ** 24)

    def __init__(self, body):
        self.pid = next(self._pids)
        self.exitcode = None
        self.stop = threading.Event()
        self.result = None
        self._thread = threading.Thread(
            target=self._run, args=(body,), daemon=True
        )
        self._thread.start()

    def _run(self, body):
        try:
            self.result = body(self.stop)
            self.exitcode = 0
        except Exception:  # noqa: BLE001 — surfaced via exitcode
            self.exitcode = 1
            raise

    def is_alive(self):
        return self._thread.is_alive()

    def join(self, timeout=None):
        self.stop.set()
        self._thread.join(timeout)


def make_fleet_trainer(ckpt_dir, seed=7, fleet_port=0, **over):
    cfg = SACConfig(**{**TINY_FLEET, **over})
    ck = (
        Checkpointer(ckpt_dir, retry_backoff_s=0.0)
        if ckpt_dir is not None else None
    )
    procs = []

    def spawn(actor_id, incarnation):
        def body(stop):
            return _actor_loop(
                actor_id, incarnation, trainer.transport.address,
                "Pendulum-v1", 1, 1000 + 10 * actor_id + incarnation,
                stop,
                options={
                    "heartbeat_interval_s": 0.1,
                    "act_timeout_s": 2.0,
                    "push_retry_s": 1.0,
                    "probe_every": 4,
                },
            )

        proc = _ThreadProc(body)
        procs.append(proc)
        return proc

    trainer = FleetTrainer(
        "Pendulum-v1", cfg, mesh=make_mesh(dp=1), checkpointer=ck,
        seed=seed, spawn=spawn,
    )
    return trainer, procs


def test_fleet_trainer_trains_through_actor_death():
    trainer, procs = make_fleet_trainer(None)
    trainer.supervisor.backoff_s = 0.05  # fast respawn under test
    killed = {}

    def kill_one():
        # Simulate a crash: the actor thread stops; the supervisor's
        # next poll sees a dead "process" and runs the whole
        # kill -> purge -> respawn chain.
        victim = procs[0]
        killed["pid"] = victim.pid
        victim.stop.set()

    # Fire the crash at a fixed learner step (deterministic injection
    # point, the tests/test_resilience.py pattern).
    from torch_actor_critic_tpu.resilience.faultinject import FaultyEnvPool

    trainer.pool = FaultyEnvPool(trainer.pool).call_at(45, kill_one)
    try:
        out = trainer.train()
        # Both epochs completed with the invariant green at the boundary.
        assert out["decoupled/conservation_ok"] == 1.0
        assert trainer.staging.drained_total >= (
            2 * TINY_FLEET["steps_per_epoch"]
        )
        # The fleet actually fed the learner over the wire.
        tsnap = trainer.transport.snapshot()
        assert tsnap["accepted_total"] > 0
        # The conservation invariant held through death + purge.
        assert_conserved(trainer.staging)
        # The kill was observed and the slot restarted (the respawn may
        # land after train() returns — drive the supervisor until it
        # does).
        deadline = time.time() + 20.0
        while (
            trainer.supervisor.stats()["restarts_total"] < 1
            and time.time() < deadline
        ):
            trainer.supervisor.poll_once()
            time.sleep(0.02)
        st = trainer.supervisor.stats()
        assert st["deaths_total"] >= 1
        assert st["restarts_total"] >= 1
        assert st["actors"][0]["incarnation"] >= 1
        # Zero double-ingestion: per-actor accepted counts sum to the
        # server total, and for a never-retired actor the watermark
        # bounds its accepts (sheds skip seqs, so seq+1 >= accepted;
        # a retire resets seq to -1, which is why retired slots are
        # excluded — their audit is the purge count).
        # (Both sides from ONE snapshot: the surviving and the respawned
        # actors keep feeding while the loop above waits for the respawn,
        # so a total read before it is stale by then on a loaded host.)
        snap = trainer.transport.snapshot()
        per_actor = snap["actors"]
        assert sum(
            a["accepted_total"] for a in per_actor.values()
        ) == snap["accepted_total"]
        for aid, a in per_actor.items():
            if st["actors"][int(aid)]["restarts"] == 0:
                assert a["accepted_total"] <= a["seq"] + 1
        # Fleet metrics reached telemetry.
        m = trainer.metrics_snapshot()["decoupled"]
        assert m["fleet"]["deaths_total"] >= 1
        assert m["transport"]["accepted_total"] > 0
    finally:
        trainer.close()
    # close() rolled the fleet down.
    assert all(not p.is_alive() for p in procs)


def test_fleet_checkpoint_resume_restores_watermarks_and_dedups(tmp_path):
    t1, procs1 = make_fleet_trainer(str(tmp_path))
    try:
        t1.train()
        marks1 = t1.transport.watermarks()
        assert any(int(m["seq"]) >= 0 for m in marks1.values())
    finally:
        t1.close()
    # A fresh learner process resumes from the checkpoint: watermarks
    # restore, so respawned actors start at bumped incarnations and a
    # push retried across the restart is deduplicated.
    t2, _procs2 = make_fleet_trainer(str(tmp_path))
    try:
        assert t2.restore() > 0
        marks2 = t2.transport.watermarks()
        for aid, m in marks1.items():
            assert marks2[aid]["incarnation"] == m["incarnation"]
            # The checkpoint is a consistent prefix cut: actors kept
            # pushing between the last save and the watermark read
            # above, so the restored seq can only trail it.
            assert 0 <= marks2[aid]["seq"] <= m["seq"]
            assert t2._restored_incarnations[int(aid)] == (
                int(m["incarnation"]) + 1
            )
        assert_conserved(t2.staging)
        # The restart counter continues, never resets.
        assert t2.supervisor.restarts_total == (
            t1.supervisor.restarts_total
        )
        # A reconnecting actor retrying its last checkpointed push
        # (same incarnation + seq — the response was lost to the
        # restart) is answered duplicate: zero double-ingested across
        # resume.
        aid = next(
            a for a, m in marks2.items() if int(m["seq"]) >= 0
        )
        staged_before = t2.staging.staged_total
        code, payload, _ = t2.transport.handle_stage(stage_body(
            0, actor_id=int(aid),
            incarnation=int(marks2[aid]["incarnation"]),
            seq=int(marks2[aid]["seq"]),
            transition=txn(0, n_envs=1),
        ))
        assert code == 200 and payload["duplicate"] is True
        assert t2.staging.staged_total == staged_before
        # And its NEXT seq is accepted normally.
        code, payload, _ = t2.transport.handle_stage(stage_body(
            1, actor_id=int(aid),
            incarnation=int(marks2[aid]["incarnation"]),
            seq=int(marks2[aid]["seq"]) + 1,
            transition=txn(1, n_envs=1),
        ))
        assert code == 200 and payload["duplicate"] is False
        assert_conserved(t2.staging)
    finally:
        t2.close()
