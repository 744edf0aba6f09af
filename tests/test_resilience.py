"""Resilience tests: every recovery path proven end-to-end on CPU.

Each production fault class is injected into a REAL Trainer through
``resilience/faultinject.py`` (ISSUE 2) and the recovery is asserted,
not hoped for:

- NaN batch   -> divergence sentinel -> rollback to last-good -> recovery
- SIGTERM     -> emergency checkpoint -> requeue exit -> bitwise resume
- flaky IO    -> bounded retry; corrupt newest step -> fallback to older
- dead worker -> diagnosed error (with exit code), bounded close()

Synchronization discipline: every injection keys off an exact lockstep
step count (``FaultyEnvPool``) or a joined process — no wall-clock
sleeps anywhere, so nothing here is timing-flaky.
"""

import json
import os
import signal
import time

import jax
import numpy as np
import pytest

from torch_actor_critic_tpu.envs.vec_env import ParallelEnvPool
from torch_actor_critic_tpu.native import NativeRuntimeError, load_runtime
from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.resilience import (
    REQUEUE_EXIT_CODE,
    DivergenceSentinel,
    Preempted,
    PreemptionGuard,
    TrainingDiverged,
    call_with_retries,
    tree_all_finite,
)
from torch_actor_critic_tpu.resilience.faultinject import (
    FaultyEnvPool,
    corrupt_checkpoint,
    kill_env_worker,
    make_flaky,
)
from torch_actor_critic_tpu.sac.trainer import Trainer
from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
from torch_actor_critic_tpu.utils.config import SACConfig

def _native_missing() -> bool:
    try:
        load_runtime()
    except NativeRuntimeError:
        return True
    return False


needs_native = pytest.mark.skipif(
    _native_missing(), reason="native runtime unavailable"
)

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
)


def make_trainer(ckpt_dir, seed=7, dp=1, preemption=None, **over):
    cfg = SACConfig(**{**TINY, **over})
    ck = (
        Checkpointer(ckpt_dir, retry_backoff_s=0.0)
        if ckpt_dir is not None
        else None
    )
    return Trainer(
        "Pendulum-v1",
        cfg,
        mesh=make_mesh(dp=dp),
        checkpointer=ck,
        seed=seed,
        preemption=preemption,
    )


def comparable_state(tr):
    """Every array that defines the learner: full TrainState (PRNG key
    as raw uint32) + the replay ring and its cursors."""
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


# ------------------------------------------------- path 1: NaN -> rollback


def test_nan_batch_rolls_back_and_recovers(tmp_path):
    """A NaN reward mid-epoch-1 must cost exactly one rollback (to the
    sentinel-validated epoch-0 checkpoint) and training must finish
    with finite metrics, finite params and a clean replay ring — the
    reference trains on the poison forever."""
    tr = make_trainer(tmp_path / "ck", epochs=4)
    # Lockstep step 50 is inside epoch 1 (steps 40..79): the epoch-0
    # checkpoint already exists, so rollback has a target.
    tr.pool = FaultyEnvPool(tr.pool).nan_rewards_at(50)
    try:
        metrics = tr.train()
        assert tr.sentinel.total_rollbacks == 1
        assert metrics["rollbacks"] == 1
        assert np.isfinite(metrics["loss_q"])
        assert np.isfinite(metrics["loss_pi"])
        # Rollback restored the ring too: the poisoned rows are gone
        # (a params-only rollback would re-diverge on the next sample).
        assert np.isfinite(np.asarray(tr.buffer.data.rewards)).all()
        assert tree_all_finite(tr.state, tr.buffer.data)
    finally:
        tr.close()


def test_divergence_without_checkpoint_aborts():
    """No checkpointer -> nothing to roll back to: the run must abort
    with a diagnosed TrainingDiverged, not keep training on NaNs."""
    tr = make_trainer(None, epochs=2)
    tr.pool = FaultyEnvPool(tr.pool).nan_rewards_at(5)
    try:
        with pytest.raises(TrainingDiverged, match="no checkpoint"):
            tr.train()
    finally:
        tr.close()


def test_rollback_budget_bounds_consecutive_divergence(tmp_path):
    """Persistent (systematic) divergence must exhaust max_rollbacks
    and abort instead of rolling back forever: NaN injected in two
    consecutive epochs with a budget of one."""
    tr = make_trainer(tmp_path / "ck", epochs=4, max_rollbacks=1)
    tr.pool = (
        FaultyEnvPool(tr.pool).nan_rewards_at(50).nan_rewards_at(90)
    )
    try:
        with pytest.raises(TrainingDiverged, match="consecutive"):
            tr.train()
    finally:
        tr.close()


# --------------------------------- path 2: SIGTERM -> save -> requeue code


def test_sigterm_preemption_saves_and_resume_is_bitwise(tmp_path):
    """The full preemption round-trip with a REAL signal: SIGTERM lands
    mid-epoch-1, the trainer finishes the epoch, checkpoints, and
    raises with the requeue exit code; a resumed run continues and
    finishes with a learner state BITWISE identical to an uninterrupted
    run — epochs are replayable units (epoch-boundary reseeding + the
    checkpointed step counter and acting key)."""
    # Run A: 3 epochs, uninterrupted.
    tra = make_trainer(tmp_path / "a", epochs=3, save_every=10)
    try:
        tra.train()
        ref = comparable_state(tra)
    finally:
        tra.close()

    # Run B: same seed/config; SIGTERM delivered at lockstep step 45
    # (epoch 1). The installed handler only flags; the trainer exits at
    # the epoch boundary after an emergency save.
    guard = PreemptionGuard().install()
    trb = make_trainer(
        tmp_path / "b", epochs=3, save_every=10, preemption=guard
    )
    trb.pool = FaultyEnvPool(trb.pool).call_at(
        45, lambda: os.kill(os.getpid(), signal.SIGTERM)
    )
    try:
        with pytest.raises(Preempted) as ei:
            trb.train()
    finally:
        guard.uninstall()
        trb.close()
    assert ei.value.exit_code == REQUEUE_EXIT_CODE
    assert ei.value.epoch == 1
    meta = trb.checkpointer.peek_meta()
    assert meta["epoch"] == 1
    assert meta["step"] == 80  # epoch boundary: 2 epochs x 40 steps
    assert meta["act_key"]  # the acting stream is part of the state

    # Run C: resume B and train the remaining epoch.
    trc = make_trainer(tmp_path / "b", epochs=1, save_every=10)
    try:
        assert trc.restore() == 2
        assert trc._resume_step == 80  # no warmup redo on resume
        trc.train()
        got = comparable_state(trc)
    finally:
        trc.close()
    for x, y in zip(ref, got, strict=True):
        np.testing.assert_array_equal(x, y)


def test_urgent_preemption_saves_at_window_boundary(tmp_path):
    """A second signal (here the programmatic harness path) must not
    wait for the epoch: the checkpoint lands at the next update-window
    boundary with the mid-epoch step counter, and resume continues
    from it without re-randomizing warmup."""
    guard = PreemptionGuard()  # never installed: API-driven preemption
    tr = make_trainer(
        tmp_path / "ck", epochs=3, save_every=10, preemption=guard
    )
    tr.pool = FaultyEnvPool(tr.pool).call_at(
        52, lambda: guard.request_preemption(urgent=True)
    )
    try:
        with pytest.raises(Preempted) as ei:
            tr.train()
    finally:
        tr.close()
    assert ei.value.urgent
    meta = tr.checkpointer.peek_meta()
    assert meta["epoch"] == 1
    assert meta["step"] == 60  # first window boundary after step 52

    tr2 = make_trainer(tmp_path / "ck", epochs=1, save_every=10)
    try:
        assert tr2.restore() == 2
        assert tr2._resume_step == 60
        m = tr2.train()
        assert np.isfinite(m["loss_q"])
        assert int(tr2.state.step) > 50  # gradient steps continued
    finally:
        tr2.close()


def test_train_cli_maps_preempted_to_requeue_exit_code(tmp_path, monkeypatch):
    """train.py converts Preempted into SystemExit(75) so `make`/
    schedulers can tell *requeue me* from a crash."""
    from torch_actor_critic_tpu import train as train_mod

    def fake_train(self, render=False):
        raise Preempted(epoch=0)

    monkeypatch.setattr(Trainer, "train", fake_train)
    with pytest.raises(SystemExit) as ei:
        train_mod.main(
            [
                "--environment", "Pendulum-v1",
                "--devices", "1",
                "--runs-root", str(tmp_path),
                "--epochs", "1",
                "--steps-per-epoch", "10",
                "--batch-size", "16",
                "--buffer-size", "100",
                "--hidden-sizes", "16,16",
            ]
        )
    assert ei.value.code == REQUEUE_EXIT_CODE


# ------------------------- path 3: checkpoint IO retry / corrupt fallback


def test_checkpoint_save_and_restore_retry_transient_io(tmp_path):
    """Transient OSErrors (network FS hiccups) are absorbed by the
    bounded retry ladder; persistent ones still surface."""
    ck = Checkpointer(
        tmp_path / "ck", retries=2, retry_backoff_s=0.0,
        sleep=lambda s: None, save_buffer=False,
    )
    state = {"w": np.arange(4, dtype=np.float32)}
    ck._mgr.save = make_flaky(ck._mgr.save, failures=2)
    ck.save(0, state, wait=True)  # 2 failures < 3 attempts -> lands
    ck._mgr.restore = make_flaky(ck._mgr.restore, failures=2)
    assert ck.peek_meta(0)["epoch"] == 0
    ck.close()

    ck2 = Checkpointer(
        tmp_path / "ck2", retries=1, retry_backoff_s=0.0,
        sleep=lambda s: None, save_buffer=False,
    )
    ck2._mgr.save = make_flaky(ck2._mgr.save, failures=2)
    with pytest.raises(OSError, match="injected"):
        ck2.save(0, state, wait=True)
    ck2.close()


def test_retry_backoff_is_exponential_and_fnf_gives_up():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert (
        call_with_retries(
            flaky, attempts=3, base_delay_s=0.5, sleep=sleeps.append
        )
        == "ok"
    )
    assert sleeps == [0.5, 1.0]

    def missing():
        raise FileNotFoundError("gone for good")

    with pytest.raises(FileNotFoundError):
        # Deterministic failure: must NOT burn retry attempts on it.
        call_with_retries(
            missing, attempts=3, base_delay_s=0.5, sleep=sleeps.append
        )
    assert sleeps == [0.5, 1.0]  # no additional sleeps


@pytest.mark.parametrize("mode", ["drop-item", "truncate"])
def test_corrupt_newest_checkpoint_falls_back_to_previous(tmp_path, mode):
    """An interrupted/corrupt newest step (simulated exactly as a
    mid-write crash leaves it) must cost one save_every interval, not
    the resume: restore falls back to epoch 0 and training continues."""
    tr = make_trainer(tmp_path / "ck", epochs=2)  # checkpoints 0 and 1
    try:
        tr.train()
    finally:
        tr.close()
    corrupt_checkpoint(tmp_path / "ck", 1, mode=mode)

    tr2 = make_trainer(tmp_path / "ck", epochs=1)
    try:
        assert tr2.restore() == 1  # fell back: resumes AFTER epoch 0
        m = tr2.train()
        assert np.isfinite(m["loss_q"])
    finally:
        tr2.close()


def test_unreadable_meta_is_skipped_by_latest_epoch(tmp_path):
    tr = make_trainer(tmp_path / "ck", epochs=2)
    try:
        tr.train()
    finally:
        tr.close()
    corrupt_checkpoint(tmp_path / "ck", 1, mode="drop-meta")
    ck = Checkpointer(tmp_path / "ck")
    try:
        assert ck.latest_epoch() == 0
        assert ck.peek_meta()["epoch"] == 0
    finally:
        ck.close()


def test_explicit_epoch_never_falls_back(tmp_path):
    """Fallback is a resume (epoch=None) behavior only: a caller that
    pins an epoch asked for THAT state — substituting another would be
    silent corruption."""
    tr = make_trainer(tmp_path / "ck", epochs=2)
    try:
        tr.train()
    finally:
        tr.close()
    corrupt_checkpoint(tmp_path / "ck", 1, mode="drop-item")
    tr2 = make_trainer(tmp_path / "ck", epochs=1)
    try:
        with pytest.raises(Exception):  # noqa: PT011 — orbax's error class
            tr2.restore(epoch=1)
    finally:
        tr2.close()


# -------------------------------- path 4: dead env worker, bounded close


@needs_native
def test_dead_env_worker_is_diagnosed_with_exit_code_and_close_is_bounded():
    pool = ParallelEnvPool(
        "Pendulum-v1", 2, base_seed=0, timeout_s=3, start_method="fork"
    )
    try:
        pool.reset_all()
        code = kill_env_worker(pool, 1)  # SIGKILL + join: death observed
        assert code == -signal.SIGKILL
        with pytest.raises(
            RuntimeError, match=r"worker 1 died \(exitcode -9\)"
        ):
            pool.step(np.zeros((2, 1), np.float32))
    finally:
        t0 = time.monotonic()
        pool.close()
        # Bounded teardown: CLOSE dispatch + joins + escalation, never
        # a blocking wait on the dead worker's ack.
        assert time.monotonic() - t0 < 30.0


@needs_native
def test_env_worker_death_mid_training_surfaces_and_cleans_up():
    """End-to-end: a worker SIGKILLed mid-training must surface as a
    diagnosed RuntimeError from train() (not a deadlock, the
    reference's behavior), and teardown must complete."""
    cfg = SACConfig(
        **{
            **TINY,
            "epochs": 1,
            "parallel_envs": True,
            "env_timeout_s": 3.0,
            "env_start_method": "fork",
        }
    )
    tr = Trainer("Pendulum-v1", cfg, mesh=make_mesh(dp=2))
    tr.pool = FaultyEnvPool(tr.pool).call_at(
        15, lambda: kill_env_worker(tr.pool, 1)
    )
    try:
        with pytest.raises(RuntimeError, match="exitcode"):
            tr.train()
    finally:
        tr.close()


# ----------------------------------------------------------- unit pieces


def test_tree_all_finite_skips_non_inexact_leaves():
    key = jax.random.key(0)
    assert tree_all_finite(
        {"i": np.arange(3), "f": np.ones(3), "k": key, "b": np.array([True])}
    )
    assert not tree_all_finite({"f": np.array([1.0, np.nan])})
    assert not tree_all_finite(np.array([np.inf]))
    assert tree_all_finite()  # vacuously true


def test_sentinel_budget_resets_on_good_interval():
    s = DivergenceSentinel(max_rollbacks=1)
    s.note_divergence()
    s.note_good()  # a finite epoch closes the streak
    s.note_divergence()
    with pytest.raises(TrainingDiverged):
        s.note_divergence()
    assert s.total_rollbacks == 3


def test_guard_signal_escalation():
    prev = signal.getsignal(signal.SIGTERM)
    guard = PreemptionGuard().install()
    try:
        assert not guard.triggered and not guard.urgent
        os.kill(os.getpid(), signal.SIGTERM)
        assert guard.triggered and not guard.urgent
        os.kill(os.getpid(), signal.SIGTERM)
        assert guard.urgent
    finally:
        guard.uninstall()
    # install/uninstall round-trips the previous handler exactly.
    assert signal.getsignal(signal.SIGTERM) == prev


def test_checkpoint_meta_carries_resume_state(tmp_path):
    """Every checkpoint persists the host-loop state (step counter,
    acting key) alongside the TrainState — JSON-round-trippable."""
    tr = make_trainer(tmp_path / "ck", epochs=1)
    try:
        tr.train()
    finally:
        tr.close()
    meta = Checkpointer(tmp_path / "ck").peek_meta()
    assert meta["step"] == 40
    key = np.asarray(meta["act_key"], dtype=np.uint32)
    assert key.shape == np.asarray(
        jax.random.key_data(jax.random.key(0))
    ).shape
    json.dumps(meta)  # the whole meta stays JSON-serializable


# ---------------------------------------- path 5: lossy actor<->serving link


def test_lossy_link_degrades_actor_and_recovery_rehomes(tmp_path):
    """The decoupled plane's link fault (resilience/faultinject.py
    LossyLink): a dropped-then-recovering actor<->serving link must
    degrade acting to the local snapshot WITHOUT stalling the env loop,
    keep training, and re-home when the link heals — the decoupled
    twin of the env-worker-death path (ISSUE 10; the full matrix lives
    in tests/test_decoupled.py and `make decouple-smoke`)."""
    from torch_actor_critic_tpu.decoupled import DecoupledTrainer
    from torch_actor_critic_tpu.resilience.faultinject import LossyLink

    cfg = SACConfig(**{**TINY, "epochs": 2, "decoupled": True})
    tr = DecoupledTrainer(
        "Pendulum-v1", cfg, mesh=make_mesh(dp=1),
        checkpointer=None, seed=7,
    )
    # Every serving call from lockstep step 15 to ~step 30 dies at the
    # link; the actor's probe cadence re-homes it before the run ends.
    link = LossyLink(tr.client).drop_next(5)
    tr.pool = FaultyEnvPool(tr.pool).call_at(
        15, lambda: setattr(tr.actor, "client", link)
    )
    try:
        metrics = tr.train()
        assert np.isfinite(metrics["loss_q"])
        assert link.drops_injected == 5
        assert tr.actor.degradations_total >= 1
        assert tr.actor.fallback_actions_total >= 1
        assert tr.actor.rehomes_total >= 1
        assert not tr.actor.degraded  # healed link, re-homed actor
        assert tr.staging.conservation_holds()
    finally:
        tr.close()
