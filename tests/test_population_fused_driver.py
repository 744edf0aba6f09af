"""Population-fused on-device training through its driver
(``sac/ondevice.py::train_population_on_device``): checkpoint and resume to
the bit, distinct member curves, a member's export for serving, the CLI's
route and its PBT events, and the per-member normalizer. The epochs
themselves are ``test_population_fused.py``'s."""

import json

import jax
import numpy as np
import pytest

from population_fused_helpers import _assert_bitwise

from torch_actor_critic_tpu.sac.ondevice import train_population_on_device
from torch_actor_critic_tpu.utils.config import SACConfig

# ------------------------------------------- driver, checkpoint, export


def _driver_config(epochs):
    return SACConfig(
        population=3, on_device=True, on_device_envs=2,
        pbt_every=2, pbt_quantile=0.34, pbt_ema=0.5,
        hidden_sizes=(16, 16), batch_size=8,
        epochs=epochs, steps_per_epoch=20, update_every=10,
        start_steps=10, update_after=0, buffer_size=400,
        save_every=1, max_ep_len=100,
    )


@pytest.fixture(scope="module")
def resumed_vs_straight(tmp_path_factory):
    """Run A: 3 epochs straight. Run B: 2 epochs, then a fresh resumed
    driver for 1 more — the lossless-resume pin for populations."""
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer

    root = tmp_path_factory.mktemp("popckpt")
    m_straight = train_population_on_device(
        "Pendulum-v1", _driver_config(3),
        checkpointer=Checkpointer(root / "a"), seed=3,
    )
    train_population_on_device(
        "Pendulum-v1", _driver_config(2),
        checkpointer=Checkpointer(root / "b"), seed=3,
    )
    m_resumed = train_population_on_device(
        "Pendulum-v1", _driver_config(1),
        checkpointer=Checkpointer(root / "b"), seed=3,
    )
    return root, m_straight, m_resumed


def test_population_checkpoint_resume_is_bitwise(resumed_vs_straight):
    root, m_straight, m_resumed = resumed_vs_straight
    # Per-member loss/reward curves of the final epoch match EXACTLY —
    # the resumed run recomputed the identical epoch (stacked state +
    # member PRNG keys + hyperparams + env states all round-tripped).
    for k, v in m_straight.items():
        if k.endswith("_per_sec"):
            continue
        if isinstance(v, float) and np.isnan(v):
            assert np.isnan(m_resumed[k]), k
            continue
        assert m_resumed[k] == v, (k, v, m_resumed[k])
    # And the final checkpoints hold bitwise-identical actor params.
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer

    pa, meta_a = Checkpointer(root / "a").restore_actor_params()
    pb, meta_b = Checkpointer(root / "b").restore_actor_params()
    assert meta_a["epoch"] == meta_b["epoch"] == 2
    _assert_bitwise(pa, pb)


def test_member_curves_are_distinct(resumed_vs_straight):
    _, m_straight, _ = resumed_vs_straight
    losses = [m_straight[f"loss_q_m{i}"] for i in range(3)]
    assert all(np.isfinite(losses)), losses
    assert len(set(losses)) == 3, losses  # three real curves


def test_export_member_checkpoint_for_serving(resumed_vs_straight):
    from torch_actor_critic_tpu.utils.checkpoint import (
        Checkpointer,
        export_member_checkpoint,
    )

    root, _, _ = resumed_vs_straight
    member, epoch = export_member_checkpoint(root / "a", root / "export")
    pop_params, meta = Checkpointer(root / "a").restore_actor_params()
    best = (meta.get("pbt") or {}).get("return_ema")
    assert member == int(np.argmax(best))
    one, one_meta = Checkpointer(root / "export").restore_actor_params()
    _assert_bitwise(
        one, jax.tree_util.tree_map(lambda x: x[member], pop_params)
    )
    assert one_meta["exported_member"] == member
    cfg = SACConfig.from_json(one_meta["config"])
    assert cfg.population == 1 and cfg.pbt_every == 0


def test_cli_routes_population_fused_and_emits_pbt_events(tmp_path):
    """train.py --on-device --population N end to end: per-member
    metrics in metrics.jsonl, a schema-valid pbt telemetry event, and
    a --run resume."""
    from torch_actor_critic_tpu.train import main as train_main

    args = [
        "--environment", "Pendulum-v1",
        "--on-device", "true",
        "--population", "2",
        "--pbt-every", "1",
        "--pbt-quantile", "0.5",
        "--telemetry", "true",
        "--devices", "1",
        "--runs-root", str(tmp_path),
        "--epochs", "2",
        "--steps-per-epoch", "20",
        "--update-every", "10",
        "--start-steps", "10",
        "--update-after", "0",
        "--batch-size", "8",
        "--buffer-size", "400",
        "--hidden-sizes", "16,16",
        "--on-device-envs", "2",
        "--max-ep-len", "100",
    ]
    metrics = train_main(args)
    assert "loss_q_m0" in metrics and "loss_q_m1" in metrics
    run_dir = next((tmp_path / "Default").iterdir())
    events = [
        json.loads(line)
        for line in (run_dir / "telemetry.jsonl").read_text().splitlines()
    ]
    pbt = [e for e in events if e.get("type") == "pbt"]
    assert pbt, "no pbt telemetry events"
    for e in pbt:
        assert {"epoch", "exploited", "src", "return_ema",
                "hyperparams"} <= set(e)
        assert len(e["src"]) == 2
    # Resume through the CLI (config comes from the stored run params).
    resumed = train_main(["--run", run_dir.name, "--runs-root", str(tmp_path)])
    assert "loss_q_m0" in resumed


# ------------------------------------------------ per-member normalizer


def test_per_member_normalizer_members_are_independent():
    from torch_actor_critic_tpu.utils.normalize import PerMemberNormalizer

    norm = PerMemberNormalizer(2, 3)
    rng = np.random.default_rng(0)
    # Member 0 sees N(0,1); member 1 sees N(100, 10).
    for _ in range(200):
        batch = np.stack([
            rng.normal(0.0, 1.0, 3), rng.normal(100.0, 10.0, 3)
        ])
        out = norm.normalize(batch)
    assert out.shape == (2, 3)
    np.testing.assert_allclose(norm.mean[0], 0.0, atol=0.5)
    np.testing.assert_allclose(norm.mean[1], 100.0, atol=3.0)
    # Pooling would have landed both means near 50 — independence held.
    one = norm.normalize(np.full(3, 100.0), update=False, member=1)
    assert one.shape == (3,)
    assert np.all(np.abs(one) < 2.0)  # near member 1's own mean
    far = norm.normalize(np.full(3, 100.0), update=False, member=0)
    assert np.all(far > 50.0)  # way off member 0's distribution
    # state_dict round-trip.
    d = norm.state_dict()
    norm2 = PerMemberNormalizer(2, 3)
    norm2.load_state_dict(d)
    np.testing.assert_array_equal(norm2.mean, norm.mean)
    np.testing.assert_array_equal(norm2.count, norm.count)
    with pytest.raises(ValueError, match="member-aligned"):
        norm.normalize(np.zeros((5, 3)))


def test_population_trainer_accepts_normalization(tmp_path):
    """population > 1 + normalize_observations no longer raises: the
    host trainer builds a PerMemberNormalizer and trains."""
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.utils.normalize import PerMemberNormalizer

    cfg = SACConfig(
        population=2, normalize_observations=True,
        hidden_sizes=(16, 16), batch_size=8,
        epochs=1, steps_per_epoch=30, start_steps=10, update_after=10,
        update_every=10, buffer_size=300, max_ep_len=100,
    )
    tr = Trainer("Pendulum-v1", cfg, mesh=make_mesh(dp=1), seed=0)
    try:
        assert isinstance(tr.normalizer, PerMemberNormalizer)
        metrics = tr.train()
        assert np.isfinite(metrics["loss_q"])
        # Both members contributed their own statistics.
        assert (tr.normalizer.count > 0).all()
        ev = tr.evaluate(episodes=1, deterministic=True, seed=5)
        assert len(ev["per_member"]) == 2
    finally:
        tr.close()


def test_split_member_metrics_layout():
    from torch_actor_critic_tpu.diagnostics import split_member_metrics

    out = split_member_metrics({
        "loss_q": np.array([1.0, 3.0]),
        "loss_q_max": np.array([2.0, 5.0]),
        "reward": np.array([np.nan, -10.0]),
        "episodes": np.array([0.0, 4.0]),
        "scalar": np.float32(7.0),
    })
    assert out["loss_q_m0"] == 1.0 and out["loss_q_m1"] == 3.0
    assert out["loss_q"] == 2.0          # default suffix -> mean
    assert out["loss_q_max"] == 5.0      # _max suffix -> max
    assert np.isnan(out["reward_m0"]) and out["reward_m1"] == -10.0
    assert out["reward"] == -10.0        # finite members only
    assert out["scalar"] == 7.0
