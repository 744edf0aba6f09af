"""aot/ subsystem tests (docs/SERVING.md "Cold start & warm-start
bundles"): manifest derivation from the checked tables, warm-start
bundle build/verify/round-trip, loud rejection with counted fallback,
the pre-forked warm pool, and a learner restart riding the persistent
compilation cache. All CPU (conftest pins JAX_PLATFORMS=cpu).
"""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.analysis.reachability import ENTRY_POINTS
from torch_actor_critic_tpu.aot import (
    BundleMismatchError,
    ManifestError,
    WarmPool,
    build_bundle,
    bundled_entry_points,
    default_bundle_dir,
    entry_point_table,
    load_bundle,
    serve_programs,
)
from torch_actor_critic_tpu.aot.manifest import (
    program_filename,
    program_name,
)
from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog
from torch_actor_critic_tpu.models import Actor
from torch_actor_critic_tpu.serve import ModelRegistry
from torch_actor_critic_tpu.serve.engine import PolicyEngine

OBS_DIM, ACT_DIM = 17, 6


def make_actor_and_params(seed=0, hidden=(32, 32)):
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=hidden)
    params = actor.init(
        jax.random.key(seed), jnp.zeros((OBS_DIM,)), jax.random.key(1)
    )
    return actor, params


def flat_spec():
    return jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)


# ---------------------------------------------------------------- manifest


def test_manifest_matches_entry_points_exactly():
    """No third list: the manifest's identity set IS the checked
    ENTRY_POINTS set, and every row carries an explicit bundleability
    verdict (the stale-bundle-manifest lint pins the literal)."""
    table = entry_point_table()
    assert set(table) == set(ENTRY_POINTS)
    assert all(isinstance(v, bool) for v in table.values())
    # The single-device serve forward is the one bundled identity;
    # train-plane programs ride the shared persistent cache instead.
    assert table["serve/forward"] is True
    assert bundled_entry_points() == ("serve/forward",)
    assert table["serve/sharded_forward"] is False
    assert table["train/update_burst"] is False


def test_manifest_raises_on_table_divergence(monkeypatch):
    """A jit entry point with no contract row (or vice versa) must fail
    the build loudly, not silently skip a program."""
    import torch_actor_critic_tpu.aot.manifest as manifest_mod

    monkeypatch.setattr(
        manifest_mod, "ENTRY_POINTS",
        dict(ENTRY_POINTS, **{"serve/new_thing": ("x.py", "f")}),
    )
    with pytest.raises(ManifestError, match="serve/new_thing"):
        manifest_mod.entry_point_table()


def test_program_naming():
    assert program_name("serve/forward", 4, True) == "serve/forward[b4].det"
    assert (
        program_name("serve/forward", 16, False)
        == "serve/forward[b16].sampled"
    )
    assert (
        program_filename("serve/forward[b4].det")
        == "serve__forward-b4.det.jexp"
    )


def test_serve_programs_cover_the_warmup_ladder():
    specs = serve_programs((2, 4))
    assert [s.name for s in specs] == [
        "serve/forward[b2].det", "serve/forward[b2].sampled",
        "serve/forward[b4].det", "serve/forward[b4].sampled",
    ]
    det_only = serve_programs((2, 4), deterministic_only=True)
    assert all(s.deterministic for s in det_only)
    assert len(det_only) == 2


# ------------------------------------------------------------------ bundle


@pytest.fixture(scope="module")
def built(tmp_path_factory, compile_cache_at):
    """One bundle shared by the read-only bundle tests: a real
    build_bundle() run (engine warmup -> compile cache + jax.export),
    with the cache in a directory of this module's own."""
    tmp = tmp_path_factory.mktemp("aot")
    actor, params = make_actor_and_params()
    with compile_cache_at(tmp / "jax_cache"):
        bundle = build_bundle(
            tmp / "warm_start", actor, flat_spec(), params, max_batch=4,
        )
        yield bundle, actor, params


def test_bundle_layout_and_manifest(built):
    bundle, _, _ = built
    manifest = json.loads((bundle.root / "MANIFEST.json").read_text())
    assert manifest["format"] == 1
    assert manifest["buckets"] == [2, 4]
    assert manifest["entry_points"] == entry_point_table()
    # The cache really was populated by the build-time warmup — the
    # mechanism behind live_compiles == 0 on a fresh worker.
    assert manifest["cache_entries"] > 0
    assert set(manifest["programs"]) == {
        s.name for s in serve_programs((2, 4))
    }
    bundle.check()  # same process, same fingerprint: must pass


def test_bundle_roundtrip_bitwise_identical_to_live_compile(built):
    """The serialized programs ARE the engine's programs: every
    (bucket, deterministic) export replays bitwise against the live
    jit forward it was exported from."""
    bundle, actor, params = built
    engine = PolicyEngine(actor, flat_spec(), max_batch=4)
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    key_data = jax.random.key_data(key)
    for spec in serve_programs(engine.buckets):
        obs = rng.standard_normal((spec.bucket, OBS_DIM)).astype(np.float32)
        exported = bundle.load_program(spec.name)
        if spec.deterministic:
            got = exported.call(params, obs)
            want = engine._fwd[True](params, obs)
        else:
            # The artifact takes raw uint32 key data (jax.export has no
            # dtype kind for typed keys) and re-wraps inside — bitwise
            # identical to the engine's typed-key program.
            got = exported.call(params, obs, key_data)
            want = engine._fwd[False](params, obs, key)
        got_leaves = jax.tree_util.tree_leaves(got)
        want_leaves = jax.tree_util.tree_leaves(want)
        assert len(got_leaves) == len(want_leaves)
        for g, w in zip(got_leaves, want_leaves):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_fingerprint_mismatch_rejected_loudly(built):
    bundle, _, _ = built
    stale = load_bundle(bundle.root)
    stale.manifest["fingerprint"]["jaxlib"] = "0.0.0-elsewhere"
    with pytest.raises(BundleMismatchError, match="jaxlib"):
        stale.check()


def test_aval_mismatch_rejected(built):
    """Model/obs drift since the build: the program verifies against
    the consumer's own call avals and refuses on any disagreement."""
    bundle, _, params = built
    name = "serve/forward[b2].det"
    wrong_obs = np.zeros((2, OBS_DIM + 1), np.float32)
    with pytest.raises(BundleMismatchError, match="aval mismatch"):
        bundle.verify_program(name, params, wrong_obs)
    with pytest.raises(BundleMismatchError, match="no program"):
        bundle.load_program("serve/forward[b999].det")


def test_bundle_armed_warmup_pays_zero_live_compiles(built):
    """The headline pin: a bundle-armed warmup classifies every compile
    as bundle-load (disk-read cost), and the first real act afterwards
    pays nothing live."""
    bundle, actor, params = built
    wd = get_watchdog().install()
    wd.reset()
    engine = PolicyEngine(actor, flat_spec(), max_batch=4)
    engine.warmup(params, bundle=bundle)
    engine.act(params, np.zeros((3, OBS_DIM), np.float32))
    stats = engine.compile_stats()
    assert stats["live_compiles"] == 0
    warmup_total = sum(b["warmup"] for b in stats["buckets"].values())
    assert warmup_total == 0
    assert stats["bundle_compiles"] == len(serve_programs(engine.buckets))
    assert stats["bundle_loaded"] is True
    snap = wd.snapshot()
    assert snap["bundle_hits"] == len(serve_programs(engine.buckets))
    assert snap["bundle_load_compiles"] > 0
    assert wd.live_compiles_for("serve/") == 0
    wd.assert_zero_live("serve/")


def test_registry_rejection_falls_back_and_counts(built, tmp_path):
    """A corrupted bundle must cost the cold start back, never the
    slot: registration falls back to a live warmup, the rejection is
    counted on the watchdog, and the slot serves correctly."""
    bundle, actor, params = built
    broken_root = tmp_path / "broken"
    shutil.copytree(bundle.root, broken_root)
    victim = json.loads(
        (broken_root / "MANIFEST.json").read_text()
    )["programs"]["serve/forward[b2].det"]["file"]
    (broken_root / "programs" / victim).write_bytes(b"not a program")
    broken = load_bundle(broken_root)

    wd = get_watchdog().install()
    wd.reset()
    reg = ModelRegistry()
    try:
        reg.register(
            "default", actor, flat_spec(), params=params, max_batch=4,
            bundle=broken,
        )
        snap = wd.snapshot()
        assert snap["bundle_rejected"] == 1
        assert any(
            "deserialize" in r for r in snap["bundle_reject_reasons"]
        )
        slots = reg.slots()
        assert slots["default"]["bundle_loaded"] is False
        engine, _, _ = reg.acquire("default")
        stats = engine.compile_stats()
        # Fallback really was a LIVE warmup — nothing bundle-tagged,
        # nothing charged to a request.
        assert stats["bundle_compiles"] == 0
        assert sum(b["warmup"] for b in stats["buckets"].values()) > 0
        assert stats["live_compiles"] == 0
        act = engine.act(params, np.zeros((2, OBS_DIM), np.float32))
        assert np.isfinite(act).all()
        assert engine.compile_stats()["live_compiles"] == 0
    finally:
        reg.close()


# --------------------------------------------------------------- warm pool


def test_warm_pool_draw_answers_first_act_with_zero_live(built):
    """The pool's contract: spawn() returns READY workers, so a draw
    is O(pop) and the drawn worker's first act pays zero live compiles
    (here the worker is an in-process bundle-armed engine; serve.py
    wraps the real subprocess launcher around the same pool)."""
    bundle, actor, params = built
    killed = []

    def spawn():
        engine = PolicyEngine(actor, flat_spec(), max_batch=4)
        engine.warmup(params, bundle=bundle)
        return engine, f"inproc://{id(engine)}"

    pool = WarmPool(spawn, lambda h: killed.append(h), size=2)
    try:
        worker = pool.draw(timeout=120)
        assert worker is not None
        engine = worker.handle
        engine.act(params, np.zeros((1, OBS_DIM), np.float32))
        stats = engine.compile_stats()
        assert stats["live_compiles"] == 0
        assert stats["bundle_loaded"] is True
        # The pool refills behind the draw.
        deadline_stats = None
        for _ in range(600):
            deadline_stats = pool.stats()
            if deadline_stats["ready"] >= 2:
                break
            import time

            time.sleep(0.05)
        assert deadline_stats["ready"] == 2, deadline_stats
        assert deadline_stats["drawn"] == 1
        assert deadline_stats["spawned"] >= 3
    finally:
        pool.shutdown()
    # Unclaimed spares are reaped on shutdown; the drawn one is ours.
    assert len(killed) == 2
    assert pool.draw(timeout=0.1) is None  # post-shutdown draws refuse


def test_warm_pool_zero_size_is_inert():
    pool = WarmPool(
        lambda: (_ for _ in ()).throw(AssertionError("spawned")),
        lambda h: None, size=0,
    )
    assert pool.draw() is None
    assert pool.stats()["spawned"] == 0
    pool.shutdown()
    pool.shutdown()  # idempotent


def test_warm_pool_counts_spawn_failures():
    attempts = []

    def flaky_spawn():
        attempts.append(1)
        if len(attempts) == 1:
            raise RuntimeError("boom")
        return object(), "inproc://ok"

    pool = WarmPool(flaky_spawn, lambda h: None, size=1)
    try:
        assert pool.draw(timeout=120) is not None
        assert pool.stats()["spawn_failures"] == 1
    finally:
        pool.shutdown()


# ------------------------------------------- learner restart on the cache


def test_learner_restart_rides_cache_bitwise(tmp_path, compile_cache_at):
    """A restarted learner on the same persistent compilation cache
    re-jits from disk hits — the donated burst and push programs
    included: the jaxlib 0.4.36 defect that once kept them out of the
    cache does not reproduce on the installed jaxlib — and produces a
    loss stream BITWISE identical to the cold-cache run; --emit-bundle
    drops the checkpoint-adjacent warm_start bundle at the first
    update epoch."""
    from torch_actor_critic_tpu.aot.cache import enable_persistent_cache
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
    from torch_actor_critic_tpu.utils.config import SACConfig

    tiny = dict(
        hidden_sizes=(16, 16), batch_size=16, epochs=2,
        steps_per_epoch=40, start_steps=10, update_after=10,
        update_every=10, buffer_size=500, max_ep_len=100, save_every=1,
    )
    def run(sub, emit):
        losses = []
        cfg = SACConfig(**tiny, emit_bundle=emit)
        ckpt_dir = tmp_path / sub / "ckpts"
        tr = Trainer(
            "Pendulum-v1", cfg, mesh=make_mesh(dp=1),
            checkpointer=Checkpointer(str(ckpt_dir), retry_backoff_s=0.0),
            seed=7,
        )
        real_hook = tr._epoch_boundary_hook

        def hook(e, ok, saved, metrics, rec, _real=real_hook):
            _real(e, ok, saved, metrics, rec)
            losses.append(metrics["loss_q"])

        tr._epoch_boundary_hook = hook
        try:
            tr.train()
        finally:
            tr.close()
        return losses, ckpt_dir

    wd = get_watchdog().install()
    with compile_cache_at(tmp_path / "jax_cache"):
        enable_persistent_cache()
        wd.reset()
        losses_a, ckpt_a = run("a", emit=True)
        cold = wd.snapshot()
        # --emit-bundle: the bundle landed next to the checkpoint at
        # the first update epoch, cache populated by its own warmup.
        bundle = load_bundle(default_bundle_dir(ckpt_a))
        assert bundle.manifest["cache_entries"] > 0
        bundle.check()

        wd.reset()
        losses_b, _ = run("b", emit=False)
        snap = wd.snapshot()

    assert losses_a and losses_a == losses_b  # bitwise on the stream
    # The restarted learner really did ride the cache, not re-derive
    # it: every program the cold run had to compile — the train
    # plane's donated burst and push among them — came back as a
    # persistent-cache disk hit.
    assert cold["cache_misses_total"] > 0
    assert snap["cache_hits_total"] > 0
    assert snap["cache_misses_total"] == 0
