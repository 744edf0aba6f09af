"""The named-mesh GSPMD substrate (PR 8): parity with the retired
shard_map path, the un-gated dp+tp/fsdp hybrid, size-thresholded fsdp
parameter sharding, named-axis skew collectives, and per-device cost
attribution — all on the forced 8-device CPU mesh (conftest.py). The
member-sharded fused population is ``test_mesh_gspmd_population.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from torch_actor_critic_tpu.core.types import Batch
from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.parallel import (
    DataParallelSAC,
    init_sharded_buffer,
    make_mesh,
    shard_chunk,
)
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.utils.config import SACConfig

OBS_DIM, ACT_DIM = 4, 2


def make_sac(**overrides):
    cfg = SACConfig(hidden_sizes=(32, 32), batch_size=8, **overrides)
    return SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=cfg.hidden_sizes),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        ACT_DIM,
    )


def make_chunk(key, n_dev, per_dev):
    ks = jax.random.split(key, 5)
    shape = (n_dev, per_dev)
    return Batch(
        states=jax.random.normal(ks[0], shape + (OBS_DIM,)),
        actions=jnp.tanh(jax.random.normal(ks[1], shape + (ACT_DIM,))),
        rewards=jax.random.normal(ks[2], shape),
        next_states=jax.random.normal(ks[3], shape + (OBS_DIM,)),
        done=jnp.zeros(shape),
    )


def _dp_inputs(dp, seed_buf=128, n_updates_chunk=10):
    state = dp.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    buf = init_sharded_buffer(
        seed_buf, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM,
        dp.mesh,
    )
    n_dev = dp.n_devices
    warm = shard_chunk(make_chunk(jax.random.key(1), n_dev, 32), dp.mesh)
    chunk = shard_chunk(
        make_chunk(jax.random.key(2), n_dev, n_updates_chunk), dp.mesh
    )
    return state, buf, warm, chunk


# ------------------------------------------------------- substrate parity


def test_gspmd_burst_matches_legacy_shard_map_burst():
    """THE substrate-parity pin: one update burst through the retired
    manual ``jax.shard_map`` body and through the jit-with-sharding
    path, same 2-device mesh, same inputs — params, opt state and
    metrics must agree. Proves the rebuild is a pure substrate swap:
    identical per-device key streams and math, only the mapping
    machinery changed (on CPU the two even agree bitwise; the pin is
    allclose so TPU reduction-order freedom can't break it)."""
    from jax import shard_map

    from torch_actor_critic_tpu.parallel import dp as dp_mod

    sac = make_sac()
    mesh = make_mesh(dp=2, devices=jax.devices()[:2])
    dp = DataParallelSAC(sac, mesh)
    num_updates = 3

    def legacy_burst(state, buffer, chunk):
        """The pre-PR-8 manual body, verbatim semantics: strip the
        device axis, fold ``axis_index('dp')`` into the rng, run the
        shared burst with named-axis pmean, restore a replicated rng."""
        buf_specs = dp_mod._buffer_specs(buffer, 1)
        chunk_specs = dp_mod._batch_specs(chunk, 1)

        def body(state, buffer, chunk):
            buffer = jax.tree_util.tree_map(lambda x: x[0], buffer)
            chunk = jax.tree_util.tree_map(lambda x: x[0], chunk)
            dev = jax.lax.axis_index("dp")
            local = state.replace(rng=jax.random.fold_in(state.rng, dev))
            local, buffer, metrics = sac.update_burst(
                local, buffer, chunk, num_updates, axis_name="dp"
            )
            state_out = local.replace(
                rng=jax.random.fold_in(state.rng, jnp.uint32(0xB0057))
            )
            metrics = jax.lax.pmean(metrics, "dp")
            buffer = jax.tree_util.tree_map(lambda x: x[None], buffer)
            return state_out, buffer, metrics

        return jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), buf_specs, chunk_specs),
                out_specs=(P(), buf_specs, P()),
                axis_names={"dp"},
                check_vma=False,
            )
        )(state, buffer, chunk)

    state, buf, warm, chunk = _dp_inputs(dp)
    s_old, b_old, m_old = legacy_burst(state, buf, warm)
    s_old, b_old, m_old = legacy_burst(s_old, b_old, chunk)

    state, buf, warm, chunk = _dp_inputs(dp)
    s_new, b_new, m_new = dp.update_burst(state, buf, warm, num_updates)
    s_new, b_new, m_new = dp.update_burst(s_new, b_new, chunk, num_updates)

    assert int(s_new.step) == int(s_old.step) == 2 * num_updates
    for key in m_old:
        np.testing.assert_allclose(
            np.asarray(m_new[key]), np.asarray(m_old[key]),
            rtol=1e-6, atol=1e-7, err_msg=key,
        )
    for group in ("actor_params", "critic_params", "target_critic_params",
                  "pi_opt_state", "q_opt_state"):
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(getattr(s_new, group))[0],
            jax.tree_util.tree_leaves(getattr(s_old, group)),
        ):
            name = group + "/".join(
                str(getattr(p, "key", p)) for p in path
            )
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7,
                err_msg=name,
            )
    # Replay rings too: the push path swapped substrate as well.
    np.testing.assert_array_equal(
        np.asarray(b_new.size), np.asarray(b_old.size)
    )
    np.testing.assert_allclose(
        np.asarray(b_new.data.states), np.asarray(b_old.data.states),
        atol=0,
    )


def test_dp_burst_no_shard_map_on_hot_path():
    """The acceptance pin, promoted from a source-regex check to the
    tac-lint ``shard-map-hot-path`` rule (docs/ANALYSIS.md): any
    ``shard_map`` reference outside ``parallel/context.py`` must sit
    in the rule's checked allowlist (the manual-by-nature sp ring
    burst), and every allowlist entry must still match real code
    (``stale-allowlist``). Zero findings over the whole package means
    the allowlist is the single source of truth for where manual
    mapping is allowed to live."""
    import pathlib

    from torch_actor_critic_tpu.analysis import lint_paths

    pkg = pathlib.Path(
        __import__("torch_actor_critic_tpu").__file__
    ).parent
    findings = [
        f for f in lint_paths([str(pkg)])
        if f.rule in ("shard-map-hot-path", "stale-allowlist")
    ]
    assert findings == [], "\n".join(f.format() for f in findings)


# ----------------------------------------------------- hybrid, no gate


def test_dp_fsdp_hybrid_runs_without_version_gate():
    """(dp=2, fsdp=2) with the size threshold forced to 0: parameters
    really shard over fsdp, the burst compiles and runs under plain
    auto partitioning, and the update equals the all-replicated
    (fsdp=1) burst — fsdp changes layout, not math."""

    def run(fsdp):
        sac = make_sac()
        dp = DataParallelSAC(
            sac, make_mesh(dp=2, fsdp=fsdp, devices=jax.devices()[:2 * fsdp]),
            fsdp_min_bytes=0,
        )
        state, buf, warm, chunk = _dp_inputs(dp)
        if fsdp > 1:
            kern = state.actor_params["params"]["MLP_0"]["Dense_0"]["col"][
                "kernel"
            ]
            assert "fsdp" in (kern.sharding.spec or ())
            assert not kern.sharding.is_fully_replicated
        state, buf, _ = dp.update_burst(state, buf, warm, 2)
        state, buf, metrics = dp.update_burst(state, buf, chunk, 2)
        return state, metrics

    s_f, m_f = run(fsdp=2)
    s_r, m_r = run(fsdp=1)
    np.testing.assert_allclose(
        float(m_f["loss_q"]), float(m_r["loss_q"]), rtol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_f.critic_params),
        jax.tree_util.tree_leaves(s_r.critic_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ------------------------------------------------- fsdp sharding specs


def test_fsdp_spec_size_threshold_and_dim_choice():
    from torch_actor_critic_tpu.parallel.sharding import fsdp_spec

    big = jnp.zeros((128, 64))          # 32 KiB
    assert fsdp_spec(big, fsdp=4, min_bytes=0) == P("fsdp")
    # Largest divisible dim wins; dim 0 (96) > dim 1 (64) here.
    assert fsdp_spec(jnp.zeros((96, 64)), 4, 0) == P("fsdp")
    # dim 0 indivisible -> falls to the next divisible dim.
    assert fsdp_spec(jnp.zeros((97, 64)), 4, 0) == P(None, "fsdp")
    # Below threshold -> replicated.
    assert fsdp_spec(big, 4, big.nbytes + 1) == P()
    # Scalars / 1-D / fully indivisible -> replicated.
    assert fsdp_spec(jnp.zeros(()), 4, 0) == P()
    assert fsdp_spec(jnp.zeros((128,)), 4, 0) == P()
    assert fsdp_spec(jnp.zeros((3, 5)), 4, 0) == P()
    # fsdp=1 mesh -> replicated regardless of size.
    assert fsdp_spec(big, 1, 0) == P()


def test_fsdp_composes_with_tp_on_disjoint_dims():
    """A tp-taken dimension is skipped: fsdp lands on the largest
    remaining divisible dim, so the two families never collide."""
    from torch_actor_critic_tpu.parallel.sharding import fsdp_spec

    leaf = jnp.zeros((64, 32))
    assert fsdp_spec(leaf, 2, 0, taken=P(None, "tp")) == P("fsdp", "tp")
    assert fsdp_spec(leaf, 2, 0, taken=P("tp", None)) == P("tp", "fsdp")
    # Everything taken -> the tp spec passes through.
    assert fsdp_spec(jnp.zeros((64,)), 2, 0, taken=P("tp")) == P("tp")


def test_param_specs_replicate_scalars_and_small_arrays():
    """The scaling-book contract on a real model tree: scalars (step,
    log_alpha) and small arrays replicate, every spec on a trivial
    mesh is P()."""
    from torch_actor_critic_tpu.parallel.sharding import param_specs

    sac = make_sac()
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    trivial = make_mesh(dp=8)
    specs = jax.tree_util.tree_leaves(
        param_specs(state, trivial),
        is_leaf=lambda s: isinstance(s, P),
    )
    assert all(s == P() for s in specs)
    sharded = param_specs(
        state, make_mesh(dp=2, fsdp=4), min_bytes=0
    )
    assert sharded.log_alpha == P()
    assert sharded.step == P()
    kernel_specs = [
        s
        for path, s in jax.tree_util.tree_flatten_with_path(
            sharded.critic_params,
            is_leaf=lambda s: isinstance(s, P),
        )[0]
        if "kernel" in "/".join(str(getattr(p, "key", p)) for p in path)
    ]
    assert any("fsdp" in (s or ()) for s in kernel_specs)


# ------------------------------------------- named-axis skew collectives


def test_replica_skew_under_vmap_named_axis():
    """The dp-skew reductions read the SAME named axis whether the
    substrate is manual or a GSPMD vmap axis: pmax-pmin over
    ``axis_name='dp'`` inside jit-with-sharding equals the known
    spread."""
    from jax.sharding import NamedSharding
    from torch_actor_critic_tpu.diagnostics.ingraph import replica_skew

    mesh = make_mesh(dp=4, devices=jax.devices()[:4])

    def per_dev(v):
        skew = replica_skew({"diag/param_norm": v}, ("diag/param_norm",), "dp")
        return skew["diag/param_norm_skew"]

    def f(x):
        return jax.vmap(per_dev, axis_name="dp")(x)[0]

    xs = jax.device_put(
        jnp.asarray([0.0, 1.0, 2.0, 3.0]), NamedSharding(mesh, P("dp"))
    )
    out = jax.jit(
        f, in_shardings=NamedSharding(mesh, P("dp")),
        out_shardings=NamedSharding(mesh, P()),
    )(xs)
    assert float(out) == 3.0


def test_dp_skew_metrics_via_gspmd_burst_forced_devices():
    """Forced 4-device run of the NEW burst with diagnostics on: the
    desync canary still reads exactly 0.0 (pmean'd grads keep the
    per-device replicas bit-identical under the vmap substrate too)
    and per-shard grad skew is a real positive spread."""
    sac = make_sac(diagnostics="light")
    dp = DataParallelSAC(sac, make_mesh(dp=4, devices=jax.devices()[:4]))
    state, buf, warm, chunk = _dp_inputs(dp)
    _, _, m = dp.update_burst(state, buf, warm, 4)
    assert float(m["diag/param_norm_skew"]) == 0.0
    assert float(m["diag/grad_norm_q_skew"]) > 0.0
    assert float(m["diag/grad_norm_pi_skew"]) > 0.0


# --------------------------------------------- per-device cost division


def test_cost_registry_divides_by_mesh_size():
    """Satellite regression: registering the SAME dp=4 burst with and
    without ``devices=4`` must differ by exactly 4x on every cost
    column — roofline/MFU reads per-device FLOPs under dp>1."""
    from torch_actor_critic_tpu.telemetry.costmodel import CostRegistry

    sac = make_sac()
    dp = DataParallelSAC(sac, make_mesh(dp=4, devices=jax.devices()[:4]))
    state, buf, warm, chunk = _dp_inputs(dp)
    state, buf, _ = dp.update_burst(state, buf, warm, 2)
    fn = dp.burst_jit(2)
    assert fn is not None
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (state, buf, chunk),
    )
    reg = CostRegistry()
    whole = reg.register_jit("whole", fn, *abstract)
    per_dev = reg.register_jit("per_dev", fn, *abstract, devices=4)
    assert whole is not None and per_dev is not None
    assert per_dev["devices"] == 4
    for k in ("flops", "bytes_accessed"):
        assert whole[k] > 0
        np.testing.assert_allclose(per_dev[k], whole[k] / 4, rtol=1e-9)
