"""Multi-host self-test: one process of an N-process distributed run.

Exercises the full multi-host surface that single-process tests cannot
reach (round-1 missing #7): :func:`initialize_multihost` joining the
runtime, a data-parallel training burst over a mesh spanning processes
(params replicated globally, replay shards process-local, ``pmean``
riding the cross-process link — since PR 8 the burst is a plain GSPMD
``jit`` with shardings, so this doubles as the multi-process proof that
the substrate swap holds off one host), :func:`global_statistics` aggregation,
coordinator gating, and a COLLECTIVE Orbax checkpoint save + restore
(every process writes its addressable buffer shards).

Run one process per "host"::

    python -m torch_actor_critic_tpu.parallel.selftest \
        --coordinator 127.0.0.1:29400 --processes 2 --process-id 0 \
        --ckpt-dir /tmp/mh_ckpt

(tests/test_multihost.py launches two of these on a CPU backend with 2
virtual devices each — a 2-host x 2-device topology; on real pods the
same flags come from the scheduler.)

The reference's equivalent surface is ``mpi_fork`` + per-rank
``main()`` + rank-gated MLflow saves (ref ``sac/mpi.py:10-34``,
``main.py:135-138``), which its test suite never exercises
(SURVEY.md §4 "no distributed tests").
"""

from __future__ import annotations

import argparse
import sys


def run_selftest(
    coordinator: str, num_processes: int, process_id: int, ckpt_dir: str
) -> None:
    import jax

    from torch_actor_critic_tpu.parallel.distributed import (
        global_statistics,
        initialize_multihost,
        is_coordinator,
        process_info,
    )

    initialize_multihost(coordinator, num_processes, process_id)
    idx, count = process_info()
    assert count == num_processes, (count, num_processes)
    assert idx == process_id, (idx, process_id)
    assert is_coordinator() == (process_id == 0)

    import jax.numpy as jnp

    from torch_actor_critic_tpu.parallel import init_sharded_buffer, local_dp_info
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer

    # Canonical tiny learner + global dp mesh + multi-host chunk
    # discipline — shared with the elastic phases below so save/resume
    # topologies can never drift from this test's structure.
    sac, dp, mesh, obs_dim, act_dim = _build_learner_and_mesh()
    n_dp = mesh.shape["dp"]
    assert n_dp == jax.device_count(), (n_dp, jax.device_count())

    # Same seed on every process -> identical init, the multi-process
    # analogue of sync_params (each process device_puts the same host
    # values onto its addressable shards of the global sharding).
    state = dp.init_state(jax.random.key(0), jnp.zeros((obs_dim,)))
    buffer = init_sharded_buffer(
        64, jax.ShapeDtypeStruct((obs_dim,), jnp.float32), act_dim, mesh
    )
    n_local, dp_offset = local_dp_info(mesh)
    assert n_local == jax.local_device_count(), (n_local, dp_offset)
    chunk = _local_chunk(mesh, obs_dim, act_dim, seed=1)
    assert chunk.states.shape[0] == n_dp, chunk.states.shape
    state, buffer, metrics = dp.update_burst(state, buffer, chunk, 2)
    jax.block_until_ready(metrics)
    loss_q = float(metrics["loss_q"])
    assert jnp.isfinite(loss_q), loss_q
    assert int(state.step) == 2

    # Cross-process episode statistics (ref mpi_statistics_scalar,
    # sac/mpi.py:101-115): each process contributes distinct values.
    stats = global_statistics([float(process_id + 1)])
    expect_mean = (num_processes + 1) / 2.0
    assert abs(stats["mean"] - expect_mean) < 1e-9, stats
    assert stats["n"] == num_processes, stats
    assert stats["max"] == float(num_processes), stats

    # Cross-process Welford sync: each process feeds DIFFERENT data;
    # after sync_global both hold the pooled statistics (computable on
    # every process since the per-process streams are seed-derived).
    import numpy as np

    from torch_actor_critic_tpu.utils.normalize import WelfordNormalizer

    streams = [
        np.random.default_rng(100 + p).normal(p, 1.0 + p, (50, obs_dim))
        for p in range(num_processes)
    ]
    norm = WelfordNormalizer(obs_dim)
    for row in streams[process_id]:
        norm.normalize(row, update=True)
    norm.sync_global()
    pooled = np.concatenate(streams)
    assert norm.count == pooled.shape[0], norm.count
    # f32 tolerance: the allgather payload rides jax arrays (x64 off).
    np.testing.assert_allclose(norm.mean, pooled.mean(0), rtol=1e-5)
    np.testing.assert_allclose(
        norm.m2 / norm.count, pooled.var(0), rtol=1e-5
    )
    # Second sync with no new data must be a no-op (no double counting).
    norm.sync_global()
    assert norm.count == pooled.shape[0], norm.count

    # Collective Orbax save: EVERY process calls save (each owns shards
    # of the dp-sharded buffer); then a collective restore round-trips.
    ckpt = Checkpointer(ckpt_dir)
    ckpt.save(0, state, buffer, extra={"selftest": True}, wait=True)
    restored_state, restored_buffer, meta = ckpt.restore(
        jax.tree_util.tree_map(lambda x: x, state), buffer
    )
    assert int(meta["epoch"]) == 0 and meta["selftest"] is True
    assert int(restored_state.step) == 2
    assert int(restored_buffer.size[0]) == 16
    ckpt.close()

    # One line the launcher greps for; only visible success counts.
    print(
        f"MULTIHOST_OK proc={process_id}/{num_processes} "
        f"devices={jax.local_device_count()}/{jax.device_count()} "
        f"loss_q={loss_q:.4f} coordinator={is_coordinator()}",
        flush=True,
    )


def _build_learner_and_mesh():
    """Deterministic tiny learner + global dp mesh (shared by the
    elastic phases so save/resume agree on tree structure)."""
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.parallel import DataParallelSAC, make_mesh
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.config import SACConfig

    obs_dim, act_dim = 6, 2
    cfg = SACConfig(hidden_sizes=(16, 16), batch_size=8)
    sac = SAC(
        cfg,
        Actor(act_dim=act_dim, hidden_sizes=cfg.hidden_sizes),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        act_dim,
    )
    mesh = make_mesh()
    return sac, DataParallelSAC(sac, mesh), mesh, obs_dim, act_dim


def _local_chunk(mesh, obs_dim, act_dim, seed=1, per_dev=16):
    """The Trainer's multi-host chunk discipline: this process builds
    only its local dp slices' rows of a host-layout-invariant chunk."""
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.parallel import (
        local_dp_info,
        shard_chunk_from_local,
    )

    n_dp = mesh.shape["dp"]
    n_local, dp_offset = local_dp_info(mesh)
    ks = jax.random.split(jax.random.key(seed), 5)
    shape = (n_dp, per_dev)
    full = Batch(
        states=jax.random.normal(ks[0], shape + (obs_dim,)),
        actions=jnp.tanh(jax.random.normal(ks[1], shape + (act_dim,))),
        rewards=jax.random.normal(ks[2], shape),
        next_states=jax.random.normal(ks[3], shape + (obs_dim,)),
        done=jnp.zeros(shape),
    )
    local = jax.tree_util.tree_map(
        lambda x: x[dp_offset : dp_offset + n_local], full
    )
    return shard_chunk_from_local(local, mesh)


def run_elastic_phase(
    phase: str,
    coordinator: str,
    num_processes: int,
    process_id: int,
    ckpt_dir: str,
    old_ndev: int = 0,
) -> None:
    """Elastic resume across topologies (VERDICT r4 #8).

    ``save``: burst twice on THIS topology, collectively checkpoint the
    full state + dp-sharded buffer. ``resume``: restore that checkpoint
    on a DIFFERENT process topology (same global dp — Orbax re-reads
    each host's newly addressable shards) and keep training.
    ``resume-reshard``: restore on a mesh whose GLOBAL dp differs from
    the saved one (``--old-ndev``), rebuilding replay rings via
    :func:`~torch_actor_critic_tpu.parallel.elastic.reshard_buffer`,
    and keep training.
    """
    import jax

    from torch_actor_critic_tpu.parallel.distributed import (
        initialize_multihost,
    )

    if num_processes > 1:
        initialize_multihost(coordinator, num_processes, process_id)

    import jax.numpy as jnp

    from torch_actor_critic_tpu.parallel import init_sharded_buffer
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer

    sac, dp, mesh, obs_dim, act_dim = _build_learner_and_mesh()
    obs_spec = jax.ShapeDtypeStruct((obs_dim,), jnp.float32)

    if phase == "save":
        state = dp.init_state(jax.random.key(0), jnp.zeros((obs_dim,)))
        buffer = init_sharded_buffer(64, obs_spec, act_dim, mesh)
        chunk = _local_chunk(mesh, obs_dim, act_dim, seed=1)
        state, buffer, m = dp.update_burst(state, buffer, chunk, 2)
        chunk = _local_chunk(mesh, obs_dim, act_dim, seed=2)
        state, buffer, m = dp.update_burst(state, buffer, chunk, 2)
        jax.block_until_ready(m)
        ckpt = Checkpointer(ckpt_dir)
        ckpt.save(0, state, buffer, extra={"elastic": "save"}, wait=True)
        ckpt.close()
        print(
            f"ELASTIC_SAVE_OK proc={process_id}/{num_processes} "
            f"dp={mesh.shape['dp']} sizes_total="
            f"{int(jnp.sum(buffer.size))}",
            flush=True,
        )
        return

    if phase == "resume":
        # Same GLOBAL device count, different process topology: the
        # abstract trees carry THIS mesh's shardings; Orbax hands every
        # host its newly addressable shards.
        state = dp.init_state(jax.random.key(0), jnp.zeros((obs_dim,)))
        buffer = init_sharded_buffer(64, obs_spec, act_dim, mesh)
        ckpt = Checkpointer(ckpt_dir)
        state, buffer, meta = ckpt.restore(
            jax.tree_util.tree_map(lambda x: x, state), buffer
        )
        ckpt.close()
        assert meta["elastic"] == "save", meta
        assert int(state.step) == 4, int(state.step)
        total = int(jnp.sum(buffer.size))
        assert total == mesh.shape["dp"] * 32, total
        chunk = _local_chunk(mesh, obs_dim, act_dim, seed=3)
        state, buffer, m = dp.update_burst(state, buffer, chunk, 2)
        jax.block_until_ready(m)
        assert int(state.step) == 6
        print(
            f"ELASTIC_RESUME_OK proc={process_id}/{num_processes} "
            f"dp={mesh.shape['dp']} step={int(state.step)} "
            f"loss_q={float(m['loss_q']):.4f}",
            flush=True,
        )
        return

    assert phase == "resume-reshard" and old_ndev > 0, (phase, old_ndev)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torch_actor_critic_tpu.parallel.elastic import reshard_buffer

    n_new = mesh.shape["dp"]
    assert n_new != old_ndev, "reshard phase needs a different global dp"
    # Restore the OLD-topology buffer replicated on this mesh (the
    # train state is replicated anyway), then rebuild the rings.
    state = dp.init_state(jax.random.key(0), jnp.zeros((obs_dim,)))
    old_buffer_abstract = jax.tree_util.tree_map(
        lambda x: jax.device_put(
            jnp.zeros((old_ndev,) + x.shape, x.dtype),
            NamedSharding(mesh, P()),
        ),
        init_replay_buffer_single(64, obs_spec, act_dim),
    )
    ckpt = Checkpointer(ckpt_dir)
    state, old_buffer, meta = ckpt.restore(
        jax.tree_util.tree_map(lambda x: x, state), old_buffer_abstract
    )
    ckpt.close()
    assert int(state.step) == 4
    total_before = int(jnp.sum(old_buffer.size))
    buffer = reshard_buffer(old_buffer, n_new, mesh=mesh)
    assert int(jnp.sum(buffer.size)) == total_before
    assert buffer.size.shape == (n_new,)
    chunk = _local_chunk(mesh, obs_dim, act_dim, seed=4)
    state, buffer, m = dp.update_burst(state, buffer, chunk, 2)
    jax.block_until_ready(m)
    assert int(state.step) == 6
    print(
        f"ELASTIC_RESHARD_OK dp={old_ndev}->{n_new} "
        f"transitions={total_before} step={int(state.step)} "
        f"loss_q={float(m['loss_q']):.4f}",
        flush=True,
    )


def init_replay_buffer_single(capacity, obs_spec, act_dim):
    """One UNSHARDED ring (no leading device axis) — the per-device
    element the reshard phase wraps with the old topology's axis."""
    from torch_actor_critic_tpu.buffer.replay import init_replay_buffer

    return init_replay_buffer(capacity, obs_spec, act_dim)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--coordinator", required=True)
    p.add_argument("--processes", type=int, required=True)
    p.add_argument("--process-id", type=int, required=True)
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument(
        "--phase", default="full",
        choices=["full", "save", "resume", "resume-reshard"],
        help="full: the original multi-host selftest; save/resume/"
        "resume-reshard: the elastic-resume phases (VERDICT r4 #8)",
    )
    p.add_argument(
        "--old-ndev", type=int, default=0,
        help="resume-reshard: the GLOBAL dp size the checkpoint was "
        "saved with",
    )
    args = p.parse_args(argv)
    if args.phase == "full":
        run_selftest(
            args.coordinator, args.processes, args.process_id, args.ckpt_dir
        )
    else:
        run_elastic_phase(
            args.phase, args.coordinator, args.processes, args.process_id,
            args.ckpt_dir, args.old_ndev,
        )


if __name__ == "__main__":
    sys.exit(main())
