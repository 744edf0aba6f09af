"""Device scopes (telemetry/scopes.py): the names reach the compiled
programs, the table joins them back by instruction name, and the
recorder's phases reach the profiler's trace."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.core.types import Batch
from torch_actor_critic_tpu.parallel.mesh import make_mesh
from torch_actor_critic_tpu.sac.trainer import Trainer, build_models, make_learner
from torch_actor_critic_tpu.telemetry import PHASES, TelemetryRecorder, classify_epoch, scopes
from torch_actor_critic_tpu.telemetry.recorder import CHILDREN
from torch_actor_critic_tpu.utils.config import SACConfig

HLO = """HloModule jit_toy, is_scheduled=true

%fused_inner (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p), metadata={op_name="jit(toy)/vmap(tac/critic)/jvp()/neg"}
}

%fused_two_groups (a: f32[8], i: s32[2]) -> f32[2] {
  %a = f32[8]{0} parameter(0)
  %i = s32[2]{0} parameter(1)
  %inner.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_inner
  %gather.1 = f32[2]{0} gather(%inner.1, %i), metadata={op_name="jit(toy)/while/body/tac/sample/jit(_take)/gather"}
  ROOT %mul.1 = f32[2]{0} multiply(%gather.1, %gather.1), metadata={op_name="jit(toy)/transpose(jvp(tac/critic))/mul"}
}

%made_body (w: (s32[], f32[8])) -> (s32[], f32[8]) {
  %w = (s32[], f32[8]{0}) parameter(0)
  %n = s32[] get-tuple-element(%w), index=0
  %x = f32[8]{0} get-tuple-element(%w), index=1
  %copy.7 = f32[8]{0} copy(%x)
  ROOT %t = (s32[], f32[8]{0}) tuple(%n, %copy.7)
}

%made_cond (w.1: (s32[], f32[8])) -> pred[] {
  %w.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (ring: f32[8], idx: s32[2]) -> (f32[2], f32[8]) {
  %ring = f32[8]{0} parameter(0), metadata={op_name="ring"}
  %idx = s32[2]{0} parameter(1), metadata={op_name="idx"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %ring)
  %while.9 = (s32[], f32[8]{0}) while(%init), condition=%made_cond, body=%made_body
  %laid = f32[8]{0} get-tuple-element(%while.9), index=1
  %scatter.3 = f32[8]{0} scatter(%laid, %idx, %laid), metadata={op_name="jit(toy)/tac/push/scatter"}
  %copy.4 = f32[8]{0} copy(%scatter.3)
  %fusion.5 = f32[2]{0} fusion(%scatter.3, %idx), kind=kLoop, calls=%fused_two_groups
  %decode.6 = f32[2]{0} convert(%fusion.5), metadata={op_name="jit(toy)/tac/sample/tac/sample/decode/convert"}
  %plain.8 = f32[2]{0} add(%decode.6, %decode.6), metadata={op_name="jit(toy)/add"}
  ROOT %out = (f32[2]{0}, f32[8]{0}) tuple(%plain.8, %copy.4)
}
"""


def test_scope_table_of_a_hand_written_program():
    table = scopes.scope_table(HLO)
    assert scopes.module_name(HLO) == "jit_toy"
    # a fusion counts the instructions it calls, through the nested fusion:
    # one gather under sample, two under critic: it spans two groups
    assert table["fusion.5"] == {"tac/critic": 2, "tac/sample": 1}
    assert table["scatter.3"] == {"tac/push": 1}
    assert table["decode.6"] == {"tac/sample/decode": 1}  # the innermost scope
    # instructions of fused computations have no entry of their own
    assert "gather.1" not in table and "neg.1" not in table
    # what the compiler made takes its nearest scoped neighbour's name, marked:
    # the loop before the scatter, its body, and the copy after it
    assert table["while.9"] == {"tac/push~": 1}
    assert table["copy.7"] == {"tac/push~": 1}
    assert table["copy.4"] == {"tac/push~": 1}
    # an op_name of the program's that is none of ours inherits the same way
    assert table["plain.8"] == {"tac/sample/decode~": 1}


def _scopes_in(table):
    return {s for counts in table.values() for s in counts if s and not s.endswith("~")}


UPDATE_SCOPES = {
    scopes.PUSH, scopes.SAMPLE, scopes.CRITIC, scopes.ACTOR, scopes.ALPHA,
    scopes.OPTIMIZER, scopes.POLYAK,
}


class _Env:
    act_dim, act_limit = 2, 1.0
    obs_spec = jax.ShapeDtypeStruct((3,), jnp.float32)


def _learner(**overrides):
    cfg = SACConfig(
        hidden_sizes=(16, 16), batch_size=8, buffer_size=64, update_every=4,
        learn_alpha=True, **overrides,
    )
    return cfg, make_learner(cfg, *build_models(cfg, _Env), _Env.act_dim)


def _chunk(lead):
    ones = lambda *shape: np.ones(lead + shape, np.float32)  # noqa: E731
    return Batch(states=ones(3), actions=ones(2), rewards=ones(), next_states=ones(3), done=ones())


def _named_share_in_loops(table, text):
    """Of the instructions of loop bodies that do work (no tuple plumbing),
    the share whose entry names a scope of ours, its own or inherited."""
    computations = scopes._parse(text)
    bodies = {i.attrs["body"] for body in computations.values() for i in body if "body" in i.attrs}
    work = [
        i.name for c in bodies for i in computations[c]
        if i.opcode not in scopes._PLUMBING + ("constant",)
    ]
    return sum(any(table[name]) for name in work) / len(work)


def test_scope_table_of_a_dp1_burst():
    from torch_actor_critic_tpu.parallel.dp import (
        DataParallelSAC, init_sharded_buffer, shard_chunk,
    )

    _, sac = _learner()
    mesh = make_mesh(dp=1)
    dp = DataParallelSAC(sac, mesh)
    state = dp.init_state(jax.random.key(0), jnp.zeros(3))
    ring = init_sharded_buffer(64, _Env.obs_spec, 2, mesh)
    assert dp.burst_abstract == ()
    dp.update_burst(state, ring, shard_chunk(_chunk((1, 4)), mesh), 4)
    scoped = dp.burst_scope_table()
    assert scoped["module"] == "jit_burst"
    assert _scopes_in(scoped["table"]) >= UPDATE_SCOPES
    text = dp.burst_jit(4).lower(*dp.burst_abstract).compile().as_text()
    # what is left without a name: copies inside the key generator's own loops
    assert _named_share_in_loops(scoped["table"], text) > 0.8


def test_scope_table_of_a_vmapped_population_burst():
    from torch_actor_critic_tpu.parallel.population import PopulationLearner

    _, sac = _learner()
    pop = PopulationLearner(sac, 3)
    state = pop.init_state(jax.random.key(0), jnp.zeros(3))
    ring = pop.init_buffer(64, _Env.obs_spec, 2)
    pop.update_burst(state, ring, pop.place_chunk(_chunk((3, 4))), 4)
    scoped = pop.burst_scope_table()
    assert _scopes_in(scoped["table"]) >= UPDATE_SCOPES


def test_scope_table_of_a_fused_epoch():
    from torch_actor_critic_tpu.envs.ondevice import get_on_device_env
    from torch_actor_critic_tpu.sac.ondevice import PopulationOnDeviceLoop, _wrap_and_build

    cfg = SACConfig(hidden_sizes=(16, 16), batch_size=8, update_every=5, population=2)
    env_cls, sac = _wrap_and_build(get_on_device_env("Pendulum-v1"), cfg)
    loop = PopulationOnDeviceLoop(sac, env_cls, n_members=2, n_envs=2)
    state, ring, envs, keys, _ = loop.init(jax.random.key(0), buffer_capacity=64)
    assert loop.epoch_abstract(10, 5) == ()
    loop.epoch(state, ring, envs, keys, steps=10, update_every=5)
    scoped = loop.epoch_scope_table(10, 5)
    assert scoped["module"] == "jit_epoch"
    assert _scopes_in(scoped["table"]) >= (UPDATE_SCOPES - {scopes.ALPHA}) | {
        scopes.COLLECT_ACT, scopes.COLLECT_ENV,
    }
    text = loop.epoch_jit(10, 5).lower(*loop.epoch_abstract(10, 5)).compile().as_text()
    assert _named_share_in_loops(scoped["table"], text) > 0.8


def test_scope_table_is_compiled_past_the_caches(tmp_path):
    """The persistent cache's key leaves metadata out, so a program compiled
    without scopes answers for the same program with them, and jax keeps the
    executable a jit first ran with; the table's compile must take neither."""
    from jax.experimental.compilation_cache import compilation_cache

    def plain(x):
        return jnp.tanh(x @ x).sum()

    def scoped(x):
        with jax.named_scope(scopes.CRITIC):
            return jnp.tanh(x @ x).sum()

    scoped.__name__ = scoped.__qualname__ = "plain"  # one module name, one key
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    saved = {
        k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache",
        )
    }
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        jax.jit(plain).lower(x).compile()
        assert os.listdir(tmp_path), "the cache took no entry: the test shows nothing"
        program = jax.jit(scoped)
        program(jnp.ones(x.shape, x.dtype))  # runs what the cache hands it
        table = scopes.scope_table_for(program, x)["table"]
        assert jax.config.jax_enable_compilation_cache is True  # put back
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert scopes.CRITIC in _scopes_in(table)


TINY = dict(
    hidden_sizes=(16, 16), batch_size=16, epochs=2, steps_per_epoch=40,
    start_steps=10, update_after=10, update_every=10, buffer_size=500,
    max_ep_len=100,
)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A short host-actor Trainer run whose second epoch is captured the way
    ``train.py --telemetry true --profile-epochs 1:2`` captures it."""
    from jax.profiler import ProfileData

    run_dir = tmp_path_factory.mktemp("traced_run")
    rec = TelemetryRecorder(run_dir=run_dir, profile_epochs=(1, 2))
    tr = Trainer(
        "Pendulum-v1", SACConfig(**TINY), mesh=make_mesh(dp=1), seed=3, telemetry=rec,
    )
    try:
        tr.train()
    finally:
        tr.close()
    (path,) = glob.glob(str(run_dir / "trace" / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events += [
                (ev.name[len(scopes.HOST_PREFIX):], ev.start_ns, ev.duration_ns, dict(ev.stats))
                for ev in line.events if ev.name.startswith(scopes.HOST_PREFIX)
            ]
    first, epoch = [e for e in rec_events(run_dir) if e["type"] == "epoch"][:2]
    # the PhaseTimer's own laps of that epoch, in the order it took them
    before, during = (
        sum(p["count"] for p in e["phases"].values()) for e in (first, epoch)
    )
    laps = [rec.phases[i] for i, _, _ in rec.ring.spans()[before:before + during]]
    return events, epoch, laps


def rec_events(run_dir):
    import json

    return [json.loads(line) for line in (run_dir / "telemetry.jsonl").read_text().splitlines()]


def test_every_phase_is_an_annotation_with_its_window(traced_run):
    events, _, _ = traced_run
    # the phases, and the parts of the spans the window's functions open
    assert {name for name, *_ in events} == set(PHASES) | set(CHILDREN)
    assert all(int(stats["epoch"]) == 1 for *_, stats in events)
    # a window's act .. burst_dispatch spans share one number; the next
    # window's spans (its param_sync waits for that burst) the next one
    by_window = {}
    for name, _, _, stats in events:
        by_window.setdefault(int(stats["window"]), []).append(name)
    windows = TINY["steps_per_epoch"] // TINY["update_every"]
    full = [w for w, names in by_window.items() if "burst_dispatch" in names]
    assert len(full) == windows and full == list(range(min(full), min(full) + windows))
    for w in full:
        # stage, place_chunk and burst_dispatch each interrupt the window's
        # last env_step (three pieces here), which goes on after the
        # dispatch under the next number (one piece from the window before;
        # the first window's lies in the epoch before, outside the trace)
        assert by_window[w].count("env_step") == TINY["update_every"] + 3 - (w == min(full))
        assert by_window[w].count("burst_dispatch") == 1
        for name in ("stage", "place_chunk", "place_chunk/transfer", "place_chunk/unpack"):
            assert by_window[w].count(name) == 1, name
    # who opened what: the spans under the env_step they interrupt, the
    # parts under their span, the Trainer's own phases under nothing
    parents = {name: {stats.get("parent", "") for n, _, _, stats in events if n == name}
               for name in set(PHASES) | set(CHILDREN)}
    assert parents["stage"] == parents["place_chunk"] == parents["burst_dispatch"] == {"env_step"}
    assert parents["place_chunk/transfer"] == parents["place_chunk/unpack"] == {"place_chunk"}
    assert parents["drain/reduce"] == parents["drain/fetch"] == {"drain"}
    assert parents["act"] == parents["env_step"] == parents["param_sync"] == {""}
    # the epoch's drain waits for the last window dispatched and says so
    (fetch,) = [stats for n, _, _, stats in events if n == "drain/fetch"]
    assert int(fetch["window"]) == max(full)


def test_annotations_partition_the_epoch_like_the_phase_timer(traced_run):
    """Two clocks took the same epoch: the profiler's (annotations) and the
    host's (PhaseTimer). What must agree exactly is what was taken: the
    same phases, in the same order, as often. The seconds agree as far as
    two clocks on a busy host can: a phase's gap is held to a share of the
    epoch, not to a count of milliseconds."""
    events, epoch, laps = traced_run
    events = [ev for ev in events if ev[0] in PHASES]  # the parts nest inside
    ordered = sorted(events, key=lambda ev: ev[1])
    spans = [(start, start + dur) for _, start, dur, _ in ordered]
    assert all(b[0] >= a[1] - 1 for a, b in zip(spans, spans[1:]))  # no overlap (ns)
    assert [name for name, *_ in ordered] == laps
    wall_s = epoch["wall_s"]
    for name in PHASES:
        traced_s = 1e-9 * sum(dur for n, _, dur, _ in events if n == name)
        timed = epoch["phases"][name]
        assert laps.count(name) == timed["count"]
        assert abs(traced_s - timed["total_s"]) <= 0.05 * wall_s
    covered = 1e-9 * sum(dur for _, _, dur, _ in events)
    assert 0.8 * wall_s <= covered <= 1.05 * wall_s


def test_param_sync_is_charged_and_booked_to_the_device(traced_run):
    _, epoch, _ = traced_run
    sync = epoch["phases"]["param_sync"]
    assert sync["count"] == TINY["steps_per_epoch"] // TINY["update_every"]
    assert sync["total_s"] > 0.0
    only_sync = {"param_sync": {"total_s": 3.0}, "act": {"total_s": 1.0}}
    booked = classify_epoch(only_sync, wall_s=4.0)
    assert booked["class"] == "device-bound" and booked["device_busy_frac"] == 0.75
