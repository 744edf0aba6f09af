"""The SDAR history-trunk cell: its configuration file against the published
widths, its program compiled for a described v5e at its real sizes, and how
its ``correct`` comes out false (the control one precision lower, a trunk that
skips an expert)."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_cut import ROOT, check_configuration, cut

from benchmark import control
from benchmark.harness import flops_trunk, registry, spans

CELL = "sdar30b_a3b_trunk_burst"
GIB = 2**30
# The published config.json (model-configs catalog, SDAR-30B-A3B-Chat).
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000, "tie_word_embeddings": False, "vocab_size": 151936,
}


def test_configuration_keeps_every_published_width():
    """What ``test_configuration_file`` asserts for a configuration (no width
    in ``reduced``, by ``bench_cut``'s rule), and the file against the
    published numbers: only what ``reduced`` names differs."""
    bench = registry.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "sdar30b_a3b_trunk")
    cfg = check_configuration(bench, entry)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["reference_mode"] == "bf16_operands"
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, None)
    model = cfg["model"]
    assert (model["hidden"], model["q_heads"], model["kv_heads"], model["head_dim"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    assert (model["experts"], model["experts_per_tok"], model["expert_width"]) == (
        PUBLISHED["num_experts"], cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]
    )
    lo, hi = model["experts_held"]
    assert hi - lo == cfg["num_experts"] and model["layers"] == cfg["num_hidden_layers"]
    assert model["rms_eps"] == cfg["rms_norm_eps"] and model["rope_theta"] == cfg["rope_theta"]
    # the fill a deployment would hold: 20 B a trunk parameter
    assert 7.5e9 < 20 * flops_trunk.trunk_params(model) < 7.6e9
    for key in ("assumed", "deployment", "reduced_how"):
        assert cfg[key]


def test_cell_entry_names_its_traffic():
    bench, cell, config = registry.resolve(CELL)
    traffic = cell["traffic"]
    assert (cell["chips"], cell["driver"]) == (1, "trunkburst")
    assert (traffic["ring_rows"], traffic["pool_windows"], traffic["trace_seconds"]) == (8192, 8, 8)
    assert (config["model"]["history_len"], config["sac"]["batch_size"]) == (1024, 8)
    assert config["sac"]["update_every"] == 10
    names = {m["name"] for m in registry.metrics_for(bench, "per_layer", CELL)}
    assert names >= {
        "trunk.moe_us_per_step", "trunk.attention_us_per_step", "trunk.moe_experts_roofline",
        "trunk.flash_roofline", "trunk.expert_load_max_over_mean", "trunk.mfu",
        "update.device_us_per_step", "update.push_us_per_step", "update.sample_us_per_step",
        "update.compute_us_per_step", "trace.unscoped_share", "device.idle_share",
        # the window stages and places rows, the expert layer gathers and scatters
        "host.span_stage_ms", "host.span_place_chunk_ms", "ops.copy_gather_us_per_step",
    }
    reported = {m["name"] for m in registry.metrics_for(bench, "end_to_end", CELL)}
    assert reported == {"grad_steps_per_s", "setup_s"}


def test_the_trunk_cell_holds_a_quarter_of_the_chip_at_rest():
    """The contract's memory floor for this cell, by its own arithmetic: the
    trunk, its polyak target and Adam's moments, and the ring of histories
    (``trunkburst.Driver.at_rest_bytes`` is this arithmetic)."""
    _, cell, config = registry.resolve(CELL)
    model = config["model"]
    assert flops_trunk.row_bytes(model) == 139_296
    ring = cell["traffic"]["ring_rows"] * flops_trunk.row_bytes(model)
    assert ring == 1_141_112_832
    assert flops_trunk.at_rest_bytes(model, cell["traffic"]["ring_rows"]) - ring >= 4 * GIB


def test_flops_arithmetic():
    model = registry.load_config("sdar30b_a3b_trunk")["model"]
    assert flops_trunk.visible_pairs(8, 4) == 16 + 32
    assert flops_trunk.visible_pairs(6, 4) == 16 + 2 * 6
    assert flops_trunk.visible_pairs(1024, 1) == 1024 * 1025 // 2
    assert flops_trunk.dense_macs_per_token(model) == 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
    balanced = 4 * 8192  # one of a token's 8 assignments lands here, in 4 layers
    per_step = flops_trunk.flops_per_step(model, 8, balanced, balanced)
    assert 7.2e12 < per_step < 7.5e12
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # The grouped products are handed bfloat16 and write float32: a forward row
    # moves 2 (2 h + f) + 4 (2 f + h) bytes, the held kernels 10 B a parameter
    # a step (read by two forwards and the input gradients, gradient written).
    one_forward = flops_trunk.expert_bytes_per_step(model, 0, 1) - flops_trunk.expert_bytes_per_step(model, 0, 0)
    assert one_forward == 2 * (2 * 2048 + 768) + 4 * (2 * 768 + 2048)
    assert flops_trunk.expert_bytes_per_step(model, 0, 0) == 10 * 3 * 16 * 2048 * 768 * 4
    moved = flops_trunk.expert_bytes_per_step(model, balanced, balanced)
    assert moved == 6_023_020_544  # 7.35 ms at 819 GB/s: byte-bound, the FLOPs are 6.28 ms
    assert moved / 819e9 > flops_trunk.expert_flops_per_step(model, balanced, balanced) / 197e12
    assert flops_trunk.roofline_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert flops_trunk.roofline_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)


@pytest.mark.parametrize("cell_name, reader, count", [
    (CELL, "trunk.moe_experts_roofline", "flops_trunk"),
    ("nemotron3_super_trunk_burst", "trunk.latent_experts_roofline", "flops_hybrid"),
])
def test_an_expert_roofline_reads_a_scoped_kernel_where_there_is_no_ragged_dot(
    cell_name, reader, count
):
    """A grouped-product kernel of our own carries the program's scope
    ``tac/trunk/moe/experts/products`` and not XLA:TPU's name: both families'
    readers find its time through ``trunk_read.grouped_product_seconds``, add
    XLA's kernels to it where both run, and are silent where neither does."""
    import importlib

    from benchmark.harness import trunk_read

    flops = importlib.import_module("benchmark.harness." + count)
    _, cell, config = registry.resolve(cell_name)
    model, rows = config["model"], (7040.0, 7040.0)
    counters = {"trunk/held_assignments": rows[0], "trunk/held_assignments_target": rows[1]}

    def ctx(by_scope, by_kind):
        return types.SimpleNamespace(
            trace={"busy_s": 5.0, "by_kind": by_kind}, scope_summary={"by_scope": by_scope},
            cell=cell, config=config, n_windows=2, per_window={"grad_steps": 10},
            device={"kind": "TPU v5 lite"},
            driver=types.SimpleNamespace(model=model, trunk_counters=lambda: counters),
        )

    read = registry.load_layer_metric(reader)
    least = flops.roofline_seconds(
        flops.expert_flops_per_step(model, *rows), flops.expert_bytes_per_step(model, *rows),
        {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
    )
    scoped = {trunk_read.EXPERT_PRODUCTS: 20 * 30e-3, "tac/trunk/moe/experts": 1.0}
    ours = read(ctx(scoped, {"fusion": 1.0}))  # 30 ms a step under the scope, no ragged-dot
    assert ours == pytest.approx(100 * least / 30e-3) and 0 < ours < 100
    both = read(ctx(scoped, {"fusion": 1.0, trunk_read.GROUPED_PRODUCT: 20 * 10e-3}))
    assert both == pytest.approx(100 * least / 40e-3)
    named = read(ctx({"tac/trunk/moe/experts": 1.0}, {trunk_read.GROUPED_PRODUCT: 20 * 10e-3}))
    assert named == pytest.approx(100 * least / 10e-3)  # today's programs: the name alone
    assert read(ctx({"tac/trunk/moe/experts": 1.0}, {"fusion": 1.0})) is None


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"the v5e:2x2 topology cannot be described here: {e!r}")
    return topo.devices


@pytest.fixture
def chip_compiler(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_trunk_cell_fits_a_v5e(v5e, chip_compiler):
    """The cell's burst, as its driver builds it, compiled for a described
    v5e at the cell's own sizes: what the chip's compiler would refuse (memory,
    a Mosaic kernel, a batched grouped product) is refused here."""
    from benchmark.drivers import trunkburst
    from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
    from torch_actor_critic_tpu.core.types import BufferState
    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC
    from torch_actor_critic_tpu.parallel.mesh import make_mesh
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    _, cell, config = registry.resolve(CELL)
    driver = trunkburst.Driver(cell, config, 1, spans.Spans(), {"rehearsal": False})
    assert driver.model["hidden"] == 2048 and driver.sac_config().trunk_bf16_dots
    cfg = driver.sac_config()
    env = trunkburst.Spec(driver.model)
    sac = make_learner(cfg, *build_models(cfg, env), env.act_dim)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=v5e[:1]))
    state = jax.eval_shape(sac.init_state, jax.random.key(0), env.example_obs())
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.critic_params))
    assert abs(n_params - flops_trunk.trunk_params(driver.model)) < 2e6  # the Q heads

    def rows(n):
        one = jax.eval_shape(lambda: init_replay_buffer(n, env.obs_spec, env.act_dim).data)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), one
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(data=rows(cell["traffic"]["ring_rows"]), ptr=index, size=index)
    chunk = rows(cfg.update_every)
    compiled = learner._build_burst(cfg.update_every, state, ring, chunk).lower(
        state, ring, chunk
    ).compile()
    mem = compiled.memory_analysis()
    # trunk, target, Adam's moments (16 B a parameter at rest) and the ring
    assert mem.argument_size_in_bytes >= 16 * n_params >= 4 * GIB
    assert mem.alias_size_in_bytes >= 16 * n_params  # updated in place
    text = compiled.as_text()
    assert "ragged-dot" in text and text.count("tpu_custom_call") >= 3


def _readings(seed, **kwargs):
    _, cell, config = cut(CELL)
    return cell, control.readings(cell, config, seed, {"rehearsal": True}, 1, **kwargs)


def test_control_one_precision_lower_comes_out_not_correct():
    """The reference with float8 operands in the program's place misses a
    limit the sound program keeps."""
    cell, values = _readings(31, low="fp8_operands")
    numbers = ("loss_q.rel_gap", "loss_pi.gap_over_terms", "adam_nu.worst_leaf_gap",
               "param_change.worst_leaf_gap", "router_choices.disagree_share")
    limit = cell["limits"]["loss_q"]
    assert all(values[n] <= limit for n in numbers), values
    assert any(values["fp8_operands:" + n] > limit for n in numbers), values


def test_sound_only_reads_a_seed_without_the_controls_run():
    """``control.py --sound-only``: a seed's sound readings, every number
    compared, and no run of the float8 control (no ``fp8_operands:`` key)."""
    _, cell, config = cut(CELL)
    (line,) = control.seed_lines(
        cell, config, [37], windows=1, sound_only=True, overrides={"rehearsal": True}
    )
    assert (line["seed"], line["variant"]) == (37, "sound") and "error" not in line
    numbers = ("loss_q.rel_gap", "loss_pi.gap_over_terms", "adam_nu.worst_leaf_gap",
               "param_change.worst_leaf_gap", "router_choices.disagree_share")
    assert all(line[n] <= cell["limits"]["loss_q"] for n in numbers), line
    assert not any(key.startswith(config["control"]["reference_mode"]) for key in line)


def test_a_trunk_that_skips_an_expert_comes_out_not_correct(monkeypatch):
    """The program with one held expert's terms left out (its rows counted
    to no group) against the reference that computes them."""
    from torch_actor_critic_tpu.ops import moe

    real = moe.plan_assignments

    def skipping(top_e, held):
        lo, hi = held
        return real(jnp.where(top_e == lo, hi, top_e), held)  # expert `lo` never held

    monkeypatch.setattr(moe, "plan_assignments", skipping)
    moe._experts_for.cache_clear()
    cell, values = _readings(29)
    limit = cell["limits"]["loss_q"]
    assert max(values[n] for n in ("loss_q.rel_gap", "adam_nu.worst_leaf_gap",
                                   "param_change.worst_leaf_gap")) > limit, values


def test_disagree_share_counts_assignments_not_order():
    from benchmark.drivers.trunkburst import disagree_share

    a = np.array([[[0, 1, 2, 3], [4, 5, 6, 7]]])
    assert disagree_share(a, a[..., ::-1]) == 0.0  # the same experts in another order
    b = np.array([[[0, 1, 2, 9], [4, 5, 6, 7]]])
    assert disagree_share(a, b) == pytest.approx(1 / 8)
    assert disagree_share(a.astype(np.float32), b) == pytest.approx(1 / 8)


def test_benchmark_json_keeps_or_parks_every_entry():
    """Every entry the BENCHMARK.json of PR 25 had is still there, in the file
    or parked beside it (``benchmark/parked/``), unchanged but for ``workloads``
    lists that grew and a bound that shrank, or that a ``benchmark`` PR refitted
    to the spreads it read (PR 36, ``PERF.md`` section 2: the two bounds the
    visual cell's host share outgrew), or that a ``benchmark`` PR retired and
    said why (PR 44: the harness's outside span, whose inside twins
    ``host.span_stage_ms`` and ``host.span_place_chunk_ms`` every burst cell has)."""
    refitted = {"grad_steps_per_s": 0.055, "window_ms.p95": 0.04}
    retired = {"host.stage_place_ms"}
    import subprocess

    old = subprocess.run(
        ["git", "show", "09dbf80538862d10171801bc52bcf9093dd863ad:BENCHMARK.json"],
        capture_output=True, text=True, cwd=ROOT,
    )
    if old.returncode:
        pytest.skip("that commit is not in this checkout")
    old, new = json.loads(old.stdout), registry.load_benchmark(parked=True)
    for key in ("command", "paths", "run_seconds"):
        assert old[key] == new[key]
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for was in old[section]:
            if was["name"] in retired:
                assert all(e["name"] != was["name"] for e in new[section])
                continue
            (now,) = [e for e in new[section] if e["name"] == was["name"]]
            same = dict(now)
            if "workloads" in was:
                assert set(was["workloads"]) <= set(now["workloads"])
                same["workloads"] = was["workloads"]
            if "bound" in was:
                assert now["bound"] <= was["bound"] or now["bound"] == refitted[was["name"]]
                same["bound"] = was["bound"]
            assert same == was
