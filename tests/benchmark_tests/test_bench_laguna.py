"""The ``laguna`` history-trunk cell: its configuration file against the
published row key by key, its arithmetic against hand-computed values, a
brute-force count of the windowed mask and the program's own
``cost_analysis``, its readers on a made-up trace, and the clean refusal of
a program from before the family, and the loss limits' upper reading (half
of the batch left out).  (The cell's CPU rehearsal is
``test_bench_rehearsal.py``'s, by rule; that its control comes out not correct
is read on the chip: ``data/limit_readings.laguna_s21_trunk_burst.json``.)"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_cut import check_configuration

from benchmark.harness import flops_laguna, registry, spans

CONFIG, CELL = "laguna_s21_trunk", "laguna_s21_trunk_burst"
FULL, SLIDING = "full_attention", "sliding_attention"
# The published config.json (model-configs catalog, row 60: Laguna-S-2.1),
# every key of the row's `config`.
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
    "num_hidden_layers": 48, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False, "rms_norm_eps": 1e-06,
    "num_experts": 256, "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        FULL: {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
            "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5,
        },
        SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    },
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0,
}
CUT = {  # key: (published, held here)
    "num_hidden_layers": (48, 5), "num_experts": (256, 8), "num_attention_heads": (48, 12),
    "num_key_value_heads": (8, 2), "vocab_size": (100352, None),
    "num_attention_heads_per_layer": ([48, 72, 72, 72] * 12, [12, 18, 18, 18, 12]),
}
BY_LAYER = ("layer_types", "mlp_layer_types", "gating_types")  # cut to the five layers held


def test_configuration_keeps_every_published_number():
    """``test_configuration_file``'s assertions (no width in ``reduced``, by
    ``bench_cut``'s rule; every count held with its ``reduced_how``), and the
    file against the published row: only what ``reduced`` names differs."""
    bench = registry.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    cfg = check_configuration(bench, entry)
    assert cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
        "num_attention_heads_per_layer", "num_experts", "num_attention_heads",
        "num_key_value_heads", "vocab_size",
    ]
    assert set(cfg["reduced"]) == set(CUT) | set(BY_LAYER)
    assert cfg["reference_mode"] == "bf16_operands" and cfg["family"] == "laguna_trunk"
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    for key, (published, held) in CUT.items():
        assert PUBLISHED[key] == published and cfg[key] == held, key
    for key in BY_LAYER:  # the first five layers of the published stack, as they stand
        assert cfg[key] == PUBLISHED[key][:5], key
    for key in cfg["reduced"]:
        assert cfg["reduced_how"][key], key
    # the heads divided four ways: what a layer of each kind holds of its published count
    assert [n // 4 for n in PUBLISHED["num_attention_heads_per_layer"][:5]] == cfg[
        "num_attention_heads_per_layer"
    ]
    assert PUBLISHED["num_key_value_heads"] // 4 == cfg["num_key_value_heads"]
    assert PUBLISHED["num_experts"] // 32 == cfg["num_experts"] == 8  # the floor of a cut
    readings = {"router", "no_qk_norm", "gate_input", "window", "yarn"}
    assert readings | {"history", "lr", "remat", "weights", "two_passes"} <= set(cfg["assumed"])
    for key in readings:  # each said to be a reading and not a key
        assert "a reading, not a key of the row" in cfg["assumed"][key], key
    assert cfg["deployment"] and "32-chip" in cfg["deployment"]


def test_the_program_is_handed_the_published_widths_and_this_chips_share():
    cfg = registry.load_config(CONFIG)
    m = cfg["model"]
    assert (m["hidden"], m["head_dim"], m["dense_width"]) == (
        cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    )
    kinds = {(FULL, "dense"): "f", (SLIDING, "dense"): "w", (FULL, "sparse"): "F", (SLIDING, "sparse"): "W"}
    assert m["pattern"] == "".join(
        kinds[pair] for pair in zip(cfg["layer_types"], cfg["mlp_layer_types"])
    ) == "fWWWF"
    heads = {"f": m["q_heads"], "F": m["q_heads"], "W": m["window_q_heads"], "w": m["window_q_heads"]}
    assert [heads[k] for k in m["pattern"]] == cfg["num_attention_heads_per_layer"]
    assert (m["q_heads"], m["kv_heads"]) == (cfg["num_attention_heads"], cfg["num_key_value_heads"])
    assert (m["experts"], m["experts_per_tok"]) == (PUBLISHED["num_experts"], cfg["num_experts_per_tok"])
    lo, hi = m["experts_held"]
    assert hi - lo == cfg["num_experts"]
    assert (m["expert_width"], m["shared_expert_width"], m["routed_scale"]) == (
        cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"],
        cfg["moe_routed_scaling_factor"],
    )
    full, sliding = cfg["rope_parameters"][FULL], cfg["rope_parameters"][SLIDING]
    assert (m["window"], m["window_rope_theta"]) == (cfg["sliding_window"], sliding["rope_theta"])
    assert sliding["partial_rotary_factor"] == 1 and sliding["rope_type"] == "default"
    assert (
        m["rope_theta"], m["rope_share"], m["rope_yarn_factor"], m["rope_yarn_positions"],
        m["rope_yarn_beta_fast"], m["rope_yarn_beta_slow"], m["rope_attention_factor"],
    ) == (
        full["rope_theta"], full["partial_rotary_factor"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"], full["beta_slow"],
        full["attention_factor"],
    )
    # the three of them the program has as YaRN's own constants, the same
    from torch_actor_critic_tpu.ops import attention

    assert (attention.YARN_BETA_FAST, attention.YARN_BETA_SLOW) == (full["beta_fast"], full["beta_slow"])
    assert attention.yarn_scale(full["factor"]) == pytest.approx(full["attention_factor"], rel=1e-12)
    assert m["rms_eps"] == cfg["rms_norm_eps"] and m["head_gate"] and not m["qk_norm"]
    assert m["remat"] <= len(m["pattern"]) and m["bf16_dots"] and m["block_length"] == 1
    # the driver hands every one of these to the program's own fields
    _, cell, config = registry.resolve(CELL)
    driver = registry.load_driver(cell["driver"])
    sac = driver(cell, config, 1, spans.Spans(), {"rehearsal": False}).sac_config()
    from benchmark.drivers import lagunaburst

    for key in lagunaburst.TRUNK_KEYS:
        assert getattr(sac, "trunk_" + key) == m[key], key
    assert sac.trunk_experts_held == (0, 8) and sac.shared_trunk and sac.lr == 1e-6
    assert sac.trunk_router == "softmax" and sac.trunk_qk_norm_rope
    # the rehearsal's cut keeps a window smaller than its history and both layer kinds
    small = m["rehearsal_cut"]
    assert small["window"] < small["history_len"] and set(small["pattern"]) >= {"f", "W", "F"}


def test_cell_entry_names_its_traffic_and_its_readers():
    bench, cell, config = registry.resolve(CELL)
    traffic = cell["traffic"]
    assert (cell["chips"], cell["driver"]) == (1, "lagunaburst")
    assert next(w for w in bench["workloads"] if w["name"] == CELL)["traffic"] == "history_burst"
    assert (traffic["ring_rows"], traffic["pool_windows"], traffic["fill_slab_rows"]) == (2048, 8, 256)
    assert traffic["trace_seconds"] == 8
    assert (config["model"]["history_len"], config["sac"]["batch_size"]) == (4096, 2)
    assert config["sac"]["update_every"] == 10
    names = {m["name"] for m in registry.metrics_for(bench, "per_layer", CELL)}
    new = {
        "trunk.attention_full_us_per_step", "trunk.attention_sliding_us_per_step",
        "trunk.dense_ffn_us_per_step", "trunk.window_flash_roofline",
        "trunk.window_key_blocks_share", "trunk.laguna_experts_roofline", "trunk.window_mfu",
        "trunk.laguna_shared_expert_us_per_step",
    }
    # every list that held both trunk cells holds this one too
    both = {
        m["name"] for m in bench["per_layer"]
        if {"sdar30b_a3b_trunk_burst", "nemotron3_super_trunk_burst"} <= set(m.get("workloads", []))
    }
    assert names == new | both | {"shell.compile_s"}
    assert len(both) == 21
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "grad_steps_per_s"
    reported = {m["name"] for m in registry.metrics_for(bench, "end_to_end", CELL)}
    assert reported == {"grad_steps_per_s", "setup_s"}


def test_the_cell_holds_over_half_of_the_chip_at_rest():
    """ISSUE 45's arithmetic, to the parameter: 525.6M, 10.51 GB at 20 B."""
    _, cell, config = registry.resolve(CELL)
    model = config["model"]
    attention_full = 3072 * (1536 + 256 + 256) + 1536 * 3072 + 3072 * 12
    attention_sliding = 3072 * (2304 + 256 + 256) + 2304 * 3072 + 3072 * 18
    assert (attention_full, attention_sliding) == (11_046_912, 15_783_936)
    expert, router = 3 * 3072 * 1024, 3072 * 256
    assert flops_laguna.layer_params(model, "f") == attention_full + 3 * 3072 * 12288 + 2 * 3072
    assert flops_laguna.layer_params(model, "W") == (
        attention_sliding + router + 8 * expert + expert + 2 * 3072
    ) == 101_511_168
    assert flops_laguna.layer_params(model, "F") == 96_774_144
    assert flops_laguna.trunk_params(model) == 525_662_208
    assert 20 * 525_662_208 == 10_513_244_160
    assert flops_laguna.row_bytes(model) == 2 * 4096 * 17 * 4 + 6 * 4 + 8 == 557_088
    ring = cell["traffic"]["ring_rows"] * flops_laguna.row_bytes(model)
    assert ring == 1_140_916_224
    at_rest = flops_laguna.at_rest_bytes(model, cell["traffic"]["ring_rows"])
    assert at_rest == 16 * 525_662_208 + ring == 9_551_511_552
    assert at_rest / 16_909_336_064 > 0.56  # of the chip's bytes_limit; the floor asks 25%
    assert registry.load_driver(cell["driver"]).at_rest_bytes(cell, config) == at_rest
    # the other divisions of ISSUE 45: heads two ways, every head held
    two_ways = dict(model, q_heads=24, window_q_heads=36, kv_heads=4)
    whole = dict(model, q_heads=48, window_q_heads=72, kv_heads=8)
    assert round(flops_laguna.trunk_params(two_ways) / 1e6, 1) == 595.1
    assert flops_laguna.trunk_params(whole) == 733_999_104  # ISSUE 45's 733.9M: 14.68 GB at 20 B


@pytest.mark.parametrize("t, window", [(12, 5), (4096, 512), (100, 100), (64, 200), (7, 1)])
def test_visible_pairs_of_the_windowed_mask_against_a_brute_force_count(t, window):
    i = np.arange(t)
    seen = (i[None, :] <= i[:, None]) & (i[None, :] > i[:, None] - window)
    assert flops_laguna.visible_pairs(t, window) == int(seen.sum())
    assert flops_laguna.visible_pairs(t) == t * (t + 1) // 2


def test_laguna_flops_arithmetic():
    model = registry.load_config(CONFIG)["model"]
    # a sliding query sees 480 keys on average where a causal one sees 2,048
    assert flops_laguna.layer_pairs(model, "W") / 4096 == pytest.approx(480.06, abs=0.01)
    assert flops_laguna.layer_pairs(model, "F") / 4096 == 2048.5
    # MFLOP a token of the three sliding layers' kernels, windowed and not (ISSUE 45: 13.3, 56.6)
    per_token = lambda pairs: 3 * 4 * pairs / 4096 * 128 * 18 / 1e6  # noqa: E731
    assert per_token(flops_laguna.layer_pairs(model, "W")) == pytest.approx(13.27, abs=0.01)
    assert per_token(flops_laguna.layer_pairs(model, "F")) == pytest.approx(56.64, abs=0.01)
    assert flops_laguna.attention_flops_forward(model, 2, "W") == 4 * (131_328 + 3584 * 512) * 128 * 18 * 2
    assert flops_laguna.attention_flops_per_step(model, 2) == 4 * (
        3 * flops_laguna.attention_flops_forward(model, 2, "W")
        + 2 * flops_laguna.attention_flops_forward(model, 2, "F")
    )
    assert flops_laguna.attention_bytes_per_step(model, 2) == 4 * 4 * 2 * 4096 * 128 * (3 * 40 + 2 * 28)
    assert flops_laguna.ffn_macs_per_token(model, "f") == 3 * 3072 * 12288
    assert flops_laguna.ffn_macs_per_token(model, "W") == 3072 * 256 + 3 * 3072 * 1024
    assert flops_laguna.expert_flops_per_row(model) == 2 * 3 * 3072 * 1024
    balanced = 4 * 8192 * 10 * 8 // 256  # 320 rows a held expert a layer a pass
    assert balanced == 4 * 8 * 320
    per_step = flops_laguna.flops_per_step(model, 2, balanced, balanced)
    assert 16.6e12 < per_step < 16.8e12  # 84.7 ms at the chip's peak
    dense_ffn = 4 * 2 * 8192 * 3 * 3072 * 12288
    assert 0.44 < dense_ffn / per_step < 0.45
    # the grouped products: byte-bound by the held kernels, 10 B a parameter a step
    kernels = 4 * 8 * 3 * 3072 * 1024
    assert flops_laguna.expert_bytes_per_step(model, 0, 0) == 10 * kernels
    # the SDAR reader's count would take the dense block for an expert layer
    from benchmark.harness import flops_trunk

    assert flops_trunk.expert_bytes_per_step(dict(model, layers=5), 0, 0) == 10 * kernels * 5 // 4


SMALL = dict(  # the program at a size the CPU compiles in seconds
    hidden=64, pattern="fWWF", q_heads=2, window_q_heads=4, kv_heads=2, head_dim=16, window=8,
    rope_theta=5e5, window_rope_theta=1e4, rope_share=0.5, rope_yarn_factor=8.0,
    rope_yarn_positions=8, qk_norm=False, head_gate=True, dense_width=96, experts=16,
    experts_per_tok=4, expert_width=48, experts_held=[2, 6], routed_scale=2.5,
    shared_expert_width=48, history_len=32, obs_dim=5, act_dim=3, num_qs=2, q_hidden=32,
)


def test_the_counts_stay_under_the_programs_own_cost_analysis():
    """No share of a peak may read over 100%: what ``flops_laguna`` counts is
    at most what the program executes, by XLA's own ``cost_analysis`` of one
    whole trunk pass with its gradient on the CPU (where the window is a mask
    over every pair, so the executed attention is the causal layers' and
    more)."""
    from torch_actor_critic_tpu.models import SequenceTrunk, TrunkSpec

    batch, t = 2, SMALL["history_len"]
    spec = TrunkSpec(
        **{k: v for k, v in SMALL.items() if k not in (
            "history_len", "obs_dim", "act_dim", "num_qs", "q_hidden", "experts_held")},
        experts_held=tuple(SMALL["experts_held"]), block_length=1, bf16_dots=False,
    )
    trunk = SequenceTrunk(spec=spec)
    obs = jnp.ones((batch, t, SMALL["obs_dim"]))
    params = jax.jit(trunk.init)(jax.random.key(0), obs)["params"]

    def loss(p_):
        out, sown = trunk.apply({"params": p_}, obs, mutable=["moe_stats"])
        return jnp.sum(out), sown

    (_, sown), _ = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    rows = sum(int(jnp.sum(layer["moe"]["sizes"][0])) for layer in sown["moe_stats"].values())
    executed = jax.jit(jax.grad(lambda p_: loss(p_)[0])).lower(params).compile().cost_analysis()["flops"]
    tokens = batch * t
    counted = 3 * (  # a forward pass and its backward at twice that
        2 * tokens * (flops_laguna.dense_macs_per_token(SMALL) + SMALL["obs_dim"] * SMALL["hidden"])
        + flops_laguna.expert_flops_per_row(SMALL) * rows
    ) + 3 * flops_laguna.attention_flops_per_step(SMALL, batch) / flops_laguna.PASSES
    # at this size the uncounted work (rotary, the masks over every pair and over
    # 16 experts, softmax, the gates) is several times the products'
    assert rows > 0 and counted <= executed <= 8 * counted, (counted, executed)


def _ctx(trace, driver, config=None, cell=None):
    _, the_cell, the_config = registry.resolve(CELL)
    return types.SimpleNamespace(
        trace=trace, cell=cell or the_cell, config=config or the_config, n_windows=2,
        per_window={"grad_steps": 10, "env_steps": 0}, device={"kind": "TPU v5 lite"},
        driver=driver,
    )


def _new_readers():
    bench = registry.load_benchmark()
    return [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]


def test_new_readers_are_silent_where_there_is_nothing_to_read():
    """A program without the scopes or counters (the parent's), an untraced
    run, another family's model: every new reader answers ``None`` and does
    not raise."""
    new = _new_readers()
    assert len(new) == 8
    other = registry.load_config("sdar30b_a3b_trunk")
    for driver in (types.SimpleNamespace(), types.SimpleNamespace(trunk_counters=lambda: {})):
        for name in new:
            assert registry.load_layer_metric(name)(_ctx(None, driver, other)) is None, name
    # the key blocks' share needs a program that has the function
    from torch_actor_critic_tpu.ops import attention

    read = registry.load_layer_metric("trunk.window_key_blocks_share")
    model = registry.load_config(CONFIG)["model"]
    ctx = _ctx(None, types.SimpleNamespace(model=model))
    assert read(ctx) == pytest.approx(15 / 36)
    was = attention.visited_key_blocks
    try:
        del attention.visited_key_blocks
        assert read(ctx) is None
    finally:
        attention.visited_key_blocks = was


def test_the_new_readers_on_a_made_up_trace(monkeypatch):
    """With 30 ms a step of flash kernels and 9 ms of grouped products the
    shares are the least times over them; the time of a scope comes from the
    scope, whatever operations run there."""
    from benchmark.harness import trunk_read

    _, cell, config = registry.resolve(CELL)
    model = config["model"]
    by_scope = {
        "tac/trunk/attention/full": 20_000.0, "tac/trunk/attention/sliding": 45_000.0,
        "tac/trunk/dense_ffn": 110_000.0, "tac/trunk/moe/experts/products": 3_000.0,
        "tac/trunk/moe/shared": 17_000.0,
    }
    monkeypatch.setattr(trunk_read, "scope_us_per_step", lambda ctx, prefix: by_scope.get(prefix))
    counters = {"trunk/held_assignments": 10240.0, "trunk/held_assignments_target": 10240.0}
    ctx = _ctx(
        {"busy_s": 6.0, "by_kind": {"ragged-dot-none": 20 * 6e-3, "attention": 20 * 30e-3}},
        types.SimpleNamespace(model=model, trunk_counters=lambda: counters),
    )
    read = lambda name: registry.load_layer_metric(name)(ctx)  # noqa: E731
    assert read("trunk.attention_full_us_per_step") == 20_000.0
    assert read("trunk.attention_sliding_us_per_step") == 45_000.0
    assert read("trunk.dense_ffn_us_per_step") == 110_000.0
    assert read("trunk.laguna_shared_expert_us_per_step") == 17_000.0
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops_laguna.roofline_seconds(
        flops_laguna.attention_flops_per_step(model, 2),
        flops_laguna.attention_bytes_per_step(model, 2), peaks,
    )
    assert least == pytest.approx(1.2598e12 / 197e12, rel=1e-3)  # compute-bound: 6.4 ms a step
    assert read("trunk.window_flash_roofline") == pytest.approx(100 * least / 30e-3)
    experts = read("trunk.laguna_experts_roofline")
    moved = flops_laguna.expert_bytes_per_step(model, 10240, 10240) / 819e9
    assert experts == pytest.approx(100 * moved / 9e-3) and 0 < experts < 100
    per_step = flops_laguna.flops_per_step(model, 2, 10240, 10240)
    assert read("trunk.window_mfu") == pytest.approx(100 * per_step * 20 / 6.0 / 197e12)  # 28.2%
    # a kernel that visited the causal triangle on the sliding layers would do
    # 3.3 times the counted work of those layers for the same count
    causal = flops_laguna.attention_flops_per_step(dict(model, window=4096), 2, "WWW")
    assert causal / flops_laguna.attention_flops_per_step(model, 2, "WWW") > 4.2


def test_a_program_from_before_this_family_is_refused_cleanly(monkeypatch):
    """The parent's ``SACConfig`` lacks the family's fields: the driver says
    so and exits, with no traceback and no hang."""
    import dataclasses

    from torch_actor_critic_tpu.utils import config as program_config

    _, cell, config = registry.resolve(CELL)
    driver = registry.load_driver(cell["driver"])(cell, config, 1, spans.Spans(), {"rehearsal": True})
    kept = [
        (f.name, f.type, f) for f in dataclasses.fields(program_config.SACConfig)
        if f.name not in ("trunk_window", "trunk_head_gate")
    ]
    older = dataclasses.make_dataclass("SACConfig", [(n, t, dataclasses.field(default=f.default)) for n, t, f in kept])
    monkeypatch.setattr(program_config, "SACConfig", older)
    with pytest.raises(SystemExit, match="trunk_head_gate.*trunk_window"):
        driver.sac_config()


def test_a_routers_second_moment_is_held_by_the_parameters_change_alone(capsys):
    """``lagunaburst.Driver._compare``: a router's ``nu`` three times the
    reference's moves no compared number (at the cell's batch of 2 its norm is
    one token's gradient to the fourth power, and read 0.33 and 0.46 on sound
    seeds: ``PERF.md`` section 6, PR 45) and is printed; any other leaf's fails
    ``adam_nu``; a router's kernel still counts in ``param_change``."""
    import copy

    from benchmark.drivers import lagunaburst

    _, cell, config = registry.resolve(CELL)
    driver = lagunaburst.Driver(cell, config, 1, spans.Spans(), {"rehearsal": False})
    rng = np.random.default_rng(0)
    leaf = lambda *shape: rng.uniform(0.5, 1.0, shape).astype(np.float32)  # noqa: E731
    critic = {"params": {
        "trunk": {"layer_1": {
            "moe": {"router": leaf(6, 4), "w_up": leaf(2, 6, 3)},
            "attention": {"k_proj": {"kernel": leaf(6, 4)}},
        }},
        "ensemble": {"Dense_0": {"kernel": leaf(6, 2)}},
    }}
    actor = {"params": {"mu": {"kernel": leaf(6, 2)}}}
    driver.actor0 = jax.tree_util.tree_map(np.zeros_like, actor)
    driver.critic0 = jax.tree_util.tree_map(np.zeros_like, critic)
    ref = {
        "loss_q": 1.0, "loss_pi": 0.5, "pi_terms": 1.0, "actor": actor, "critic": critic,
        "pi_nu": copy.deepcopy(actor), "q_nu": copy.deepcopy(critic),
        "choices": np.zeros((1, 1, 4, 2), np.int32),
    }

    def read(account):
        return {c.name: c for c in driver._compare(account, ref, ref["choices"])}

    rest, routers = lagunaburst.routers_apart(critic)
    assert [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(routers)[0]] == [
        "['params']['trunk']['layer_1']['moe']['router']"
    ]
    assert len(jax.tree_util.tree_leaves(rest)) == len(jax.tree_util.tree_leaves(critic)) - 1
    assert lagunaburst.routers_apart(actor) == (actor, {})

    got = copy.deepcopy(ref)
    got["q_nu"]["params"]["trunk"]["layer_1"]["moe"]["router"] *= 3.0
    capsys.readouterr()
    numbers = read(got)
    assert all(c.ok for c in numbers.values()) and numbers["adam_nu.worst_leaf_gap"].value == 0.0
    said = capsys.readouterr().out
    assert "routers' adam_nu, compared with nothing: [(\"[1]['params']['trunk']['layer_1']['moe']['router']\", 2.0)]" in said
    assert "router" not in said.split("trunk worst leaves, adam_nu: ")[1].split("; param_change")[0]

    got = copy.deepcopy(ref)
    got["q_nu"]["params"]["trunk"]["layer_1"]["attention"]["k_proj"]["kernel"] *= 3.0
    numbers = read(got)
    assert not numbers["adam_nu.worst_leaf_gap"].ok and numbers["adam_nu.worst_leaf_gap"].value == pytest.approx(2.0)

    got = copy.deepcopy(ref)
    got["critic"]["params"]["trunk"]["layer_1"]["moe"]["router"] *= 1.5
    numbers = read(got)
    assert numbers["adam_nu.worst_leaf_gap"].ok
    assert not numbers["param_change.worst_leaf_gap"].ok
    assert numbers["param_change.worst_leaf_gap"].value == pytest.approx(0.5)


def test_each_loss_limit_stands_between_the_sound_runs_and_half_of_the_batch_left_out():
    """The float8 control is no upper reading for the two losses (it passes
    ``loss_q`` on one seed and reads under the sound runs on ``loss_pi``), so
    theirs is the fault they are there to catch, read at the cell's sizes
    (``tools/batch_fault.py``): each limit has room on both sides."""
    from bench_cut import limit_readings

    entries = limit_readings()[CELL]
    for number in ("loss_q", "loss_pi"):
        e = entries[number]
        assert not e["separates"] and e["half_batch_seeds"] >= 3
        assert 2 * e["sound_max"] <= e["limit"] <= e["half_batch_min"] / 2, (number, e)


def test_half_of_the_batch_left_out_is_the_first_half_twice():
    """``tools/batch_fault.py``'s fault on a leaf ``(updates, shard, batch,
    ...)``: the later half of the batch replaced by the earlier."""
    from benchmark.tools import batch_fault

    x = jnp.arange(48.0).reshape(3, 1, 4, 4)
    got = batch_fault.first_half_twice(x, 2)
    assert got.shape == x.shape
    np.testing.assert_array_equal(got[:, :, :2], x[:, :, :2])
    np.testing.assert_array_equal(got[:, :, 2:], x[:, :, :2])


@pytest.mark.slow
def test_half_of_the_batch_left_out_fails_both_losses_at_the_rehearsals_size():
    """The tool whole, on the CPU at the rehearsal's cut: the sound readings
    and, beside them, the reference on half of the batch against itself on
    the whole (a minute and a half)."""
    from benchmark.tools import batch_fault

    _, cell, config = registry.resolve(CELL)
    got = batch_fault.readings(cell, config, 7, 1, {"rehearsal": True})
    assert got["losses.non_finite"] == 0.0
    for number, name in (("loss_q", "loss_q.rel_gap"), ("loss_pi", "loss_pi.gap_over_terms")):
        assert got["half_batch:" + name] > 3 * cell["limits"][number] > 3 * got[name]


@pytest.mark.slow
def test_the_cell_fits_a_v5e():
    """The cell's burst, as its driver builds it, compiled for a described
    v5e at the cell's own sizes (minutes; the builder of PR 45 ran it before
    the first chip call: PERF.md section 4 has the compiler's account)."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.drivers import trunkburst
    from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
    from torch_actor_critic_tpu.core.types import BufferState
    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC
    from torch_actor_critic_tpu.parallel.mesh import make_mesh
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        v5e = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"the v5e:2x2 topology cannot be described here: {e!r}")
    _, cell, config = registry.resolve(CELL)
    driver = registry.load_driver(cell["driver"])(cell, config, 1, spans.Spans(), {"rehearsal": False})
    cfg, env = driver.sac_config(), trunkburst.Spec(driver.model)
    sac = make_learner(cfg, *build_models(cfg, env), env.act_dim)
    was, backend = jax.config.jax_enable_compilation_cache, jax.default_backend
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.default_backend = lambda: "tpu"
    try:
        learner = DataParallelSAC(sac, make_mesh(dp=1, devices=v5e[:1]))
        state = jax.eval_shape(sac.init_state, jax.random.key(0), env.example_obs())

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
    finally:
        jax.default_backend = backend
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    mem = compiled.memory_analysis()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.critic_params))
    assert abs(n_params - flops_laguna.trunk_params(driver.model)) < 2e6  # the Q heads
    assert mem.argument_size_in_bytes >= 16 * n_params and mem.alias_size_in_bytes >= 16 * n_params
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16_909_336_064
