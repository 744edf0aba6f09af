"""The ``nemotron_h`` history-trunk cell: its configuration file against the
published row, its arithmetic against hand-computed values and against the
program's own ``cost_analysis``, its readers, and how its ``correct`` comes out
false (the control one precision lower)."""

import types

import jax
import jax.numpy as jnp
import pytest
from bench_cut import check_configuration, cut, limit_readings

from benchmark import control
from benchmark.harness import flops_hybrid, registry

CONFIG, CELL = "nemotron3_super_trunk", "nemotron3_super_trunk_burst"
# The published config.json (model-configs catalog, row 57:
# NVIDIA-Nemotron-3-Super-120B-A12B-BF16), every key of the row's `config`.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
CUT = {  # key: (published, held here)
    "num_hidden_layers": (88, 11), "n_routed_experts": (512, 8), "mamba_num_heads": (128, 16),
    "n_groups": (8, 1), "num_attention_heads": (32, 4), "num_key_value_heads": (2, 1),
    "vocab_size": (131072, None), "num_nextn_predict_layers": (1, 0),
}


def test_configuration_keeps_every_published_width():
    """``test_configuration_file``'s assertions (no width or head size in
    ``reduced``, by ``bench_cut``'s rule; every count held with its
    ``reduced_how``), and the file against the published row: only what
    ``reduced`` names differs."""
    bench = registry.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = check_configuration(bench, entry)
    assert set(cfg["reduced"]) == set(CUT) | {"hybrid_override_pattern"}
    assert cfg["reference_mode"] == "bf16_operands"
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    for key, (published, held) in CUT.items():
        assert PUBLISHED[key] == published and cfg[key] == held, key
        assert cfg["reduced_how"][key]
    pattern = cfg["hybrid_override_pattern"]
    assert pattern in PUBLISHED["hybrid_override_pattern"] and len(pattern) == 11
    whole = PUBLISHED["hybrid_override_pattern"]
    assert (whole.count("M"), whole.count("E"), whole.count("*")) == (40, 40, 8)
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (5, 5, 1)
    for key in ("assumed", "deployment", "reduced_how"):
        assert cfg[key]
    assert {"no_rotary", "latent_wiring", "weights", "history", "lr", "remat"} <= set(cfg["assumed"])


def test_the_program_is_handed_the_published_widths_and_this_chips_share():
    cfg = registry.load_config(CONFIG)
    m = cfg["model"]
    assert (m["hidden"], m["head_dim"], m["pattern"]) == (
        cfg["hidden_size"], cfg["head_dim"], cfg["hybrid_override_pattern"]
    )
    assert (m["q_heads"], m["kv_heads"]) == (cfg["num_attention_heads"], cfg["num_key_value_heads"])
    assert (m["experts"], m["experts_per_tok"]) == (PUBLISHED["n_routed_experts"], cfg["num_experts_per_tok"])
    lo, hi = m["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] == 8  # the floor of a model_config cut
    assert (m["expert_width"], m["expert_latent"], m["shared_expert_width"]) == (
        cfg["moe_intermediate_size"], cfg["moe_latent_size"],
        cfg["moe_shared_expert_intermediate_size"],
    )
    assert (m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"]) == (
        cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    )
    assert (m["ssm_conv"], m["ssm_chunk"]) == (cfg["conv_kernel"], cfg["chunk_size"])
    # a share holds whole groups: the published 16 heads a group, and the inner width
    # of the uncut mixer is expand x hidden
    assert PUBLISHED["mamba_num_heads"] * m["ssm_head_dim"] == cfg["expand"] * m["hidden"]
    assert m["ssm_heads"] // m["ssm_groups"] == PUBLISHED["mamba_num_heads"] // PUBLISHED["n_groups"]
    assert PUBLISHED["num_attention_heads"] // PUBLISHED["num_key_value_heads"] >= m["q_heads"]
    assert (m["router"], m["routed_scale"], m["expert_form"]) == (
        "sigmoid", cfg["routed_scaling_factor"], cfg["mlp_hidden_act"]
    )
    assert m["rms_eps"] == cfg["layer_norm_epsilon"] and not m["qk_norm_rope"]
    assert m["remat"] == len(m["pattern"]) and m["bf16_dots"] and m["block_length"] == 1
    # the driver hands every one of these to the program's own fields
    from benchmark.drivers import hybridburst
    from benchmark.harness import spans

    _, cell, config = registry.resolve(CELL)
    sac = hybridburst.Driver(cell, config, 1, spans.Spans(), {"rehearsal": False}).sac_config()
    for key in hybridburst.TRUNK_KEYS:
        assert getattr(sac, "trunk_" + key) == m[key], key
    assert sac.trunk_experts_held == (0, 8) and sac.shared_trunk and sac.lr == 1e-6


def test_cell_entry_names_its_traffic_and_its_readers():
    bench, cell, config = registry.resolve(CELL)
    traffic = cell["traffic"]
    assert (cell["chips"], cell["driver"]) == (1, "hybridburst")
    assert (traffic["ring_rows"], traffic["pool_windows"], traffic["trace_seconds"]) == (4096, 8, 8)
    assert (config["model"]["history_len"], config["sac"]["batch_size"]) == (1024, 4)
    assert config["sac"]["update_every"] == 10
    names = {m["name"] for m in registry.metrics_for(bench, "per_layer", CELL)}
    new = {
        "trunk.ssm_us_per_step", "trunk.shared_expert_us_per_step", "trunk.ssm_scan_roofline",
        "trunk.latent_experts_roofline", "trunk.hybrid_mfu",
    }
    assert names >= new | {
        "trunk.moe_us_per_step", "trunk.attention_us_per_step", "trunk.expert_load_max_over_mean",
        "update.device_us_per_step", "update.push_us_per_step", "update.sample_us_per_step",
        "update.compute_us_per_step", "trace.unscoped_share", "device.idle_share",
        "host.span_stage_ms", "host.span_place_chunk_ms", "host.span_burst_dispatch_ms",
        "ops.copy_gather_us_per_step", "shell.compile_s",
    }
    # the readers that count SDAR's work stay SDAR's
    assert not names & {"trunk.mfu", "trunk.flash_roofline", "trunk.moe_experts_roofline"}
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "grad_steps_per_s"
    reported = {m["name"] for m in registry.metrics_for(bench, "end_to_end", CELL)}
    assert reported == {"grad_steps_per_s", "setup_s"}
    # ISSUE 40: this cell's control was read on at least 8 seeds
    separating = [e for e in limit_readings()[CELL].values() if e["separates"]]
    assert len(separating) == 3 and all(e["control_seeds"] >= 8 for e in separating)


def test_the_cell_holds_over_half_of_the_chip_at_rest():
    _, cell, config = registry.resolve(CELL)
    model = config["model"]
    assert flops_hybrid.trunk_params(model) == 566_717_424
    assert flops_hybrid.row_bytes(model) == 2 * 1024 * 17 * 4 + 6 * 4 + 8 == 139_296
    ring = cell["traffic"]["ring_rows"] * flops_hybrid.row_bytes(model)
    assert ring == 570_556_416
    at_rest = flops_hybrid.at_rest_bytes(model, cell["traffic"]["ring_rows"])
    assert at_rest == 16 * 566_717_424 + ring == 9_638_035_200
    assert at_rest / 16_909_336_064 > 0.55  # of the chip's bytes_limit; the floor asks 25%
    assert registry.load_driver(cell["driver"]).at_rest_bytes(cell, config) == at_rest


def test_hybrid_flops_arithmetic():
    """The counts of ISSUE 40, by hand: a state-space layer whole is 109.6M
    parameters and an eighth of its heads 13.7M of products a token; an
    attention layer's share 5.2M; an expert layer 54.5M outside its routed
    experts and 5.505M a routed expert."""
    model = registry.load_config(CONFIG)["model"]
    assert flops_hybrid.mixer_macs_per_token(model, "M") == 4096 * (1024 + 1280 + 16) + 1024 * 4096
    assert flops_hybrid.mixer_macs_per_token(model, "*") == 4096 * 512 + 2 * 4096 * 128 + 512 * 4096
    outside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert flops_hybrid.mixer_macs_per_token(model, "E") == outside == 54_525_952
    whole_m = dict(model, ssm_heads=128, ssm_groups=8)
    assert flops_hybrid.mixer_macs_per_token(whole_m, "M") == 4096 * 18_560 + 8192 * 4096 == 109_576_192
    assert flops_hybrid.expert_flops_per_row(model) == 2 * 2 * 1024 * 2688
    assert 2 * 1024 * 2688 == 5_505_024  # a routed expert
    assert flops_hybrid.mixer_params(model, "E") == outside + 512 + 8 * 5_505_024 + 4096
    balanced = 5 * 4096 * 22 * 8 // 512  # 1,408 rows a layer, 176 a held expert
    assert balanced == 7040
    per_step = flops_hybrid.flops_per_step(model, 4, balanced, balanced)
    assert 11.6e12 < per_step < 11.8e12  # 59.6 ms at the chip's peak
    dense = 4 * 2 * 4096 * flops_hybrid.dense_macs_per_token(model)
    shared = 4 * 2 * 4096 * 5 * 2 * 4096 * 5376
    assert 0.60 < shared / per_step < 0.64 and dense / per_step > 0.95
    # the recurrence: 5 p n operations a head and step; its operands and output once
    assert flops_hybrid.scan_flops_forward(model, 4) == 5 * 64 * 128 * 16 * 4096
    assert flops_hybrid.scan_bytes_forward(model, 4) == 4 * (1024 + 256 + 16 + 1024) * 4096
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops_hybrid.roofline_seconds(
        flops_hybrid.scan_flops_per_step(model, 4), flops_hybrid.scan_bytes_per_step(model, 4), peaks
    )
    assert least == pytest.approx(20 * 38_010_880 / 819e9)  # byte-bound: 0.93 ms a step
    # the grouped products: byte-bound by the held kernels, 10 B a parameter a step
    kernels = 5 * 8 * 2 * 1024 * 2688
    assert flops_hybrid.expert_bytes_per_step(model, 0, 0) == 10 * kernels
    one_forward = flops_hybrid.expert_bytes_per_step(model, 0, 1) - 10 * kernels
    assert one_forward == 2 * (1024 + 2688) + 4 * (2688 + 1024)
    moved = flops_hybrid.expert_bytes_per_step(model, balanced, balanced)
    assert moved / 819e9 > flops_hybrid.expert_flops_per_step(model, balanced, balanced) / 197e12


SMALL = dict(  # the program at a size the CPU compiles in seconds
    hidden=64, pattern="EMEM*", q_heads=2, kv_heads=1, head_dim=16, experts=16,
    experts_per_tok=4, expert_width=48, experts_held=[2, 6], expert_latent=32,
    shared_expert_width=80, ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
    ssm_conv=4, ssm_chunk=8, history_len=32, obs_dim=5, act_dim=3, num_qs=2, q_hidden=32,
)


def test_the_counts_stay_under_the_programs_own_cost_analysis():
    """No share of a peak may read over 100%: what ``flops_hybrid`` counts is
    at most what the program executes, by XLA's own ``cost_analysis`` of the
    program on the CPU: the scan alone (forward, and forward with gradient),
    and one whole trunk pass with its gradient."""
    from torch_actor_critic_tpu.models import SequenceTrunk, TrunkSpec
    from torch_actor_critic_tpu.ops import ssm

    batch, t = 2, SMALL["history_len"]
    h, p, g, n = SMALL["ssm_heads"], SMALL["ssm_head_dim"], SMALL["ssm_groups"], SMALL["ssm_state"]
    shapes = (
        jnp.zeros((batch, t, h, p)), jnp.ones((batch, t, h)), -jnp.ones((h,)),
        jnp.zeros((batch, t, g, n)), jnp.zeros((batch, t, g, n)), jnp.ones((h,)),
    )
    scan = lambda *v: ssm.ssd_scan(*v, chunk=SMALL["ssm_chunk"])  # noqa: E731
    cost = lambda f, *a: jax.jit(f).lower(*a).compile().cost_analysis()  # noqa: E731
    forward = cost(scan, *shapes)
    assert flops_hybrid.scan_flops_forward(SMALL, batch) <= forward["flops"]
    assert flops_hybrid.scan_bytes_forward(SMALL, batch) <= forward["bytes accessed"]
    both = cost(jax.grad(lambda *v: jnp.sum(scan(*v)), range(6)), *shapes)
    assert 3 * flops_hybrid.scan_flops_forward(SMALL, batch) <= both["flops"]
    assert 3 * flops_hybrid.scan_bytes_forward(SMALL, batch) <= both["bytes accessed"]

    spec = TrunkSpec(
        **{k: v for k, v in SMALL.items() if k not in (
            "history_len", "obs_dim", "act_dim", "num_qs", "q_hidden", "experts_held")},
        experts_held=tuple(SMALL["experts_held"]), block_length=1, qk_norm_rope=False,
        router="sigmoid", routed_scale=2.5, expert_form="relu2", rms_eps=1e-5, bf16_dots=False,
    )
    trunk = SequenceTrunk(spec=spec)
    obs = jnp.ones((batch, t, SMALL["obs_dim"]))
    params = trunk.init(jax.random.key(0), obs)["params"]

    def loss(p_):
        out, sown = trunk.apply({"params": p_}, obs, mutable=["moe_stats"])
        return jnp.sum(out), sown

    (_, sown), _ = jax.value_and_grad(loss, has_aux=True)(params)
    rows = sum(int(jnp.sum(layer["mixer"]["sizes"][0])) for layer in sown["moe_stats"].values())
    executed = cost(jax.grad(lambda p_: loss(p_)[0]), params)["flops"]
    tokens = batch * t
    counted = 3 * (  # a forward pass and its backward at twice that
        2 * tokens * (flops_hybrid.dense_macs_per_token(SMALL) + SMALL["obs_dim"] * SMALL["hidden"])
        + 2 * flops_hybrid.scan_flops_forward(SMALL, batch)
        + flops_hybrid.expert_flops_per_row(SMALL) * rows
    ) + 3 * flops_hybrid.attention_flops_per_step(SMALL, batch) / flops_hybrid.PASSES
    # at this size the uncounted elementwise work (the router's masks over 16
    # experts, the scan's decay terms) is twice the products'
    assert rows > 0 and counted <= executed <= 5 * counted, (counted, executed)


def test_new_readers_are_silent_where_there_is_nothing_to_read():
    """A program without the scopes or counters (the parent's), an untraced
    run, another family's model: every new reader answers ``None`` and does
    not raise."""
    bench = registry.load_benchmark()
    new = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(new) == 5
    config = registry.load_config("sdar30b_a3b_trunk")
    for driver in (types.SimpleNamespace(), types.SimpleNamespace(trunk_counters=lambda: {})):
        ctx = types.SimpleNamespace(
            trace=None, cell={"name": "small_cell"}, n_windows=2, config=config,
            per_window={"grad_steps": 10, "env_steps": 0}, device={"kind": "TPU v5 lite"},
            driver=driver,
        )
        for name in new:
            assert registry.load_layer_metric(name)(ctx) is None, name


def test_the_roofline_readers_divide_the_least_time_by_the_scopes_time(monkeypatch):
    """With 2 ms a step under the scan's scope the share is the least time
    over it; the time comes from the scope, whatever operations run there."""
    from benchmark.harness import trunk_read

    _, cell, config = registry.resolve(CELL)
    monkeypatch.setattr(
        trunk_read, "scope_us_per_step",
        lambda ctx, prefix: {"tac/trunk/ssm/scan": 2000.0, "tac/trunk/moe/experts/products": 3000.0}.get(prefix),
    )
    counters = {"trunk/held_assignments": 7040.0, "trunk/held_assignments_target": 7040.0}
    # XLA:TPU's own grouped-product kernels carry no scope of ours: 6 ms a step by name
    ctx = types.SimpleNamespace(
        trace={"busy_s": 5.0, "by_kind": {"ragged-dot-none": 20 * 6e-3}}, cell=cell, config=config, n_windows=2,
        per_window={"grad_steps": 10}, device={"kind": "TPU v5 lite"},
        driver=types.SimpleNamespace(model=config["model"], trunk_counters=lambda: counters),
    )
    scan = registry.load_layer_metric("trunk.ssm_scan_roofline")(ctx)
    assert scan == pytest.approx(100 * (20 * 38_010_880 / 819e9) / 2e-3)  # 46.4%
    experts = registry.load_layer_metric("trunk.latent_experts_roofline")(ctx)
    least = flops_hybrid.expert_bytes_per_step(config["model"], 7040, 7040) / 819e9
    assert experts == pytest.approx(100 * least / 9e-3) and 0 < experts < 100
    mfu = registry.load_layer_metric("trunk.hybrid_mfu")(ctx)
    per_step = flops_hybrid.flops_per_step(config["model"], 4, 7040, 7040)
    assert mfu == pytest.approx(100 * per_step * 20 / 5.0 / 197e12)  # 23.8% at 250 ms a step


def test_control_one_precision_lower_comes_out_not_correct():
    """The reference with float8 operands in the program's place misses a
    limit the sound program keeps, at the rehearsal's size."""
    _, cell, config = cut(CELL)
    values = control.readings(cell, config, 33, {"rehearsal": True}, 1, low="fp8_operands")
    numbers = ("loss_q.rel_gap", "loss_pi.gap_over_terms", "adam_nu.worst_leaf_gap",
               "param_change.worst_leaf_gap", "router_choices.disagree_share")
    limit = cell["limits"]["loss_q"]
    assert all(values[n] <= limit for n in numbers), values
    assert any(values["fp8_operands:" + n] > limit for n in numbers), values
