"""The chip's compiler, asked in the sandbox (``test_chip_compile.py`` says
how): the flash kernels at the trunk cells' shapes, each a few seconds, and
the one burst compiled at a cell's own size, ``nemotron3_super_trunk_burst``'s
(80-115 s alone, 165 s beside five other files: the longest test of the
suite, which is why the file's other cases are short)."""

import os

import jax
import jax.numpy as jnp
import pytest

from chip_compile_helpers import (  # noqa: F401  (``v5e`` and ``chip_compiler`` are fixtures)
    _expert_layer_rows,
    _kernel_kind,
    _kernels,
    _plan_sorts_the_held_candidates,
    _reads_as_attention,
    _selection_is_a_pass,
    _shape,
    _weight_gradients_stand_alone,
    chip_compiler,
    v5e,
)

from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
from torch_actor_critic_tpu.core.types import BufferState
from torch_actor_critic_tpu.ops import moe
from torch_actor_critic_tpu.ops.attention import flash_attention
from torch_actor_critic_tpu.parallel import DataParallelSAC, make_mesh

def _benchmark_on_path():
    """``benchmark`` is a package of the repo's root, not of ``tests/``."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)


def _flash_grouped(devices, block_length):
    """The three flash kernels at the SDAR trunk's shapes: 32 query heads
    over 4 shared key/value heads of 128 (read through the index maps),
    the block-causal mask, float32 tiles with bfloat16 products."""
    q = _shape((8, 32, 1024, 128), jnp.float32, devices[0])
    kv = _shape((8, 4, 1024, 128), jnp.float32, devices[0])

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, None, None, False, 128, block_length, True)

    grads = jax.grad(lambda q, k, v: fwd(q, k, v).sum(), (0, 1, 2))
    for fn, n_kernels in ((fwd, 1), (grads, 3)):
        compiled = jax.jit(fn).lower(q, kv, kv).compile()
        assert compiled.as_text().count("tpu_custom_call") == n_kernels
    assert [x.shape for x in jax.eval_shape(grads, q, kv, kv)] == [
        q.shape, kv.shape, kv.shape
    ]


def _flash_window(devices):
    """The three flash kernels at a sliding layer's shapes in
    ``laguna_s21_trunk_burst``: 18 query heads over 2 shared key/value heads
    of 128, histories of 4,096, a window of 512: the grids run over the 15
    tile pairs a head's schedule holds (its tables go in by scalar prefetch)
    where the causal one holds 36, and the forward kernel keeps
    the kind the benchmark's flash readers find it by (inside the cell's
    burst all 25 kernels of the five layers read so: the sandbox compile at
    size, PERF.md section 4)."""
    q = _shape((2, 18, 4096, 128), jnp.float32, devices[0])
    kv = _shape((2, 2, 4096, 128), jnp.float32, devices[0])

    def fwd(q, k, v):
        # a kernel is named for the scope that calls it: the layer's module, `attention`
        with jax.named_scope("attention"):
            return flash_attention(q, k, v, True, None, None, False, 128, 1, True, 512)

    grads = jax.grad(lambda q, k, v: fwd(q, k, v).sum(), (0, 1, 2))
    for fn, n_kernels in ((fwd, 1), (grads, 3)):
        text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
        kinds = [_kernel_kind(name) for name in _kernels(text)]
        assert len(kinds) == n_kernels and (fn is grads or _reads_as_attention(kinds[0])), kinds
    assert [x.shape for x in jax.eval_shape(grads, q, kv, kv)] == [
        q.shape, kv.shape, kv.shape
    ]


def _tiles_by_rule(devices):
    """The tile each trunk cell's attention layers get where the caller names
    none: 512, the largest that divides the history, under every mask
    (``_AUTO_BLOCK_CAP`` has the chip's readings of 256 and 128 under a
    window and under the block-causal mask: each lost)."""
    _benchmark_on_path()
    from benchmark.harness import registry
    from torch_actor_critic_tpu.ops.attention import _check_blocks, visited_key_blocks

    got = {}
    for name in ("sdar30b_a3b_trunk", "nemotron3_super_trunk", "laguna_s21_trunk"):
        model = registry.load_config(name)["model"]
        t, b = model["history_len"], model.get("block_length", 1)
        assert _check_blocks(t, t, None, None) == (512, 512), name
        for window in {None, model.get("window")}:
            got[name, window] = visited_key_blocks(t, block_length=b, window=window)
    # the tile pairs of a head's sweep, where the rectangular grids had 4, 4, 64 and 16 steps
    assert got == {
        ("sdar30b_a3b_trunk", None): 3,
        ("nemotron3_super_trunk", None): 3,
        ("laguna_s21_trunk", None): 36,
        ("laguna_s21_trunk", 512): 15,
    }


def _compile_hybrid_trunk_burst(devices):
    """The ``nemotron_h`` trunk's burst as the benchmark's cell builds it, at
    the cell's own sizes, compiled for the described v5e: ``(cell's
    configuration, abstract state, compiled burst)``."""
    _benchmark_on_path()
    from benchmark.drivers import trunkburst
    from benchmark.harness import registry, spans
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    _, cell, config = registry.resolve("nemotron3_super_trunk_burst")
    driver = registry.load_driver(cell["driver"])(
        cell, config, 1, spans.Spans(), {"rehearsal": False}
    )
    cfg, env = driver.sac_config(), trunkburst.Spec(driver.model)
    sac = make_learner(cfg, *build_models(cfg, env), env.act_dim)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=devices[:1]))
    state = jax.eval_shape(sac.init_state, jax.random.key(0), env.example_obs())

    def ring_of(n):
        one = jax.eval_shape(lambda: init_replay_buffer(n, env.obs_spec, env.act_dim).data)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), one
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(data=ring_of(cell["traffic"]["ring_rows"]), ptr=index, size=index)
    chunk = ring_of(cfg.update_every)
    compiled = learner._build_burst(cfg.update_every, state, ring, chunk).lower(
        state, ring, chunk
    ).compile()
    return cfg, state, compiled


def _hybrid_trunk_burst(devices):
    """The ``nemotron_h`` trunk's burst as the benchmark's cell builds it, at
    the cell's own sizes (one period of eleven layers at the published widths,
    this chip's share of heads and experts, 1024 x batch 4, every block
    recomputed): the chunked scan, the flash kernels without q's pass, the
    two-kernel grouped products and the router's top 22 of 512 lower for the
    v5e, and the compiler's account of the step fits the chip."""
    cfg, state, compiled = _compile_hybrid_trunk_burst(devices)
    assert (cfg.trunk_pattern, cfg.trunk_hidden, cfg.trunk_remat) == ("EMEMEMEMEM*", 4096, 11)
    mem = compiled.memory_analysis()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.critic_params))
    assert 566e6 < n_params < 570e6
    # trunk, target and Adam's moments rest in the arguments and are updated in
    # place; the whole step fits the chip's 15.75 GiB as it did before the
    # selection's kernels (read at PR 40: 13.42 GB; at PR 41: see PERF.md)
    assert mem.alias_size_in_bytes >= 16 * n_params
    assert mem.peak_memory_in_bytes < 13.43e9, mem.peak_memory_in_bytes
    text = compiled.as_text()
    kinds = [_kernel_kind(name) for name in _kernels(text)]
    assert "ragged-dot" in text and "qk-rope" not in kinds and len(kinds) >= 3, kinds
    # the expert layer still moves a piece at a time
    for op in ("gather", "scatter"):
        moved = _expert_layer_rows(text, op)
        assert moved and max(moved) <= moe.PIECE_ROWS, (op, moved)
    _selection_is_a_pass(
        text, cfg.batch_size * cfg.history_len, cfg.trunk_experts_per_tok, cfg.trunk_experts
    )
    lo, hi = cfg.trunk_experts_held
    _plan_sorts_the_held_candidates(
        text, cfg.batch_size * cfg.history_len, cfg.trunk_experts_per_tok, hi - lo
    )
    # ISSUE 46, by the rule as it stands: the shared expert's and the
    # state-space projections' weight gradients are products of their own;
    # ``k_proj`` / ``v_proj`` (4096 x 128) keep Adam fused behind theirs
    inner = cfg.trunk_ssm_heads * cfg.trunk_ssm_head_dim
    _weight_gradients_stand_alone(
        text,
        taken=[(cfg.trunk_hidden, cfg.trunk_shared_expert_width), (inner, cfg.trunk_hidden)],
        left=[(cfg.trunk_hidden, cfg.trunk_kv_heads * cfg.trunk_head_dim)],
    )


CASES = [
    pytest.param(_flash_grouped, (1,), id="flash-grouped-causal"),
    pytest.param(_flash_grouped, (4,), id="flash-grouped-block4"),
    pytest.param(_flash_window, (), id="flash-window-512-of-4096"),
    pytest.param(_tiles_by_rule, (), id="flash-tiles-by-rule"),
    pytest.param(_hybrid_trunk_burst, (), id="hybrid-trunk-burst-at-size"),
]


@pytest.mark.parametrize("compile_case, args", CASES)
def test_compiles_for_v5e(v5e, compile_case, args):
    compile_case(v5e, *args)
