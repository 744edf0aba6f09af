"""The chip's compiler, asked in the sandbox (on-chip-measurement §2.3).

libtpu compiles for a TPU that is described and not attached, so the
kernels and the step programs of the main path are compiled here for a
``v5e:2x2`` host at their real shapes: what Mosaic or XLA:TPU would
refuse on the chip is refused here, at no chip time. Nothing runs —
a compile that passes says nothing about results or speed;
``chip_smoke.py`` is where these programs execute and are compared.

This file: the Pallas kernels by themselves and the reference
configuration's burst, ring push included. The population's and the visual
cell's programs are ``test_chip_compile_population.py``'s, the SDAR trunk's
cut burst and its attention layer ``test_chip_compile_trunk.py``'s, the trunk
cells' kernels and the one burst compiled at a cell's size
``test_chip_compile_hybrid.py``'s; ``chip_compile_helpers.py`` holds what
they share.

One parametrised test a file, skipped where the topology cannot be
described. The persistent compilation cache is off around it: such a compile
can be written to the cache but not read back without a chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile_helpers import (  # noqa: F401  (``v5e`` and ``chip_compiler`` are fixtures)
    ACT_DIM,
    OBS_DIM,
    _chunk_of,
    _on,
    _ring_scatters,
    _shape,
    chip_compiler,
    v5e,
)

from torch_actor_critic_tpu.buffer.replay import (
    init_replay_buffer,
    init_visual_replay_buffer,
    nbytes,
    push,
)
from torch_actor_critic_tpu.core.types import Batch, BufferState
from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.ops import pixels
from torch_actor_critic_tpu.ops.attention import flash_attention
from torch_actor_critic_tpu.parallel import DataParallelSAC, make_mesh
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.utils.config import SACConfig

WALL_RUNNER_RING = (20000, 64, 64, 3)


def _flash(devices, shape, dtype, pad_lanes=128):
    """Forward and ``jax.grad`` of the flash kernels: 1 + 3 custom calls
    (fwd; fwd-with-lse + dQ + dK/dV)."""
    x = _shape(shape, dtype, devices[0])

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, None, None, False, pad_lanes)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    for fn, n_kernels in ((fwd, 1), (jax.grad(loss, (0, 1, 2)), 3)):
        text = jax.jit(fn).lower(x, x, x).compile().as_text()
        assert text.count("tpu_custom_call") == n_kernels


def _pixel(devices, batch, dtype, shift, frame_stack=1):
    dev = devices[0]
    offsets = _shape((batch, 2), jnp.int32, dev)

    def gather(ring, idx, offs):
        return pixels.fused_frame_gather(
            ring, idx, offs if shift else None, normalize=True,
            out_dtype=dtype, frame_stack=frame_stack, impl="pallas",
        )

    compiled = jax.jit(gather).lower(
        _shape(WALL_RUNNER_RING, jnp.uint8, dev),
        _shape((batch,), jnp.int32, dev), offsets,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _push_in_place(devices, make_ring, members, rows):
    """``jit(vmap(push), donate_argnums=0)`` alone passes over no ring
    leaf: a relayout of one reads and writes 200% of its bytes (the
    scatter this replaced: 500-700% accessed, 94-143% temp, PERF.md PR
    25), the contiguous write touches the two windows' tiles, whatever
    the ring's size."""
    ring = jax.eval_shape(
        lambda: jax.vmap(lambda _: make_ring())(jnp.arange(members))
    )
    compiled = jax.jit(jax.vmap(push), donate_argnums=0).lower(
        *_on(devices[0], (ring, _chunk_of(ring, rows)))
    ).compile()
    ring_bytes = nbytes(ring.data)
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= ring_bytes, "updated in place"
    assert mem.temp_size_in_bytes < 0.01 * ring_bytes
    assert cost["bytes accessed"] < 0.05 * ring_bytes
    text = compiled.as_text()
    assert " scatter(" not in text and " gather(" not in text


def _reference_burst(devices, dp):
    """The main path's program at the reference configuration: 50
    update steps, batch 64, (256, 256), a 1,000,000-slot ring, state
    and ring donated — through the same ``DataParallelSAC`` builder the
    trainer uses, on a mesh of described chips."""
    cfg = SACConfig()
    assert (cfg.hidden_sizes, cfg.batch_size, cfg.update_every) == (
        (256, 256), 64, 50
    )
    assert cfg.buffer_size == 1_000_000
    sac = SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=cfg.hidden_sizes),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        ACT_DIM,
    )
    learner = DataParallelSAC(sac, make_mesh(dp=dp, devices=devices[:dp]))
    state = jax.eval_shape(
        sac.init_state, jax.random.key(0), jnp.zeros((OBS_DIM,))
    )

    def rows(n):
        f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
        return Batch(
            states=f32((dp, n, OBS_DIM)), actions=f32((dp, n, ACT_DIM)),
            rewards=f32((dp, n)), next_states=f32((dp, n, OBS_DIM)),
            done=f32((dp, n)),
        )

    ring = BufferState(
        data=rows(cfg.buffer_size // dp),
        ptr=jax.ShapeDtypeStruct((dp,), jnp.int32),
        size=jax.ShapeDtypeStruct((dp,), jnp.int32),
    )
    chunk = rows(cfg.update_every)
    compiled = learner._build_burst(
        cfg.update_every, state, ring, chunk
    ).lower(state, ring, chunk).compile()
    mem = compiled.memory_analysis()
    ring_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(ring)
    )
    # Donation holds: the ring (and the state) come back in the buffers
    # they arrived in, so a device keeps one copy of its shard.
    assert mem.alias_size_in_bytes >= ring_bytes // dp
    text = compiled.as_text()
    assert ("all-reduce" in text) == (dp > 1), "pmean over dp"
    assert _ring_scatters(text, cfg.buffer_size // dp) == []


CASES = [
    pytest.param(_flash, (shape, dtype), id=f"flash-{name}-{dtype.__name__}")
    for name, shape in (("2k", (4, 8, 2048, 64)), ("seq", (64, 4, 8, 16)))
    for dtype in (jnp.float32, jnp.bfloat16)
] + [
    pytest.param(
        _flash, ((4, 8, 2048, 64), jnp.bfloat16, 64), id="flash-2k-lanes64"
    ),
] + [
    pytest.param(
        _pixel, (batch, dtype, shift),
        id=f"pixel-b{batch}-{dtype.__name__}-{'shift' if shift else 'plain'}",
    )
    for batch in (32, 512)
    for dtype in (jnp.float32, jnp.bfloat16)
    for shift in (False, True)
] + [
    pytest.param(_pixel, (32, jnp.bfloat16, True, 3), id="pixel-stack3"),
    pytest.param(_reference_burst, (1,), id="update-burst"),
    pytest.param(_reference_burst, (4,), id="dp4-burst"),
    pytest.param(
        _push_in_place,
        (
            functools.partial(
                init_replay_buffer, 1_000_000,
                jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM,
            ),
            32, 800,
        ),
        id="push-pop32-1M-rows",
    ),
    pytest.param(
        _push_in_place,
        (
            functools.partial(
                init_visual_replay_buffer, 200_000, 168,
                WALL_RUNNER_RING[1:], 56,
            ),
            1, 50,
        ),
        id="push-frames-200k-rows",
    ),
]


@pytest.mark.parametrize("compile_case, args", CASES)
def test_compiles_for_v5e(v5e, compile_case, args):
    compile_case(v5e, *args)
