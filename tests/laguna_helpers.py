"""What the ``laguna`` trunk's two test files share (``test_laguna_trunk.py``,
``test_laguna_trunk_stack.py``): the uncut layer at a small size and a chip's
share of it, inputs of its width, and what the reference reads of the
published YaRN group beside the program's fields. A plain module,
imported by name."""

import math

import jax

HIDDEN, T, BATCH = 32, 12, 2
# The uncut layer at a small size: 8 query heads on a full layer and 12 on a
# sliding one over 4 key/value heads, a window of 5 in a history of 12, 32
# experts of which a token takes 4 beside a shared expert.
WHOLE = dict(
    hidden=HIDDEN, q_heads=8, window_q_heads=12, kv_heads=4, head_dim=8, window=5,
    rope_theta=5e5, window_rope_theta=1e4, rope_share=0.5, rope_yarn_factor=8.0,
    rope_yarn_positions=8, qk_norm=False, head_gate=True, dense_width=48,
    experts=32, experts_per_tok=4, expert_width=12, experts_held=(0, 32),
    routed_scale=2.5, shared_expert_width=12, block_length=1, rms_eps=1e-6, bf16_dots=False,
)
SHARE = dict(q_heads=2, window_q_heads=3, kv_heads=1, experts_held=(8, 16))
YARN = dict(  # what the reference reads of the published group beside the program's fields
    rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0, rope_attention_factor=0.1 * math.log(8.0) + 1,
)


def _inputs(seed=1, batch=BATCH, t=T):
    return jax.random.normal(jax.random.key(seed), (batch, t, HIDDEN))
