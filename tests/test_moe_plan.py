"""The expert layer's plan (``ops/moe.py::plan_assignments``) against the form
it replaced, kept here as the oracle: one key a choice, a stable ``argsort``.

Integers only: no trunk, no model, nothing compiled for a chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.ops import moe


def plan_by_stable_argsort(top_e, held):
    """``plan_assignments`` as it was: every choice's key (its held expert,
    or ``n_held`` for one held elsewhere) under a stable sort."""
    lo, hi = held
    n_held = hi - lo
    flat = top_e.reshape(-1).astype(jnp.int32) - lo
    is_held = (flat >= 0) & (flat < n_held)
    key = jnp.where(is_held, flat, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32
    )
    return moe.Plan(
        order=order, held=is_held.reshape(top_e.shape), starts=jnp.cumsum(sizes) - sizes,
        sizes=sizes, n_rows=jnp.sum(sizes),
    )


def routed(seed, n, k, num_experts):
    """Choices as ``route`` hands them over: ``k`` distinct experts a token,
    in no order of index."""
    scores = np.random.default_rng(seed).random((n, num_experts))
    return np.argsort(-scores, axis=1)[:, :k].astype(np.int32)


def first_choice(top_e, expert):
    """Every token's first choice ``expert``, its other choices distinct
    still (where a token chose ``expert`` later, the two change places)."""
    later = np.where(top_e == expert, top_e[:, :1], top_e)
    later[:, 0] = expert
    return later


def elsewhere_twice(top_e, held):
    """The benchmark test's input (``test_bench_trunk.py``): the choices of
    expert ``lo`` turned into ``hi``, held elsewhere, and one more of them a
    token, so that ``hi`` stands twice in a token."""
    lo, hi = held
    twice = np.where(top_e == lo, hi, top_e)
    twice[:, -1] = hi
    return twice


def both_plans(top_e, held):
    """(the plan, the oracle's), as one program: a case compiles once."""
    return jax.jit(
        lambda e: (moe.plan_assignments(e, held), plan_by_stable_argsort(e, held))
    )(jnp.asarray(top_e))


# name: (choices, held)
CASES = {
    "hybrid-cell-4096x22-of-512-held-8": lambda: (routed(1, 4096, 22, 512), (0, 8)),
    "sdar-cell-8192x8-of-128-held-16": lambda: (routed(2, 8192, 8, 128), (0, 16)),
    "as-many-held-as-chosen": lambda: (routed(3, 300, 4, 16), (4, 8)),
    "nobody-held": lambda: (routed(4, 200, 4, 16) % 8, (8, 12)),
    "nobody-held-fewer-held-than-chosen": lambda: (routed(5, 200, 6, 32) % 16, (16, 19)),
    "first-choice-one-held-expert": lambda: (first_choice(routed(6, 96, 4, 16), 5), (4, 8)),
    "first-choice-one-held-expert-fewer-held-than-chosen": lambda: (
        first_choice(routed(7, 257, 6, 32), 9), (8, 11)
    ),
    "everything-held": lambda: (routed(8, 130, 4, 16), (0, 16)),
    "everything-held-one-choice-a-token": lambda: (routed(9, 77, 1, 4), (0, 4)),
    "held-range-last": lambda: (routed(10, 500, 6, 32), (29, 32)),
    "held-range-last-more-held-than-chosen": lambda: (routed(11, 500, 2, 32), (24, 32)),
    "held-elsewhere-twice-a-token": lambda: (
        elsewhere_twice(routed(12, 128, 8, 128), (0, 16)), (0, 16)
    ),
    "held-elsewhere-twice-a-token-fewer-held-than-chosen": lambda: (
        elsewhere_twice(routed(13, 128, 8, 32), (4, 8)), (4, 8)
    ),
    "one-held-expert": lambda: (routed(14, 64, 3, 8), (2, 3)),
}


def held_to_the_oracle(got, want, top_e, held):
    n, k = top_e.shape
    n_rows = int(want.n_rows)
    for name in ("held", "starts", "sizes", "n_rows"):
        np.testing.assert_array_equal(getattr(got, name), getattr(want, name), err_msg=name)
        assert getattr(got, name).dtype == getattr(want, name).dtype, name
    order = np.asarray(got.order)
    assert order.dtype == np.int32 and order.ndim == 1
    # as long as the most rows a call can hold, never longer than before
    assert n * min(k, held[1] - held[0]) <= order.shape[0] <= n * k
    np.testing.assert_array_equal(order[:n_rows], np.asarray(want.order)[:n_rows])
    assert ((order >= 0) & (order < n * k)).all()


@pytest.mark.parametrize("case", CASES)
def test_the_plan_is_the_stable_sorts_over_the_held_rows(case):
    top_e, held = CASES[case]()
    got, want = both_plans(top_e, held)
    held_to_the_oracle(got, want, top_e, held)
    if case == "everything-held":
        assert int(want.n_rows) == top_e.size
    if case.startswith("nobody-held"):
        assert int(want.n_rows) == 0
    if case.startswith("first-choice"):
        assert int(want.sizes.max()) == top_e.shape[0]


@pytest.mark.parametrize("mapped", [1, 3])
@pytest.mark.parametrize("held", [(4, 8), (5, 7)], ids=["held-4-of-4-chosen", "held-2-of-4-chosen"])
def test_the_plan_under_jit_and_vmap(held, mapped):
    """The burst maps the update over its device axis: the plan of each mapped
    element is its own."""
    top_e = np.stack([routed(20 + i, 96, 4, 16) for i in range(mapped)])
    plans = jax.jit(jax.vmap(lambda e: moe.plan_assignments(e, held)))(top_e)
    for i in range(mapped):
        got = jax.tree_util.tree_map(lambda x: x[i], plans)
        held_to_the_oracle(got, both_plans(top_e[i], held)[1], top_e[i], held)


def test_a_second_choice_of_one_held_expert_by_one_token_gets_no_row():
    """What the docstrings warn of, where fewer experts are held than a token
    chooses: ``route`` never hands this over; a caller that does gets one row
    for the token and the expert, the later choice's, and every index in
    bounds."""
    top_e = jnp.asarray([[5, 9, 5], [4, 5, 9], [9, 9, 5]], jnp.int32)
    plan = moe.plan_assignments(top_e, (4, 6))
    np.testing.assert_array_equal(plan.sizes, [1, 3])
    np.testing.assert_array_equal(plan.order[: int(plan.n_rows)], [3, 2, 4, 8])
    assert ((plan.order >= 0) & (plan.order < top_e.size)).all()


def test_a_key_that_does_not_fit_the_word_is_refused_when_traced():
    """(n_held + 1) * N * K past an int32: refused, not wrapped. Nothing runs:
    the shapes alone are traced."""
    top_e = jax.ShapeDtypeStruct((1 << 20, 64), jnp.int32)
    with pytest.raises(ValueError, match="does not fit"):
        jax.eval_shape(lambda e: moe.plan_assignments(e, (0, 32)), top_e)
    fits = jax.eval_shape(lambda e: moe.plan_assignments(e, (0, 30)), top_e)
    assert fits.order.shape == ((1 << 20) * 30,)
