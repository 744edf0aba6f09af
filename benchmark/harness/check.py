"""The comparison that decides ``correct``.

A driver hands over what the timed path's first call produced (its mean
losses, the parameters and Adam's first moments after it) and what that call
consumed (initial parameters, the rows and the noise of every update).  The
plain reference follows the same updates, and four numbers are compared, each
against a limit of its own from the cell's file:

- ``loss_q`` and ``loss_pi``: relative gap of the call's mean losses (for a
  population, the mean over its members too);
- ``adam_nu``: Adam's second moment after the call, the gradient as the
  optimizer got it (its square, averaged over the call's updates with nearly
  equal weights; the first moment weighs the last ten updates, where two
  sound trajectories have drifted furthest apart, and swings tenfold from
  seed to seed).  By the worst leaf: the gap between the program's norm and
  the reference's, over the reference's norm of that leaf or of the median
  leaf, whichever is larger;
- ``param_change``: the same worst-leaf gap for parameters after minus before.
"""

from __future__ import annotations

import dataclasses
import typing as t

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import reference


@dataclasses.dataclass
class Comparison:
    name: str
    value: float
    limit: float
    kind: str = "max"  # "max": value <= limit; "exact": value == limit

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value == self.limit if self.kind == "exact" else self.value <= self.limit

    def line(self) -> str:
        op = "==" if self.kind == "exact" else "<="
        return (
            f"check {self.name}: {self.value!r} {op} {self.limit!r} "
            f"{'ok' if self.ok else 'FAILED'}"
        )


def rel_gap(value, ref) -> float:
    value, ref = float(value), float(ref)
    return abs(value - ref) / max(abs(ref), 1e-12)


def worst_leaf_gap(tree, ref_tree, lead_axes: int = 0) -> float:
    """Largest over leaves (and over ``lead_axes`` leading member axes) of
    ``|norm(x) - norm(ref)| / max(norm(ref), median leaf norm(ref))``."""

    def norms(t_):
        out = []
        for leaf in jax.tree_util.tree_leaves(t_):
            a = np.asarray(leaf, np.float64)
            a = a.reshape(a.shape[:lead_axes] + (-1,))
            out.append(np.sqrt((a * a).sum(-1)))
        return np.stack(out, -1)  # (members..., leaves)

    n, r = norms(tree), norms(ref_tree)
    floor = np.median(r, axis=-1, keepdims=True)
    return float(np.max(np.abs(n - r) / np.maximum(np.maximum(r, floor), 1e-30)))


def tree_sub(a, b):
    return jax.tree_util.tree_map(lambda x, y: np.asarray(x) - np.asarray(y), a, b)


def visible_rows(pre_rows, pushed, idx, ptr0, capacity: int, n_visible):
    """The rows a step samples: ``pre_rows`` (gathered from the ring as it
    was filled, at ``idx``) except where ``idx`` falls on one of the first
    ``n_visible[step]`` rows pushed since, which start at ``ptr0``.

    ``idx``: ``(steps, batch)``; ``pre_rows`` leaves ``(steps, batch, ...)``;
    ``pushed`` leaves ``(n_pushed, ...)``; ``n_visible``: ``(steps,)``."""
    offset = (idx - ptr0) % capacity
    fresh = offset < n_visible[:, None]
    n_pushed = jax.tree_util.tree_leaves(pushed)[0].shape[0]
    take = jnp.clip(offset, 0, n_pushed - 1)

    def pick(old, new):
        new_rows = jnp.take(new, take, axis=0)
        mask = fresh.reshape(fresh.shape + (1,) * (old.ndim - 2))
        return jnp.where(mask, new_rows.astype(old.dtype), old)

    return jax.tree_util.tree_map(pick, pre_rows, pushed)


def follow(
    *, model: dict, sac: dict, mode: str, actor0, critic0, batches, eps_q,
    eps_pi, members: bool,
) -> dict:
    """The reference's account of one call: its mean losses, parameters and
    Adam's first moments after the recorded updates, as host arrays.

    ``batches``/``eps_*``: ``(steps, D, batch, ...)``, with a leading member
    axis before ``steps`` when ``members``."""
    run = lambda a, c, b, eq, ep: reference.follow(  # noqa: E731
        reference.init_state(a, c), b, eq, ep, model, sac, mode
    )
    if members:
        run = jax.vmap(run)
    state, lq, lp = jax.device_get(jax.jit(run)(actor0, critic0, batches, eps_q, eps_pi))
    return {
        "loss_q": lq, "loss_pi": lp, "actor": state["actor"], "critic": state["critic"],
        "pi_nu": state["pi_nu"], "q_nu": state["q_nu"],
    }


def compare(got: dict, ref: dict, actor0, critic0, limits: dict, members: bool) -> t.List[Comparison]:
    """``got`` (the program's account of the call, or a control's) against the
    reference's."""
    lead = 1 if members else 0
    a0, c0 = jax.device_get((actor0, critic0))
    change = worst_leaf_gap(
        (tree_sub(got["actor"], a0), tree_sub(got["critic"], c0)),
        (tree_sub(ref["actor"], a0), tree_sub(ref["critic"], c0)),
        lead,
    )
    nu = worst_leaf_gap((got["pi_nu"], got["q_nu"]), (ref["pi_nu"], ref["q_nu"]), lead)
    # A population's loss is the mean over its members (what the Trainer
    # reports): a member's own drift after hundreds of updates has either
    # sign and averages out, a lower precision's does not.
    lq = rel_gap(np.mean(got["loss_q"]), np.mean(ref["loss_q"]))
    lp = rel_gap(np.mean(got["loss_pi"]), np.mean(ref["loss_pi"]))
    return [
        Comparison("loss_q.rel_gap", lq, limits["loss_q"]),
        Comparison("loss_pi.rel_gap", lp, limits["loss_pi"]),
        Comparison("adam_nu.worst_leaf_gap", nu, limits["adam_nu"]),
        Comparison("param_change.worst_leaf_gap", change, limits["param_change"]),
    ]
