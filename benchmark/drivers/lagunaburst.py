"""Replay-fed learner on the ``laguna`` history trunk: ``trunkburst``'s
window, comparison and fifth number (the share of the first update's expert
choices on which program and reference disagree) with this family's own
spec, the SDAR family's seeded weights (``harness/trunk_weights.py``: every
leaf here is a projection, a norm's weight, a router or an expert kernel)
and reference: the shared SAC step of ``harness/reference_trunk.py`` over
this family's forward (``harness/reference_laguna_trunk.py::features``).

The program takes the stack from ``SACConfig.trunk_pattern`` and, by
attention kind, head counts, window and rotary from the ``trunk_*`` fields;
nothing here names a block.
"""

from __future__ import annotations

from collections.abc import Mapping

import jax

from benchmark.drivers import _common, trunkburst
from benchmark.harness import flops_laguna, reference_laguna_trunk, reference_trunk

TRUNK_KEYS = (
    "hidden", "pattern", "q_heads", "kv_heads", "head_dim", "experts", "experts_per_tok",
    "expert_width", "block_length", "rope_theta", "rms_eps", "q_hidden", "remat", "bf16_dots",
    "routed_scale", "shared_expert_width", "qk_norm", "head_gate", "dense_width", "window",
    "window_q_heads", "window_rope_theta", "rope_share", "rope_yarn_factor",
    "rope_yarn_positions",
)
# (the model's ``rope_yarn_beta_*`` and ``rope_attention_factor``, the published
# group's other numbers, are the reference's to read: the program has YaRN's
# own for them, the same)


NU = ("pi_nu", "q_nu")


def routers_apart(tree):
    """``tree`` (nested dicts of arrays) as two: without the routers' kernels
    (the leaves named ``router``), and those alone."""
    if not isinstance(tree, Mapping):
        return tree, None
    rest, routers = {}, {}
    for k, v in tree.items():
        if k == "router":
            routers[k] = v
            continue
        rest[k], r = routers_apart(v)
        if r:
            routers[k] = r
    return rest, routers


class Driver(trunkburst.Driver):
    def _compare(self, got: dict, ref: dict, got_choices):
        """``trunkburst``'s five numbers, with ``adam_nu.worst_leaf_gap`` taken
        over every leaf but the routers' kernels.  At this cell's batch of 2
        the norm of a router's ``nu`` is one token's gradient to the fourth
        power (the heads read the last step alone; the held experts' columns
        carry 99.8% of the leaf's squared norm and, where it read high, one
        of them 98-99.7%): where that token's ten choices differ in one held
        expert between program and reference, which bfloat16 rounding
        upstream decides, the leaf read 0.10-0.46 on 4 of 22 sound seeds, and
        up to 2.95 under float8: a coin, no measure
        (``README.laguna_s21_trunk.md``; ``PERF.md`` section 6, PR 45).  The
        routers' kernels stay in ``param_change.worst_leaf_gap``, where
        Adam's ``m / sqrt(nu)`` carries a fault of their ``nu`` at half its
        size; their own gaps are printed for the run's log (over the routers'
        own median norm, not the whole tree's)."""
        rest, routers = {}, {}
        for side, account in (("got", got), ("ref", ref)):
            pairs = [routers_apart(account[k]) for k in NU]
            rest[side] = dict(account, **{k: pair[0] for k, pair in zip(NU, pairs)})
            routers[side] = tuple(pair[1] for pair in pairs)
        print("routers' adam_nu, compared with nothing: %s" % trunkburst.worst_leaves(
            routers["got"], routers["ref"], top=4
        ), flush=True)
        return super()._compare(rest["got"], rest["ref"], got_choices)

    def sac_config(self):
        from torch_actor_critic_tpu.utils.config import SACConfig

        fields = dict(self.sac_fields)
        fields.update({"trunk_" + k: self.model[k] for k in TRUNK_KEYS})
        fields.update(
            trunk_experts_held=tuple(self.model["experts_held"]),
            history_len=self.model["history_len"], num_qs=self.model["num_qs"],
            trunk_report_choices=True, buffer_size=self.cell["traffic"]["ring_rows"],
        )
        fields.update(self.overrides.get("sac") or {})
        lacks = sorted(set(fields) - set(SACConfig.__dataclass_fields__))
        if lacks:  # a program from before this family: a clean refusal, no traceback
            raise SystemExit(f"benchmark: this program's SACConfig has no {lacks}")
        return SACConfig(**fields)

    @staticmethod
    def at_rest_bytes(cell: dict, config: dict) -> int:
        return flops_laguna.at_rest_bytes(config["model"], cell["traffic"]["ring_rows"])

    def _follow(self, mode: str) -> dict:
        """The reference's account of the first call at ``mode``.  Four copies
        of this trunk and a gradient leave the chip little room: the initial
        parameters are donated (each call places fresh copies of the host's)
        and only what is compared comes back (``hybridburst``'s way)."""
        if mode not in self._followed:
            sac = {k: self.sac_fields[k] for k in _common.SAC_CONSTANTS}

            def account(actor, critic, rows, eps_q, eps_pi):
                state, lq, lp, chosen, terms = reference_trunk.follow(
                    reference_trunk.init_state(actor, critic), rows, eps_q, eps_pi,
                    self.model, sac, mode, reference_laguna_trunk.features,
                )
                return {
                    "loss_q": lq, "loss_pi": lp, "actor": state["actor"],
                    "critic": state["critic"], "pi_nu": state["pi_nu"],
                    "q_nu": state["q_nu"], "choices": chosen, "pi_terms": terms,
                }

            # the CPU backend of a rehearsal takes no donation and says so
            donate = (0, 1) if jax.default_backend() == "tpu" else ()
            self._followed[mode] = jax.device_get(jax.jit(account, donate_argnums=donate)(
                self.actor0, self.critic0, self._rows, self.eps_q, self.eps_pi
            ))
        return self._followed[mode]
