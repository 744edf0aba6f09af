"""Replay-fed learner on the SDAR history trunk: ``burst.Driver``'s window
(stage, place, one ``DataParallelSAC.update_burst``, drain) over a ring of
observation histories, with this family's own spec, seeded weights
(``harness/trunk_weights.py``), reference (``harness/reference_trunk.py``)
and comparison.

The comparison is ``check.compare``'s four numbers against this family's
reference, with the policy loss's gap taken over the size of the loss's two
terms and not over the loss (``alpha logp - min Q`` is a difference of like
terms and crosses zero from seed to seed: two of fourteen sound seeds read a
relative gap of 0.06 and 0.15 with every other number as usual; my chip run,
PR 26), and one more: the share of the first update's expert assignments on
which program and reference disagree.  The router's 8 largest of 128 is a
discrete choice; both sides compute the router's product at ``highest``
precision, so a choice flips only on what rounding did upstream of it.  The
program returns its choices with the burst's metrics
(``SACConfig.trunk_report_choices``); the reference makes its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import _common, burst
from benchmark.harness import check, data, reference_trunk, trunk_weights

TRUNK_KEYS = (
    "hidden", "q_heads", "kv_heads", "head_dim", "layers", "experts",
    "experts_per_tok", "expert_width", "block_length", "rope_theta", "rms_eps",
    "q_hidden", "remat", "bf16_dots",
)
NOT_THE_LEARNERS = ("population", "pbt_every")  # what a rehearsal's cut adds to `sac`


def model_of(config: dict, rehearsal: bool) -> tuple[dict, dict]:
    """The configuration's model and learner constants; at a CPU rehearsal
    the file's ``rehearsal_cut`` sizes replace the published ones."""
    model = dict(config["model"])
    cut = model.pop("rehearsal_cut")
    sac = {k: v for k, v in config["sac"].items() if k not in NOT_THE_LEARNERS}
    if rehearsal:
        model.update({k: v for k, v in cut.items() if k not in ("why", "sac")})
        sac.update(cut["sac"])
    return model, sac


class Spec:
    """The attributes ``build_models`` reads off an env pool."""

    def __init__(self, model: dict):
        self.act_dim, self.act_limit = model["act_dim"], model["act_limit"]
        self.obs_spec = jax.ShapeDtypeStruct(
            (model["history_len"], model["obs_dim"]), jnp.float32
        )

    def example_obs(self):
        return jnp.zeros(self.obs_spec.shape, self.obs_spec.dtype)


def disagree_share(a, b) -> float:
    """Share of ``a``'s assignments ``(..., tokens, top_k)`` whose expert is
    not among ``b``'s for the same token."""
    a, b = np.asarray(a).astype(np.int64), np.asarray(b).astype(np.int64)
    a, b = a.reshape((-1,) + a.shape[-2:]), b.reshape((-1,) + b.shape[-2:])
    found = (a[..., :, None] == b[..., None, :]).any(-1)
    return float(1.0 - found.mean())


def worst_leaves(tree, ref_tree, top: int = 3) -> list:
    """The leaves behind ``check.worst_leaf_gap``, by name, for the run's log:
    ``|norm - norm_ref| / max(norm_ref, median leaf norm_ref)``, largest first."""
    flat, _ = jax.tree_util.tree_flatten_with_path(ref_tree)
    norm = lambda x: float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))  # noqa: E731
    ref = [norm(leaf) for _, leaf in flat]
    got = [norm(leaf) for leaf in jax.tree_util.tree_leaves(tree)]
    floor = float(np.median(ref))
    gaps = [
        (abs(g - r) / max(r, floor, 1e-30), jax.tree_util.keystr(path))
        for g, r, (path, _) in zip(got, ref, flat)
    ]
    return [(name, round(gap, 5)) for gap, name in sorted(gaps, reverse=True)[:top]]


class Driver(burst.Driver):
    def __init__(self, cell, config, seed, spans, overrides=None):
        super().__init__(cell, config, seed, spans, overrides)
        # ``overrides["rehearsal"]``, as ``main.run_cell`` hands it, says whether
        # this is a CPU rehearsal; where nobody says, it is the real thing.
        self.rehearsal = bool(self.overrides.get("rehearsal", False))
        self.model, self.sac_fields = model_of(config, self.rehearsal)

    # ------------------------------------------------------------ set-up
    def sac_config(self):
        from torch_actor_critic_tpu.utils.config import SACConfig

        fields = dict(self.sac_fields)
        fields.update({"trunk_" + k: self.model[k] for k in TRUNK_KEYS})
        fields.update(
            trunk_block="sdar_moe", trunk_experts_held=tuple(self.model["experts_held"]),
            history_len=self.model["history_len"], num_qs=self.model["num_qs"],
            trunk_report_choices=True, buffer_size=self.cell["traffic"]["ring_rows"],
        )
        fields.update(self.overrides.get("sac") or {})
        return SACConfig(**fields)

    def learner_config(self):
        return self.sac_config(), Spec(self.model)

    def seeded_params(self, env):
        return trunk_weights.seeded_params(
            self.sac, env.example_obs(), data.state_key(self.seed, 1)
        )

    @staticmethod
    def at_rest_bytes(cell: dict, config: dict) -> int:
        from benchmark.harness import flops_trunk

        return flops_trunk.at_rest_bytes(config["model"], cell["traffic"]["ring_rows"])

    def _window(self, i: int):
        m = super()._window(i)
        if i == 0:  # the call the reference follows
            self.first_choices = jax.device_get(m["trunk/choices_first"])
        self.counters = {k: v for k, v in m.items() if k.startswith("trunk/") and v.ndim == 0}
        return m

    def trunk_counters(self) -> dict:
        """The last window's expert-layer counters (means over its updates)."""
        return {k: float(v) for k, v in jax.device_get(self.counters).items()}

    # ------------------------------------------------------------- check
    def _follow(self, mode: str) -> dict:
        """The reference's account of the first call at ``mode``."""
        if mode not in self._followed:
            sac = {k: self.sac_fields[k] for k in _common.SAC_CONSTANTS}
            run = jax.jit(lambda a, c, b, eq, ep: reference_trunk.follow(
                reference_trunk.init_state(a, c), b, eq, ep, self.model, sac, mode
            ))
            state, lq, lp, chosen, terms = jax.device_get(
                run(self.actor0, self.critic0, self._rows, self.eps_q, self.eps_pi)
            )
            self._followed[mode] = {
                "loss_q": lq, "loss_pi": lp, "actor": state["actor"],
                "critic": state["critic"], "pi_nu": state["pi_nu"],
                "q_nu": state["q_nu"], "choices": chosen, "pi_terms": terms,
            }
        return self._followed[mode]

    def _compare(self, got: dict, ref: dict, got_choices):
        # At a rehearsal every limit is the cut's.
        limit = (
            max(self.cell["limits"].values()) if self.rehearsal
            else self.cell["traffic"]["router_disagree_limit"]
        )
        print("trunk worst leaves, adam_nu: %s; param_change: %s" % (
            worst_leaves((got["pi_nu"], got["q_nu"]), (ref["pi_nu"], ref["q_nu"])),
            worst_leaves(
                (check.tree_sub(got["actor"], self.actor0), check.tree_sub(got["critic"], self.critic0)),
                (check.tree_sub(ref["actor"], self.actor0), check.tree_sub(ref["critic"], self.critic0)),
            ),
        ), flush=True)
        print("trunk losses: loss_q %r / %r, loss_pi %r / %r over terms of %r" % (
            float(got["loss_q"]), float(ref["loss_q"]), float(got["loss_pi"]),
            float(ref["loss_pi"]), float(ref["pi_terms"]),
        ), flush=True)
        four = check.compare(
            got, ref, self.actor0, self.critic0, self.cell["limits"], False
        )
        four[1] = check.Comparison(
            "loss_pi.gap_over_terms",
            abs(float(got["loss_pi"]) - float(ref["loss_pi"])) / float(ref["pi_terms"]),
            self.cell["limits"]["loss_pi"],
        )
        return four + [check.Comparison(
            "router_choices.disagree_share",
            disagree_share(got_choices, ref["choices"]), limit,
        )]

    def check(self, mode: str = "highest"):
        out = [
            check.Comparison("losses.non_finite", 0.0 if self.finite else 1.0, 0.0, "exact")
        ] + self.counter_checks(
            self.final["step"], self.final["ptr"], self.calls, self.n_updates,
            self.window_rows, self.cap,
        )
        self._rows, self._followed = self.rows_of_first_call(), {}
        return out + self._compare(self.first, self._follow(mode), self.first_choices)

    def control(self, low: str, mode: str):
        """The reference in the program's place one precision lower, against
        the reference at ``mode``.  After ``check``."""
        got = self._follow(low)
        return self._compare(got, self._follow(mode), got["choices"])
