"""Replay-fed learner on the ``nemotron_h`` history trunk: ``trunkburst``'s
window, comparison and fifth number (the share of the first update's expert
choices on which program and reference disagree) with this family's own
spec, seeded weights (``harness/hybrid_weights.py``) and reference: the
shared SAC step of ``harness/reference_trunk.py`` over this family's forward
(``harness/reference_nemotron_trunk.py::features``).

The program takes the stack from ``SACConfig.trunk_pattern`` and what this
chip holds of each layer kind from the ``trunk_*`` counts; nothing here names
a block.
"""

from __future__ import annotations

import jax

from benchmark.drivers import _common, trunkburst
from benchmark.harness import (
    data, flops_hybrid, hybrid_weights, reference_nemotron_trunk, reference_trunk,
)

TRUNK_KEYS = (
    "hidden", "pattern", "q_heads", "kv_heads", "head_dim", "experts", "experts_per_tok",
    "expert_width", "block_length", "rms_eps", "q_hidden", "remat", "bf16_dots",
    "qk_norm_rope", "router", "routed_scale", "expert_form", "expert_latent",
    "shared_expert_width", "ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state",
    "ssm_conv", "ssm_chunk",
)


class Driver(trunkburst.Driver):
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
        if lacks:  # a program from before the pattern: a clean refusal, no traceback
            raise SystemExit(f"benchmark: this program's SACConfig has no {lacks}")
        return SACConfig(**fields)

    def seeded_params(self, env):
        return hybrid_weights.seeded_params(
            self.sac, env.example_obs(), data.state_key(self.seed, 1)
        )

    @staticmethod
    def at_rest_bytes(cell: dict, config: dict) -> int:
        return flops_hybrid.at_rest_bytes(config["model"], cell["traffic"]["ring_rows"])

    def _follow(self, mode: str) -> dict:
        """The reference's account of the first call at ``mode``.  Four copies
        of this trunk and a gradient leave the chip little room: the initial
        parameters are donated (each call places fresh copies of the host's)
        and only what is compared comes back."""
        if mode not in self._followed:
            sac = {k: self.sac_fields[k] for k in _common.SAC_CONSTANTS}

            def account(actor, critic, rows, eps_q, eps_pi):
                state, lq, lp, chosen, terms = reference_trunk.follow(
                    reference_trunk.init_state(actor, critic), rows, eps_q, eps_pi,
                    self.model, sac, mode, reference_nemotron_trunk.features,
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
