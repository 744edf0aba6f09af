"""The SAC learner: state init + one fused, jittable update step.

The reference spreads a gradient step across four mutable-object calls —
``update_critic`` (zero_grad/backward/allreduce/step, ref
``sac/algorithm.py:115-141``), ``update_policy`` (freeze critic,
backward, step, ref ``:143-162``), ``update_targets`` (polyak, ref
``:77-81``) — each crossing the Python/native boundary several times and
the network once. Here the entire unit, **including replay sampling**,
compiles into one XLA program:

    update_burst = push(chunk) ; scan_{k=1..K} [ sample -> critic step
                   -> actor step -> (alpha step) -> polyak ]

so an ``update_every=50`` burst is ONE device dispatch with zero
host<->device transfers inside, and gradient averaging under data
parallelism is a ``lax.pmean`` *inside* the compiled step (the TPU-native
equivalent of ``mpi_avg_grads``, ref ``sac/mpi.py:77-85``) riding ICI.

Everything is pure: ``TrainState`` in, ``TrainState`` out. The class
holds only static configuration (hyperparams, module definitions,
optax transforms) — it is hashable setup, never traced state.
"""

from __future__ import annotations

import functools
import sys
import typing as t

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import traverse_util

from torch_actor_critic_tpu.buffer.replay import (
    as_observations,
    observation_spec,
    push,
    sample,
    sample_fused_visual,
)
from torch_actor_critic_tpu.core.types import (
    Batch,
    BufferState,
    MultiObservation,
    TrainState,
)
from torch_actor_critic_tpu.diagnostics import ingraph as diag
from torch_actor_critic_tpu.ops.polyak import polyak_update
from torch_actor_critic_tpu.ops.augment import augment_batch
from torch_actor_critic_tpu.sac import losses
from torch_actor_critic_tpu.telemetry import scopes
from torch_actor_critic_tpu.utils.config import SACConfig

Metrics = t.Dict[str, jax.Array]


def dynamic_lr_step(
    core: optax.GradientTransformation,
    tx: optax.GradientTransformation,
    grads: t.Any,
    opt_state: optax.OptState,
    params: t.Any,
    lr: jax.Array | None,
) -> t.Tuple[t.Any, optax.OptState]:
    """One Adam step with the learning rate as a *traced* value.

    ``optax.adam(lr)`` bakes the rate into the transform as a Python
    scalar, so N population members would need N compiled programs to
    train at N different rates. With ``lr`` given, this replays adam's
    exact op sequence — ``scale_by_adam`` (``core``, sharing the chain's
    first state slot) then multiply by ``-lr`` — so the update is
    bitwise-identical to ``tx.update`` when ``lr`` equals the baked-in
    rate (pinned by tests) and the opt-state pytree structure never
    changes. ``lr=None`` is the plain path.
    """
    if lr is None:
        return tx.update(grads, opt_state, params)
    inner, *rest = opt_state
    updates, inner = core.update(grads, inner, params)
    updates = jax.tree_util.tree_map(lambda u: -lr * u, updates)
    return updates, (inner, *rest)


@functools.cache
def _say_once(line: str) -> None:
    """A constant of a traced program, on standard error once a process."""
    print(line, file=sys.stderr, flush=True)


@jax.named_scope(scopes.ALLREDUCE)
def _pmean(grads: t.Any, axis_name) -> t.Any:
    """Gradient averaging over the data-parallel axis, under its scope."""
    return jax.lax.pmean(grads, axis_name)


class SAC:
    """SAC learner over arbitrary (actor_def, critic_def) Flax modules.

    ``actor_def.apply(params, obs, key) -> (action, logp)`` and
    ``critic_def.apply(params, obs, action) -> (num_qs, batch)`` is the
    whole contract, so the MLP stack (ref ``networks/linear.py``) and the
    visual stack (ref ``networks/convolutional.py``) — or any future
    model family — plug in without touching the algorithm, unlike the
    reference whose train CLI string-dispatches on env name
    (ref ``main.py:63``).
    """

    def __init__(
        self,
        config: SACConfig,
        actor_def: nn.Module,
        critic_def: nn.Module,
        act_dim: int,
    ):
        self.config = config
        self.actor_def = actor_def
        self.critic_def = critic_def
        self.act_dim = act_dim
        # Adam with torch-default eps, like the reference's
        # optim.Adam(lr=3e-4) (ref main.py:93-95). `_adam_core` is the
        # lr-free first stage of the same chain, for the dynamic-lr
        # (per-member hyperparameter) path — see dynamic_lr_step.
        self.pi_tx = optax.adam(config.lr)
        self.q_tx = optax.adam(config.lr)
        self.alpha_tx = optax.adam(config.lr)
        self._adam_core = optax.scale_by_adam()
        # One history trunk shared by actor and critics (models/sequence.py
        # SharedTrunk*): the critic's tree holds it, the losses differ.
        self.shared_trunk = bool(getattr(critic_def, "shared_trunk", False))
        self.target_entropy = (
            config.target_entropy
            if config.target_entropy is not None
            else -float(act_dim)
        )
        # One observation's shapes, learned in init_state: sampled rows
        # reach the update in them however the ring stores a row
        # (buffer/replay.py). None until then: rows arrive as stored.
        self.obs_spec = None

    def default_hyperparams(self) -> t.Dict[str, jax.Array]:
        """The PBT-perturbable hyperparameters as scalar arrays, at
        their configured values. Stored in ``TrainState.hyperparams``
        they OVERRIDE the baked-in Python scalars at trace time; with
        ``hyperparams=None`` the update traces the historical program
        bit-for-bit. SAC exposes the two learning rates plus whichever
        temperature knob is live: ``alpha`` itself when fixed,
        ``target_entropy`` when the temperature is learned."""
        import jax.numpy as jnp

        hp = {
            "actor_lr": jnp.float32(self.config.lr),
            "critic_lr": jnp.float32(self.config.lr),
        }
        if self.config.learn_alpha:
            hp["target_entropy"] = jnp.float32(self.target_entropy)
        else:
            hp["alpha"] = jnp.float32(self.config.alpha)
        return hp

    # ------------------------------------------------------------------ init

    def init_state(self, key: jax.Array, example_obs: t.Any) -> TrainState:
        """Build the full learner state from one example observation.

        The target critic starts as a copy of the online critic — the
        functional analogue of ``deepcopy(critic)`` at train start
        (ref ``sac/algorithm.py:194-196``).
        """
        self.obs_spec = observation_spec(example_obs)
        k_actor, k_critic, k_sample, k_state = jax.random.split(key, 4)
        example_act = jnp.zeros((self.act_dim,))
        critic_params = self.critic_def.init(k_critic, example_obs, example_act)
        if self.shared_trunk:
            # init also fills the collection the expert layers sow into.
            critic_params = {"params": critic_params["params"]}
            # The trained actor parameters are the policy head alone; the
            # trunk it reads is the critic's (models.sequence.policy_params).
            actor_params = self.actor_def.init(
                k_actor, jnp.zeros((1, self.critic_def.spec.hidden)), k_sample,
                method=self.actor_def.head,
            )
        else:
            actor_params = self.actor_def.init(k_actor, example_obs, k_sample)
        log_alpha = jnp.log(jnp.float32(self.config.alpha))
        return TrainState(
            step=jnp.int32(0),
            actor_params=actor_params,
            critic_params=critic_params,
            target_critic_params=jax.tree_util.tree_map(
                jnp.copy, critic_params
            ),
            pi_opt_state=self.pi_tx.init(actor_params),
            q_opt_state=self.q_tx.init(critic_params),
            log_alpha=log_alpha,
            alpha_opt_state=self.alpha_tx.init(log_alpha),
            rng=k_state,
        )

    # ----------------------------------------------------------- apply fns

    def _actor_apply(self, params, obs, key):
        return self.actor_def.apply(params, obs, key)

    def _critic_apply(self, params, obs, action):
        return self.critic_def.apply(params, obs, action)

    def _features_apply(self, params, obs):
        """One pass of the shared trunk: the last step's features, and the
        statistics of its layers that hold experts, in the stack's order:
        ``{"sizes": (layers, held experts), "choices": (layers, tokens,
        experts a token)}``, and ``"weight_grads"``: how many of the stack's
        dense projections have their kernel's gradient as a product of its
        own and what share of the projections' parameters those kernels are,
        as the projections said when this pass was traced
        (:class:`models.sequence.OwnWeightGrad`)."""
        h, sown = self.critic_def.apply(
            params, obs, method=self.critic_def.features,
            mutable=["moe_stats", "weight_grads"],
        )
        layers = sown["moe_stats"]["trunk"]
        names = sorted(layers, key=lambda n: int(n.rsplit("_", 1)[1]))
        # A layer's expert module is the one child that sowed anything.
        sown_by = [next(iter(layers[n].values())) for n in names]
        stats = {
            k: jnp.stack([layer[k][0] for layer in sown_by])
            for k in ("sizes", "choices")
        }
        sizes = {  # float32: a large stack's elements pass 2**31
            path: jnp.float32(size) for path, (size,) in
            traverse_util.flatten_dict(sown["weight_grads"]).items()
        }
        own = [size for path, size in sizes.items() if path[-1] == "own"]
        _say_once(
            "trunk: weight_grad_own_products %d of %d dense projections"
            % (len(own), len(sizes))
        )
        stats["weight_grads"] = jnp.stack(
            [jnp.float32(len(own)), sum(own, jnp.float32(0)) / sum(sizes.values())]
        )
        return h, stats

    def _q_apply(self, params, h, action):
        return self.critic_def.apply(params, h, action, method=self.critic_def.q)

    def _head_apply(self, params, h, key):
        return self.actor_def.apply(params, h, key, method=self.actor_def.head)

    def select_action(
        self, params, obs, key: jax.Array | None = None, deterministic: bool = False
    ):
        """Policy for env interaction (no log-prob, like the no-grad
        action selection at ref ``sac/algorithm.py:231-236``). ``params``
        are the policy's (``models.sequence.policy_params`` of a state)."""
        action, _ = self.actor_def.apply(
            params, obs, key, deterministic=deterministic, with_logprob=False
        )
        return action

    # -------------------------------------------------------------- update

    def update(
        self, state: TrainState, batch: Batch, axis_name: str | None = None
    ) -> t.Tuple[TrainState, Metrics]:
        """One SAC gradient step: critic, then actor (on the updated
        critic, matching the reference's sequential update order, ref
        ``sac/algorithm.py:276-278``), optional temperature step, polyak.

        Under data parallelism, pass ``axis_name`` to average gradients
        with ``lax.pmean`` — the in-program equivalent of
        ``mpi_avg_grads`` (ref ``sac/mpi.py:77-85``), applied to *both*
        critic and actor grads (deliberately fixing the reference's
        misordering at ``sac/algorithm.py:155-156``).

        ``config.diagnostics != "off"`` fuses the learning-health
        reductions (:mod:`torch_actor_critic_tpu.diagnostics.ingraph`)
        into this same program: gradient global-norms are taken on the
        PRE-pmean per-device grads (so dp skew is observable), update
        ratios after the optax transform, Q stats and the TD-error
        histogram from the raw surfaces the critic loss already
        materialized. ``"off"`` traces bit-identically to a build
        without this code.
        """
        cfg = self.config
        tier = cfg.diagnostics
        if cfg.frame_augment != "none" and cfg.pixel_pipeline != "fused":
            rng, key_q, key_pi, key_aug = jax.random.split(state.rng, 4)
            with jax.named_scope(scopes.DECODE):
                batch = augment_batch(
                    batch, key_aug, cfg.frame_augment, cfg.augment_pad
                )
        else:
            # Parity path keeps the historical 3-way split: 'none' must
            # reproduce pre-augmentation streams bit-for-bit (resumed
            # checkpoints, recorded evidence runs). The fused pixel
            # pipeline lands here too: its frames arrive already
            # shifted (offsets drawn at sample time), so the update
            # consumes no augmentation key.
            rng, key_q, key_pi = jax.random.split(state.rng, 3)
        # Per-run hyperparameters (PBT): when the state carries a
        # hyperparams dict its traced values replace the config scalars
        # — same compiled program for every member of a population.
        hp = state.hyperparams if state.hyperparams is not None else {}
        if cfg.learn_alpha:
            alpha = jnp.exp(jax.lax.stop_gradient(state.log_alpha))
            target_entropy = hp.get("target_entropy", self.target_entropy)
        else:
            alpha = hp.get("alpha", jnp.float32(cfg.alpha))

        # --- critic step ---
        # One history trunk shared by actor and critics changes the two
        # losses and nothing after them: the critic step trains trunk and Q
        # heads, the actor step trains the policy head on the critic step's
        # features, the polyak target covers trunk and Q heads.
        if self.shared_trunk:
            critic_loss = functools.partial(
                losses.shared_trunk_critic_loss,
                features_apply=self._features_apply,
                q_apply=self._q_apply,
                head_apply=self._head_apply,
            )
        else:
            critic_loss = functools.partial(
                losses.critic_loss,
                actor_apply=self._actor_apply,
                critic_apply=self._critic_apply,
                diagnostics=tier != "off",
            )
        (loss_q, q_aux), q_grads = jax.named_scope(scopes.CRITIC)(
            jax.value_and_grad(critic_loss, has_aux=True)
        )(
            state.critic_params,
            actor_params=state.actor_params,
            target_critic_params=state.target_critic_params,
            batch=batch,
            key=key_q,
            alpha=alpha,
            gamma=cfg.gamma,
            reward_scale=cfg.reward_scale,
        )
        diag_q = q_aux.pop("diag_q", None)
        diag_backup = q_aux.pop("diag_backup", None)
        diag_metrics: Metrics = {}
        if tier != "off":
            # Pre-pmean: per-device norm, so replica skew is visible.
            diag_metrics["diag/grad_norm_q"] = diag.global_norm(q_grads)
        if axis_name is not None:
            q_grads = _pmean(q_grads, axis_name)
        with jax.named_scope(scopes.OPTIMIZER):
            q_updates, q_opt_state = dynamic_lr_step(
                self._adam_core, self.q_tx, q_grads, state.q_opt_state,
                state.critic_params, hp.get("critic_lr"),
            )
            critic_params = optax.apply_updates(
                state.critic_params, q_updates
            )
        if tier != "off":
            diag_metrics["diag/update_ratio_q"] = diag.norm_ratio(
                q_updates, state.critic_params
            )

        # --- actor step (critic frozen by construction: grad w.r.t.
        # actor params only) ---
        if self.shared_trunk:
            trunk_stats = q_aux.pop("stats"), q_aux.pop("stats_target")
            actor_loss = functools.partial(
                losses.shared_trunk_actor_loss,
                head_apply=self._head_apply,
                q_apply=self._q_apply,
                features=q_aux.pop("features"),
            )
        else:
            actor_loss = functools.partial(
                losses.actor_loss,
                actor_apply=self._actor_apply,
                critic_apply=self._critic_apply,
                batch=batch,
                parity_pi_obs=cfg.parity_pi_obs,
                diagnostics=tier != "off",
            )
        (loss_pi, pi_aux), pi_grads = jax.named_scope(scopes.ACTOR)(
            jax.value_and_grad(actor_loss, has_aux=True)
        )(
            state.actor_params,
            critic_params=critic_params,
            key=key_pi,
            alpha=alpha,
        )
        diag_pi = pi_aux.pop("diag_pi", None)
        if tier != "off":
            diag_metrics["diag/grad_norm_pi"] = diag.global_norm(pi_grads)
        if axis_name is not None:
            pi_grads = _pmean(pi_grads, axis_name)
        with jax.named_scope(scopes.OPTIMIZER):
            pi_updates, pi_opt_state = dynamic_lr_step(
                self._adam_core, self.pi_tx, pi_grads, state.pi_opt_state,
                state.actor_params, hp.get("actor_lr"),
            )
            actor_params = optax.apply_updates(
                state.actor_params, pi_updates
            )
        if tier != "off":
            diag_metrics["diag/update_ratio_pi"] = diag.norm_ratio(
                pi_updates, state.actor_params
            )

        # --- entropy temperature (extension; no-op graph when fixed) ---
        log_alpha = state.log_alpha
        alpha_opt_state = state.alpha_opt_state
        if cfg.learn_alpha:
            a_grad = jax.named_scope(scopes.ALPHA)(jax.grad(
                lambda la: losses.alpha_loss(
                    la, pi_aux["logp_pi"], target_entropy
                )
            ))(state.log_alpha)
            if tier != "off":
                diag_metrics["diag/grad_norm_alpha"] = jnp.abs(a_grad)
            if axis_name is not None:
                a_grad = _pmean(a_grad, axis_name)
            with jax.named_scope(scopes.OPTIMIZER):
                a_updates, alpha_opt_state = self.alpha_tx.update(
                    a_grad, state.alpha_opt_state, state.log_alpha
                )
                log_alpha = optax.apply_updates(state.log_alpha, a_updates)
            if tier != "off":
                diag_metrics["diag/update_ratio_alpha"] = jnp.abs(
                    a_updates
                ) / (jnp.abs(state.log_alpha) + 1e-12)

        # --- polyak target update (ref sac/algorithm.py:77-81) ---
        with jax.named_scope(scopes.POLYAK):
            target_critic_params = polyak_update(
                critic_params, state.target_critic_params, cfg.polyak
            )

        new_state = TrainState(
            step=state.step + 1,
            actor_params=actor_params,
            critic_params=critic_params,
            target_critic_params=target_critic_params,
            pi_opt_state=pi_opt_state,
            q_opt_state=q_opt_state,
            log_alpha=log_alpha,
            alpha_opt_state=alpha_opt_state,
            rng=rng,
            hyperparams=state.hyperparams,
        )
        metrics = {
            "loss_q": loss_q,
            "loss_pi": loss_pi,
            "alpha": jnp.exp(log_alpha) if cfg.learn_alpha else alpha,
            **q_aux,
            **pi_aux,
        }
        if self.shared_trunk:
            metrics.update(self._trunk_counters(*trunk_stats))
        if tier != "off":
            metrics.update(diag_metrics)
            metrics.update(
                _shared_diagnostics(
                    cfg, loss_q, loss_pi, diag_q, diag_backup, diag_pi,
                    float(getattr(self.actor_def, "act_limit", 1.0)),
                )
            )
        return new_state, metrics

    def _trunk_counters(self, stats, stats_target) -> Metrics:
        """Counters of the expert layers, reduced here on the device: the
        assignments that landed on held experts in the online pass (the one
        the backward pass repeats) and in the target pass, summed over
        layers, and the online pass's largest and mean tokens a held expert;
        and how many of the stack's dense projections have their weight
        gradient as a product of its own, and what share of those projections'
        parameters that is (constants of the program, counted when it was
        traced)."""
        sizes = stats["sizes"].astype(jnp.float32)
        own_products, own_share = stats["weight_grads"]
        counters = {
            "trunk/weight_grad_own_products": own_products,
            "trunk/weight_grad_own_share": own_share,
            "trunk/held_assignments": jnp.sum(sizes),
            "trunk/held_assignments_target": jnp.sum(
                stats_target["sizes"].astype(jnp.float32)
            ),
            "trunk/expert_load_max": jnp.max(sizes),
            "trunk/expert_load_mean": jnp.mean(sizes),
        }
        if self.config.trunk_report_choices:
            # float32 holds an expert's index exactly and survives the
            # data-parallel burst's whole-tree pmean.
            counters["trunk/choices_first"] = stats["choices"].astype(jnp.float32)
        return counters

    # --------------------------------------------------------------- burst

    def update_burst(
        self,
        state: TrainState,
        buffer_state: BufferState,
        chunk: Batch,
        num_updates: int,
        axis_name: str | None = None,
    ) -> t.Tuple[TrainState, BufferState, Metrics]:
        """Push a chunk of env transitions, then run ``num_updates``
        gradient steps — the whole ``update_every`` inner loop of the
        reference (ref ``sac/algorithm.py:274-283``) as one compiled
        program (``lax.scan`` over :meth:`update`).

        Metrics are averaged over the burst, mirroring the reference's
        per-epoch loss means (ref ``sac/algorithm.py:285-290``).
        """
        return run_update_burst(
            self.update, self.config, state, buffer_state, chunk,
            num_updates, axis_name, self.obs_spec,
        )


def _shared_diagnostics(
    config: SACConfig,
    loss_q: jax.Array,
    loss_pi: jax.Array,
    diag_q: jax.Array | None,
    diag_backup: jax.Array | None,
    diag_pi: jax.Array | None,
    act_limit: float,
) -> Metrics:
    """Algorithm-independent in-graph diagnostics shared by SAC and TD3
    (both pass the raw Q surface, backup vector and policy actions
    their losses already materialized). Key suffixes select the
    reduction each metric carries through the burst scan, mesh
    collectives and epoch aggregation (see
    :mod:`torch_actor_critic_tpu.diagnostics.ingraph`)."""
    metrics: Metrics = {
        # Per-burst maxima: a single-step spike inside a 50-update
        # burst survives to metrics.jsonl instead of averaging away.
        "loss_q_max": loss_q,
        "loss_pi_max": loss_pi,
    }
    if diag_q is not None and diag_backup is not None:
        metrics.update({
            "diag/q_min": jnp.min(diag_q),
            "diag/q_max": jnp.max(diag_q),
            # Ensemble (twin-Q) disagreement: per-sample head spread.
            "diag/q_spread": jnp.mean(
                jnp.max(diag_q, axis=0) - jnp.min(diag_q, axis=0)
            ),
            # Online-vs-target bias: the Q-overestimation drift signal.
            "diag/q_bias": jnp.mean(diag_q) - jnp.mean(diag_backup),
        })
        if config.diagnostics == "full":
            abs_td = jnp.abs(diag_q - diag_backup[None, :])
            metrics.update({
                "diag/td_hist": diag.bucket_counts(abs_td),
                "diag/td_abs_min": jnp.min(abs_td),
                "diag/td_abs_max": jnp.max(abs_td),
                "diag/td_abs_sum": jnp.sum(abs_td),
            })
    if diag_pi is not None:
        metrics["diag/act_sat"] = diag.saturation_fraction(diag_pi, act_limit)
    return metrics


def run_update_burst(
    update_fn: t.Callable[[TrainState, Batch, str | None],
                          t.Tuple[TrainState, Metrics]],
    config: SACConfig,
    state: TrainState,
    buffer_state: BufferState,
    chunk: Batch,
    num_updates: int,
    axis_name: str | None = None,
    obs_spec: t.Any = None,
) -> t.Tuple[TrainState, BufferState, Metrics]:
    """The push-then-scan burst shared by every learner (SAC here, TD3
    in :mod:`torch_actor_critic_tpu.td3`): algorithm choice lives
    entirely in ``update_fn``; the burst scheduling (sampling inside
    the compiled program, scan unroll) is algorithm-independent.

    Metric reduction over the scan axis is suffix-keyed
    (:func:`~torch_actor_critic_tpu.diagnostics.ingraph.reduce_burst_metrics`);
    none of the base metric keys match a special suffix, so without
    diagnostics this is exactly the historical per-burst mean.

    ``config.pixel_pipeline="fused"`` swaps the plain :func:`sample`
    for :func:`~torch_actor_critic_tpu.buffer.replay.sample_fused_visual`
    on visual buffers: the frame leaves decode/augment/cast inside the
    fused gather and reach the learner already in the compute dtype —
    the one integration point, so the host Trainer, the dp/GSPMD
    burst, TD3 and the fused on-device + population loops all ride it.
    """
    buffer_state = push(buffer_state, chunk)
    fused_visual = config.pixel_pipeline == "fused" and isinstance(
        buffer_state.data.states, MultiObservation
    )

    def body(carry, _):
        st, buf = carry
        rng, sample_key = jax.random.split(st.rng)
        st = st.replace(rng=rng)
        if fused_visual:
            batch = sample_fused_visual(
                buf, sample_key, config.batch_size,
                out_dtype=config.model_dtype,
                augment=config.frame_augment,
                pad=config.augment_pad,
                normalize=config.normalize_pixels,
                obs_spec=obs_spec,
            )
        else:
            batch = as_observations(
                sample(buf, sample_key, config.batch_size), obs_spec
            )
        st, metrics = update_fn(st, batch, axis_name)
        return (st, buf), metrics

    (state, buffer_state), metrics = jax.lax.scan(
        body, (state, buffer_state), xs=None, length=num_updates,
        unroll=config.resolved_burst_unroll,
    )
    metrics = diag.reduce_burst_metrics(metrics)
    if config.diagnostics != "off":
        # Post-burst parameter norm: per-device, so the dp wrapper can
        # take its replica skew — the desync canary that must read 0.0
        # while pmean'd grads keep replicas bit-identical.
        metrics["diag/param_norm"] = diag.global_norm(
            state.actor_params, state.critic_params
        )
    return state, buffer_state, metrics
