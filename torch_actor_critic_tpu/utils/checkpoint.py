"""Orbax checkpoint/resume of the COMPLETE training state.

The reference checkpoints actor/critic modules + optimizer state + epoch
through MLflow (ref ``sac/algorithm.py:164-180``) and on resume rebuilds
the target critic by deepcopy and restarts with an EMPTY replay buffer
(ref ``main.py:28-51``, SURVEY.md §3.5) — i.e. resumed runs are not the
same runs. Here one Orbax composite persists strictly more:

- the full :class:`TrainState` (params, target params, both opt states,
  learned-temperature state, PRNG key, step counter),
- optionally the full sharded replay :class:`BufferState`,
- the epoch + config JSON.

Restore round-trips device placement/sharding from abstract pytrees, so
a multi-chip run resumes onto the same mesh layout.
"""

from __future__ import annotations

import logging
import time
import typing as t
from pathlib import Path

import jax
import numpy as np
import orbax.checkpoint as ocp

from torch_actor_critic_tpu.core.types import BufferState, TrainState
from torch_actor_critic_tpu.resilience.retry import call_with_retries

logger = logging.getLogger(__name__)

# Checkpoint format version, bumped on any param-tree layout change.
# 2: Dense submodules are named by their tensor-parallel role
#    (``col``/``row``/``Dense_0``) instead of always ``Dense_0`` —
#    checkpoints written before that rename have a different tree
#    structure and cannot be restored into current models.
CKPT_FORMAT = 3  # 3: VisualDoubleCritic ensemble unrolled (ensemble_i
# submodules, dense convs) — visual param trees from format<=2 (vmapped
# 'ensemble' with a stacked leading axis) no longer restore


def _is_prng_key(x) -> bool:
    dt = getattr(x, "dtype", None)
    return dt is not None and jax.dtypes.issubdtype(dt, jax.dtypes.prng_key)


def _unwrap_prng_keys(tree):
    """Typed PRNG-key leaves -> raw uint32 key data.

    The orbax in this image cannot serialize extended-dtype (typed key)
    arrays (``np.array(key)`` raises inside its serializer), so key
    leaves cross the checkpoint boundary as their underlying uint32
    bits — same information, stable on-disk layout on every jax
    version. Applied symmetrically on save and on the abstract restore
    tree; :func:`_rewrap_prng_keys` restores the typed view.
    """
    return jax.tree_util.tree_map(
        lambda x: jax.random.key_data(x) if _is_prng_key(x) else x, tree
    )


def _rewrap_prng_keys(restored, reference):
    """Re-wrap raw uint32 key data as typed keys wherever ``reference``
    (the caller's abstract tree, pre-unwrap) holds a typed key."""

    def rewrap(r, ref):
        if not _is_prng_key(ref):
            return r
        try:
            impl = jax.random.key_impl(ref)
        except Exception:  # abstract leaf without impl info
            impl = None
        return jax.random.wrap_key_data(r, impl=impl)

    return jax.tree_util.tree_map(rewrap, restored, reference)


def _has_unrolled_visual_ensemble(train_state: TrainState) -> bool:
    """True when the critic tree is a format-3 unrolled visual ensemble
    (``ensemble_i`` submodules, models/visual.py) — the ONLY family
    whose layout changed between formats 2 and 3."""
    flat = jax.tree_util.tree_flatten_with_path(train_state.critic_params)[0]
    return any(
        getattr(k, "key", None) is not None
        and str(getattr(k, "key", "")).startswith("ensemble_")
        for path, _ in flat
        for k in path
    )


class CheckpointFormatError(ValueError):
    """The checkpoint's param-tree layout predates this build (see
    ``CKPT_FORMAT``). Deliberately NOT retried/fallen-back-from: every
    epoch in the directory shares the writer's format, so walking to an
    older step cannot fix it."""


def _in_saved_row_shapes(abstract_buffer: BufferState, saved: t.Any) -> BufferState:
    """``abstract_buffer`` with every leaf in the shape the checkpoint
    holds it in, where that is the same rows in another row shape
    (``saved``: the item's Orbax metadata, a tree of dicts by field
    name). A leaf that differs in anything else is left as asked for,
    so that Orbax's own error names it."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_buffer)
    lead = len(abstract_buffer.ptr.shape) + 1  # (devices or members,) rows

    def as_saved(path, leaf):
        try:
            node = saved
            for key in path:
                node = node[key.name]
            shape = tuple(node.shape)
        except (AttributeError, KeyError, TypeError):
            return leaf
        if shape == tuple(leaf.shape) or shape[:lead] != tuple(
            leaf.shape[:lead]
        ) or int(np.prod(shape)) != int(np.prod(leaf.shape)):
            return leaf
        return jax.ShapeDtypeStruct(
            shape, leaf.dtype, sharding=getattr(leaf, "sharding", None)
        )

    return jax.tree_util.tree_unflatten(
        treedef, [as_saved(path, leaf) for path, leaf in leaves]
    )


def _in_asked_shapes(buffer: BufferState, abstract_buffer: BufferState) -> BufferState:
    """A restored buffer with every leaf in ``abstract_buffer``'s shape
    (and sharding): the inverse of :func:`_in_saved_row_shapes`."""
    def stored(x, like):
        if x.shape == tuple(like.shape):
            return x
        x = x.reshape(like.shape)
        sharding = getattr(like, "sharding", None)
        return x if sharding is None else jax.device_put(x, sharding)

    return jax.tree_util.tree_map(stored, buffer, abstract_buffer)


class Checkpointer:
    def __init__(
        self,
        directory: str | Path,
        max_to_keep: int = 3,
        save_buffer: bool = True,
        retries: int = 2,
        retry_backoff_s: float = 0.5,
        sleep: t.Callable[[float], None] = time.sleep,
    ):
        self.directory = Path(directory).absolute()
        self.save_buffer = save_buffer
        # Transient-IO policy (resilience/retry.py): every Orbax
        # save/restore call gets `retries` extra attempts with
        # exponential backoff before the error surfaces. `sleep` is
        # injectable so tests drive the ladder without real waiting.
        self._retries = int(retries)
        self._retry_backoff_s = float(retry_backoff_s)
        self._sleep = sleep
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )

    def _retry(self, fn: t.Callable[[], t.Any], what: str):
        return call_with_retries(
            fn,
            attempts=self._retries + 1,
            base_delay_s=self._retry_backoff_s,
            sleep=self._sleep,
            what=what,
        )

    def save(
        self,
        epoch: int,
        train_state: TrainState,
        buffer_state: BufferState | None = None,
        extra: t.Mapping[str, t.Any] | None = None,
        wait: bool = False,
        arrays: t.Any = None,
    ) -> None:
        """Write checkpoint for ``epoch`` (async unless ``wait``).

        ``arrays`` is an optional extra array pytree for state that is
        neither ``TrainState`` nor replay — the population-fused loop
        persists its member env states, acting keys and PBT
        bookkeeping here so resume continues bitwise. Typed PRNG-key
        leaves round-trip like the train state's.
        """
        items = {
            "train_state": ocp.args.StandardSave(_unwrap_prng_keys(train_state)),
            "meta": ocp.args.JsonSave(
                dict(extra or {}, epoch=int(epoch), ckpt_format=CKPT_FORMAT)
            ),
        }
        if buffer_state is not None and self.save_buffer:
            items["buffer"] = ocp.args.StandardSave(buffer_state)
        if arrays is not None:
            items["arrays"] = ocp.args.StandardSave(_unwrap_prng_keys(arrays))
        self._retry(
            lambda: self._mgr.save(epoch, args=ocp.args.Composite(**items)),
            what=f"checkpoint save (epoch {epoch})",
        )
        if wait:
            self._retry(
                self._mgr.wait_until_finished,
                what=f"checkpoint save finalize (epoch {epoch})",
            )

    def latest_epoch(self) -> int | None:
        """Newest *readable* checkpoint step.

        An interrupted async save (preemption mid-write, full disk) can
        leave a step directory whose metadata never landed; treating it
        as "latest" would kill every subsequent resume. Steps whose
        metadata cannot be read are skipped (with a warning) in favor
        of the newest valid epoch — exactly what resume wants.
        """
        for step in self._valid_candidates():
            return step
        return None

    def _valid_candidates(self) -> t.Iterator[int]:
        """All steps newest-first whose JSON metadata is readable."""
        for step in sorted(self._mgr.all_steps(), reverse=True):
            try:
                self._peek_meta_at(step)
            except Exception as e:  # noqa: BLE001 — any unreadable step
                # is a skip, whatever Orbax raises for it
                logger.warning(
                    "checkpoint epoch %s under %s is unreadable (%s: %s); "
                    "skipping it",
                    step, self.directory, type(e).__name__, e,
                )
                continue
            yield step

    def _peek_meta_at(self, epoch: int) -> dict:
        return dict(
            self._retry(
                lambda: self._mgr.restore(
                    epoch,
                    args=ocp.args.Composite(meta=ocp.args.JsonRestore()),
                ),
                what=f"checkpoint metadata read (epoch {epoch})",
            )["meta"]
        )

    def peek_meta(self, epoch: int | None = None) -> dict:
        """The checkpoint's JSON metadata alone (no array restore) —
        lets callers validate compatibility (e.g. which algorithm wrote
        it) BEFORE a tree-structure mismatch surfaces as an opaque
        Orbax error. ``epoch=None`` reads the newest *valid* epoch."""
        epoch = epoch if epoch is not None else self.latest_epoch()
        if epoch is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        return self._peek_meta_at(epoch)

    def restore(
        self,
        abstract_train_state: TrainState,
        abstract_buffer: BufferState | None = None,
        epoch: int | None = None,
        meta_probe: dict | None = None,
        abstract_arrays: t.Any = None,
    ) -> t.Tuple[TrainState, BufferState | None, dict]:
        """Restore ``(train_state, buffer_state, meta)``.

        With ``abstract_arrays`` given, returns a 4-tuple whose last
        element is the restored extra-array pytree (``None`` when the
        checkpoint predates the ``arrays`` item) — the counterpart of
        :meth:`save`'s ``arrays``.

        Abstract pytrees come from ``jax.eval_shape`` over the init
        functions (plus shardings); buffer restore is skipped if the
        checkpoint has none. A caller that already ran
        :meth:`peek_meta` (for its own compatibility checks) can pass
        the result as ``meta_probe`` to skip the redundant metadata
        round-trip.

        With ``epoch=None`` (resume), a corrupt or partial newest step
        — interrupted async save, truncated arrays — falls back to the
        next older epoch instead of killing the resume: losing one
        ``save_every`` interval beats losing the run. An explicitly
        requested ``epoch`` never falls back (the caller asked for that
        state, substituting another would be silent corruption).
        """
        if epoch is not None:
            return self._restore_at(
                epoch, abstract_train_state, abstract_buffer, meta_probe,
                abstract_arrays,
            )
        last_err: Exception | None = None
        tried = 0
        for step in self._valid_candidates():
            try:
                return self._restore_at(
                    step,
                    abstract_train_state,
                    abstract_buffer,
                    # The probe the caller took describes the newest
                    # valid epoch only; older fallback epochs re-probe.
                    meta_probe if tried == 0 else None,
                    abstract_arrays,
                )
            except CheckpointFormatError:
                raise  # every epoch shares the writer's format
            except Exception as e:  # noqa: BLE001 — corrupt step: any
                # Orbax error class means "this epoch is unusable"
                logger.warning(
                    "checkpoint epoch %d under %s failed to restore "
                    "(%s: %s); falling back to the previous epoch",
                    step, self.directory, type(e).__name__, e,
                )
                last_err = e
                tried += 1
        if last_err is not None:
            raise last_err
        raise FileNotFoundError(f"no checkpoints under {self.directory}")

    def _restore_at(
        self,
        epoch: int,
        abstract_train_state: TrainState,
        abstract_buffer: BufferState | None,
        meta_probe: dict | None,
        abstract_arrays: t.Any = None,
    ) -> t.Tuple[TrainState, BufferState | None, dict]:
        # Check the format version BEFORE the array restore, so a layout
        # change surfaces as this message instead of an opaque Orbax
        # tree-structure mismatch.
        if meta_probe is None:
            meta_probe = self._peek_meta_at(epoch)
        found = int(meta_probe.get("ckpt_format", 1))
        if found != CKPT_FORMAT and not (
            found == 2 and not _has_unrolled_visual_ensemble(abstract_train_state)
        ):
            # Format 3 only changed VisualDoubleCritic trees (ensemble
            # unroll); format-2 checkpoints of every other family
            # (flat MLP, TD3, sequence) restore unchanged — rejecting
            # them would invalidate working checkpoints for no reason.
            raise CheckpointFormatError(
                f"checkpoint at {self.directory} epoch {epoch} has format "
                f"{found}, this build reads format {CKPT_FORMAT}: the model "
                "parameter tree layout changed (see CKPT_FORMAT in "
                "utils/checkpoint.py). Re-train, or restore with the "
                "framework version that wrote it."
            )
        items = {
            "train_state": ocp.args.StandardRestore(
                _unwrap_prng_keys(abstract_train_state)
            ),
            "meta": ocp.args.JsonRestore(),
        }
        # Only request the buffer if this checkpoint actually contains
        # one (save_buffer may have been off). A shape/sharding mismatch
        # on a present buffer must surface, not silently resume with an
        # empty buffer — that is exactly the reference flaw (SURVEY.md
        # §3.5) this module exists to fix. The metadata probe alone
        # (keys, no arrays) makes Orbax warn that items "could not be
        # restored" without a handler registry — misleading noise for a
        # keys-only query, silenced here; the real restore below still
        # surfaces every error.
        import logging as _logging

        absl_logger = _logging.getLogger("absl")
        prev_level = absl_logger.level
        absl_logger.setLevel(_logging.ERROR)
        try:
            item_metadata = self._mgr.item_metadata(epoch)
            saved_items = set(item_metadata.keys())
        finally:
            absl_logger.setLevel(prev_level)
        if abstract_buffer is not None and "buffer" in saved_items:
            # A ring leaf is asked for in the shape it was saved in and
            # reshaped into the one this build keeps it in (buffer/
            # replay.py stores a row tile by tile since PR 30).
            items["buffer"] = ocp.args.StandardRestore(
                _in_saved_row_shapes(abstract_buffer, item_metadata["buffer"])
            )
        if abstract_arrays is not None and "arrays" in saved_items:
            items["arrays"] = ocp.args.StandardRestore(
                _unwrap_prng_keys(abstract_arrays)
            )
        out = self._retry(
            lambda: self._mgr.restore(
                epoch, args=ocp.args.Composite(**items)
            ),
            what=f"checkpoint restore (epoch {epoch})",
        )
        train_state = _rewrap_prng_keys(
            out["train_state"], abstract_train_state
        )
        if "buffer" in items:
            out = dict(out)
            out["buffer"] = _in_asked_shapes(out["buffer"], abstract_buffer)
        if abstract_arrays is None:
            return train_state, out.get("buffer"), dict(out["meta"])
        arrays = out.get("arrays")
        if arrays is not None:
            arrays = _rewrap_prng_keys(arrays, abstract_arrays)
        return train_state, out.get("buffer"), dict(out["meta"]), arrays

    def restore_actor_params(
        self, epoch: int | None = None, shardings: t.Any = None
    ) -> t.Tuple[t.Any, dict]:
        """``(actor_params, meta)`` of a checkpoint — the serving path.

        Unlike :meth:`restore` this needs NO abstract tree: the policy
        service knows only the actor module, not the critic/optimizer
        structure, so the ``train_state`` item is restored shape-from-
        disk (the replay ``buffer`` item is never requested — for a
        1M-transition run that is the difference between touching a few
        MB and tens of GB) and the actor subtree extracted. Params come
        back as a plain nested dict, which is exactly what
        ``actor_def.apply`` takes.

        ``shardings`` is the sub-mesh serving path (docs/SERVING.md
        "Sharded serving & precision tiers"): a callable taking the
        actor-params abstract tree (``ShapeDtypeStruct`` leaves, built
        from the checkpoint's OWN metadata — still no caller-side
        abstract tree) and returning a matching
        :class:`jax.sharding.Sharding` tree, or that sharding tree
        directly. Orbax then restores every actor array **straight
        into its sharded layout** — each device reads exactly its
        shards, and no host-RAM copy of the full (possibly
        bigger-than-one-host) actor is ever materialized. Non-actor
        subtrees restore as before.

        As with :meth:`restore`, ``epoch=None`` falls back past corrupt
        newest steps (a serving replica must come up on the last good
        weights, not crash-loop on a half-written save).
        """
        if epoch is None:
            last_err: Exception | None = None
            for step in self._valid_candidates():
                try:
                    return self.restore_actor_params(
                        step, shardings=shardings
                    )
                except Exception as e:  # noqa: BLE001 — corrupt step
                    logger.warning(
                        "actor restore from epoch %d under %s failed "
                        "(%s: %s); falling back to the previous epoch",
                        step, self.directory, type(e).__name__, e,
                    )
                    last_err = e
            if last_err is not None:
                raise last_err
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        # The shape-from-disk restore makes Orbax warn that a target
        # tree "is generally UNSAFE" — for serving the disk layout IS
        # the contract (the engine validates by applying the params),
        # so the warning is noise; silenced as in restore() above.
        import logging as _logging

        absl_logger = _logging.getLogger("absl")
        prev_level = absl_logger.level
        absl_logger.setLevel(_logging.ERROR)
        try:
            restore_args = ocp.args.StandardRestore(
                self._sharded_abstract_state(epoch, shardings)
            )

            def _restore():
                import warnings

                with warnings.catch_warnings():
                    # The non-actor subtrees carry no shardings on
                    # purpose (only the actor is served); Orbax warns
                    # per such leaf that it falls back to the sharding
                    # file — noise for this deliberate partial layout.
                    warnings.filterwarnings(
                        "ignore",
                        message=".*sharding info.*",
                        category=UserWarning,
                    )
                    return self._mgr.restore(
                        epoch,
                        args=ocp.args.Composite(
                            train_state=restore_args,
                            meta=ocp.args.JsonRestore(),
                        ),
                    )

            out = self._retry(
                _restore, what=f"actor restore (epoch {epoch})"
            )
        finally:
            absl_logger.setLevel(prev_level)
        train_state = out["train_state"]
        if "actor_params" not in train_state:
            raise KeyError(
                f"checkpoint at {self.directory} epoch {epoch} has no "
                "actor_params item — not a TrainState checkpoint?"
            )
        # A shared history trunk lives in the critic's tree: the policy is
        # that trunk under the actor's heads, by the one function every
        # acting site uses (``shardings`` cover the actor's own subtree).
        from torch_actor_critic_tpu.models.sequence import policy_params

        params = policy_params(
            train_state["actor_params"], train_state.get("critic_params")
        )
        return params, dict(out["meta"], epoch=epoch)

    def _sharded_abstract_state(self, epoch: int, shardings: t.Any):
        """Abstract ``train_state`` tree for a direct-to-sharded actor
        restore, built from the checkpoint's OWN array metadata (so
        serving still needs no caller-side abstract tree): the
        ``actor_params`` subtree carries the requested shardings
        (``None``: one device), every other subtree lands on this
        process's first device. Orbax cannot partially restore a
        ``StandardSave`` item, so the full tree is described. Every
        leaf names its target: left to itself Orbax restores onto the
        devices the checkpoint was WRITTEN from, which a worker shown
        one chip of the four that trained does not have."""
        ts_meta = self._retry(
            lambda: self._mgr.item_metadata(epoch),
            what=f"checkpoint array-metadata read (epoch {epoch})",
        )["train_state"]
        if ts_meta is None:
            # A manager that never SAVED this item (the serving
            # process — the trainer wrote the checkpoint) has no
            # handler registered for it and reports None; read the
            # item's array metadata straight off its directory.
            from etils import epath

            ts_meta = self._retry(
                lambda: ocp.StandardCheckpointHandler().metadata(
                    epath.Path(self.directory) / str(epoch) / "train_state"
                ),
                what=f"checkpoint array-metadata read (epoch {epoch})",
            )
        if ts_meta is None or "actor_params" not in ts_meta:
            raise KeyError(
                f"checkpoint at {self.directory} epoch {epoch} has no "
                "actor_params item — not a TrainState checkpoint?"
            )

        here = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])

        def sds(m, sharding=here):
            return jax.ShapeDtypeStruct(
                tuple(m.shape), m.dtype, sharding=sharding
            )

        abstract = {
            k: jax.tree_util.tree_map(sds, v) for k, v in ts_meta.items()
        }
        if callable(shardings):
            shardings = shardings(abstract["actor_params"])
        if shardings is not None:
            abstract["actor_params"] = jax.tree_util.tree_map(
                sds, ts_meta["actor_params"], shardings
            )
        return abstract

    def refresh(self) -> None:
        """Re-read the checkpoint directory. The manager caches its
        step list at construction and only updates it through its OWN
        saves — a reader polling for steps written by ANOTHER process
        (the serving hot-reload path) must refresh first or
        ``latest_epoch`` stays frozen at construction time."""
        self._mgr.reload()

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()


# ------------------------------------------------------- population export


def extract_member(tree: t.Any, member: int) -> t.Any:
    """Slice one member off every leaf's leading population axis —
    a stacked population ``TrainState`` (or raw checkpoint dict)
    becomes the single-learner state of member ``member``."""
    return jax.tree_util.tree_map(lambda x: x[member], tree)


def export_member_checkpoint(
    src_directory: str | Path,
    dst_directory: str | Path,
    member: int | None = None,
    epoch: int | None = None,
) -> t.Tuple[int, int]:
    """Export ONE member of a population checkpoint as a standalone
    single-learner checkpoint — the population -> serving bridge: the
    result restores through :meth:`Checkpointer.restore_actor_params`,
    so ``serve.py`` (and its hot-reload poller) can serve the winner
    of a PBT run directly.

    ``member=None`` picks the best member by the checkpoint's recorded
    PBT return EMA (falling back to member 0 when the run kept no
    ranking). Like :meth:`restore_actor_params` this is shape-from-disk:
    no abstract tree needed, and the replay rings are never touched.
    Returns ``(member, epoch)`` actually exported.
    """
    src = Checkpointer(src_directory, save_buffer=False)
    try:
        if epoch is None:
            epoch = src.latest_epoch()
        if epoch is None:
            raise FileNotFoundError(
                f"no checkpoints under {src.directory}"
            )
        import logging as _logging

        absl_logger = _logging.getLogger("absl")
        prev_level = absl_logger.level
        absl_logger.setLevel(_logging.ERROR)
        try:
            out = src._retry(
                lambda: src._mgr.restore(
                    epoch,
                    args=ocp.args.Composite(
                        train_state=ocp.args.StandardRestore(),
                        meta=ocp.args.JsonRestore(),
                    ),
                ),
                what=f"population restore (epoch {epoch})",
            )
        finally:
            absl_logger.setLevel(prev_level)
        meta = dict(out["meta"])
        population = int(meta.get("population", 1))
        if population < 2:
            raise ValueError(
                f"checkpoint at {src.directory} epoch {epoch} is not a "
                f"population checkpoint (population={population})"
            )
        if member is None:
            ema = (meta.get("pbt") or {}).get("return_ema")
            member = int(np.argmax(ema)) if ema else 0
        if not 0 <= member < population:
            raise ValueError(
                f"member {member} out of range for population "
                f"{population}"
            )
        member_state = extract_member(out["train_state"], member)
    finally:
        src.close()

    extra = {
        k: v for k, v in meta.items()
        if k not in ("epoch", "ckpt_format", "population", "pbt")
    }
    if "config" in extra:
        from torch_actor_critic_tpu.utils.config import SACConfig

        extra["config"] = SACConfig.from_json(extra["config"]).replace(
            population=1, pbt_every=0
        ).to_json()
    extra["exported_member"] = member
    extra["source_population"] = population
    dst = Checkpointer(dst_directory, save_buffer=False)
    try:
        dst.save(epoch, member_state, extra=extra, wait=True)
    finally:
        dst.close()
    return member, epoch


def _export_member_main(argv=None):
    """CLI: ``python -m torch_actor_critic_tpu.utils.checkpoint SRC DST
    [--member I] [--epoch E]`` — export a (best-by-default) population
    member for serving (docs/SCALING.md "Population training")."""
    import argparse

    p = argparse.ArgumentParser(
        description="Export one member of a population checkpoint as a "
        "standalone single-learner checkpoint."
    )
    p.add_argument("src", help="population checkpoint directory")
    p.add_argument("dst", help="output checkpoint directory")
    p.add_argument(
        "--member", type=int, default=None,
        help="member index (default: best by PBT return EMA)",
    )
    p.add_argument("--epoch", type=int, default=None)
    args = p.parse_args(argv)
    member, epoch = export_member_checkpoint(
        args.src, args.dst, member=args.member, epoch=args.epoch
    )
    print(f"exported member {member} (epoch {epoch}) -> {args.dst}")
    return member, epoch


if __name__ == "__main__":
    _export_member_main()
