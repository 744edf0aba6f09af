"""The ``train.py`` main path: ``Trainer.train`` with a host env for every
member of a population.

One window is what the Trainer does between two window boundaries: stage the
last ``update_every`` lockstep steps into a chunk, place it, dispatch one
vmapped burst of ``update_every`` updates for every member, then act (on the
host, against a mirror of the parameters, which waits for the burst) and step
every member's env ``update_every`` times.  ``Trainer.train`` itself runs, on
a thread of its own, and is let through one boundary at a time at its
``_drain_window`` seam; it is stopped through its ``preemption`` seam.  The
rings are made full on the device from the seed; warm-up (``start_steps``,
``update_after``) is skipped by entering the loop at a step past both, as a
resumed run does.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from benchmark.drivers import _common
from benchmark.harness import check, data, draws


class _Stopper:
    """The attributes ``Trainer.train`` reads off a preemption guard."""

    urgent = False
    triggered = False


class Driver(_common.FollowedCall):
    def __init__(self, cell, config, seed, spans, overrides=None):
        self.cell, self.config, self.seed, self.spans = cell, config, seed, spans
        self.overrides = overrides or {}
        self.boundaries = 0
        self.first_chunk = None

    def setup(self) -> None:
        from torch_actor_critic_tpu.core.types import BufferState
        from torch_actor_critic_tpu.parallel.mesh import make_mesh
        from torch_actor_critic_tpu.sac.trainer import Trainer
        from torch_actor_critic_tpu.telemetry.recorder import TelemetryRecorder

        self.spans.lap("setup/import")
        traffic = self.cell["traffic"]
        self.cap = traffic["ring_rows"]
        self.per_call = traffic.get("windows_per_call", 1)
        # The Trainer allocates a zero ring of buffer_size rows per member on
        # the host and copies it over; it is given a one-window ring and
        # handed the full one below, which keeps set-up short and the memory
        # peak the training loop's own.
        self.cfg = _common.sac_config(
            self.config, self.cell,
            {**(self.overrides.get("sac") or {}), "epochs": 1, "save_every": 10**9},
        )
        self.n = self.cfg.population
        self.every = self.cfg.update_every
        self.cfg.buffer_size = self.every
        driver = self

        class BenchTrainer(Trainer):
            def _drain_window(self, staging):
                driver._boundary()
                chunk = super()._drain_window(staging)
                if driver.first_chunk is None:
                    driver.first_chunk = chunk
                return chunk

        self.stopper = _Stopper()
        self.recorder = (
            TelemetryRecorder(run_dir=None) if self.spans.annotate else None
        )
        self.gate = None
        self.trainer = BenchTrainer(
            traffic["env"], config=self.cfg,
            mesh=make_mesh(dp=1, devices=jax.devices()[:1]),
            seed=int(self.seed) % (2**31 - 1), preemption=self.stopper,
            telemetry=self.recorder,
        )
        tr = self.trainer
        self.cfg.buffer_size = self.cap
        self.act_dim = tr.pool.act_dim

        self.rng0 = jax.random.split(data.state_key(self.seed, 0), self.n)
        example_obs = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), tr.pool.obs_spec
        )
        actor0, critic0 = _common.seeded_params(tr.sac, example_obs, self.seed, members=self.n)
        tr.state = _common.with_params(tr.state, actor0, critic0, self.rng0)
        self.actor0, self.critic0 = jax.device_get((actor0, critic0))

        self.spans.lap("setup/build_trainer")
        # Stored as the Trainer's own ring stores it; the seeded rows are a
        # transition's (harness/data.py).
        ring_abs = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((self.n, self.cap) + x.shape[2:], x.dtype),
            tr.buffer.data,
        )
        self.rows = data.transition_rows(ring_abs, tr.pool.obs_spec, self.act_dim)
        tr.buffer = None
        ring = data.fill_transitions(
            data.data_key(self.seed, 2), ring_abs,
            slab=traffic.get("fill_slab_rows", 65536), rows=self.rows,
        )
        tr.buffer = BufferState(
            data=ring, ptr=jnp.zeros(self.n, jnp.int32),
            size=jnp.full(self.n, self.cap, jnp.int32),
        )

        self.spans.lap("setup/fill_ring")
        _, self.idx, self.eps_q, self.eps_pi = jax.jit(jax.vmap(
            lambda k: draws.burst_draws(
                k, self.cfg.updates_per_window, self.cfg.batch_size, self.act_dim, self.cap
            )
        ))(self.rng0)
        self.pre_rows = _common.gather_rows(tr.buffer.data, self.idx, self.rows)

        self.spans.lap("setup/draws_and_rows")
        # First call: one epoch of one window, entered past start_steps and
        # update_after; its burst is the one the reference follows.
        self.step = max(self.cfg.start_steps, self.cfg.update_after)
        self.step += -self.step % self.every
        metrics = self._train(epochs=1, steps_per_epoch=self.every)
        self.spans.lap("setup/first_call")
        self.first = _common.learner_snapshot(tr.state, metrics)
        # Then one whole epoch of the long run's length: the Trainer's
        # epoch-end programs (sentinel, loss means) compile anew for every
        # number of bursts in an epoch, and nothing may compile in the window.
        # After it the long run, parked at its first boundary.
        self._train(epochs=1, steps_per_epoch=traffic["steps_per_epoch"])
        self.spans.lap("setup/warm_epoch")
        self.cfg.epochs, self.cfg.steps_per_epoch = 10**6, traffic["steps_per_epoch"]
        tr._resume_step, tr.start_epoch = self.step, tr.start_epoch + 1
        self.gate = (threading.Semaphore(0), threading.Semaphore(0))
        self.thread = threading.Thread(target=self._long_run, daemon=True)
        self.error = None
        self.thread.start()
        self.gate[1].acquire()
        self.spans.lap("setup/park_long_run")

    def _train(self, epochs: int, steps_per_epoch: int) -> dict:
        tr = self.trainer
        self.cfg.epochs, self.cfg.steps_per_epoch = epochs, steps_per_epoch
        tr._resume_step = self.step
        metrics = tr.train()
        self.step += epochs * steps_per_epoch
        tr.start_epoch += epochs
        return metrics

    def _long_run(self) -> None:
        from torch_actor_critic_tpu.resilience.preemption import Preempted

        try:
            self.trainer.train()
        except Preempted:
            pass
        except BaseException as e:  # noqa: BLE001 — reported by window()
            self.error = e
        finally:
            self.gate[1].release()

    def _boundary(self) -> None:
        """Called by the Trainer at every window boundary."""
        self.boundaries += 1
        if self.gate is not None:
            self.gate[1].release()
            self.gate[0].acquire()

    def window(self) -> None:
        """``windows_per_call`` of the Trainer's windows: one whole epoch, so
        that every measured window holds the same work, its epoch's end (the
        sentinel, every env reset) included."""
        for _ in range(self.per_call):
            self.gate[0].release()
            self.gate[1].acquire()
            if self.error is not None:
                raise self.error

    def per_window(self) -> dict:
        return {
            "grad_steps": self.per_call * self.n * self.cfg.updates_per_window,
            "env_steps": self.per_call * self.n * self.every,
        }

    def host_spans(self):
        """The Trainer's own phase spans (PhaseTimer, host clock), for the
        traced run's idle-gap owners and ``host.env_act_share``."""
        if self.recorder is None:
            return []
        names = self.recorder.phases
        return [(names[p], t0, dur) for p, t0, dur in self.recorder.ring.spans()]

    def free(self) -> None:
        tr = self.trainer
        self.stopper.urgent = True
        self.gate[0].release()
        self.thread.join(timeout=120)
        self.final = jax.device_get({"step": tr.state.step, "ptr": tr.buffer.ptr})
        self.bursts = self.boundaries
        tr.close()
        tr.state = tr.buffer = None

    def check(self, mode: str = "highest"):
        n_updates = self.cfg.updates_per_window
        out = [
            check.Comparison(
                "thread_stopped", 1.0 if self.thread.is_alive() else 0.0, 0.0, "exact"
            )
        ] + self.counter_checks(
            self.final["step"], self.final["ptr"], self.bursts, n_updates,
            self.every, self.cap,
        )
        visible = jnp.full((n_updates,), self.every)
        rows = _common.member_rows(self.pre_rows, self.first_chunk, self.idx, self.cap, visible)
        return out + self.compare_first_call(
            mode, rows, _common.with_stream_axis(self.eps_q),
            _common.with_stream_axis(self.eps_pi), True,
        )
