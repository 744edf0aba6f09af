"""Policy-inference service CLI.

Serves a trained run's policy over HTTP with micro-batched, bucketed
TPU forwards and checkpoint hot-reload (torch_actor_critic_tpu/serve/;
docs/SERVING.md).

Two ways to point it at a model:

    # a tracked training run (runs/<experiment>/<run_id>, as train.py
    # writes and run_agent.py reads) — env/config are read from the run
    python serve.py --run <id> [--experiment Default] [--runs-root runs]

    # a bare Orbax checkpoint dir + explicit flat-obs geometry
    python serve.py --ckpt-dir /path/ckpts --obs-dim 17 --act-dim 6 \\
        --act-limit 1.0

Serving knobs: --port (0 = ephemeral, printed at startup), --max-batch,
--max-wait-ms (deadline before a partial batch flushes; group mode),
--batch-mode (continuous = admit-into-next-dispatch, default; group =
legacy boundary waiting), --buckets (comma list overriding the
power-of-two ladder), --poll-interval (checkpoint hot-reload cadence
in seconds; 0 disables), --devices (engine replicas in this process:
one per local device behind least-loaded dispatch; 'all' or an int).

Fleet mode (docs/SERVING.md "Fleet"): --fleet N spawns N worker
processes on ephemeral ports and fronts them with the health-gated
router on --port (membership ejection/re-admission, failover,
rolling /reload, aggregated /metrics); --router-poll sets the
membership poll cadence.

Overload & degradation knobs (docs/SERVING.md): --queue-capacity
(admission bound; past it /act answers 429 + Retry-After),
--breaker-threshold/--breaker-cooldown (consecutive engine failures
before the slot trips open; seconds before a half-open probe),
--reload-retries/--reload-retry-backoff (transient-IO retry for the
hot-reload watcher), --drain-timeout (SIGTERM graceful-drain flush
budget — admissions stop, accepted requests are answered, exit 0).

Endpoints: POST /act, GET /healthz, GET /metrics, POST /reload.
"""

from __future__ import annotations

import argparse
import json
import logging

logging.basicConfig(level=logging.INFO)
logger = logging.getLogger("serve")


def parse_arguments(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser("Batched policy-inference service.")
    src = p.add_argument_group("model source")
    src.add_argument("--run", type=str, default=None,
                     help="Tracked run id to serve (reads env + config)")
    src.add_argument("--experiment", default="Default")
    src.add_argument("--runs-root", default="runs")
    src.add_argument("--ckpt-dir", type=str, default=None,
                     help="Bare Orbax checkpoint dir (needs --obs-dim/"
                          "--act-dim for flat observations)")
    src.add_argument("--obs-dim", type=int, default=None)
    src.add_argument("--act-dim", type=int, default=None)
    src.add_argument("--act-limit", type=float, default=1.0)
    srv = p.add_argument_group("serving")
    srv.add_argument("--host", default="127.0.0.1")
    srv.add_argument("--port", type=int, default=8321)
    srv.add_argument("--max-batch", type=int, default=64)
    srv.add_argument("--max-wait-ms", type=float, default=2.0)
    srv.add_argument("--batch-mode", choices=("continuous", "group"),
                     default="continuous",
                     help="Batch collection: 'continuous' dispatches "
                          "whatever is queued the moment the engine "
                          "frees up (deadline-priority order); "
                          "'group' is the legacy boundary-waiting "
                          "compat mode (docs/SERVING.md)")
    srv.add_argument("--devices", default="1",
                     help="Engine replicas in THIS process: an int or "
                          "'all' for one replica per local device "
                          "behind a shared admission layer + "
                          "least-loaded dispatch (serve/fleet.py)")
    srv.add_argument("--submesh", default="1x1", metavar="TPxFSDP",
                     help="Sub-mesh serving (serve/sharded.py; "
                          "docs/SERVING.md 'Sharded serving'): carve "
                          "--devices into disjoint TPxFSDP device "
                          "groups, each hosting ONE GSPMD-sharded "
                          "policy replica — params sharded by the "
                          "training side's param_specs, so the model "
                          "only needs to FIT sharded. '1x1' (default) "
                          "keeps plain per-device replicas")
    srv.add_argument("--serve-precision", choices=("f32", "bf16", "int8"),
                     default="f32",
                     help="Numeric serving tier: f32 is pinned "
                          "bitwise-identical to the classic engine; "
                          "bf16 runs matmuls at the MXU's native "
                          "width; int8 serves per-channel "
                          "weight-quantized params (dequant in-graph)")
    aot = p.add_argument_group("cold start (docs/SERVING.md)")
    aot.add_argument("--warm-start", metavar="DIR", default=None,
                     help="Warm-start bundle dir (aot/bundle.py), or "
                          "'auto' for the checkpoint-adjacent default "
                          "(<ckpt parent>/warm_start). Warmup loads "
                          "the executables the bundle's build left in "
                          "the compile cache, so the first /act pays "
                          "ZERO live compiles; a fingerprint-"
                          "mismatched bundle is rejected loudly "
                          "(watchdog bundle_rejected) and serving "
                          "falls back to live compile")
    aot.add_argument("--compile-cache", action="store_true",
                     help="Turn on the persistent XLA compilation "
                          "cache (aot/cache.py) shared across "
                          "processes — fleet workers and restarts "
                          "compile once fleet-wide. It lives where "
                          "JAX_COMPILATION_CACHE_DIR says, else in "
                          "<checkout>/.jax_cache. Implied by "
                          "--warm-start")
    flt = p.add_argument_group("fleet (multi-process)")
    flt.add_argument("--fleet", type=int, default=0,
                     help="Spawn N serve.py worker processes and front "
                          "them with the health-gated fleet router on "
                          "--port (serve/router.py; docs/SERVING.md "
                          "'Fleet')")
    flt.add_argument("--router-poll", type=float, default=1.0,
                     help="Fleet membership /healthz poll interval "
                          "seconds")
    flt.add_argument("--warm-pool", type=int, default=0,
                     help="Keep N pre-forked WARM spare workers "
                          "(booted, warmed — from the bundle when "
                          "--warm-start is set) ready behind the "
                          "router; a dead worker is replaced by "
                          "drawing a spare instead of paying "
                          "spawn+compile (aot/prefork.py)")
    flt.add_argument("--obs", action="store_true",
                     help="Run-wide observability plane (obs/): an "
                          "ObsCollector thread scrapes the router and "
                          "every worker's /metrics on a fixed "
                          "interval, merges them, evaluates SLO "
                          "rules, and serves the merged view on its "
                          "own /metrics endpoint "
                          "(docs/OBSERVABILITY.md 'Run-wide plane')")
    flt.add_argument("--obs-port", type=int, default=0,
                     help="Port for the obs collector's own HTTP "
                          "endpoint (0 = ephemeral; printed in the "
                          "fleet startup JSON)")
    flt.add_argument("--obs-interval", type=float, default=2.0,
                     help="Obs collector scrape interval seconds")
    flt.add_argument("--slo-config", metavar="PATH", default=None,
                     help="JSON list of SLO rules for the obs "
                          "collector (obs/slo.py grammar; default: "
                          "built-in rule set)")
    flt.add_argument("--elastic", choices=("off", "on"), default="off",
                     help="SLO-driven elastic autoscaling (elastic/; "
                          "docs/RESILIENCE.md 'Elasticity'): an "
                          "ElasticController subscribes to the obs "
                          "collector's scrape windows — a breached "
                          "scale-out rule draws a warm spare into "
                          "rotation; sustained all-green windows "
                          "drain the newest worker (zero accepted "
                          "requests dropped). Needs --obs and "
                          "--warm-pool >= 1")
    flt.add_argument("--elastic-min", type=int, default=1,
                     help="Elastic lower replica bound (scale-in "
                          "never goes below it)")
    flt.add_argument("--elastic-max", type=int, default=4,
                     help="Elastic upper replica bound (breaches past "
                          "it are counted as bounded, not actuated)")
    flt.add_argument("--elastic-out-cooldown", type=float, default=10.0,
                     help="Per-rule scale-out cooldown seconds (a "
                          "second, different rule can still fire)")
    flt.add_argument("--elastic-in-cooldown", type=float, default=30.0,
                     help="Scale-in cooldown seconds")
    flt.add_argument("--elastic-in-windows", type=int, default=5,
                     help="Consecutive all-green scrape windows "
                          "required before a scale-in is considered "
                          "(hysteresis)")
    srv.add_argument("--buckets", type=str, default=None,
                     help="Comma-separated bucket sizes (default: powers "
                          "of two up to max-batch)")
    srv.add_argument("--poll-interval", type=float, default=5.0,
                     help="Checkpoint hot-reload poll seconds (0 = off)")
    srv.add_argument("--seed", type=int, default=0,
                     help="PRNG seed for sampled (non-deterministic) acting")
    srv.add_argument("--sanitize", choices=("off", "on"), default="off",
                     help="Runtime transfer sanitizer (docs/ANALYSIS.md): "
                          "'on' runs every engine forward under "
                          "jax.transfer_guard('disallow') with explicit "
                          "input placement, so an implicit host<->device "
                          "transfer on the hot path fails loudly instead "
                          "of taxing every request; 'off' (default) "
                          "leaves the serving path untouched")
    srv.add_argument("--request-timeout", type=float, default=30.0,
                     help="Per-connection socket timeout in seconds (a "
                          "stalled client frees its handler thread)")
    srv.add_argument("--act-timeout", type=float, default=30.0,
                     help="Max seconds to wait on the batcher before "
                          "answering 503 + Retry-After (also the "
                          "request deadline: expired requests are "
                          "purged, never forwarded)")
    ovl = p.add_argument_group("overload & degradation")
    ovl.add_argument("--queue-capacity", type=int, default=1024,
                     help="Admission bound on queued requests; past it "
                          "/act answers 429 + Retry-After instead of "
                          "growing the queue")
    ovl.add_argument("--breaker-threshold", type=int, default=5,
                     help="Consecutive engine failures (incl. "
                          "non-finite actions) before the slot's "
                          "circuit breaker trips open")
    ovl.add_argument("--breaker-cooldown", type=float, default=5.0,
                     help="Seconds an open breaker waits before a "
                          "half-open probe re-admits traffic")
    ovl.add_argument("--reload-retries", type=int, default=1,
                     help="Extra attempts (with backoff) for each "
                          "slot's hot-reload IO before the poll "
                          "reports an error")
    ovl.add_argument("--reload-retry-backoff", type=float, default=0.5,
                     help="Base backoff seconds between hot-reload "
                          "retries (doubles per attempt)")
    ovl.add_argument("--drain-timeout", type=float, default=30.0,
                     help="SIGTERM graceful-drain flush budget in "
                          "seconds (answer everything accepted, then "
                          "exit 0)")
    fwl = p.add_argument_group("data flywheel (docs/REPLAY.md)")
    fwl.add_argument("--log-transitions", metavar="DIR", default=None,
                     help="Log served transitions (obs/action from "
                          "/act, outcome from POST /outcome) into a "
                          "replay disk tier at DIR — the same chunk "
                          "format train.py --offline consumes")
    fwl.add_argument("--log-sample-every", type=int, default=1,
                     help="Keep every Nth answered /act (traffic "
                          "downsampling; 1 = keep all)")
    fwl.add_argument("--log-max-bytes", type=int, default=0,
                     help="Disk-tier byte budget for the transition "
                          "log; oldest chunk files rotate out past it "
                          "(0 = unbounded)")
    srv.add_argument("--trace-export", metavar="PATH", default=None,
                     help="Write a Perfetto (chrome://tracing) trace "
                          "to PATH at exit: per-request serving spans "
                          "(queue/collect/forward/respond under their "
                          "X-Request-Id) plus XLA compile events on "
                          "one timeline (docs/OBSERVABILITY.md)")
    return p.parse_args(argv)


def _resolve_model(args):
    """(actor_def, obs_spec, act_dim, act_limit, ckpt_dir) from the
    CLI's model source."""
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.sac.trainer import build_models
    from torch_actor_critic_tpu.utils.config import SACConfig

    if args.run is not None:
        from torch_actor_critic_tpu.envs.vec_env import make_env_pool
        from torch_actor_critic_tpu.utils.tracking import Tracker

        tracker = Tracker.load(
            args.run, experiment=args.experiment, root=args.runs_root
        )
        params = tracker.params()
        env_name = params.get("environment", "Humanoid-v5")
        config = SACConfig.from_json(json.dumps(params.get("config", {})))
        # One throwaway env just for its specs (obs/act geometry and
        # limit); closed before serving starts.
        pool = make_env_pool(env_name, 1, base_seed=0)
        try:
            obs_spec, act_dim, act_limit = (
                pool.obs_spec, pool.act_dim, pool.act_limit
            )
        finally:
            pool.close()
        ckpt_dir = str(tracker.artifact_path("checkpoints"))
        logger.info("serving run %s (%s)", args.run, env_name)
    else:
        if args.ckpt_dir is None:
            raise SystemExit("pass --run or --ckpt-dir (see --help)")
        if args.obs_dim is None or args.act_dim is None:
            raise SystemExit("--ckpt-dir needs --obs-dim and --act-dim")
        # Model geometry (hidden sizes, algorithm family, ...) comes
        # from the checkpoint's own metadata — the trainer stores its
        # config JSON alongside the arrays, so a bare dir serves with
        # the architecture that produced it, not CLI defaults.
        from torch_actor_critic_tpu.utils.checkpoint import Checkpointer

        probe = Checkpointer(args.ckpt_dir, save_buffer=False)
        try:
            meta = probe.peek_meta()
        finally:
            probe.close()
        config = (
            SACConfig.from_json(meta["config"])
            if meta.get("config") else SACConfig()
        )
        obs_spec = jax.ShapeDtypeStruct((args.obs_dim,), jnp.float32)
        act_dim, act_limit = args.act_dim, args.act_limit
        ckpt_dir = args.ckpt_dir

    class _Spec:
        pass

    _Spec.obs_spec = obs_spec
    _Spec.act_dim = act_dim
    _Spec.act_limit = act_limit
    actor_def, _ = build_models(config, _Spec)
    return actor_def, obs_spec, act_dim, act_limit, ckpt_dir


def _worker_argv(argv, worker: int | None = None):
    """The child argv for one fleet worker: the parent's args minus the
    fleet flags, with an ephemeral port (each worker prints its real
    address on stdout; the parent reads it back). A transition-log dir
    becomes per-worker (``DIR/worker-N``) — disk-tier chunk sequence
    numbers are per-directory, so two workers must never share one."""
    import os
    import sys

    src = list(sys.argv[1:] if argv is None else argv)
    take_value = (
        "--fleet", "--port", "--router-poll", "--warm-pool",
        "--obs-port", "--obs-interval", "--slo-config",
        "--elastic", "--elastic-min", "--elastic-max",
        "--elastic-out-cooldown", "--elastic-in-cooldown",
        "--elastic-in-windows",
    )
    out, skip = [], False
    for a in src:
        if skip:
            skip = False
            continue
        if a in take_value:
            skip = True
            continue
        if a == "--obs":
            continue
        if a.split("=", 1)[0] in take_value:
            continue
        out.append(a)
    if worker is not None:
        for i, a in enumerate(out):
            if a == "--log-transitions" and i + 1 < len(out):
                out[i + 1] = os.path.join(out[i + 1], f"worker-{worker}")
            elif a.startswith("--log-transitions="):
                base = a.split("=", 1)[1]
                out[i] = "--log-transitions=" + os.path.join(
                    base, f"worker-{worker}"
                )
    return out + ["--port", "0"]


def _await_worker_ready(proc, idx: int, timeout_s: float = 300.0):
    """Read the worker's startup JSON line off its stdout and return
    its serving address; raises RuntimeError if the worker dies or
    stays silent past the deadline. On success a daemon pump thread
    keeps draining the pipe (a full pipe would wedge the worker)."""
    import threading
    import time

    address, deadline = None, time.time() + timeout_s
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"fleet worker {idx} exited rc={proc.returncode} "
                    "before becoming ready"
                )
            time.sleep(0.1)
            continue
        if line.startswith("{"):
            try:
                address = json.loads(line)["serving"]
                break
            except (json.JSONDecodeError, KeyError):
                continue
    if address is None:
        raise RuntimeError(f"fleet worker {idx} never printed its address")

    def _pump(stream=proc.stdout, i=idx):
        for out_line in stream:
            logger.debug("worker %d: %s", i, out_line.rstrip())

    threading.Thread(target=_pump, daemon=True).start()
    return address


def _spawn_worker(argv, idx: int, chip: int | None = None):
    """Launch one serve.py worker subprocess (ephemeral port) — the
    spawn half of warm-pool/replacement spawns; readiness is awaited
    separately (or by the caller via _await_worker_ready). ``chip``
    is the local chip the worker is shown, and the only one: a chip
    belongs to one process at a time, so workers that inherited one
    environment would all reach for the same (every) chip. ``None``
    (a CPU host) inherits the environment unchanged."""
    import os
    import subprocess
    import sys

    from torch_actor_critic_tpu.utils.procenv import chip_env

    here = os.path.dirname(os.path.abspath(__file__))
    return subprocess.Popen(
        [sys.executable, os.path.join(here, "serve.py")]
        + _worker_argv(argv, worker=idx),
        stdout=subprocess.PIPE, stderr=None, text=True, cwd=here,
        env=None if chip is None else chip_env(chip),
    )


def _local_chips() -> int | None:
    """How many accelerator chips this host has for workers, or None on
    a CPU host. Asked of a short-lived child that exits before any
    worker starts, so the router process itself never holds a chip."""
    import os
    import subprocess
    import sys

    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.local_devices(); "
         "print(d[0].platform, len(d))"],
        capture_output=True, text=True, timeout=300,
    )
    if probe.returncode != 0:
        raise SystemExit(
            "--fleet could not ask JAX for this host's devices: "
            + probe.stderr[-500:]
        )
    platform, count = probe.stdout.split()[-2:]
    return None if platform == "cpu" else int(count)


class _ChipLeases:
    """One chip per live worker. A worker is started on the lowest
    local chip whose last tenant has exited; on a CPU host
    (``chips=None``) workers simply inherit the environment."""

    def __init__(self, chips: int | None):
        import threading

        self.chips = chips
        self._tenant = {}  # chip -> Popen; guarded-by: _lock
        self._lock = threading.Lock()

    def check(self, args) -> None:
        """Refuse, before anything is spawned, a fleet that would ever
        need more workers alive than there are chips."""
        if self.chips is None:
            return
        replicas = max(
            args.fleet, args.elastic_max if args.elastic == "on" else 0
        )
        need = replicas + args.warm_pool
        if need > self.chips:
            raise SystemExit(
                f"--fleet needs {need} chips ({replicas} serving worker(s)"
                f" + {args.warm_pool} warm spare(s)), one for each worker "
                f"process, but this host has {self.chips}: lower --fleet"
                " / --warm-pool / --elastic-max, or serve several "
                "devices from one process with --devices"
            )

    def spawn(self, argv, idx: int):
        if self.chips is None:
            return _spawn_worker(argv, idx)
        with self._lock:
            chip = next(
                (
                    c for c in range(self.chips)
                    if c not in self._tenant
                    or self._tenant[c].poll() is not None
                ),
                None,
            )
            if chip is None:
                raise RuntimeError(
                    f"all {self.chips} chips have a live worker; worker "
                    f"{idx} cannot start"
                )
            self._tenant[chip] = proc = _spawn_worker(argv, idx, chip)
            return proc


def run_fleet(args, argv):
    """``--fleet N``: spawn N workers, front them with the router.

    Each worker is a full ``serve.py`` process (own engines, own
    drain/breaker/reload machinery) on an ephemeral port; the router
    owns membership and rolling reload (docs/SERVING.md "Fleet").
    SIGTERM to THIS process rolls the whole fleet down gracefully:
    workers get SIGTERM (their drain answers everything accepted),
    then the router stops. A worker dying on its own is NOT fatal —
    membership ejects it and the survivors keep serving; with
    ``--warm-pool N`` a pre-forked warm spare (already listening and
    warmed, from the bundle when ``--warm-start`` is set) is drawn to
    replace it, so kill-replacement costs a queue-pop instead of
    spawn+compile."""
    import itertools
    import signal
    import subprocess
    import threading

    from torch_actor_critic_tpu.serve.router import FleetRouter

    if args.elastic == "on":
        if not args.obs:
            raise SystemExit(
                "--elastic on needs --obs (the controller consumes "
                "the obs collector's SLO scrape windows)"
            )
        if args.warm_pool < 1:
            raise SystemExit(
                "--elastic on needs --warm-pool >= 1 (scale-out "
                "draws warm spares; it never cold-spawns on the "
                "serving path)"
            )

    leases = _ChipLeases(_local_chips())
    leases.check(args)
    workers, worker_lock = [], threading.Lock()
    for i in range(args.fleet):
        workers.append(leases.spawn(argv, i))
    addresses = [
        _await_worker_ready(proc, i) for i, proc in enumerate(workers)
    ]
    logger.info("fleet up: %d workers %s", len(addresses), addresses)

    span_log = None
    if args.trace_export:
        from torch_actor_critic_tpu.telemetry.traceview import RequestSpanLog

        span_log = RequestSpanLog()
    router = FleetRouter(
        addresses, host=args.host, port=args.port,
        poll_interval_s=args.router_poll,
        request_timeout_s=args.request_timeout,
        span_log=span_log,
    )
    router.poll_once()

    # Run-wide observability plane (docs/OBSERVABILITY.md): one
    # collector thread scrapes the router's aggregated /metrics plus
    # every worker's own /metrics, merges them, and evaluates SLO
    # rules.  A worker dying mid-scrape is a counted scrape failure,
    # never a collector crash.
    obs = None
    if args.obs:
        from torch_actor_critic_tpu.obs import (
            ObsCollector,
            http_source,
            load_rules,
        )

        obs = ObsCollector(
            interval_s=args.obs_interval,
            port=args.obs_port,
            rules=load_rules(args.slo_config) if args.slo_config else None,
        )
        obs.add_source("router", http_source(router.address))
        for i, addr in enumerate(addresses):
            obs.add_source(f"w{i}", http_source(addr))
        obs.start()
        logger.info("obs collector serving on %s", obs.address)

    # Pre-forked warm spares (aot/prefork.py): each spare is a fully
    # booted, warmed worker waiting off-rotation; the monitor below
    # draws one the moment a live worker dies.
    pool = None
    scaler = controller = decision_log = None
    worker_names = {}  # id(proc) -> router worker name (monitor thread)
    monitor_stop = threading.Event()
    if args.warm_pool > 0:
        from torch_actor_critic_tpu.aot import WarmPool

        spare_idx = itertools.count(args.fleet)

        def _spawn_spare():
            idx = next(spare_idx)
            proc = leases.spawn(argv, idx)
            return proc, _await_worker_ready(proc, idx)

        def _kill_worker(proc):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=args.drain_timeout + 30)
                except subprocess.TimeoutExpired:
                    proc.kill()

        pool = WarmPool(_spawn_spare, _kill_worker, size=args.warm_pool)

        # SLO-driven elasticity (elastic/; docs/RESILIENCE.md): the
        # controller rides the obs scrape thread via window_hook —
        # with --elastic off the hook stays None and the scrape loop
        # pays a single is-None pointer check per window (no threads,
        # no sockets, no metric keys: the off-parity contract).
        if args.elastic == "on":
            from torch_actor_critic_tpu.elastic import (
                DecisionLog,
                ElasticController,
                ElasticPolicy,
                FleetScaler,
            )

            decision_log = DecisionLog()

            def _on_drain_select(name, proc):
                # Scale-in victim: disown it from the warm-pool
                # monitor's tracking BEFORE it is SIGTERMed, so its
                # post-drain exit never reads as a crash the monitor
                # would "replace" from the pool (a drain->replace flap
                # that burns spares and negates the scale-in).
                worker_names.pop(id(proc), None)
                with worker_lock:
                    try:
                        workers.remove(proc)
                    except ValueError:
                        pass  # elastic-spawned: never monitor-tracked

            scaler = FleetScaler(
                router, pool, obs=obs,
                drain_exit_timeout_s=args.drain_timeout + 30,
                obs_source=http_source,
                on_drain_select=_on_drain_select,
            )
            for i, (proc, addr) in enumerate(zip(workers, addresses)):
                worker_names[id(proc)] = f"w{i}"
                scaler.register(f"w{i}", proc, addr)
            controller = ElasticController(
                scaler,
                policy=ElasticPolicy(
                    min_replicas=args.elastic_min,
                    max_replicas=args.elastic_max,
                    scale_out_cooldown_s=args.elastic_out_cooldown,
                    scale_in_cooldown_s=args.elastic_in_cooldown,
                    scale_in_ok_windows=args.elastic_in_windows,
                ),
                log=decision_log, plane="serve",
            )
            obs.window_hook = controller.observe_window
            logger.info(
                "elastic controller on: replicas [%d, %d], out-cooldown "
                "%.1fs, in after %d green windows + %.1fs cooldown",
                args.elastic_min, args.elastic_max,
                args.elastic_out_cooldown, args.elastic_in_windows,
                args.elastic_in_cooldown,
            )

        def _monitor():
            handled = set()
            while not monitor_stop.wait(max(args.router_poll, 0.2)):
                with worker_lock:
                    dead = [
                        p for p in workers
                        if p.poll() is not None and id(p) not in handled
                    ]
                for proc in dead:
                    handled.add(id(proc))
                    if scaler is not None:
                        # The scaler must stop counting the corpse as
                        # a replica before the controller's next
                        # window, or scale-out math runs against a
                        # phantom worker.
                        dead_name = worker_names.pop(id(proc), None)
                        if dead_name is not None:
                            scaler.forget(dead_name)
                    drawn = pool.draw(timeout=30.0)
                    if drawn is None:
                        logger.warning(
                            "worker pid %d died and no warm spare was "
                            "ready; relying on surviving workers",
                            proc.pid,
                        )
                        continue
                    with worker_lock:
                        workers.append(drawn.handle)
                    name = router.add_worker(drawn.address)
                    worker_names[id(drawn.handle)] = name
                    if scaler is not None:
                        scaler.register(name, drawn.handle, drawn.address)
                    if obs is not None:
                        obs.add_source(name, http_source(drawn.address))
                    logger.info(
                        "worker pid %d died; warm spare admitted as %s "
                        "at %s (pool: %s)",
                        proc.pid, name, drawn.address, pool.stats(),
                    )

        threading.Thread(
            target=_monitor, name="warm-pool-monitor", daemon=True
        ).start()

    # Satellite /metrics surface: with a warm pool (and, on top, the
    # elastic controller) the router's aggregated /metrics grows a
    # "fleet" section — spare readiness + last-refill status, scaler
    # counters, controller snapshot. Both features off leaves
    # fleet_extra None and the key absent (off-parity pin).
    if pool is not None:
        def _fleet_extra():
            out = {"warm_pool": pool.stats()}
            if scaler is not None:
                out["scaler"] = scaler.stats()
            if controller is not None:
                out["elastic"] = controller.snapshot()
            return out

        router.fleet_extra = _fleet_extra

    def _teardown(signum=None, frame=None):
        monitor_stop.set()
        if pool is not None:
            pool.shutdown()
        with worker_lock:
            procs = list(workers)
        if scaler is not None:
            # Elastic-spawned workers live in the scaler's registry,
            # not the spawn-order list; sweep them into the same
            # SIGTERM drain (dedup by identity — the initial fleet is
            # registered in both).
            known = {id(p) for p in procs}
            procs.extend(
                h for h in scaler.handles() if id(h) not in known
            )
        logger.info("fleet teardown: draining %d workers", len(procs))
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in procs:
            try:
                proc.wait(timeout=args.drain_timeout + 30)
            except subprocess.TimeoutExpired:
                proc.kill()
        if scaler is not None:
            scaler.shutdown(join_timeout=5.0)
        router._httpd.shutdown()

    signal.signal(signal.SIGTERM, lambda s, f: threading.Thread(
        target=_teardown, daemon=True).start())
    with worker_lock:
        pids = [proc.pid for proc in workers]
    print(json.dumps({
        "router": router.address,
        "workers": dict(zip(
            (f"w{i}" for i in range(len(addresses))), addresses
        )),
        "pids": pids,
        "warm_pool": pool.stats() if pool is not None else None,
        "obs": obs.address if obs is not None else None,
        "elastic": args.elastic,
    }), flush=True)
    try:
        router.serve_forever()
    finally:
        _teardown()
        if obs is not None:
            obs.close()
            for line in obs.slo.report().splitlines():
                logger.info("%s", line)
        if args.trace_export and span_log is not None:
            from torch_actor_critic_tpu.telemetry.traceview import (
                elastic_decision_events,
                export_trace,
                router_hop_events,
            )

            event_groups = [router_hop_events(span_log.records())]
            if decision_log is not None:
                event_groups.append(
                    elastic_decision_events(decision_log.records())
                )
            summary = export_trace(args.trace_export, *event_groups)
            logger.info(
                "router trace exported to %s (%d hop spans, %d "
                "elastic spans)",
                summary["path"], summary["router_spans"],
                summary.get("elastic_spans", 0),
            )


def main(argv=None):
    args = parse_arguments(argv)
    if args.fleet and args.fleet > 0:
        run_fleet(args, argv)
        return
    from torch_actor_critic_tpu.serve import (
        CircuitBreaker,
        ModelRegistry,
        PolicyServer,
        install_drain_handler,
    )

    actor_def, obs_spec, act_dim, act_limit, ckpt_dir = _resolve_model(args)
    buckets = (
        [int(b) for b in args.buckets.split(",")] if args.buckets else None
    )

    # Cold-start machinery (docs/SERVING.md "Cold start & warm-start
    # bundles"): arm the persistent compilation cache and load the
    # warm-start bundle BEFORE any engine is built, so every serve
    # program this process compiles either hits the cache or is
    # persisted for the next worker. An incompatible bundle is
    # rejected loudly + counted, never trusted.
    bundle = None
    if args.warm_start:
        from torch_actor_critic_tpu.aot import (
            BundleMismatchError,
            default_bundle_dir,
            load_bundle,
        )
        from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

        bundle_dir = (
            default_bundle_dir(ckpt_dir) if args.warm_start == "auto"
            else args.warm_start
        )
        try:
            bundle = load_bundle(bundle_dir)
            bundle.check()
        except FileNotFoundError as e:
            logger.warning("no warm-start bundle: %s", e)
            bundle = None
        except BundleMismatchError as e:
            get_watchdog().note_bundle_rejected(str(bundle_dir) + ": " + e.reason)
            bundle = None
    if args.compile_cache or bundle is not None:
        from torch_actor_critic_tpu.aot import enable_persistent_cache

        # A bundle is the index of programs its build left in this
        # cache: reads make warmup compile-free; writes (boot-time
        # host programs) accrete for the next worker.
        enable_persistent_cache()

    try:
        tp, fsdp = (int(x) for x in args.submesh.lower().split("x"))
    except ValueError:
        raise SystemExit(
            f"--submesh wants TPxFSDP (e.g. 2x2), got {args.submesh!r}"
        ) from None
    submesh = (tp, fsdp) if (tp, fsdp) != (1, 1) else None
    sharded = submesh is not None or args.serve_precision != "f32"

    # Direct-to-sharded hot-reload (docs/SERVING.md "Sharded serving"):
    # with a sub-mesh, Orbax restores actor arrays straight into the
    # first replica's NamedSharding layout — no host-RAM gather of a
    # model that may not fit one host; further replicas reshard
    # device-to-device via their generation-keyed placement.
    restore_shardings = None
    if submesh is not None:
        import jax

        from torch_actor_critic_tpu.parallel.sharding import (
            make_submesh,
            named_param_shardings,
        )

        mesh0 = make_submesh(jax.local_devices()[: tp * fsdp], tp, fsdp)
        restore_shardings = (
            lambda abstract: named_param_shardings(abstract, mesh0)
        )

    registry = ModelRegistry(
        reload_retries=args.reload_retries,
        reload_retry_backoff_s=args.reload_retry_backoff,
        restore_shardings=restore_shardings,
        sanitize=args.sanitize == "on",
    )
    info = registry.register(
        "default", actor_def, obs_spec,
        ckpt_dir=ckpt_dir, max_batch=args.max_batch, buckets=buckets,
        breaker=CircuitBreaker(
            fail_threshold=args.breaker_threshold,
            cooldown_s=args.breaker_cooldown,
        ),
        # In sharded mode the per-sub-mesh engines (warmed by the
        # fleet below) serve every forward; warming the registry's
        # single-device engine too would just buy unused compiles.
        warmup=not sharded,
        # Sharded programs are honestly NOT bundled (mesh-shaped
        # executables; ENTRY_POINT_CONTRACTS bundleable=False) — they
        # ride the persistent cache only.
        bundle=bundle if not sharded else None,
    )
    logger.info("model loaded: %s", info)
    if args.poll_interval > 0:
        registry.start_polling(args.poll_interval)

    span_log = None
    if args.trace_export:
        from torch_actor_critic_tpu.telemetry.traceview import RequestSpanLog

        span_log = RequestSpanLog()

    if args.devices == "all":
        import jax

        devices = len(jax.local_devices())
    else:
        devices = int(args.devices)
    if sharded and devices % (tp * fsdp) != 0:
        raise SystemExit(
            f"--devices {devices} does not divide into --submesh "
            f"{tp}x{fsdp} groups of {tp * fsdp}"
        )
    transition_logger = None
    if args.log_transitions:
        from torch_actor_critic_tpu.replay import TransitionLogger

        transition_logger = TransitionLogger(
            args.log_transitions, obs_spec, act_dim,
            act_limit=act_limit,
            sample_every=args.log_sample_every,
            max_bytes=args.log_max_bytes,
        )
        logger.info(
            "transition flywheel: logging 1/%d served acts to %s "
            "(budget %s)",
            args.log_sample_every, args.log_transitions,
            args.log_max_bytes or "unbounded",
        )
    server = PolicyServer(
        registry, host=args.host, port=args.port,
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        seed=args.seed,
        request_timeout_s=args.request_timeout,
        act_timeout_s=args.act_timeout,
        capacity=args.queue_capacity,
        span_log=span_log,
        mode=args.batch_mode,
        devices=(
            devices if (devices > 1 or sharded) else None
        ),
        submesh=submesh,
        precision=args.serve_precision,
        transition_logger=transition_logger,
    )
    # Rolling-restart contract: SIGTERM stops admissions, answers every
    # accepted request, then serve_forever returns and we exit 0.
    install_drain_handler(server, flush_timeout_s=args.drain_timeout)
    import jax

    devs = jax.devices()
    print(json.dumps({
        "serving": server.address, "slots": registry.slots(),
        # What this worker's forwards run on, as JAX reports it.
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    }), flush=True)
    try:
        server.serve_forever()
    finally:
        if transition_logger is not None:
            # Flush the partial chunk so a drained worker's last
            # transitions reach the dataset.
            transition_logger.close()
        if args.trace_export:
            from torch_actor_critic_tpu.diagnostics.watchdog import (
                get_watchdog,
            )
            from torch_actor_critic_tpu.telemetry.traceview import (
                compile_events,
                export_trace,
                serve_request_events,
            )

            summary = export_trace(
                args.trace_export,
                serve_request_events(span_log.records()),
                compile_events(get_watchdog().compile_log()),
            )
            logger.info(
                "trace exported to %s (%d request spans) — load at "
                "chrome://tracing or https://ui.perfetto.dev",
                summary["path"], summary["serve_spans"],
            )


if __name__ == "__main__":
    main()
