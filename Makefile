# Capability twin of the reference Makefile (ref Makefile:1-28): test
# runner plus operational helpers. The reference's mlflow/tensorboard/
# dvc/prefect UI stubs map to the file-based tracking under runs/.

.PHONY: test test-fast chip-smoke parity multihost serve serve-smoke trace-smoke diag-smoke pop-smoke mesh-smoke cost-smoke fault-smoke chaos-smoke fleet-smoke shard-serve-smoke decouple-smoke visual-smoke sanitize-smoke scenario-smoke replay-smoke coldstart-smoke obs-smoke elastic-smoke dryrun lint native native-asan clean

# Full matrix, slow tests included (CI runs this).
test:
	python -m pytest tests/ -q

# Iteration default: skips the @pytest.mark.slow tests (multi-process
# launches, real-environment and long training runs, the native ASan
# build: pyproject.toml says what the marker means) and the composer
# wall-runner construction, and stops at the first failure.
# The run the driver makes and counts (ROADMAP.md "Tier-1 verify") is
# this selection on six workers, one file to a worker, cut at 1,470 s:
#   JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow" -n 6 --dist loadfile
# A file is the unit of work there, so time a new test file alone
# under that load before committing it; one over 240 s is split by
# subject or shares its compiles (a test itself fails at 600 s:
# tests/conftest.py).
test-fast:
	python -m pytest tests/ -q -x -m "not slow" --ignore=tests/test_wall_runner_env.py

# The chip: the main path end to end at full width — train, serve,
# every Pallas kernel against its reference, a fused epoch. Needs a
# TPU (non-zero exit and no verdict line without one); run it through
# the chip tool. `python chip_smoke.py --chips 4` is the dp=4 mesh.
chip-smoke:
	python chip_smoke.py

# Return-parity runs vs the shared torch baseline (see PARITY.md).
parity:
	python scripts/parity_run.py --impl torch --env Pendulum-v1 \
		--steps 30000 --out runs_parity/torch_pendulum.jsonl
	python scripts/parity_run.py --impl jax --env Pendulum-v1 \
		--steps 30000 --out runs_parity/jax_pendulum.jsonl

# 2-process distributed dryrun (initialize_multihost, collective saves).
multihost:
	python -m pytest tests/test_multihost.py -q

# Serve a tracked run over HTTP (RUN=<id>; see docs/SERVING.md).
serve:
	python serve.py --run $(RUN)

# CI smoke: checkpoint -> serve.py CLI on a random port -> /act +
# /healthz round-trip; exits nonzero on failure.
serve-smoke:
	JAX_PLATFORMS=cpu python scripts/serve_smoke.py

# Observability smoke: tiny CPU run with telemetry + a --profile-epochs
# window; asserts the JSONL event stream, the XLA trace artifacts and
# the phase-coverage contract (docs/OBSERVABILITY.md).
trace-smoke:
	JAX_PLATFORMS=cpu python scripts/trace_smoke.py

# Learning-health diagnostics smoke: short full-tier CPU train;
# asserts every diagnostic key is present, finite and schema-valid in
# telemetry.jsonl/metrics.jsonl, the TD-error histogram merged, and
# the recompilation watchdog counting (docs/OBSERVABILITY.md
# "Learning-health diagnostics").
diag-smoke:
	JAX_PLATFORMS=cpu python scripts/diag_smoke.py

# Population-fused smoke: tiny CPU run of the vmapped Anakin loop
# (--on-device --population 4 --pbt-every 1) through the real CLI;
# asserts N distinct finite learning curves, at least one PBT exploit
# event with a schema-valid telemetry record, and a successful resume
# of the population checkpoint (docs/SCALING.md "population").
pop-smoke:
	JAX_PLATFORMS=cpu python scripts/pop_smoke.py

# Named-mesh GSPMD smoke: forced 4-device CPU run exercising the dp
# burst (jit-with-sharding, replica canary 0.0), the dp+fsdp hybrid
# (no version gate) and --population 8 member-sharded fused training
# end-to-end through the CLI, incl. a sharded-checkpoint resume
# (docs/SCALING.md "The mesh"). The script forces the device count
# itself before importing jax.
mesh-smoke:
	python scripts/mesh_smoke.py

# Compute-cost attribution smoke: short CPU train with telemetry + an
# in-process serve round -> every per-epoch `cost` event present and
# finite, serving /metrics carries per-bucket roofline entries, FLOPs
# monotone with bucket size, and one cross-plane Perfetto trace holds
# BOTH planes' spans (docs/OBSERVABILITY.md "Cost attribution").
cost-smoke:
	JAX_PLATFORMS=cpu python scripts/cost_smoke.py

# Fault-injection suite: every recovery path (NaN rollback, SIGTERM
# save+requeue+bitwise resume, checkpoint retry/fallback, dead env
# worker) driven through a real Trainer (docs/RESILIENCE.md).
fault-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q -m "not slow"

# Serving overload chaos: flood an in-process server past capacity
# with injected engine faults — queue stays bounded, breaker trips and
# recovers, NaN-checkpoint reload is rejected, drain answers every
# accepted request (docs/SERVING.md "Overload & degradation").
chaos-smoke:
	JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

# Fleet smoke: 3-worker CPU fleet through the real `serve.py --fleet`
# entry point — flood through the router, SIGKILL one worker MID-flood
# (membership ejects it, in-flight requests fail over, zero accepted
# drops), rolling /reload across the survivors, aggregated /metrics,
# graceful SIGTERM teardown (docs/SERVING.md "Fleet").
fleet-smoke:
	JAX_PLATFORMS=cpu python scripts/fleet_smoke.py

# Sharded-serving smoke: real `serve.py --devices all --submesh 2x2
# --fleet 2` under the forced 8-device CPU shim — each worker carves
# its devices into two (2,2) GSPMD sub-mesh replicas; flood the
# router, mid-flood validated hot-reload (one sharded transfer per
# replica, asserted via the transfer-bytes counter) and SIGKILL one
# worker: zero accepted drops, graceful SIGTERM teardown
# (docs/SERVING.md "Sharded serving & precision tiers").
shard-serve-smoke:
	JAX_PLATFORMS=cpu python scripts/shard_serve_smoke.py

# Decoupled actor/learner chaos: (1) in-process bitwise proof — SIGTERM
# mid-epoch with a staged-transition tail, resume is bitwise on learner
# state AND replay; (2) real processes — learner acts over HTTP through
# a serve.py worker hot-reloading its checkpoints, the worker is
# SIGKILLed mid-collection (actors degrade to the local snapshot, envs
# never stall), the learner SIGTERMs mid-epoch (requeue 75) and
# resumes: zero accepted transitions lost, staleness bounded by
# --max-actor-lag; (3) actor-process fleet — train.py --actors 3 over
# the networked staging transport with TAC_FLAKY_PUSH drops, one actor
# SIGKILLed (supervised restart + dead-actor purge), learner SIGTERM ->
# requeue 75 -> resume with restored dedup watermarks: the extended
# conservation invariant green, no push lost or double-ingested
# (docs/RESILIENCE.md "Decoupled-plane failure modes").
decouple-smoke:
	JAX_PLATFORMS=cpu python scripts/decouple_smoke.py

# Mixed-precision + fused-pixel-pipeline smoke (CPU, real CLI):
# Pallas pixel-kernel interpret-vs-reference bit parity, f32 fused
# pipeline bitwise vs the reference run, bf16 fused visual training
# finite, cost/epoch_mfu present in metrics.jsonl and cost events
# carrying the compute dtype (docs/SCALING.md "Mixed precision & the
# pixel pipeline").
visual-smoke:
	JAX_PLATFORMS=cpu python scripts/visual_smoke.py

# Transfer-sanitizer smoke (forced 4-device CPU, real CLIs): a short
# train and a 60-request serve flood both run CLEAN under --sanitize
# on (train loss stream bitwise == off), while an injected host read
# (numpy chunk into the guarded burst; numpy params into the guarded
# forward) trips jax.transfer_guard("disallow") loudly on each plane
# (docs/ANALYSIS.md "Runtime sanitizers"). The script forces the
# device count itself before importing jax.
sanitize-smoke:
	python scripts/sanitize_smoke.py

# Scenario-workloads smoke (CPU, real CLI): every scenarios/ pillar —
# multi-agent (per-agent reward curves), procedural (fresh level per
# episode, finite returns), multi-task (schema-valid per-task metrics
# from striped replay) — plus a bitwise population resume over the
# multi-task scenario (docs/SCENARIOS.md).
scenario-smoke:
	JAX_PLATFORMS=cpu python scripts/scenario_smoke.py

# Tiered-replay smoke (CPU, real CLI): --replay-tiers host is bitwise
# vs the tiers-off loss stream (and tiers-off emits zero replay/
# columns); a tiny-disk-budget run drives spill -> fifo evict ->
# refill -> prefetch with the conservation invariant holding every
# epoch; then --offline trains CQL-regularized SAC from the spilled
# chunks to a saved checkpoint (docs/REPLAY.md).
replay-smoke:
	JAX_PLATFORMS=cpu python scripts/replay_smoke.py

# Cold-start smoke (CPU, real CLI): build a warm-start bundle next to
# a real checkpoint (aot/), then prove against fresh serve.py workers
# that the bundle answers the first /act with ZERO serve-plane live
# compiles and holds zero through a closed-loop herd flood, that a
# second worker hits the shared persistent compile cache, and that a
# fingerprint-tampered bundle is loudly rejected with a counted
# fallback to live warmup (docs/SERVING.md "Cold start & warm-start
# bundles").
coldstart-smoke:
	JAX_PLATFORMS=cpu python scripts/coldstart_smoke.py

# Run-wide observability smoke (CPU, real CLI): a serving fleet
# (serve.py --fleet 2) plus an actor-fleet learner (--actors 2 --obs)
# whose ObsCollector aggregates three planes with zero scrape
# failures; an injected serving-goodput outage drives the SLO engine
# through exactly one breach + one recovery; and the exported Perfetto
# timeline stitches one staging span id across actor, transport, and
# learner process lanes (docs/OBSERVABILITY.md "Run-wide plane").
obs-smoke:
	JAX_PLATFORMS=cpu python scripts/obs_smoke.py

# Elastic self-healing fleet end-to-end (docs/RESILIENCE.md
# "Elasticity"): an SLO breach scales the serving fleet out from the
# warm pool, a worker SIGKILLed mid-spike is absorbed with ZERO
# dropped requests and a counted recovery, green windows drain one
# worker back in; on the training plane an actor SIGKILL degrades the
# run to the surviving slice (conservation green) and the slot is
# re-admitted at an epoch boundary — every decision a schema-valid
# event on the exported Perfetto elastic lane.
elastic-smoke:
	JAX_PLATFORMS=cpu python scripts/elastic_smoke.py

dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		python __graft_entry__.py 8

# tac-lint: the codebase-native static pass (docs/ANALYSIS.md) —
# jit-hygiene, recompile-risk, lock-discipline, convention lints plus
# the dataflow families (donation-safety, prng-discipline,
# contract-drift). --json is the machine contract: one JSON object CI
# can diff, stable per-family exit codes (0 clean, 10..17 per family,
# 1 mixed). Also wired into tier-1 via tests/test_analysis.py's
# whole-package clean-run test.
lint:
	python -m torch_actor_critic_tpu.analysis --json torch_actor_critic_tpu scripts

native:
	$(MAKE) -C torch_actor_critic_tpu/native

native-asan:
	$(MAKE) -C torch_actor_critic_tpu/native asan

clean:
	rm -rf runs __pycache__ **/__pycache__
