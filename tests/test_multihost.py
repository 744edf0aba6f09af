"""Two-process multi-host dryrun (round-1 missing #7).

Launches two real OS processes, each a "host" with 2 virtual CPU
devices, joined via ``jax.distributed`` over a local coordinator —
exercising ``initialize_multihost``, a cross-process DP burst,
``global_statistics``, coordinator gating, and collective Orbax
save/restore (see ``torch_actor_critic_tpu/parallel/selftest.py``).

This is the capability gap called out in SURVEY.md §4: the reference's
MPI paths silently degrade to no-ops in its single-process test suite;
here the cross-process collectives actually run.
"""

import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_launcher_runs_two_process_selftest(tmp_path):
    """The mpi_fork-counterpart launcher (parallel/launch.py) drives
    the same 2-process selftest: one command line fans out to N
    processes wired to one coordinator via argument placeholders."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "PYTHONPATH": repo_root
            + (
                os.pathsep + env["PYTHONPATH"]
                if os.environ.get("PYTHONPATH")
                else ""
            ),
        }
    )
    proc = subprocess.run(
        [
            sys.executable, "-m", "torch_actor_critic_tpu.parallel.launch",
            "--processes", "2", "--",
            sys.executable, "-m", "torch_actor_critic_tpu.parallel.selftest",
            "--coordinator", "{coordinator}",
            "--processes", "{num_processes}",
            "--process-id", "{process_id}",
            "--ckpt-dir", str(tmp_path / "ckpt"),
        ],
        env=env, capture_output=True, text=True, timeout=540, cwd=repo_root,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    assert "[p0] MULTIHOST_OK proc=0/2" in out, out
    assert "[p1] MULTIHOST_OK proc=1/2" in out, out


def test_launcher_fast_fails_and_passes_literal_braces():
    """A dead rank must tear the group down promptly (not strand the
    survivors in a collective), with the failing rank's exit code; and
    arguments with literal braces (JSON) must pass through the
    placeholder substitution untouched."""
    import time

    from torch_actor_critic_tpu.parallel.launch import launch

    script = (
        "import json, sys, time\n"
        "assert json.loads(sys.argv[2]) == {'a': 1}\n"
        "rank = int(sys.argv[1])\n"
        "sys.exit(3) if rank == 1 else time.sleep(120)\n"
    )
    t0 = time.time()
    rc = launch(
        [sys.executable, "-c", script, "{process_id}", '{"a": 1}'],
        num_processes=2,
    )
    assert rc == 3
    assert time.time() - t0 < 60  # rank 0's 120s sleep was terminated


@pytest.mark.slow
def test_two_process_distributed_dryrun(tmp_path):
    # (hang protection comes from the subprocess communicate timeout)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "PYTHONPATH": repo_root
            + (
                os.pathsep + env["PYTHONPATH"]
                if os.environ.get("PYTHONPATH")
                else ""
            ),
        }
    )
    procs = []
    for pid in (0, 1):
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "torch_actor_critic_tpu.parallel.selftest",
                    "--coordinator",
                    f"127.0.0.1:{port}",
                    "--processes",
                    "2",
                    "--process-id",
                    str(pid),
                    "--ckpt-dir",
                    str(tmp_path / "ckpt"),
                ],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                cwd=repo_root,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"multihost dryrun hung; partial output: {outs}")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} rc={p.returncode}:\n{out}"
        assert f"MULTIHOST_OK proc={pid}/2" in out, out
        assert "devices=2/4" in out, out
    assert "coordinator=True" in outs[0] and "coordinator=False" in outs[1]


@pytest.mark.slow
def test_elastic_resume_across_topologies(tmp_path):
    """Elastic resume (VERDICT r4 #8): checkpoint from a 4-process x
    2-device run restores onto (a) 2 processes x 4 devices — same
    global dp, different host topology, Orbax re-reads each host's new
    shards — and (b) a single process with dp=4 — different GLOBAL dp,
    replay rings rebuilt by parallel/elastic.reshard_buffer. Both
    resumed runs keep training (burst runs, step advances)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ckpt = str(tmp_path / "elastic_ckpt")

    def env_for(devices_per_proc):
        env = dict(os.environ)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (
                    f"--xla_force_host_platform_device_count={devices_per_proc}"
                ),
                "PYTHONPATH": repo_root
                + (
                    os.pathsep + env["PYTHONPATH"]
                    if os.environ.get("PYTHONPATH")
                    else ""
                ),
            }
        )
        return env

    def launch(n_procs, devices_per_proc, phase, extra=()):
        return subprocess.run(
            [
                sys.executable, "-m",
                "torch_actor_critic_tpu.parallel.launch",
                "--processes", str(n_procs), "--",
                sys.executable, "-m",
                "torch_actor_critic_tpu.parallel.selftest",
                "--coordinator", "{coordinator}",
                "--processes", "{num_processes}",
                "--process-id", "{process_id}",
                "--ckpt-dir", ckpt,
                "--phase", phase, *extra,
            ],
            env=env_for(devices_per_proc),
            capture_output=True, text=True, timeout=900, cwd=repo_root,
        )

    # Phase 1: 4 hosts x 2 devices (global dp=8) trains and saves.
    proc = launch(4, 2, "save")
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    for pid in range(4):
        assert f"ELASTIC_SAVE_OK proc={pid}/4 dp=8" in out, out

    # Phase 2: 2 hosts x 4 devices (same dp=8) resumes and trains on.
    proc = launch(2, 4, "resume")
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    for pid in range(2):
        assert f"ELASTIC_RESUME_OK proc={pid}/2 dp=8 step=6" in out, out

    # Phase 3: one host, dp=4 (global dp HALVED) — ring reshard path.
    proc = launch(1, 4, "resume-reshard", extra=("--old-ndev", "8"))
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    assert "ELASTIC_RESHARD_OK dp=8->4 transitions=256 step=6" in out, out
