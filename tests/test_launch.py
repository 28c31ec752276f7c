"""Launch layer validation: the scripts and manifests themselves.

The reference's launchers are its most battle-tested artifact
(run_fsdp.sh:63-70, run_pipeline_parallel.sh); this repo's three
launch modes (launch/README.md) previously had zero execution
evidence. These tests execute what this environment can execute:

- ``gke_jobset.yaml`` parses and carries the structural invariants a
  JobSet TPU launch needs (worker identity injection, pod grouping,
  restart policy) -- the CI-side lint the verdict asked for;
- ``tpu_vm_run.sh`` runs end-to-end against a stub gcloud, proving
  the env assembly (tuning-profile validation, per-worker redirect,
  the remote command block) without a pod;
- ``local_multiprocess.sh`` actually launches two OS processes with
  the explicit JAX_* env and both sides rendezvous -- the
  explicit-env mode as a script, not just get_host_info unit tests.
"""
import os
import stat
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "launch")

# The two-process rendezvous worker, shared by the bare smoke test
# and its supervisor-wrapped port (docs/guide/resilience.md: supervise
# the LAUNCHER, not individual ranks).
RENDEZVOUS_WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("TPU_VISIBLE_DEVICES",
                "TPU_CHIPS_PER_PROCESS_BOUNDS",
                "TPU_WORKER_HOSTNAMES"):
        os.environ.pop(var, None)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from tpu_hpc.runtime.distributed import (
        get_host_info, init_distributed,
    )
    info = get_host_info()
    assert info.launcher == "explicit", info
    init_distributed()
    assert jax.process_count() == 2, jax.process_count()
    print(f"proc {jax.process_index()}/{jax.process_count()} ok")
""")


class TestGkeJobset:
    @pytest.fixture(scope="class")
    def manifest(self):
        # Scoped skip: only the manifest lint needs PyYAML; the
        # script-execution tests below run regardless.
        yaml = pytest.importorskip(
            "yaml", reason="the JobSet manifest lint needs PyYAML"
        )
        with open(os.path.join(LAUNCH, "gke_jobset.yaml")) as f:
            docs = list(yaml.safe_load_all(f))
        assert len(docs) == 1, "expected a single JobSet document"
        return docs[0]

    def test_kind_and_api(self, manifest):
        assert manifest["kind"] == "JobSet"
        assert manifest["apiVersion"].startswith("jobset.x-k8s.io/")

    def test_worker_job_shape(self, manifest):
        jobs = manifest["spec"]["replicatedJobs"]
        assert len(jobs) == 1
        spec = jobs[0]["template"]["spec"]
        # Every host must run exactly once; a parallelism/completions
        # mismatch would strand the rendezvous.
        assert spec["parallelism"] == spec["completions"]
        assert spec["backoffLimit"] == 0

    def test_pod_grouping_and_selectors(self, manifest):
        pod = (
            manifest["spec"]["replicatedJobs"][0]["template"]["spec"]
            ["template"]["spec"]
        )
        sel = pod["nodeSelector"]
        assert "cloud.google.com/gke-tpu-accelerator" in sel
        assert "cloud.google.com/gke-tpu-topology" in sel
        # The headless-service subdomain is what makes
        # TPU_WORKER_HOSTNAMES resolvable between pods.
        assert pod["subdomain"] == manifest["metadata"]["name"]
        assert pod["restartPolicy"] == "Never"
        (container,) = pod["containers"]
        assert container["command"][0] == "python"
        # TPU chips must be requested or the device plugin injects
        # nothing (no TPU_WORKER_ID -> the tpu_pod detection branch
        # never fires).
        assert "google.com/tpu" in container["resources"]["limits"]

    def test_restart_policy(self, manifest):
        assert manifest["spec"]["failurePolicy"]["maxRestarts"] >= 1


class TestTpuVmRunScript:
    def test_env_assembly_via_stub_gcloud(self, tmp_path):
        """Execute the launcher itself: a stub gcloud records the ssh
        invocation; the assembled remote command must contain the
        tuning eval, the venv activation, and the target script."""
        stub = tmp_path / "gcloud"
        capture = tmp_path / "captured.txt"
        stub.write_text(
            "#!/usr/bin/env bash\n"
            f'printf \'%s\\n---ARG---\\n\' "$@" >> "{capture}"\n'
        )
        stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
        proc = subprocess.run(
            [
                os.path.join(LAUNCH, "tpu_vm_run.sh"),
                "bench.py", "--steps", "5",
            ],
            env=dict(
                os.environ,
                GCLOUD=str(stub),
                TPU_NAME="smoke-pod",
                ZONE="test-zone-1a",
                TUNING="collective-overlap",
                LOG_DIR=str(tmp_path / "logs"),
            ),
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        got = capture.read_text()
        # The ssh leg.
        assert "compute\n---ARG---\ntpus" in got.replace("\r", "")
        assert "smoke-pod" in got and "test-zone-1a" in got
        assert "--worker=all" in got
        # The assembled remote command block.
        assert "tpu_hpc.runtime.tuning --profile collective-overlap" in got
        assert "source ~/tpu-hpc-venv/bin/activate" in got
        assert "python bench.py --steps 5" in got
        # LOG_DIR set -> per-worker redirect + the scp collection leg.
        assert "tee ~/tpu_hpc_logs/" in got
        assert "scp" in got

    def test_bad_tuning_profile_fails_fast(self, tmp_path):
        stub = tmp_path / "gcloud"
        stub.write_text("#!/usr/bin/env bash\nexit 0\n")
        stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
        proc = subprocess.run(
            [os.path.join(LAUNCH, "tpu_vm_run.sh"), "bench.py"],
            env=dict(
                os.environ, GCLOUD=str(stub), TUNING="no-such-profile"
            ),
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        assert proc.returncode != 0
        assert "no-such-profile" in (proc.stderr + proc.stdout)

    def test_supervise_wraps_remote_command(self, tmp_path):
        """SUPERVISE=N: the remote program runs under the resilience
        supervisor (bounded restart-with-resume per worker) instead of
        bare -- the launcher-level adoption of the subsystem."""
        stub = tmp_path / "gcloud"
        capture = tmp_path / "captured.txt"
        stub.write_text(
            "#!/usr/bin/env bash\n"
            f'printf \'%s\\n---ARG---\\n\' "$@" >> "{capture}"\n'
        )
        stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
        proc = subprocess.run(
            [
                os.path.join(LAUNCH, "tpu_vm_run.sh"),
                "bench.py", "--steps", "5",
            ],
            env=dict(
                os.environ, GCLOUD=str(stub), SUPERVISE="2",
                TUNING="collective-overlap",
            ),
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        got = capture.read_text()
        assert "python -m tpu_hpc.resilience.supervisor" in got
        assert "--max-restarts 2" in got
        # The target program rides behind the '--' separator.
        assert "-- python bench.py --steps 5" in got


class TestExplicitEnvMode:
    def test_two_process_rendezvous(self, tmp_path):
        """launch/local_multiprocess.sh really launches two OS
        processes with explicit JAX_* env; both must detect the
        'explicit' launcher and rendezvous to process_count == 2."""
        worker = tmp_path / "worker.py"
        worker.write_text(RENDEZVOUS_WORKER)
        proc = subprocess.run(
            [
                os.path.join(LAUNCH, "local_multiprocess.sh"),
                "2", str(worker),
            ],
            env=dict(os.environ, COORD_PORT="12421", PYTHON=sys.executable),
            capture_output=True, text=True, timeout=300, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "proc 0/2 ok" in proc.stdout
        assert "proc 1/2 ok" in proc.stdout

    def test_fail_fast_kills_survivors(self, tmp_path):
        """One rank dying must take the group down immediately
        (torchrun process-group semantics), not leave the survivors
        blocking on the JAX coordinator timeout (ADVICE r5)."""
        import sys
        import time

        worker = tmp_path / "worker.py"
        worker.write_text(textwrap.dedent("""
            import os, sys, time
            if os.environ["JAX_PROCESS_ID"] == "1":
                sys.exit(3)   # this rank fails at startup
            time.sleep(120)   # this one would block for minutes
        """))
        t0 = time.monotonic()
        proc = subprocess.run(
            [
                os.path.join(LAUNCH, "local_multiprocess.sh"),
                "2", str(worker),
            ],
            env=dict(os.environ, COORD_PORT="12429",
                     PYTHON=sys.executable),
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        elapsed = time.monotonic() - t0
        assert proc.returncode == 3, (proc.returncode, proc.stderr)
        assert elapsed < 60, f"did not fail fast: {elapsed:.0f}s"
        assert "killing survivors" in proc.stderr


class TestSupervisedLaunch:
    def test_supervisor_wraps_multiprocess_smoke(self, tmp_path):
        """The explicit-env smoke test ported onto the resilience
        supervisor: supervise the LAUNCHER (one restartable unit that
        re-rendezvouses the whole group), attempt log + event trail
        land in --log-dir."""
        import json
        import sys

        worker = tmp_path / "worker.py"
        worker.write_text(RENDEZVOUS_WORKER)
        sup_dir = tmp_path / "sup"
        proc = subprocess.run(
            [
                sys.executable, "-m", "tpu_hpc.resilience.supervisor",
                "--max-restarts", "1", "--log-dir", str(sup_dir),
                "--",
                os.path.join(LAUNCH, "local_multiprocess.sh"),
                "2", str(worker),
            ],
            env=dict(os.environ, COORD_PORT="12433",
                     PYTHON=sys.executable),
            capture_output=True, text=True, timeout=300, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        log = (sup_dir / "run.attempt0.log").read_text()
        assert "proc 0/2 ok" in log
        assert "proc 1/2 ok" in log
        events = [
            json.loads(x)
            for x in open(sup_dir / "supervisor.jsonl")
        ]
        assert [
            e["rc"] for e in events if e["event"] == "attempt_end"
        ] == [0]
