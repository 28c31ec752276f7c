"""``run.py`` where JAX finds no accelerator: another exit code than 0
and no result line. Also with the simulation asked for by name, and in
a directory that holds only BENCHMARK.json and benchmark/."""
import os
import shutil
import subprocess
import sys

from benchmark import harness

ARGS = ["--workload", "train-mistral7b-1chip", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("TPU_HPC_SIM_DEVICES", "XLA_FLAGS")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *ARGS],
        cwd=cwd, env=full, capture_output=True, text=True, timeout=300,
    )


def _no_result(proc):
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout


def test_no_tpu_no_result():
    proc = _run(harness.ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_simulation_is_refused_by_name():
    proc = _run(harness.ROOT, TPU_HPC_SIM_DEVICES="4")
    _no_result(proc)
    assert "TPU_HPC_SIM_DEVICES" in proc.stderr


def test_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        harness.BENCH_DIR, tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("out", "__pycache__"),
    )
    _no_result(_run(tmp_path))
