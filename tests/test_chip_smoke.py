"""The chip path cannot be entered on a CPU by accident.

``chip_smoke.py``, ``bench.py`` and ``python -m tpu_hpc.serve`` share
one device check (runtime.require_accelerator): a backend that is not
a TPU ends the run non-zero at once, naming the platform, unless the
simulation was asked for by name (TPU_HPC_SIM_DEVICES -- which this
suite's conftest sets, and which chip_smoke.py refuses outright).
"""
import os
import subprocess
import sys

import pytest

from tpu_hpc.runtime import distributed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_chip_smoke(**env_overrides):
    env = {
        k: v for k, v in os.environ.items() if k != "TPU_HPC_SIM_DEVICES"
    }
    env.update(JAX_PLATFORMS="cpu", **env_overrides)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )


def test_chip_smoke_preflight_fails_on_cpu_and_names_the_platform():
    proc = _run_chip_smoke()
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert "{" not in proc.stdout  # no result line


def test_chip_smoke_refuses_the_simulator():
    proc = _run_chip_smoke(TPU_HPC_SIM_DEVICES="4")
    assert proc.returncode != 0
    assert "TPU_HPC_SIM_DEVICES" in proc.stderr
    assert "{" not in proc.stdout


def test_chip_smoke_verdict_line_has_the_contract_keys_and_no_other():
    # The driver refuses any other key on the last stdout line.
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)  # no JAX at import
    line = smoke.verdict_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_require_accelerator_accepts_cpu_only_when_asked_by_name(
    monkeypatch,
):
    # conftest asked for the simulation by name: accepted, and the
    # simulated run keeps JAX's default (no) compile cache directory.
    assert distributed.require_accelerator().platform == "cpu"
    monkeypatch.delenv("TPU_HPC_SIM_DEVICES")
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        distributed.require_accelerator()


def test_compile_cache_placed_from_outside_or_at_one_fixed_path(
    monkeypatch,
):
    import jax

    seen = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.append((k, v))
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    def placed():
        return [v for k, v in seen if k == "jax_compilation_cache_dir"]

    assert distributed.compile_cache_dir() == "/elsewhere/cache"
    assert placed() == []  # set from outside: code sets no other
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert distributed.compile_cache_dir() == fixed
    assert distributed.compile_cache_dir() == fixed  # never a temp name
    assert placed() == [fixed] * 2
    # Wherever the cache lies, its key keeps the operations' names: a
    # trace must not show the scopes of a build from before an edit.
    assert [kv for kv in seen if kv[0] != "jax_compilation_cache_dir"] \
        == [("jax_compilation_cache_include_metadata_in_key", True)] * 3
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_bench_and_serve_cli_refuse_a_cpu_they_fell_into(monkeypatch):
    """The same check guards ``bench.py`` and ``python -m
    tpu_hpc.serve``: without the simulation asked for by name they
    exit before any workload starts."""
    import importlib.util

    from tpu_hpc.serve import server

    spec = importlib.util.spec_from_file_location(
        "bench_cli_guard", os.path.join(REPO, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.delenv("TPU_HPC_SIM_DEVICES")
    for main in (bench.main, server.main):
        with pytest.raises(SystemExit, match="platform 'cpu'"):
            main([])


def test_unknown_tpu_kind_is_an_error_not_a_borrowed_peak():
    from tpu_hpc.checks.roofline import peak_flops_for_device

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert peak_flops_for_device(Dev()) == 197e12
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        peak_flops_for_device(Dev())
    Dev.platform, Dev.device_kind = "cpu", "cpu"
    assert peak_flops_for_device(Dev()) is None  # simulated run
