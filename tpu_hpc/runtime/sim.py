"""CPU-simulated device meshes: the no-cluster development mode.

The reference cannot test multi-node logic without a cluster (SURVEY.md
section 4: "multi-node without a cluster: not solved" -- its only
degraded modes are world_size==1 fallbacks and the gloo CPU backend,
/root/reference/utils/distributed.py:99-100). JAX can: XLA's host
platform exposes N virtual devices via
``--xla_force_host_platform_device_count``, making every sharding
recipe unit-testable on CPU.

Two entry points:
  * ``force_sim_devices(n)`` -- flip THIS process to the n-device CPU
    backend. Only valid before the first backend use.
  * ``run_in_sim_subprocess(code, n)`` -- run a python snippet in a
    child process on an n-device CPU backend; the escape hatch when the
    caller's jax is already initialized on a real accelerator.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys


def _force_flag(flags: str, n: int) -> str:
    if "xla_force_host_platform_device_count" in flags:
        return re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            f"--xla_force_host_platform_device_count={n}",
            flags,
        )
    return f"{flags} --xla_force_host_platform_device_count={n}".strip()


def backends_initialized() -> bool:
    from jax._src.xla_bridge import backends_are_initialized

    return backends_are_initialized()


def force_sim_devices(n: int) -> None:
    """Force the host-CPU platform with ``n`` virtual devices.

    Must run before the first ``jax.devices()``/``jit`` call: XLA reads
    the flag at backend initialization. The request is also recorded
    in ``TPU_HPC_SIM_DEVICES``: that variable is how a simulated run is
    asked for by name, child processes inherit it, and
    ``runtime.distributed.require_accelerator`` accepts a non-TPU
    backend only where it is set.
    """
    import jax

    if backends_initialized():
        # Idempotent when the backend already matches the request.
        devs = jax.devices()
        if devs[0].platform == "cpu" and len(devs) == n:
            return
        raise RuntimeError(
            f"cannot force {n} simulated devices: the JAX backend is "
            f"already initialized ({len(devs)} {devs[0].platform} "
            "device(s)) -- set TPU_HPC_SIM_DEVICES (or call "
            "force_sim_devices) before the first jax.devices()/jit "
            "call, or use run_in_sim_subprocess."
        )
    os.environ["TPU_HPC_SIM_DEVICES"] = str(n)
    os.environ["XLA_FLAGS"] = _force_flag(os.environ.get("XLA_FLAGS", ""), n)
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def sim_subprocess_env(n: int) -> dict:
    """Env for a child process that must come up on an n-device CPU
    backend regardless of this process's platform."""
    env = dict(os.environ)
    env["TPU_HPC_SIM_DEVICES"] = str(n)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _force_flag(env.get("XLA_FLAGS", ""), n)
    # A parent that holds real chips may have bound them by env; the
    # simulated child must not inherit the binding.
    for var in ("TPU_VISIBLE_DEVICES", "TPU_CHIPS_PER_PROCESS_BOUNDS"):
        env.pop(var, None)
    return env


def run_in_sim_subprocess(
    argv: list, n: int, timeout: int = 1800, cwd: str | None = None
) -> subprocess.CompletedProcess:
    """Run ``python <argv...>`` on an n-device simulated CPU backend."""
    return subprocess.run(
        [sys.executable, *argv],
        env=sim_subprocess_env(n),
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=cwd,
    )
