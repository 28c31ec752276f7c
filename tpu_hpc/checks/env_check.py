"""Pre-flight environment verification.

Parity with /root/reference/tests/check_environment.py (distributed
env check: host->device map :240-244, library discovery :31-58, env
dump :263-301, collective smoke test, pass/fail summary :349-373) and
tests/test_env.py (single-process version-and-smoke check).

TPU translation: NCCL version -> libtpu/jax versions; rank->node map ->
process->chip map with ICI coords; Slingshot NIC check -> ICI
coordinate/torus sanity; NCCL env dump -> XLA/TPU env var dump; NCCL
all-reduce smoke test -> psum over all devices with exact-value check.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_hpc.runtime.topology import topology_report

# Env vars that shape XLA/TPU behavior -- the dump parity of the
# reference's 25-var NCCL env block (check_environment.py:263-301).
_ENV_VARS = (
    "JAX_PLATFORMS",
    "XLA_FLAGS",
    "LIBTPU_INIT_ARGS",
    "TPU_WORKER_ID",
    "TPU_WORKER_HOSTNAMES",
    "TPU_CHIPS_PER_HOST_BOUNDS",
    "TPU_HOST_BOUNDS",
    "JAX_PROCESS_ID",
    "JAX_NUM_PROCESSES",
    "JAX_COORDINATOR_ADDRESS",
    "JAX_ENABLE_X64",
    "JAX_DISABLE_JIT",
)


def _library_versions() -> Dict[str, str]:
    """Version discovery (parity: NCCL version+path, :31-73)."""
    out = {"python": sys.version.split()[0], "jax": jax.__version__}
    try:
        import jaxlib

        out["jaxlib"] = jaxlib.__version__
    except Exception:
        pass
    try:
        from jax._src.lib import xla_extension_version

        out["xla_extension"] = str(xla_extension_version)
    except Exception:
        pass
    try:
        import libtpu  # type: ignore

        out["libtpu"] = getattr(libtpu, "__version__", "present")
    except Exception:
        out["libtpu"] = "not importable (ok off-TPU)"
    return out


def _pinned_versions() -> Dict[str, str]:
    """Parse the repo's ``constraints.txt`` (the known-good pins every
    recorded benchmark was measured with -- the reference's
    environment.yml:1-13 discipline). Empty dict if the file is not
    found (installed-package deployments)."""
    import pathlib

    here = pathlib.Path(__file__).resolve()
    # Bounded walk (checks/ -> tpu_hpc/ -> repo root), and only a dir
    # that also holds pyproject.toml counts as the repo: an installed
    # site-packages deployment must not pick up an unrelated
    # constraints.txt further up the tree and report bogus drift.
    for parent in here.parents[:3]:
        cpath = parent / "constraints.txt"
        if cpath.is_file() and (parent / "pyproject.toml").is_file():
            pins = {}
            for line in cpath.read_text().splitlines():
                line = line.strip()
                if line and not line.startswith("#") and "==" in line:
                    name, _, ver = line.partition("==")
                    pins[name.strip()] = ver.strip()
            return pins
    return {}


def check_version_pins() -> Tuple[bool, str]:
    """Warn-only drift check of installed packages vs constraints.txt.

    A pod launched months later resolves different wheels than the
    ones the recorded BENCH_*/REPORT_* artifacts were measured on;
    this surfaces the drift at preflight instead of in a confusing
    perf regression. Always "passes" -- drift is a warning, since
    newer stacks are usually fine -- but the detail names every
    mismatch."""
    import importlib.metadata as md

    pins = _pinned_versions()
    if not pins:
        return True, "no constraints.txt found (skipped)"
    drift = []
    for name, want in pins.items():
        try:
            have = md.version(name)
        except md.PackageNotFoundError:
            drift.append(f"{name}: pinned {want}, not installed")
            continue
        if have != want:
            drift.append(f"{name}: pinned {want}, installed {have}")
    if drift:
        return True, ("DRIFT from constraints.txt (warn only): "
                      + "; ".join(drift))
    return True, f"all {len(pins)} pins match constraints.txt"


def _smoke_all_reduce() -> Tuple[bool, str]:
    """All-device psum smoke test with exact expected value.

    Parity with test_env.py:54-79 (world-size-1 NCCL all-reduce) and
    the device-mesh sanity assert result == sum(range(world_size))
    (scripts/03_tensor_parallel_tp/01_device_mesh_basics.py:82-87).
    """
    try:
        n = jax.device_count()
        mesh = jax.make_mesh((n,), ("d",))
        x = jax.device_put(
            jnp.arange(n, dtype=jnp.float32),
            jax.NamedSharding(mesh, jax.P("d")),
        )
        total = jax.jit(
            jax.shard_map(
                lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                in_specs=jax.P("d"), out_specs=jax.P(),
            )
        )(x)
        expected = float(sum(range(n)))
        got = float(np.asarray(total)[0])
        ok = got == expected
        return ok, f"psum over {n} devices: got {got}, expected {expected}"
    except Exception as e:  # pragma: no cover
        return False, f"all-reduce smoke test raised: {e!r}"


def chip_microbench(
    dim: int = 4096, iters: int = 10
) -> Dict[str, float]:
    """Per-chip burn-in numbers: dense bf16 matmul TFLOP/s and HBM
    copy GB/s, measured on this host's first local chip.

    The role of the reference's per-GPU props dump + single-device
    NCCL smoke (test_env.py:54-79), upgraded to *measured* rates: a
    chip delivering far below its spec sheet (thermal throttle, wrong
    binding, sharing) shows up here before any training run does.
    """
    import time

    import jax.numpy as jnp

    # local_devices, not devices: on a multi-host pod global device 0
    # is addressable only from host 0, and device_put to a
    # non-addressable device raises on every other host.
    d = jax.local_devices()[0]
    key = jax.random.key(0)
    a = jax.device_put(
        jax.random.normal(key, (dim, dim), jnp.bfloat16), d
    )

    # The loop lives INSIDE one jit (per-dispatch latency would
    # otherwise dominate), and the clock brackets block_until_ready on
    # the result: JAX dispatch is asynchronous, so the wait is what
    # makes the interval cover the execution.
    def run(n, fn, x):
        f = jax.jit(
            lambda x: jnp.sum(
                jax.lax.fori_loop(0, n, fn, x).astype(jnp.float32)
            )
        )
        f(x).block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        f(x).block_until_ready()
        return time.perf_counter() - t0

    # *1e-3 keeps the iterated matmul finite (cost unchanged).
    mmstep = lambda i, y: (y @ y) * jnp.bfloat16(1e-3)  # noqa: E731
    n_mm = iters * 10
    tflops = 2 * dim**3 * n_mm / run(n_mm, mmstep, a) / 1e12

    big = jax.device_put(
        jnp.zeros((256, 1024, 1024), jnp.float32), d
    )  # 1 GiB
    cpstep = lambda i, y: y + 1.0  # noqa: E731
    n_cp = iters * 5
    # read + write per pass.
    gbs = 2 * big.nbytes * n_cp / run(n_cp, cpstep, big) / 1e9
    return {"matmul_tflops": tflops, "hbm_gb_s": gbs}


def check_environment(verbose: bool = True) -> Dict:
    """Run all checks; return a report dict with a pass/fail summary
    (parity: check_environment.py:349-373)."""
    report = {
        "versions": _library_versions(),
        "topology": topology_report(),
        "env": {k: os.environ.get(k) for k in _ENV_VARS if os.environ.get(k)},
    }
    checks: List[Tuple[str, bool, str]] = []

    n_local = jax.local_device_count()
    checks.append(
        ("devices_visible", n_local > 0, f"{n_local} local device(s)")
    )
    ok, msg = check_version_pins()
    checks.append(("version_pins", ok, msg))
    ok, msg = _smoke_all_reduce()
    checks.append(("all_reduce_smoke", ok, msg))

    backend = jax.default_backend()
    simulated = bool(os.environ.get("TPU_HPC_SIM_DEVICES"))
    checks.append((
        "accelerator_backend", backend == "tpu" or simulated,
        f"backend={backend}" + (
            "" if backend == "tpu"
            else " (simulation requested via TPU_HPC_SIM_DEVICES)"
            if simulated else " (not a TPU, and no simulation was "
            "asked for: set TPU_HPC_SIM_DEVICES=N to run one)"
        ),
    ))
    if backend == "tpu":
        coords = [getattr(d, "coords", None) for d in jax.local_devices()]
        checks.append(
            ("ici_coords", all(c is not None for c in coords),
             f"chip coords: {coords}")
        )
        try:
            rates = chip_microbench()
            report["microbench"] = rates
            checks.append((
                "chip_microbench", rates["matmul_tflops"] > 10,
                f"{rates['matmul_tflops']:.0f} bf16 TFLOP/s, "
                f"{rates['hbm_gb_s']:.0f} GB/s HBM",
            ))
        except Exception as e:  # pragma: no cover
            checks.append(("chip_microbench", False, f"raised: {e!r}"))

    report["checks"] = [
        {"name": n, "passed": p, "detail": d} for n, p, d in checks
    ]
    report["all_passed"] = all(p for _, p, _ in checks)

    if verbose and jax.process_index() == 0:
        print("=" * 64)
        print("tpu_hpc environment check")
        print("=" * 64)
        for k, v in report["versions"].items():
            print(f"  {k:>16}: {v}")
        topo = report["topology"]
        print(f"  {'backend':>16}: {topo['backend']}")
        print(
            f"  {'devices':>16}: {topo['global_device_count']} global / "
            f"{topo['local_device_count']} local, "
            f"{topo['process_count']} process(es)"
        )
        for d in topo["devices"]:
            print(f"    device {d['id']}: {d['device_kind']}"
                  + (f" coords={d['coords']}" if "coords" in d else ""))
        if report["env"]:
            print("  relevant env:")
            for k, v in report["env"].items():
                print(f"    {k}={v}")
        print("-" * 64)
        for c in report["checks"]:
            mark = "PASS" if c["passed"] else "FAIL"
            print(f"  [{mark}] {c['name']}: {c['detail']}")
        print("=" * 64)
        print("ALL CHECKS PASSED" if report["all_passed"] else "FAILURES PRESENT")
    return report


def main(argv: Optional[Sequence[str]] = None) -> int:
    from tpu_hpc.runtime import init_distributed

    init_distributed()
    report = check_environment(verbose=True)
    return 0 if report["all_passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
