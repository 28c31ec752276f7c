"""Multi-host initialization and launcher auto-detection.

Capability parity with the reference's ``utils/distributed.py``
(/root/reference/utils/distributed.py:26-158), which sniffs
torchrun/OpenMPI/Cray-MPICH env vars, broadcasts the head-node IP over
MPI, and calls ``dist.init_process_group``. On TPU the whole dance
collapses into ``jax.distributed.initialize``: the coordinator address
plays the MASTER_ADDR role and XLA's runtime owns rendezvous.

We keep the reference's ergonomics: a single ``init_distributed()`` that
works under every launcher (TPU-VM pod metadata, GKE/JobSet, SLURM,
OpenMPI, Cray PALS, or plain single-process) by detecting
``(process_id, num_processes, coordinator)`` from the environment in
priority order, mirroring ``get_rank_info``'s launcher-priority design.
"""
from __future__ import annotations

import dataclasses
import os
import socket
from typing import Optional

_DEFAULT_COORDINATOR_PORT = 8476


@dataclasses.dataclass(frozen=True)
class HostInfo:
    """Identity of this process in the job.

    TPU analogue of the reference's ``(local_rank, world_size, world_rank,
    launcher)`` tuple (utils/distributed.py:26-100). One process per host
    drives all local chips, so ``process_id`` is a *host* index, not a
    per-chip rank; per-chip identity lives in ``jax.devices()``.
    """

    process_id: int
    num_processes: int
    coordinator_address: Optional[str]
    launcher: str

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1


def _env_int(*names: str) -> Optional[int]:
    for n in names:
        v = os.environ.get(n)
        if v is not None and v != "":
            try:
                return int(v)
            except ValueError:
                pass
    return None


def _env_str(*names: str) -> Optional[str]:
    for n in names:
        v = os.environ.get(n)
        if v:
            return v
    return None


def get_host_info() -> HostInfo:
    """Detect process identity from the environment, launcher by launcher.

    Priority order (mirrors the torchrun -> OpenMPI -> Cray-MPICH -> mpi4py
    -> single-process cascade of utils/distributed.py:26-100):

    1. Explicit JAX vars (``JAX_PROCESS_ID``/``JAX_NUM_PROCESSES``/
       ``JAX_COORDINATOR_ADDRESS``) -- ours, always wins.
    2. Cloud TPU pod metadata (libtpu sets these on TPU-VM pods; handled
       natively by ``jax.distributed.initialize()`` with no args).
    3. SLURM (``SLURM_PROCID``/``SLURM_NTASKS``).
    4. OpenMPI (``OMPI_COMM_WORLD_RANK``/``OMPI_COMM_WORLD_SIZE``).
    5. Cray PALS/PMI (``PALS_RANKID``/``PMI_RANK``/``PMI_SIZE``).
    6. Single-process fallback.
    """
    # 1. Explicit.
    pid = _env_int("JAX_PROCESS_ID")
    nproc = _env_int("JAX_NUM_PROCESSES")
    coord = _env_str("JAX_COORDINATOR_ADDRESS")
    if pid is not None and nproc is not None:
        return HostInfo(pid, nproc, coord, "explicit")

    # 2. Cloud TPU pod: let jax.distributed auto-detect. TPU_WORKER_ID /
    # TPU_WORKER_HOSTNAMES are set by the TPU-VM runtime.
    if os.environ.get("TPU_WORKER_ID") is not None and os.environ.get(
        "TPU_WORKER_HOSTNAMES"
    ):
        wid = _env_int("TPU_WORKER_ID") or 0
        hosts = os.environ["TPU_WORKER_HOSTNAMES"].split(",")
        coord = f"{hosts[0]}:{_DEFAULT_COORDINATOR_PORT}"
        return HostInfo(wid, len(hosts), coord, "tpu_pod")

    # 3. SLURM. Coordinator resolution is left to jax.distributed's own
    # SlurmCluster auto-detection (it derives the first node of the step
    # nodelist, handling bracketed forms like "nid[001-004]"); resolving
    # it here from SLURM_LAUNCH_NODE_IPADDR would point every rank at the
    # *submitting* node, not rank 0's node.
    pid = _env_int("SLURM_PROCID")
    nproc = _env_int("SLURM_NTASKS")
    if pid is not None and nproc is not None and nproc > 1:
        return HostInfo(pid, nproc, None, "slurm")

    # 4. OpenMPI (mpiexec). Reference: utils/distributed.py:49-60.
    pid = _env_int("OMPI_COMM_WORLD_RANK")
    nproc = _env_int("OMPI_COMM_WORLD_SIZE")
    if pid is not None and nproc is not None:
        return HostInfo(pid, nproc, _coordinator_from_env(), "openmpi")

    # 5. Cray PALS / PMI. Reference: utils/distributed.py:62-76.
    pid = _env_int("PALS_RANKID", "PMI_RANK")
    nproc = _env_int("PALS_SIZE", "PMI_SIZE")
    if pid is not None and nproc is not None:
        return HostInfo(pid, nproc, _coordinator_from_env(), "cray_pals")

    # 6. Single process. Reference: utils/distributed.py:99-100.
    return HostInfo(0, 1, None, "single")


def _coordinator_from_env() -> Optional[str]:
    """MASTER_ADDR/MASTER_PORT compatibility shim.

    The reference broadcasts rank-0's IP over MPI and exports MASTER_ADDR
    (utils/distributed.py:103-121). Under JAX we just read it if the
    launcher set it; otherwise jax.distributed's own bootstrap handles it.
    """
    addr = _env_str("JAX_COORDINATOR_ADDRESS", "MASTER_ADDR")
    if addr is None:
        return None
    if ":" in addr:
        return addr
    port = _env_str("JAX_COORDINATOR_PORT", "MASTER_PORT") or str(
        _DEFAULT_COORDINATOR_PORT
    )
    return f"{addr}:{port}"


_INITIALIZED = False


def init_distributed(
    host_info: Optional[HostInfo] = None, verbose: bool = True
) -> HostInfo:
    """Initialize multi-host JAX. Parity: utils/distributed.py:124-158.

    Safe to call in single-process mode (no-op beyond detection), exactly
    like the reference's world_size==1 fallback. Idempotent.
    """
    global _INITIALIZED
    info = host_info or get_host_info()
    if info.is_distributed and not _INITIALIZED:
        import jax

        from tpu_hpc.logging_ import get_logger
        from tpu_hpc.resilience.retry import retry_call

        if info.launcher in ("slurm", "tpu_pod"):
            # Full auto-detection: jax.distributed knows these clusters
            # natively and derives the coordinator from the scheduler's
            # own metadata (correct rank-0 node, bracketed nodelists).
            kwargs = {}
        else:
            kwargs = dict(
                coordinator_address=info.coordinator_address,
                num_processes=info.num_processes,
                process_id=info.process_id,
            )
        # Rendezvous is the flakiest moment of a pod job: worker VMs
        # come up seconds apart and a restarted coordinator may still
        # hold its old port. Bounded retry instead of one-shot
        # (TPU_HPC_INIT_RETRIES extra attempts; per-host jittered
        # backoff de-synchronizes the re-knocks).
        def _initialize_once():
            try:
                jax.distributed.initialize(**kwargs)
            except Exception:
                # A failed rendezvous can leave the half-built client
                # in jax's global state; without this reset every
                # retry would die on "already initialized" instead of
                # re-attempting the connection.
                try:
                    jax.distributed.shutdown()
                except Exception:  # noqa: BLE001 - best-effort reset
                    pass
                raise

        retry_call(
            _initialize_once,
            retries=int(os.environ.get("TPU_HPC_INIT_RETRIES", "2")),
            base_delay=2.0, max_delay=30.0,
            on_retry=lambda attempt, exc, delay: get_logger().warning(
                "jax.distributed.initialize failed (attempt %d: %s); "
                "retrying in %.1fs", attempt, exc, delay,
            ),
        )
        _INITIALIZED = True
    if verbose and info.process_id == 0:
        from tpu_hpc.logging_ import get_logger

        get_logger().info(
            "init_distributed: launcher=%s process %d/%d host=%s",
            info.launcher,
            info.process_id,
            info.num_processes,
            socket.gethostname(),
        )
    return info


# The persistent compile cache when JAX_COMPILATION_CACHE_DIR does not
# name one: a fixed, git-ignored directory in the checkout. The path is
# part of the cache key's world -- one built from a temp name, a pid or
# a time would never hit.
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """Place JAX's persistent compilation cache and return the
    directory in use. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and this sets nothing; otherwise the cache goes to
    the one fixed path above. Call before the first compile.

    Either way the cache key keeps each operation's metadata (its
    ``jax.named_scope`` path and source line). JAX strips them by
    default, and an executable compiled before a scope was named, or
    by another checkout, would then be loaded with ITS names: a
    profiler trace would show stages the program no longer has. The
    price is a cold compile after any edit that moves a traced line."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE_DIR)
    return _COMPILE_CACHE_DIR


def require_accelerator():
    """THE device check of every chip-path entry point (bench.py,
    ``python -m tpu_hpc.serve``, chip_smoke.py): bring up the runtime,
    then exit non-zero at once, naming the platform found, unless JAX
    came up on a TPU. A simulated run is something the caller asks for
    by name (``TPU_HPC_SIM_DEVICES=N``), never something a measurement
    falls into -- a CPU answering under a device metric's name is the
    failure this exists to make impossible. On the chip it also places
    the compile cache (:func:`compile_cache_dir`); simulated runs keep
    JAX's default. Returns ``jax.devices()[0]``."""
    import jax

    init_distributed(verbose=False)
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        compile_cache_dir()
    elif not os.environ.get("TPU_HPC_SIM_DEVICES"):
        raise SystemExit(
            f"tpu_hpc: no TPU: JAX came up on platform "
            f"{dev.platform!r} ({jax.device_count()} x "
            f"{dev.device_kind}). This entry point runs on the chip; "
            "ask for the CPU simulation by name with "
            "TPU_HPC_SIM_DEVICES=N."
        )
    return dev


def cleanup_distributed() -> None:
    """Shut down the multi-host runtime. Parity: utils/distributed.py:161-164."""
    global _INITIALIZED
    if _INITIALIZED:
        import jax

        jax.distributed.shutdown()
        _INITIALIZED = False


def is_main_host() -> bool:
    """True on the coordinator host. Parity: is_main_rank (utils/distributed.py:167-171)."""
    import jax

    return jax.process_index() == 0


def print_host0(*args, **kwargs) -> None:
    """Print only from host 0. Parity: print_rank0 (utils/distributed.py:174-177)."""
    if is_main_host():
        print(*args, **kwargs)
