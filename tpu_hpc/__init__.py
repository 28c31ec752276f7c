"""tpu_hpc -- a TPU-native distributed training framework.

Capability match for the reference recipe collection
``negin513/distributed-pytorch-hpc`` (multi-node PyTorch/NCCL on NCAR
Derecho), re-designed from scratch for TPU: one ``jax.sharding.Mesh`` +
PartitionSpec mechanism replaces the DDP/FSDP/DTensor/pipelining wrapper
zoo; XLA collectives over ICI/DCN replace NCCL over NVLink/Slingshot;
``jax.distributed.initialize`` replaces the mpiexec/torchrun launcher
detection matrix.

Layer map (mirrors SURVEY.md section 1):
  runtime/    distributed init, mesh construction, topology introspection
  comm/       collective primitives + ICI/DCN benchmark suite
  parallel/   named parallelism recipes: dp, fsdp, tp, pp, sp, ring, domain
  models/     llama2, unet, vit, pipeline transformer, synthetic datasets
  train/      trainer loop, throughput metrics, losses
  ckpt/       orbax checkpointing + snapshot auto-resume
  resilience/ preemption guard, hang watchdog, retry/backoff, run
              supervisor, deterministic fault injection
  config/     unified dataclass + YAML/CLI config
  profiling/  jax.profiler wrapper with schedule windows
  logging_/   host-0 logging, per-process output redirect
  checks/     environment verification
  kernels/    pallas kernels (flash / ring attention)
"""

__version__ = "0.1.0"

import os as _os

# The simulated run, asked for by name: TPU_HPC_SIM_DEVICES=N puts this
# process on the host CPU platform with N virtual devices before any
# backend exists. It is the only way onto the CPU that the chip-path
# entry points (bench.py, python -m tpu_hpc.serve, chip_smoke.py)
# accept -- see runtime.distributed.require_accelerator. This is the
# no-cluster development mode the reference lacks entirely (SURVEY.md
# section 4: "multi-node without a cluster: not solved").
_sim = _os.environ.get("TPU_HPC_SIM_DEVICES")
if _sim:
    from tpu_hpc.runtime.sim import force_sim_devices as _force_sim

    _force_sim(int(_sim))

from tpu_hpc.runtime import (  # noqa: F401
    HostInfo,
    MeshSpec,
    build_mesh,
    cleanup_distributed,
    get_host_info,
    init_distributed,
    is_main_host,
    print_host0,
)
