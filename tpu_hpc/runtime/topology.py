"""Device/topology introspection.

Parity with the reference's environment-introspection habit: every
script prints torch/CUDA/NCCL versions and GPU properties at startup
(tests/check_environment.py:118-179, tests/test_env.py). The TPU
equivalents are libtpu/jax versions, chip kind, per-chip coords on the
ICI torus, and HBM stats.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax


def memory_stats(device) -> Dict[str, int]:
    """``device.memory_stats()``, or ``{}`` from a backend that keeps
    none (the CPU) or cannot be asked."""
    try:
        return device.memory_stats() or {}
    except Exception:
        return {}


def device_summary() -> List[Dict[str, Any]]:
    """One record per addressable device: TPU analogue of the per-GPU
    property gather in check_environment.py:118-179."""
    out = []
    for d in jax.local_devices():
        rec: Dict[str, Any] = {
            "id": d.id,
            "process_index": d.process_index,
            "platform": d.platform,
            "device_kind": d.device_kind,
        }
        coords = getattr(d, "coords", None)
        if coords is not None:
            rec["coords"] = tuple(coords)
        core = getattr(d, "core_on_chip", None)
        if core is not None:
            rec["core_on_chip"] = core
        slice_idx = getattr(d, "slice_index", None)
        if slice_idx is not None:
            rec["slice_index"] = slice_idx
        stats = memory_stats(d)
        if stats:
            rec["bytes_limit"] = stats.get("bytes_limit")
            rec["bytes_in_use"] = stats.get("bytes_in_use")
        out.append(rec)
    return out


def topology_report() -> Dict[str, Any]:
    """Job-level topology: host->chip map (parity with the rank->node map
    printed by check_environment.py:240-244)."""
    from tpu_hpc.runtime.mesh import slice_groups

    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        # Multi-slice shape: >1 means DCN separates the groups and
        # dcn_axes meshes apply (09_hybrid_parallelism.md).
        "num_slices": len(slice_groups(jax.devices())),
        "devices": device_summary(),
    }
