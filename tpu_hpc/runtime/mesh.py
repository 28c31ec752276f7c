"""Device-mesh construction: the single mechanism behind every strategy.

The reference builds 1D and 2D ``init_device_mesh`` meshes and slices
sub-meshes (scripts/03_tensor_parallel_tp/01_device_mesh_basics.py:29-73,
scripts/06_hybrid_parallelism/01_fsdp_tp_hybrid.py:88). Here the mesh is
not one strategy's plumbing -- it *is* the parallelism engine: DP shards
the batch over an axis, FSDP shards params over it, TP shards weights
over another, SP shards the sequence dim, PP/ring use ``shard_map`` over
an axis. ``MeshSpec`` names the axes once; every recipe in
``tpu_hpc.parallel`` is a PartitionSpec plan over these names.

On real TPU hardware ``jax.make_mesh`` lays axes onto the ICI torus so
that the innermost (most communication-hungry) axes ride the
fastest links -- the TPU analogue of the reference's "TP intra-node on
NVLink, FSDP across nodes on Slingshot" doctrine
(fsdp_tp/fsdp_tp_example.py:12-26).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis names used by the recipes. Order matters: earlier axes
# change slowest across the device list, so put the bandwidth-tolerant
# axis (data/fsdp, the reference's cross-node axis) first and the
# latency-sensitive axis (model/tensor, the reference's NVLink axis) last.
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape: ordered ``{axis_name: size}``.

    A size of -1 means "all remaining devices" (at most one axis may use
    it). Examples::

        MeshSpec(axes={"data": -1})                    # pure DP / FSDP
        MeshSpec(axes={"data": 2, "model": 4})         # hybrid FSDPxTP
        MeshSpec(axes={"data": 2, "seq": 4})           # ring attention
        MeshSpec(axes={"pipe": 4, "data": 2})          # PP x DP

    ``dcn_axes`` marks axes that additionally span TPU *slices* over
    the data-center network -- the TPU analogue of the reference's
    two-tier fabric doctrine (TP intra-node on NVLink, FSDP across
    nodes on Slingshot; fsdp_tp/fsdp_tp_example.py:12-26). Each entry
    multiplies the axis: ``axes`` gives the per-slice (ICI) extent,
    ``dcn_axes`` the cross-slice extent, and the built mesh axis has
    size ``ici * dcn`` with the DCN component varying slowest -- so
    collectives on that axis decompose into fast intra-slice ICI
    phases and one inter-slice DCN phase. Example, two v4 slices::

        MeshSpec(axes={"data": -1, "model": 4}, dcn_axes={"data": 2})
    """

    axes: Mapping[str, int]
    dcn_axes: Mapping[str, int] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        unknown = [k for k in self.dcn_axes if k not in self.axes]
        if unknown:
            raise ValueError(
                f"dcn_axes {unknown} not present in axes "
                f"{tuple(self.axes)}; give each DCN axis an ICI extent "
                f"(use 1 for a pure cross-slice axis)"
            )
        bad = {k: v for k, v in self.dcn_axes.items() if v < 1}
        if bad:
            raise ValueError(f"dcn_axes sizes must be >= 1, got {bad}")

    @property
    def num_slices(self) -> int:
        return math.prod(self.dcn_axes.values()) if self.dcn_axes else 1

    def resolved_sizes(self, n_devices: int) -> "dict[str, int]":
        """Full (ICI x DCN) axis sizes for ``n_devices`` total devices."""
        sizes = dict(self.axes)
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wild}")
        dcn_total = self.num_slices
        if n_devices % dcn_total != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by "
                f"{dcn_total} slices (dcn_axes={dict(self.dcn_axes)})"
            )
        per_slice = n_devices // dcn_total
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if per_slice % fixed != 0:
                raise ValueError(
                    f"{per_slice} per-slice devices not divisible by "
                    f"fixed axes {fixed}"
                )
            sizes[wild[0]] = per_slice // fixed
        return {
            k: v * self.dcn_axes.get(k, 1) for k, v in sizes.items()
        }

    def ici_sizes(self, n_devices: int) -> "dict[str, int]":
        """Per-slice (intra-ICI) axis sizes."""
        full = self.resolved_sizes(n_devices)
        return {k: v // self.dcn_axes.get(k, 1) for k, v in full.items()}

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self.axes.keys())


def build_mesh(
    spec: MeshSpec, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` from a spec.

    Uses ``jax.make_mesh`` on the default device set and
    ``mesh_utils.create_device_mesh`` on explicit TPU subsets (both
    ICI-topology-aware); a plain reshape over the device list only for
    explicit non-TPU subsets (simulated devices have no topology). Specs with
    ``dcn_axes`` build a hybrid ICI x DCN mesh (see
    :func:`build_hybrid_mesh`).
    """
    use_default = devices is None
    if use_default:
        devices = jax.devices()
    sizes = spec.resolved_sizes(len(devices))
    total = math.prod(sizes.values())
    if total > len(devices):
        raise ValueError(
            f"mesh {sizes} needs {total} devices, only {len(devices)} available"
        )
    if use_default and total != len(devices):
        # A whole-job mesh that leaves chips idle is almost always a
        # misconfiguration (half-throughput job with no error); demand an
        # explicit device subset when that is truly intended.
        raise ValueError(
            f"mesh {sizes} uses {total} of {len(devices)} devices; pass an "
            f"explicit devices= subset or add a -1 wildcard axis"
        )
    if spec.dcn_axes:
        return build_hybrid_mesh(spec, devices[:total])
    shape = tuple(sizes.values())
    names = tuple(sizes.keys())
    if use_default:
        # ICI-topology-aware layout: jax.make_mesh assigns axes onto the
        # physical torus so inner axes get the fastest links. Auto axis
        # types: the framework relies on GSPMD sharding propagation, not
        # the newer explicit sharding-in-types mode.
        return jax.make_mesh(
            shape, names,
            axis_types=(jax.sharding.AxisType.Auto,) * len(names),
        )
    subset = list(devices[:total])
    if all(getattr(d, "platform", None) == "tpu" for d in subset):
        # Explicit TPU device subsets (pod sub-meshes, virtual-topology
        # AOT compiles) still need ICI-aware placement: a flat reshape
        # makes ring neighbors physically distant, which v5e's limited
        # ICI routing rejects outright for async collective-permutes
        # and which throttles any real pod. mesh_utils orders by
        # physical coords; a subset it cannot lay out (an irregular
        # one) is an error here, never a silent flat reshape.
        from jax.experimental import mesh_utils

        return Mesh(
            mesh_utils.create_device_mesh(shape, devices=subset), names
        )
    arr = np.asarray(subset).reshape(shape)
    return Mesh(arr, names)


def slice_groups(devices: Sequence[jax.Device]) -> "list[list[jax.Device]]":
    """Group devices by TPU slice.

    Real multi-slice TPU devices carry ``slice_index``; everything else
    (single slice, CPU simulation) reports one group. Groups are ordered
    by slice index and each is ordered by the original device order.
    """
    by_slice: "dict[int, list[jax.Device]]" = {}
    for d in devices:
        by_slice.setdefault(getattr(d, "slice_index", 0) or 0, []).append(d)
    return [by_slice[k] for k in sorted(by_slice)]


def build_hybrid_mesh(
    spec: MeshSpec, devices: Sequence[jax.Device]
) -> Mesh:
    """Hybrid ICI x DCN mesh: DCN components vary slowest on each axis.

    On real multi-slice hardware the slice partition comes from each
    device's ``slice_index`` and the per-slice layout from
    ``mesh_utils.create_device_mesh`` (ICI-topology-aware, same
    contiguous-ring guarantee ``jax.make_mesh`` gives single-slice
    meshes). Under CPU simulation -- where devices carry no slice
    identity -- slices are emulated as equal contiguous chunks of the
    device list, so the sharding math and collective decomposition
    (intra-slice phases + one cross-slice phase) compile and can be
    tested without hardware.

    TPU analogue of the reference's NVLink-intra / Slingshot-inter mesh
    doctrine (fsdp_tp/fsdp_tp_example.py:12-26): put the
    bandwidth-tolerant axis (FSDP data) on DCN, keep latency-sensitive
    axes (TP/SP) inside a slice.
    """
    names = spec.axis_names
    n = len(devices)
    full = spec.resolved_sizes(n)
    ici = spec.ici_sizes(n)
    n_slices = spec.num_slices
    per_slice = n // n_slices
    ici_shape = tuple(ici[k] for k in names)
    dcn_shape = tuple(spec.dcn_axes.get(k, 1) for k in names)

    if getattr(devices[0], "platform", "") == "tpu":
        # Real hardware: the slice partition must come from the devices
        # themselves. A dcn_axes request against fewer physical slices
        # (e.g. --dcn-data-parallel 2 on a single slice) is a
        # misconfiguration, never something to emulate silently.
        groups = slice_groups(devices)
        if len(groups) != n_slices:
            raise ValueError(
                f"spec wants {n_slices} slices (dcn_axes="
                f"{dict(spec.dcn_axes)}) but the devices span "
                f"{len(groups)} physical slice(s)"
            )
        sizes = {len(g) for g in groups}
        if sizes != {per_slice}:
            raise ValueError(
                f"uneven slices: sizes {sorted(sizes)}, need "
                f"{per_slice} devices in each of {n_slices} slices"
            )
        from jax.experimental import mesh_utils

        # Groups by slice_index, lays each slice out ICI-topology-aware,
        # stacks with the DCN component slowest -- the hardware-path
        # behavior this module would otherwise have to track by hand.
        arr = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices
        )
        return Mesh(arr, names)

    # No slice identity (CPU simulation): emulate slices as equal
    # contiguous chunks of the device list so the sharding math and
    # collective decomposition are testable without hardware.
    flat = list(devices)
    groups = [
        flat[i * per_slice:(i + 1) * per_slice] for i in range(n_slices)
    ]
    per_slice_arrays = [np.asarray(g).reshape(ici_shape) for g in groups]
    # Stack slices into the DCN dims, then interleave so each named axis
    # factors as (dcn, ici) with dcn slowest: index = dcn_i * ici_k + ici_i.
    arr = np.empty(dcn_shape + ici_shape, dtype=object)
    for si, sa in enumerate(per_slice_arrays):
        arr[np.unravel_index(si, dcn_shape)] = sa
    k = len(names)
    perm = [x for i in range(k) for x in (i, k + i)]
    arr = arr.transpose(perm).reshape(tuple(full[k_] for k_ in names))
    return Mesh(arr, names)


def two_tier_spec(
    n_dev: int,
    n_slices: int,
    dcn: Optional[int] = None,
    inner_axis: str = "ici",
    dcn_axis: str = "dcn",
) -> MeshSpec:
    """Spec for a two-tier (dcn x ici) data mesh -- THE construction
    policy for everything that runs the hierarchical collectives
    (comm.hierarchical), shared so the resolution/validation/routing
    can never drift between callers (bench.py's --comm-mode path and
    tpu_hpc.comm.bench build from here).

    ``dcn=None`` resolves to the physical slice count when there is
    more than one slice, else an emulated 2 (CPU sim / single slice:
    the decomposition still compiles and parity-checks; the DCN win
    needs real slices). On real multi-slice hardware the dcn axis is
    declared via ``dcn_axes`` so :func:`build_hybrid_mesh` partitions
    it by physical ``slice_index`` -- a plain two-axis mesh does not
    survive ``jax.make_mesh`` on a multi-slice device set, and would
    not align the axis named "dcn" with slice boundaries even where
    it built. Topologies that cannot split into dcn x (ici >= 2)
    raise -- measuring something else while claiming "hierarchical"
    would poison any sweep built on the result.
    """
    if dcn is None:
        dcn = n_slices if n_slices > 1 else 2
    if dcn < 2 or n_dev % dcn or n_dev // dcn < 2:
        raise ValueError(
            f"no two-tier ({dcn_axis} x {inner_axis}>=2) mesh from "
            f"{n_dev} device(s) with {dcn_axis}={dcn} across "
            f"{n_slices} physical slice(s)"
        )
    if n_slices > 1:
        return MeshSpec(
            axes={dcn_axis: 1, inner_axis: n_dev // dcn},
            dcn_axes={dcn_axis: dcn},
        )
    return MeshSpec(axes={dcn_axis: dcn, inner_axis: n_dev // dcn})


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """Shorthand: ``named_sharding(mesh, 'data', None)``."""
    return NamedSharding(mesh, PartitionSpec(*spec))


def local_batch_size(global_batch: int, mesh: Mesh, axis: str = DATA_AXIS) -> int:
    """Per-data-shard batch size, validating divisibility.

    Parity with the reference's DistributedSampler contract: the global
    batch divides evenly over the data axis
    (scripts/01_data_parallel_ddp/multinode_ddp_unet.py:283-292).
    """
    n = mesh.shape[axis]
    if global_batch % n != 0:
        raise ValueError(f"global batch {global_batch} not divisible by {axis}={n}")
    return global_batch // n


def mesh_axis_size(mesh: Mesh, axis: Optional[str]) -> int:
    if axis is None:
        return 1
    return mesh.shape[axis]
