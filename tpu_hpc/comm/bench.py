"""Collective micro-benchmark over ICI/DCN: the ``torch_comm_bench`` port.

Parity with /root/reference/tests/torch_comm_bench.py:
  * broadcast + all-reduce (plus TPU extras: all-gather, reduce-scatter,
    ring send/recv) across element counts 10^3..10^8  (:196-240)
  * N warmup + M timed iterations, barrier-bracketed   (:40-90)
  * ring bus-bandwidth accounting 2(n-1)/n * size / t  (:92-116)
  * CSV output with a full environment-metadata header (:137-194)
  * CLI flags for sizes/warmup/bench/output             (:253-267)

Beyond the port, the comm-performance layer's ops are benched too:
  * hierarchical (two-phase) all-reduce / all-gather / reduce-scatter
    over a (dcn x ici) mesh (comm.hierarchical), with two-phase
    bus-bandwidth accounting: each record carries the per-device wire
    bytes of the ICI and DCN phases separately, because the whole
    point of the decomposition is that the DCN share shrinks by
    ~n_ici while the flat op ships the full payload cross-slice.
  * the overlap building blocks (comm.overlap): the ppermute ring
    all-gather and the collective-matmul-style gather_matmul (whose
    time includes the overlapped partial matmuls -- its busbw row is
    a lower bound on the gather throughput, by design).

Records land as CSV (metadata header + rows) AND JSONL (one record
per line, the BENCH-artifact format) when an output path is given.

The "barrier" on TPU is ``block_until_ready`` on the input (ensures
async dispatch has drained) before starting the clock, and on the
output before stopping it -- the same wall-clock bracketing as the
reference's ``dist.barrier(); t0; op; synchronize; barrier; t1``.
"""
from __future__ import annotations

import csv
import dataclasses
import io
import json
import os
import socket
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_hpc.comm import hierarchical, overlap, primitives

DEFAULT_SIZES = tuple(10**k for k in range(3, 9))  # torch_comm_bench.py:174
OPS = (
    "broadcast", "all_reduce", "all_gather", "reduce_scatter",
    "ring_shift", "all_to_all",
)
# Two-phase decompositions: need a (dcn x ici) mesh (comm.hierarchical).
HIER_OPS = ("hier_all_reduce", "hier_all_gather", "hier_reduce_scatter")
# Comm/compute-overlap building blocks (comm.overlap); run on the flat
# axis like the classic ops.
OVERLAP_OPS = ("ppermute_all_gather", "gather_matmul")
# Reshard-engine ops (tpu_hpc.reshard): plan + execute timings with
# modeled vs. measured bytes; each has a ``_bounded`` flavor running
# the chunked decomposition under max_inflight_bytes = total/4.
RESHARD_OPS = ("reshard_exchange", "reshard_replicate")
ALL_OPS = OPS + HIER_OPS + OVERLAP_OPS + RESHARD_OPS

# gather_matmul's fixed output width: the benched payload is the
# sharded weight [K/n, N]; K scales with the requested element count.
_GM_COLS = 128
_GM_ROWS_PER_SHARD = 8

# busbw factor class of each op (NCCL-tests convention, applied to the
# per-shard payload): the hierarchical/overlap ops reuse their flat
# op's factor so their rows are directly comparable to the flat rows.
_BUSBW_BASE = {
    "broadcast": "broadcast",
    "all_reduce": "all_reduce",
    "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "ring_shift": "ring_shift",
    "all_to_all": "all_to_all",
    "hier_all_reduce": "all_reduce",
    "hier_all_gather": "all_gather",
    "hier_reduce_scatter": "reduce_scatter",
    "ppermute_all_gather": "all_gather",
    "gather_matmul": "all_gather",
}


def wire_factor(op: str, n: int) -> float:
    """Per-device wire share of one flat collective over an ``n``-wide
    axis (the NCCL-tests busbw factor table) -- THE one copy: the CSV
    rows' busbw accounting and the planner's analytic cost model
    (comm/planner.py) both read it, so a factor correction can never
    leave the two computing from different wire models."""
    return {
        "broadcast": 1.0,
        "all_reduce": 2.0 * (n - 1) / n,
        "all_gather": (n - 1) / n,
        "reduce_scatter": (n - 1) / n,
        "ring_shift": 1.0,
        "all_to_all": (n - 1) / n,
    }[op]


def bus_bandwidth_gb_s(op: str, bytes_per_shard: int, n: int, t: float) -> float:
    """Ring bus-bandwidth model, matching torch_comm_bench.py:92-116.

    broadcast: size/t. all-reduce: 2(n-1)/n * size/t. all-gather and
    reduce-scatter move (n-1)/n * size: the standard NCCL-tests busbw
    factors (:func:`wire_factor`), applied unchanged to ICI.
    Hierarchical/overlap ops use their flat op's factor over the TOTAL
    axis extent (comparability with the flat row; the phase split is
    reported separately by :func:`two_phase_bytes`).
    """
    if t <= 0:
        return float("inf")
    return wire_factor(_BUSBW_BASE[op], n) * bytes_per_shard / t / 1e9


def two_phase_bytes(
    op: str, bytes_per_shard: int, n_dcn: int, n_ici: int
) -> Tuple[float, float]:
    """Per-device wire bytes of each phase of a hierarchical op:
    ``(ici_bytes, dcn_bytes)``.

    S = per-shard payload bytes; the decompositions are in
    comm.hierarchical. The headline number is the DCN column: the
    flat op ships its FULL cross-slice share over DCN, the two-phase
    op only the 1/n_ici-reduced shard (all-reduce) or exactly one
    copy of each remote shard (all-gather).

      hier_all_reduce:     ICI 2*S*(n_ici-1)/n_ici   (RS + AG on S)
                           DCN 2*(S/n_ici)*(n_dcn-1)/n_dcn
      hier_all_gather:     ICI S*n_dcn*(n_ici-1)     (redistribute)
                           DCN S*(n_dcn-1)           (one copy each)
      hier_reduce_scatter: ICI n*S*(n_ici-1)/n_ici   (scatter on n*S)
                           DCN S*(n_dcn-1)           (1/n_ici chunk)
    """
    s = float(bytes_per_shard)
    if op == "hier_all_reduce":
        return (
            2.0 * s * (n_ici - 1) / n_ici,
            2.0 * (s / n_ici) * (n_dcn - 1) / n_dcn,
        )
    if op == "hier_all_gather":
        return s * n_dcn * (n_ici - 1), s * (n_dcn - 1)
    if op == "hier_reduce_scatter":
        n = n_dcn * n_ici
        return n * s * (n_ici - 1) / n_ici, s * (n_dcn - 1)
    raise ValueError(f"not a two-phase op: {op}")


@dataclasses.dataclass
class CommBenchmark:
    """Configurable collective benchmark over one mesh axis (flat and
    overlap ops) or a (dcn x ici) axis pair (hierarchical ops, with
    ``dcn_axis`` naming the outer tier)."""

    mesh: Mesh
    axis: str = "data"
    sizes: Sequence[int] = DEFAULT_SIZES
    warmup: int = 5  # torch_comm_bench default :255
    iters: int = 20  # :256
    ops: Sequence[str] = OPS
    dtype: str = "float32"
    dcn_axis: Optional[str] = None

    def _world(self, op: str) -> int:
        n = self.mesh.shape[self.axis]
        if op in HIER_OPS:
            return n * self.mesh.shape[self.dcn_axis]
        return n

    def _fn_for(self, op: str):
        if op in HIER_OPS:
            if self.dcn_axis is None:
                raise ValueError(
                    f"{op} needs dcn_axis= (a two-tier mesh); got a "
                    "flat single-axis benchmark"
                )
            return getattr(hierarchical, op)(
                self.mesh, self.dcn_axis, self.axis
            )
        if op == "ppermute_all_gather":
            return overlap.ppermute_all_gather(self.mesh, self.axis)
        if op == "gather_matmul":
            return overlap.make_pipelined_gather_matmul(self.mesh, self.axis)
        return getattr(primitives, op)(self.mesh, self.axis)

    def _input_for(self, op: str, n_elements: int):
        """Build the benchmark payload: ``(args, bytes_per_shard)``.
        ``n_elements`` is the per-shard element count (matching the
        reference, where every rank holds `size` elements)."""
        n = self._world(op)
        dt = jnp.dtype(self.dtype)
        data_spec = (
            P((self.dcn_axis, self.axis)) if op in HIER_OPS
            else P(self.axis)
        )
        if op in (
            "broadcast", "all_reduce", "all_gather", "ring_shift",
            "hier_all_reduce", "hier_all_gather", "ppermute_all_gather",
        ):
            # globally [n*size], sharded over the axis (pair): each
            # device holds `size`.
            x = jnp.arange(n * n_elements, dtype=dt)
            x = jax.device_put(x, NamedSharding(self.mesh, data_spec))
            return (x,), x.nbytes // n
        elif op in ("reduce_scatter", "hier_reduce_scatter"):
            # replicated [n*size] input; output sharded.
            x = jnp.arange(n * n_elements, dtype=dt)
            x = jax.device_put(x, NamedSharding(self.mesh, P()))
            return (x,), x.nbytes // n
        elif op == "all_to_all":
            # The Ulysses building block: [n, inner] sharded on dim 0
            # in, dim 1 out; each device still holds ~``size`` elements
            # (inner rounded up so the n-way column split is exact).
            inner = -(-n_elements // n) * n
            x = jnp.arange(n * inner, dtype=dt).reshape(n, inner)
            x = jax.device_put(x, NamedSharding(self.mesh, P(self.axis)))
            return (x,), x.nbytes // n
        elif op == "gather_matmul":
            # FSDP forward shape: x batch-sharded [n*rows, K], weight
            # dim-0-sharded [K, cols]; the benched payload is the
            # weight shard (what the ring gathers).
            k_shard = max(-(-n_elements // _GM_COLS), 1)
            k = n * k_shard
            w = jnp.arange(k * _GM_COLS, dtype=dt).reshape(k, _GM_COLS)
            x = jnp.ones((n * _GM_ROWS_PER_SHARD, k), dtype=dt)
            w = jax.device_put(w, NamedSharding(self.mesh, P(self.axis)))
            x = jax.device_put(x, NamedSharding(self.mesh, P(self.axis)))
            return (x, w), w.nbytes // n
        raise ValueError(op)

    def run(self) -> List[Dict]:
        from tpu_hpc.comm.planner import fingerprint_mesh

        # Topology fingerprint: the planner's cost-table cache key.
        # Deliberately a function of the DEVICE SET (not this mesh's
        # axis layout), so the flat and hierarchical rows of one sweep
        # key the same table (comm/planner.py).
        fp = fingerprint_mesh(self.mesh).digest
        records = []
        for op in self.ops:
            fn = self._fn_for(op)
            n = self._world(op)
            for size in self.sizes:
                args, nbytes = self._input_for(op, size)
                for a in args:
                    a.block_until_ready()
                for _ in range(self.warmup):
                    fn(*args).block_until_ready()
                times = []
                for _ in range(self.iters):
                    for a in args:  # barrier (ref :44-46)
                        a.block_until_ready()
                    t0 = time.perf_counter()
                    out = fn(*args)
                    out.block_until_ready()  # synchronize (ref :52-56)
                    times.append(time.perf_counter() - t0)
                times = np.asarray(times)
                rec = {
                    "op": op,
                    "size_elements": size,
                    "bytes_per_shard": nbytes,
                    "dtype": self.dtype,
                    "fingerprint": fp,
                    "world_size": n,
                    "mean_s": float(times.mean()),
                    "std_s": float(times.std()),
                    "min_s": float(times.min()),
                    "max_s": float(times.max()),
                    "busbw_GB_s": bus_bandwidth_gb_s(
                        op, nbytes, n, float(times.mean())
                    ),
                }
                if op in HIER_OPS:
                    n_dcn = self.mesh.shape[self.dcn_axis]
                    n_ici = self.mesh.shape[self.axis]
                    ici_b, dcn_b = two_phase_bytes(
                        op, nbytes, n_dcn, n_ici
                    )
                    rec.update({
                        "n_dcn": n_dcn,
                        "n_ici": n_ici,
                        "ici_bytes_per_shard": round(ici_b),
                        "dcn_bytes_per_shard": round(dcn_b),
                        "dcn_fraction": round(
                            dcn_b / (dcn_b + ici_b), 6
                        ) if (dcn_b + ici_b) else 0.0,
                    })
                records.append(rec)
        return records


def run_reshard_bench(
    mesh: Mesh,
    axis: str = "data",
    sizes: Sequence[int] = DEFAULT_SIZES,
    warmup: int = 5,
    iters: int = 20,
    ops: Sequence[str] = RESHARD_OPS,
    dtype: str = "float32",
) -> List[Dict]:
    """Benchmark the reshard engine's plan + execute over one mesh
    axis, emitting SCHEMA-STAMPED bench rows (obs.schema ``bench``
    events) so the rows ride straight into the regress gate's --bank
    diff next to the training/serving history.

    Two ops x two flavors per size:

    * ``reshard_exchange``  -- ``[n, inner]`` sharded dim 0 -> dim 1
      (the Ulysses-style axis swap, GSPMD's full-remat trap);
    * ``reshard_replicate`` -- sharded -> fully replicated (the
      required-residency case; never bounded, the full copy IS the
      target);
    * ``*_bounded``         -- the same exchange decomposed under
      ``max_inflight_bytes = total_bytes / 4``: what the bound costs
      in time is exactly what it saves in peak HBM, and both sides of
      that trade land in one row (``plan_ms``, ``mean_s``,
      ``wire_bytes_modeled`` vs ``bytes_moved``, ``chunks``,
      ``peak_inflight_bytes``).
    """
    from tpu_hpc import reshard
    from tpu_hpc.comm.planner import fingerprint_mesh
    from tpu_hpc.obs.schema import stamp

    fp = fingerprint_mesh(mesh).digest
    n = mesh.shape[axis]
    if n < 2:
        print(
            f"comm.bench: skipping reshard ops -- axis {axis!r} has "
            f"size {n} (< 2): nothing to redistribute",
            file=sys.stderr,
        )
        return []
    dt = jnp.dtype(dtype)
    records: List[Dict] = []
    for op in ops:
        if op not in RESHARD_OPS:
            raise ValueError(f"not a reshard op: {op}")
        flavors = (
            (False, True) if op == "reshard_exchange" else (False,)
        )
        for bounded in flavors:
            for size in sizes:
                if op == "reshard_exchange":
                    inner = -(-size // n) * n
                    x = jnp.arange(n * inner, dtype=dt).reshape(
                        n, inner
                    )
                    src, tgt = P(axis), P(None, axis)
                else:
                    x = jnp.arange(n * size, dtype=dt)
                    src, tgt = P(axis), P()
                x = jax.device_put(x, NamedSharding(mesh, src))
                x.block_until_ready()
                bound = x.nbytes // 4 if bounded else None
                t0 = time.perf_counter()
                plan = reshard.plan_reshard(
                    {"x": x}, {"x": NamedSharding(mesh, tgt)},
                    max_inflight_bytes=bound,
                )
                plan_ms = (time.perf_counter() - t0) * 1e3
                for _ in range(warmup):
                    plan.execute({"x": x})["x"].block_until_ready()
                times = []
                for _ in range(iters):
                    x.block_until_ready()
                    t0 = time.perf_counter()
                    out = plan.execute({"x": x})
                    out["x"].block_until_ready()
                    times.append(time.perf_counter() - t0)
                times = np.asarray(times)
                mean = float(times.mean())
                name = op + ("_bounded" if bounded else "")
                step = plan.steps[0]
                common = {
                    "op": name,
                    "size_elements": size,
                    "bytes_per_shard": x.nbytes // n,
                    "dtype": dtype,
                    "fingerprint": fp,
                    "world_size": n,
                    "max_inflight_bytes": bound,
                }
                # The size rides IN the metric name: the bank gate
                # reduces per metric (best on the baseline side,
                # latest on the candidate side), and a sweep emitting
                # one name for every size would diff
                # min-across-sizes against the last size measured.
                records.append(stamp({
                    "event": "bench",
                    "metric": f"{name}_n{size}_ms",
                    "value": round(mean * 1e3, 6),
                    "unit": "ms",
                    **common,
                    "mean_s": mean,
                    "std_s": float(times.std()),
                    "min_s": float(times.min()),
                    "max_s": float(times.max()),
                    "plan_ms": round(plan_ms, 6),
                    "wire_bytes_modeled": plan.wire_bytes,
                    "bytes_moved": plan.bytes,
                    "peak_inflight_bytes": plan.peak_inflight_bytes,
                    "chunks": (
                        step.chunk.count if step.chunk else 1
                    ),
                    "busbw_GB_s": (
                        plan.wire_bytes / mean / 1e9 if mean > 0
                        else float("inf")
                    ),
                }))
                records.append(stamp({
                    "event": "bench",
                    "metric": f"{name}_n{size}_wire_bytes",
                    "value": plan.wire_bytes,
                    "unit": "bytes",
                    **common,
                }))
    return records


def _env_metadata(mesh: Mesh) -> Dict[str, str]:
    """CSV metadata header block, parity with torch_comm_bench.py:153-194
    (host, versions, backend, world size -> TPU equivalents)."""
    d = jax.devices()[0]
    return {
        "hostname": socket.gethostname(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": d.device_kind,
        "process_count": str(jax.process_count()),
        "global_devices": str(jax.device_count()),
        "mesh": str(dict(mesh.shape)),
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
    }


def _fieldnames(records: List[Dict]) -> List[str]:
    """Union of record keys in first-seen order: hierarchical records
    carry phase columns the flat rows lack, and DictWriter must see
    one superset schema (missing cells stay empty)."""
    names: List[str] = []
    for r in records:
        for k in r:
            if k not in names:
                names.append(k)
    return names


def write_csv(records: List[Dict], mesh: Mesh, path: Optional[str]) -> str:
    """Write benchmark CSV (metadata as comment lines, then rows).
    Returns the CSV text. Rank-0-only output is implicit: call from
    host 0 (jax arrays are process-global)."""
    buf = io.StringIO()
    for k, v in _env_metadata(mesh).items():
        buf.write(f"# {k}: {v}\n")
    if records:
        w = csv.DictWriter(buf, fieldnames=_fieldnames(records))
        w.writeheader()
        w.writerows(records)
    text = buf.getvalue()
    if path and jax.process_index() == 0:
        with open(path, "w") as f:
            f.write(text)
    return text


def write_jsonl(records: List[Dict], path: str) -> None:
    """One JSON record per line -- the BENCH-artifact format, so comm
    rows can ride next to training/serving rows in the same tooling."""
    if jax.process_index() != 0:
        return
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def run_comm_bench(
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    sizes: Sequence[int] = DEFAULT_SIZES,
    warmup: int = 5,
    iters: int = 20,
    ops: Sequence[str] = OPS,
    output: Optional[str] = None,
    dcn: Optional[int] = None,
    hier_mesh: Optional[Mesh] = None,
) -> List[Dict]:
    """One-call benchmark entry (the ``init_processes`` analogue,
    torch_comm_bench.py:144-251).

    Flat and overlap ops run over ``axis`` of ``mesh`` (built over all
    devices when None); hierarchical ops run over ``hier_mesh`` (a
    ``{dcn: dcn, ici: rest}`` mesh built on demand -- the 8-device sim
    gives the 2x4 dcn x ici shape the parity tests pin). ``dcn=None``
    resolves to the physical slice count on multi-slice hardware (the
    only extent the fabric supports) and an emulated 2 elsewhere; on
    real slices the mesh routes through ``MeshSpec.dcn_axes`` ->
    ``build_hybrid_mesh`` so the "dcn" axis is partitioned by physical
    ``slice_index`` -- the dcn-bytes columns must label actual DCN
    traffic, and a plain two-axis ``jax.make_mesh`` over a multi-slice
    device set crashes outright. With ``output=...`` the records land
    as CSV there plus JSONL at the same stem; without, the CSV text
    prints to stdout.
    """
    unknown = [op for op in ops if op not in ALL_OPS]
    if unknown:
        raise ValueError(f"unknown ops {unknown}; choose from {ALL_OPS}")
    flat_ops = [
        op for op in ops if op not in HIER_OPS and op not in RESHARD_OPS
    ]
    hier_ops = [op for op in ops if op in HIER_OPS]
    reshard_ops = [op for op in ops if op in RESHARD_OPS]
    records: List[Dict] = []
    from tpu_hpc.runtime import MeshSpec, build_mesh

    if flat_ops or reshard_ops:
        if mesh is None:
            mesh = build_mesh(MeshSpec(axes={axis: -1}))
    if flat_ops:
        records += CommBenchmark(
            mesh=mesh, axis=axis, sizes=sizes, warmup=warmup,
            iters=iters, ops=flat_ops,
        ).run()
    if reshard_ops:
        records += run_reshard_bench(
            mesh, axis=axis, sizes=sizes, warmup=warmup, iters=iters,
            ops=reshard_ops,
        )
    if hier_ops:
        if hier_mesh is None:
            from tpu_hpc.runtime.mesh import slice_groups, two_tier_spec

            # Follow the flat mesh's extent when one was given: rows
            # from two different world sizes in one artifact would
            # make every cross-op busbw comparison apples-to-oranges.
            # The construction policy itself (dcn resolution,
            # validity, slice-aligned dcn_axes routing on real
            # multi-slice hardware) is runtime.mesh.two_tier_spec --
            # single-sourced with bench.py's --comm-mode path.
            n_dev = jax.device_count() if mesh is None else mesh.size
            n_slices = len(slice_groups(jax.devices()))
            if n_slices > 1 and n_dev != jax.device_count():
                print(
                    f"comm.bench: skipping {hier_ops} -- the "
                    "hierarchical mesh needs the whole multi-slice "
                    f"device set (slice-aligned dcn axis), but the "
                    f"flat mesh spans only {n_dev} of "
                    f"{jax.device_count()} devices",
                    file=sys.stderr,
                )
                hier_ops = []
            else:
                try:
                    # build_mesh is inside the skip handler too: an
                    # explicit --dcn that disagrees with the physical
                    # slice count raises in build_hybrid_mesh, and the
                    # already-measured flat rows must still be written.
                    hier_mesh = build_mesh(
                        two_tier_spec(n_dev, n_slices, dcn=dcn),
                        devices=None if n_dev == jax.device_count()
                        else jax.devices()[:n_dev],
                    )
                except ValueError as e:
                    print(
                        f"comm.bench: skipping {hier_ops} -- {e}",
                        file=sys.stderr,
                    )
                    hier_ops = []
        if hier_ops:
            records += CommBenchmark(
                mesh=hier_mesh, axis="ici", dcn_axis="dcn",
                sizes=sizes, warmup=warmup, iters=iters, ops=hier_ops,
            ).run()
    meta_mesh = mesh if mesh is not None else hier_mesh
    if meta_mesh is None:
        # Every requested op was skipped (hier-only request with no
        # buildable two-tier mesh): nothing measured, nothing to
        # write -- the skip notice above already said why.
        return records
    if output:
        # --output x.jsonl must not have the JSONL overwrite the CSV
        # just written to the same path: the two artifacts always land
        # at <stem>.csv and <stem>.jsonl.
        stem, ext = os.path.splitext(output)
        csv_path = stem + ".csv" if ext == ".jsonl" else output
        jsonl_path = stem + ".jsonl"
        write_csv(records, meta_mesh, csv_path)
        write_jsonl(records, jsonl_path)
        if jax.process_index() == 0:
            print(f"comm bench: wrote {csv_path} and {jsonl_path}")
    else:
        text = write_csv(records, meta_mesh, None)
        if jax.process_index() == 0:
            print(text)
    return records


def main(argv: Optional[Sequence[str]] = None) -> None:
    import argparse

    p = argparse.ArgumentParser(
        description="XLA collective benchmark over ICI/DCN"
    )
    p.add_argument("--sizes", type=int, nargs="+", default=list(DEFAULT_SIZES))
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--ops", nargs="+", default=list(ALL_OPS), choices=ALL_OPS)
    p.add_argument(
        "--op", action="append", default=None, choices=ALL_OPS,
        metavar="OP",
        help="bench only this op (repeatable); overrides --ops",
    )
    p.add_argument(
        "--output", type=str, default="comm_bench.csv",
        help="CSV path; a JSONL lands at the same stem ('-' = print "
        "CSV to stdout only)",
    )
    p.add_argument("--axis-size", type=int, default=-1)
    p.add_argument(
        "--emit-table", type=str, default=None, metavar="PATH",
        help="also write a planner-consumable cost table built from "
        "this run's rows (tpu_hpc.comm.planner CostTable JSON). A "
        "directory path writes <fingerprint>.json inside it -- point "
        "it at the planner's cache dir ($TPU_HPC_COMM_TABLES) and "
        "comm_mode='auto' picks the measurements up directly",
    )
    p.add_argument(
        "--dcn", type=int, default=None,
        help="DCN (outer-tier) extent for the hierarchical ops' "
        "(dcn x ici) mesh; default: the physical slice count on "
        "multi-slice hardware, else an emulated 2 (CPU sim / single "
        "slice)",
    )
    args = p.parse_args(argv)

    from tpu_hpc.runtime import MeshSpec, build_mesh, init_distributed

    init_distributed()
    ops = tuple(args.op) if args.op else tuple(args.ops)
    mesh = None
    if any(op not in HIER_OPS for op in ops):
        mesh = build_mesh(MeshSpec(axes={"data": args.axis_size}))
    output = None if args.output == "-" else args.output
    records = run_comm_bench(
        mesh,
        sizes=args.sizes,
        warmup=args.warmup,
        iters=args.iters,
        ops=ops,
        output=output,
        dcn=args.dcn,
    )
    if args.emit_table and jax.process_index() == 0:
        from tpu_hpc.comm import planner

        try:
            # The whole-device-set fingerprint: rows measured on a
            # sub-mesh (--axis-size) key a different topology and are
            # filtered out rather than poisoning the live table.
            table = planner.CostTable.from_rows(
                records, fingerprint=planner.fingerprint_devices()
            )
        except planner.CostTableError as e:
            print(
                f"comm bench: --emit-table skipped -- {e}",
                file=sys.stderr,
            )
        else:
            path = table.save(args.emit_table)
            print(
                f"comm bench: wrote cost table {path} "
                f"({len(table)} entries, fingerprint {table.digest})"
            )


if __name__ == "__main__":
    main()
