"""The resharding engine: exact wire model, parity vs the naive
reference, the max_inflight_bytes contract, and elastic resume.

Four invariant families:

* **wire model** -- modeled wire bytes come from the shardings'
  device->index maps, so hand-checkable cases must match exactly
  (equivalent placements 0, replicated->sharded 0, known overlaps);
* **parity** -- for random param trees and random source->target
  ``NamedSharding`` pairs (non-divisible shapes, bf16, degenerate
  1-sized axes, scalars, mesh-shape changes) the planned execution is
  BIT-identical to the naive replicate-then-shard reference
  (device_get -> host -> device_put): the engine moves bytes, it never
  touches them;
* **memory bound** -- a plan built under ``max_inflight_bytes``
  decomposes big moves into chunks, and the per-step compiled HLO's
  largest live tensor (checks/hlo.max_tensor_bytes -- compiled HLO is
  per-device) stays within the step's modeled HBM ceiling, while the
  unbounded program for the same leaf materializes the FULL array
  (GSPMD's involuntary full rematerialization -- the failure mode the
  decomposition exists to forbid);
* **elastic resume** -- a checkpoint saved on one mesh shape restores
  onto a different shape through the explicit reshard path, bit-exact,
  end-to-end under the supervisor with fault injection
  (TestElasticSupervised = the acceptance run), and a structurally
  incompatible checkpoint raises the typed TopologyMismatchError
  naming both topologies.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_hpc import reshard
from tpu_hpc.checks import hlo
from tpu_hpc.runtime import MeshSpec, build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def mesh_a(devices):
    """4-device 1-D mesh -- the 'before' topology."""
    return build_mesh(MeshSpec(axes={"data": 4}), devices=devices[:4])


@pytest.fixture(scope="module")
def mesh_b(devices):
    """4-device 2x2 mesh over the SAME chips -- the 'after' topology."""
    return build_mesh(
        MeshSpec(axes={"data": 2, "model": 2}), devices=devices[:4]
    )


def _put(mesh, spec, arr):
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _naive(x, tgt):
    """The replicate-then-shard reference: gather everything to host,
    place it in the target layout. Trivially correct, maximally
    memory-hungry -- the behavior the engine must match bit-for-bit
    while never being forced to replicate."""
    return jax.device_put(np.asarray(jax.device_get(x)), tgt)


def _assert_moved(out, x, tgt):
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(_naive(x, tgt))
    )
    assert out.sharding.is_equivalent_to(tgt, out.ndim)


# ---------------------------------------------------------------------
# wire model
# ---------------------------------------------------------------------
class TestWireModel:
    def test_equivalent_placements_are_noop(self, mesh_2d):
        x = _put(mesh_2d, P("data"), jnp.zeros((8, 4)))
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P("data"))}
        )
        assert plan.steps[0].kind == "noop"
        assert plan.wire_bytes == 0
        # noop passthrough: the SAME array comes back, no move at all.
        assert plan.execute({"x": x})["x"] is x

    def test_equivalence_across_mesh_spellings(self, mesh_2d):
        """P(('data','model')) on the 2x4 mesh assigns exactly what
        P('data') does on a flat 8-mesh over the same devices: the
        planner must see through the spelling."""
        mesh8 = build_mesh(MeshSpec(axes={"data": 8}))
        x = _put(mesh8, P("data"), jnp.arange(16.0))
        plan = reshard.plan_reshard(
            {"x": x},
            {"x": NamedSharding(mesh_2d, P(("data", "model")))},
        )
        assert plan.steps[0].kind == "noop"

    def test_replicated_to_sharded_is_local(self, mesh_2d):
        x = _put(mesh_2d, P(), jnp.zeros((8, 4)))
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P("data", "model"))}
        )
        step = plan.steps[0]
        assert step.kind == "local"
        assert step.wire_bytes == 0
        out = plan.execute({"x": x})["x"]
        _assert_moved(out, x, NamedSharding(mesh_2d, P("data", "model")))

    def test_exchange_wire_bytes_hand_case(self, mesh_2d):
        """64x32 fp32, P('data') -> P(None,'model') on the 2x4 mesh:
        every device needs 64x8 elems (2048 B), already holds the
        32x8 intersection (1024 B) -> 8 x 1024 B = 8 KiB wire."""
        x = _put(
            mesh_2d, P("data"),
            jnp.zeros((64, 32), jnp.float32),
        )
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P(None, "model"))}
        )
        step = plan.steps[0]
        assert step.kind == "exchange"
        assert step.wire_bytes == 8 * 1024

    def test_gather_wire_and_kind(self, mesh_2d):
        """Sharded -> fully replicated: every device fetches what it
        lacks; the step is 'gather' and lowers to an all-gather."""
        x = _put(mesh_2d, P("data"), jnp.zeros((8, 4), jnp.float32))
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P())}
        )
        step = plan.steps[0]
        assert step.kind == "gather"
        # 8 devices each hold half (64 B) and need the rest (64 B).
        assert step.wire_bytes == 8 * 64
        counts = hlo.collective_counts(plan.step_hlo(0)[0])
        assert counts["all-gather"] >= 1

    def test_summary_and_describe(self, mesh_2d):
        x = _put(mesh_2d, P("data"), jnp.zeros((8, 4)))
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P())}
        )
        s = plan.summary()
        assert s["steps"] == 1 and s["kinds"] == {"gather": 1}
        assert "gather" in plan.describe()


# ---------------------------------------------------------------------
# parity: random trees x random sharding pairs == naive reference
# ---------------------------------------------------------------------
# (shape, dtype): non-divisible dims, a scalar, a degenerate 1-sized
# axis, bf16 -- the shapes the satellite calls out.
_LEAF_CASES = (
    ((8, 12), jnp.float32),
    ((7, 4), jnp.bfloat16),
    ((16,), jnp.int32),
    ((1, 8, 6), jnp.float32),
    ((), jnp.float32),
    ((5,), jnp.bfloat16),
)


def _random_spec(rng, shape, mesh):
    """A random legal PartitionSpec: each dim claims an unused mesh
    axis (or axis pair) that divides it, or stays unsharded."""
    used = set()
    entries = []
    for dim in shape:
        opts = [None]
        free = [a for a in mesh.axis_names if a not in used]
        for ax in free:
            if mesh.shape[ax] > 1 and dim % mesh.shape[ax] == 0:
                opts.append(ax)
        if len(free) == 2:
            prod = mesh.shape[free[0]] * mesh.shape[free[1]]
            if dim % prod == 0:
                opts.append(tuple(free))
        pick = opts[int(rng.integers(len(opts)))]
        if isinstance(pick, str):
            used.add(pick)
        elif isinstance(pick, tuple):
            used.update(pick)
        entries.append(pick)
    return P(*entries)


class TestParity:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_tree_random_pairs_same_mesh(self, mesh_2d, seed):
        rng = np.random.default_rng(seed)
        tree, targets = {}, {}
        for i, (shape, dtype) in enumerate(_LEAF_CASES):
            data = rng.integers(-100, 100, size=shape or (1,))
            arr = jnp.asarray(
                data.reshape(shape) if shape else data[0], dtype
            )
            src = _random_spec(rng, shape, mesh_2d)
            tgt = _random_spec(rng, shape, mesh_2d)
            tree[f"l{i}"] = _put(mesh_2d, src, arr)
            targets[f"l{i}"] = NamedSharding(mesh_2d, tgt)
        out = reshard.apply(tree, targets)
        for k in tree:
            _assert_moved(out[k], tree[k], targets[k])

    @pytest.mark.parametrize("seed", [0, 1])
    def test_random_pairs_bounded(self, mesh_2d, seed):
        """Same property under a tight bound: chunked decomposition
        must stay bit-identical (uneven final chunks included)."""
        rng = np.random.default_rng(seed)
        tree, targets = {}, {}
        for i, (shape, dtype) in enumerate(_LEAF_CASES):
            data = rng.integers(-100, 100, size=shape or (1,))
            arr = jnp.asarray(
                data.reshape(shape) if shape else data[0], dtype
            )
            tree[f"l{i}"] = _put(
                mesh_2d, _random_spec(rng, shape, mesh_2d), arr
            )
            targets[f"l{i}"] = NamedSharding(
                mesh_2d, _random_spec(rng, shape, mesh_2d)
            )
        out = reshard.apply(tree, targets, max_inflight_bytes=96)
        for k in tree:
            _assert_moved(out[k], tree[k], targets[k])

    def test_mesh_shape_change(self, mesh_a, mesh_b, mesh_2d):
        """Cross-topology moves: 4 -> 2x2 over the same chips, 2x4
        (8 chips) -> 4 (a shrink), 4 -> 2x4 (a grow)."""
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.integers(-9, 9, size=(8, 4)), jnp.float32)
        cases = [
            (mesh_a, P("data"), mesh_b, P(None, "model")),
            (mesh_2d, P("data", "model"), mesh_a, P("data")),
            (mesh_a, P(None), mesh_2d, P(("data", "model"))),
        ]
        for src_mesh, src, tgt_mesh, tgt in cases:
            arr = _put(src_mesh, src, x)
            sharding = NamedSharding(tgt_mesh, tgt)
            plan = reshard.plan_reshard({"x": arr}, {"x": sharding})
            assert plan.steps[0].kind in ("transfer", "local", "noop")
            _assert_moved(plan.execute({"x": arr})["x"], arr, sharding)

    def test_mesh_change_bounded_chunked(self, mesh_a, mesh_b):
        """Cross-mesh chunked path, odd extent: 10 rows under a bound
        forcing 3-row chunks (last chunk is 1 row)."""
        x = _put(
            mesh_a, P(None, "data"),
            jnp.arange(10 * 8, dtype=jnp.float32).reshape(10, 8),
        )
        tgt = NamedSharding(mesh_b, P("data", "model"))
        plan = reshard.plan_reshard(
            {"x": x}, {"x": tgt}, max_inflight_bytes=3 * 8 * 4
        )
        step = plan.steps[0]
        assert step.kind == "transfer" and step.chunk is not None
        assert step.chunk.count == 4  # ceil(10 / 3)
        _assert_moved(plan.execute({"x": x})["x"], x, tgt)

    def test_single_sharding_broadcast_target(self, mesh_2d):
        tree = {
            "a": _put(mesh_2d, P("data"), jnp.zeros((8, 2))),
            "b": _put(mesh_2d, P(), jnp.ones((4,))),
        }
        rep = NamedSharding(mesh_2d, P())
        out = reshard.apply(tree, rep)
        for k in tree:
            assert out[k].sharding.is_fully_replicated

    def test_host_leaves_are_placed(self, mesh_2d):
        """Leaves with no committed sharding (host numpy, fresh jnp
        arrays) take the 'place' path."""
        tree = {"w": jnp.arange(8.0)}
        tgt = {"w": NamedSharding(mesh_2d, P("data"))}
        plan = reshard.plan_reshard(tree, tgt)
        assert plan.steps[0].kind == "place"
        out = plan.execute(tree)
        np.testing.assert_array_equal(
            np.asarray(out["w"]), np.arange(8.0)
        )

    def test_copy_noop_gives_fresh_buffers(self, mesh_2d):
        """The serve weight-placement contract: with copy_noop=True an
        already-correctly-placed leaf still comes back as a FRESH
        array (safe next to donation of the source tree), while the
        default passes the input through untouched."""
        x = _put(mesh_2d, P("data"), jnp.arange(8.0))
        tgt = {"x": NamedSharding(mesh_2d, P("data"))}
        assert reshard.apply({"x": x}, tgt)["x"] is x
        fresh = reshard.apply({"x": x}, tgt, copy_noop=True)["x"]
        assert fresh is not x
        np.testing.assert_array_equal(
            np.asarray(fresh), np.asarray(x)
        )

    def test_copy_noop_severs_device_put_aliasing(
        self, mesh_a, devices
    ):
        """device_put onto an overlapping device set can return
        buffers ALIASED with the source; under copy_noop the executor
        must sever that (the fresh-buffer contract holds on every
        path): deleting the source afterwards leaves the output
        readable."""
        sub = build_mesh(MeshSpec(axes={"data": 2}),
                         devices=devices[:2])
        x = _put(mesh_a, P(), jnp.arange(12.0))
        out = reshard.apply(
            {"x": x}, {"x": NamedSharding(sub, P())}, copy_noop=True
        )["x"]
        x.delete()
        np.testing.assert_array_equal(
            np.asarray(out), np.arange(12.0)
        )

    def test_place_params_fresh_buffer_contract(self, mesh_2d):
        """serve/weights.place_params keeps the old jitted-identity
        guarantee through the engine: no output leaf aliases its
        input, even when the input is already in the serving layout."""
        from tpu_hpc.serve.weights import place_params

        params = {"w": _put(mesh_2d, P(None, "model"),
                            jnp.zeros((4, 8)))}
        out = place_params(params, mesh_2d, {"w": P(None, "model")})
        assert out["w"] is not params["w"]

    def test_donate_frees_disjoint_tier_sources(self, devices):
        """The cross-tier memory contract (the disagg KV hop's shape):
        donate=True deletes each source buffer as its stage's target
        materializes when the tiers are DISJOINT -- the case jit
        donation cannot reach and buffer aliasing cannot occur."""
        lo = build_mesh(MeshSpec(axes={"data": 4}),
                        devices=devices[:4])
        hi = build_mesh(MeshSpec(axes={"data": 2, "model": 2}),
                        devices=devices[4:])
        x = _put(lo, P("data"), jnp.arange(32.0).reshape(8, 4))
        tgt = {"x": NamedSharding(hi, P(None, "model"))}
        out = reshard.apply({"x": x}, tgt, donate=True)
        assert x.is_deleted()
        np.testing.assert_array_equal(
            np.asarray(out["x"]), np.arange(32.0).reshape(8, 4)
        )

    def test_donate_keeps_overlapping_set_sources_alive(
        self, mesh_a, mesh_b
    ):
        """Overlapping device sets (the elastic-restore shape): jax
        may hand back ALIASED buffers from device_put, so donate must
        NOT hard-delete the source -- the output has to survive, and
        noop leaves pass through untouched."""
        x = _put(mesh_a, P("data"), jnp.arange(32.0).reshape(8, 4))
        tgt = {"x": NamedSharding(mesh_b, P(None, "model"))}
        out = reshard.apply({"x": x}, tgt, donate=True)
        np.testing.assert_array_equal(
            np.asarray(out["x"]), np.arange(32.0).reshape(8, 4)
        )
        y = _put(mesh_a, P("data"), jnp.arange(8.0))
        out2 = reshard.apply(
            {"y": y}, {"y": NamedSharding(mesh_a, P("data"))},
            donate=True,
        )
        assert out2["y"] is y and not y.is_deleted()

    def test_mismatched_tree_rejected(self, mesh_2d):
        x = _put(mesh_2d, P(), jnp.zeros((4,)))
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P("data"))}
        )
        with pytest.raises(ValueError, match="does not match"):
            plan.execute({"x": _put(mesh_2d, P(), jnp.zeros((8,)))})


class TestLongShapes:
    def test_long_shape_bounded_parity_sweep(self, mesh_2d):
        """The slow-tier sweep: long shapes, more seeds, tight bounds
        driving chunk counts into the tens -- the same bit-identity
        property at a scale where a modeling bug would actually show
        (uneven final chunks, multi-axis specs, bf16)."""
        shapes = [
            ((256, 96), jnp.float32),
            ((130, 64), jnp.bfloat16),
            ((64, 48, 2), jnp.float32),
            ((1024,), jnp.int32),
            ((999,), jnp.bfloat16),
        ]
        for seed in range(4):
            rng = np.random.default_rng(seed)
            for shape, dtype in shapes:
                arr = jnp.asarray(
                    rng.integers(-100, 100, size=shape), dtype
                )
                src = _random_spec(rng, shape, mesh_2d)
                tgt = _random_spec(rng, shape, mesh_2d)
                x = _put(mesh_2d, src, arr)
                sharding = NamedSharding(mesh_2d, tgt)
                bound = max(256, x.nbytes // 7)
                plan = reshard.plan_reshard(
                    {"x": x}, {"x": sharding},
                    max_inflight_bytes=bound,
                )
                step = plan.steps[0]
                if step.chunk is not None and step.bound_met:
                    assert step.inflight_bytes <= bound
                _assert_moved(
                    plan.execute({"x": x})["x"], x, sharding
                )


# ---------------------------------------------------------------------
# the max_inflight_bytes contract, pinned via HLO introspection
# ---------------------------------------------------------------------
class TestMemoryBound:
    def test_max_tensor_bytes_reads_both_dialects(self, mesh_2d):
        """The instrument must not pass vacuously on lowered
        (StableHLO) text: both the compiled ``f32[64,32]`` and the
        StableHLO ``tensor<64x32xf32>`` spellings are measured."""
        x = _put(mesh_2d, P("data"), jnp.zeros((64, 32), jnp.float32))
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P())}
        )
        compiled = plan.step_hlo(0, compiled=True)[0]
        lowered = plan.step_hlo(0, compiled=False)[0]
        assert hlo.max_tensor_bytes(compiled) == 64 * 32 * 4
        assert hlo.max_tensor_bytes(lowered) == 64 * 32 * 4

    def test_unbounded_exchange_materializes_full_replica(self, mesh_2d):
        """The failure mode: GSPMD solves P('data') -> P(None,'model')
        by involuntary full rematerialization -- the compiled per-device
        HLO holds the FULL 8 KiB array."""
        x = _put(
            mesh_2d, P("data"), jnp.zeros((64, 32), jnp.float32)
        )
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P(None, "model"))}
        )
        assert plan.steps[0].chunk is None
        mx = max(hlo.max_tensor_bytes(t) for t in plan.step_hlo(0))
        assert mx == 64 * 32 * 4  # the full replica

    def test_bounded_plan_never_materializes_full_replica(self, mesh_2d):
        """THE acceptance pin: under max_inflight_bytes, every step
        program's largest live per-device tensor stays within the
        step's modeled HBM ceiling -- no program is ever allowed the
        full array the unbounded path materializes."""
        full = 64 * 32 * 4
        bound = full // 4
        x = _put(
            mesh_2d, P("data"), jnp.zeros((64, 32), jnp.float32)
        )
        plan = reshard.plan_reshard(
            {"x": x},
            {"x": NamedSharding(mesh_2d, P(None, "model"))},
            max_inflight_bytes=bound,
        )
        step = plan.steps[0]
        assert step.chunk is not None and step.bound_met
        assert step.inflight_bytes <= bound
        assert plan.peak_inflight_bytes <= bound
        for text in plan.step_hlo(0):
            mx = hlo.max_tensor_bytes(text)
            assert mx <= step.hbm_bound_bytes, (mx, step.hbm_bound_bytes)
            assert mx < full
        # And it still moves the bytes correctly.
        out = plan.execute({"x": x})["x"]
        assert out.sharding.is_equivalent_to(
            NamedSharding(mesh_2d, P(None, "model")), 2
        )

    def test_bound_unachievable_is_reported_not_fatal(self, mesh_2d):
        """A leaf that cannot chunk under the bound (single row
        already over it) still moves, with bound_met=False on record
        -- the plan is honest, not stuck."""
        x = _put(
            mesh_2d, P("data"), jnp.zeros((8, 64), jnp.float32)
        )
        plan = reshard.plan_reshard(
            {"x": x},
            {"x": NamedSharding(mesh_2d, P(None, "model"))},
            max_inflight_bytes=16,  # one 256 B row >> 16 B
        )
        assert not plan.bound_met
        assert not plan.steps[0].bound_met
        out = plan.execute({"x": x})["x"]
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(x)
        )

    def test_gather_is_exempt_from_chunking(self, mesh_2d):
        """Target-replicated moves: the full per-device copy is the
        REQUESTED residency; the bound must not chunk what it cannot
        reduce."""
        x = _put(mesh_2d, P("data"), jnp.zeros((64, 32), jnp.float32))
        plan = reshard.plan_reshard(
            {"x": x}, {"x": NamedSharding(mesh_2d, P())},
            max_inflight_bytes=128,
        )
        step = plan.steps[0]
        assert step.kind == "gather" and step.chunk is None
        assert step.inflight_bytes == 0

    def test_repeat_execute_uses_cached_programs(self, mesh_2d):
        x = _put(mesh_2d, P("data"), jnp.zeros((64, 32), jnp.float32))
        plan = reshard.plan_reshard(
            {"x": x},
            {"x": NamedSharding(mesh_2d, P(None, "model"))},
            max_inflight_bytes=2048,
        )
        plan.execute({"x": x})
        n_programs = len(plan._programs)
        plan.execute({"x": x})
        assert len(plan._programs) == n_programs


# ---------------------------------------------------------------------
# obs integration: the reshard_plan event + gauges
# ---------------------------------------------------------------------
class TestObsIntegration:
    def test_execution_emits_schema_valid_plan_event(
        self, mesh_2d, tmp_path
    ):
        from tpu_hpc import obs

        sink = str(tmp_path / "reshard.jsonl")
        x = _put(mesh_2d, P("data"), jnp.zeros((64, 32), jnp.float32))
        reshard.apply(
            {"x": x},
            {"x": NamedSharding(mesh_2d, P(None, "model"))},
            max_inflight_bytes=2048, label="test_move", sink=sink,
        )
        assert obs.validate_file(sink) >= 2  # span + reshard_plan
        recs = [json.loads(l) for l in open(sink)]
        plans = [r for r in recs if r["event"] == "reshard_plan"]
        assert len(plans) == 1
        rec = plans[0]
        assert rec["label"] == "test_move"
        assert rec["chunked_steps"] == 1
        assert rec["measured_bytes"] == 64 * 32 * 4
        assert rec["wire_bytes"] > 0
        spans = [r for r in recs if r["event"] == "span"]
        assert any(s["name"] == "reshard" for s in spans)

    def test_peak_hbm_gauge_set(self, mesh_2d):
        from tpu_hpc import obs

        x = _put(mesh_2d, P("data"), jnp.zeros((64, 32), jnp.float32))
        reshard.apply(
            {"x": x}, {"x": NamedSharding(mesh_2d, P(None, "model"))},
            max_inflight_bytes=2048,
        )
        reg = obs.get_registry()
        assert reg.gauge("reshard_peak_hbm_bytes") > 0
        assert reg.gauge("reshard_inflight_bytes") == 0  # reset after
        assert reg.counter("reshard_wire_bytes_total") > 0

    def test_peak_hbm_gauge_sums_packed_stages(self, mesh_2d):
        """An unbounded plan packs every same-mesh leaf into ONE
        program, so the modeled peak is the per-stage SUM, not the
        largest single leaf."""
        from tpu_hpc import obs

        tree = {
            "a": _put(mesh_2d, P("data"),
                      jnp.zeros((8, 8), jnp.float32)),
            "b": _put(mesh_2d, P("data"),
                      jnp.zeros((8, 8), jnp.float32)),
        }
        tgt = NamedSharding(mesh_2d, P(None, "model"))
        plan = reshard.plan_reshard(tree, {"a": tgt, "b": tgt})
        plan.execute(tree)
        one = (
            plan.steps[0].src_resident_bytes
            + plan.steps[0].resident_bytes
            + plan.steps[0].inflight_bytes
        )
        assert obs.get_registry().gauge(
            "reshard_peak_hbm_bytes"
        ) == 2 * one


# ---------------------------------------------------------------------
# elastic resume (in-process): sidecar -> reshard path -> bit-exact
# ---------------------------------------------------------------------
class TestElasticRestore:
    def _state(self, mesh, spec, value=None):
        w = (
            jnp.arange(32.0, dtype=jnp.float32).reshape(8, 4)
            if value is None else value
        )
        return {
            "w": _put(mesh, spec, w),
            "step": _put(mesh, P(), jnp.int32(7)),
        }

    def test_cross_topology_restore_bit_exact(
        self, mesh_a, mesh_b, tmp_path
    ):
        from tpu_hpc.ckpt import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
        saved = self._state(mesh_a, P("data"))
        mgr.save(saved, step=7)
        mgr.wait()
        template = self._state(mesh_b, P(None, "model"),
                               value=jnp.zeros((8, 4)))
        restored = mgr.restore_latest(template)
        info = mgr.last_restore_info
        assert info["elastic"] is True
        assert info["src_mesh"] == {"data": 4}
        assert info["tgt_mesh"] == {"data": 2, "model": 2}
        assert info["plan"]["steps"] == 2
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(saved["w"])
        )
        assert restored["w"].sharding.is_equivalent_to(
            template["w"].sharding, 2
        )
        mgr.close()

    def test_same_topology_stays_on_direct_path(self, mesh_a, tmp_path):
        from tpu_hpc.ckpt import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
        saved = self._state(mesh_a, P("data"))
        mgr.save(saved, step=3)
        mgr.wait()
        restored = mgr.restore_latest(saved)
        assert mgr.last_restore_info == {"step": 3, "elastic": False}
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(saved["w"])
        )
        mgr.close()

    def test_missing_sidecar_falls_back_to_direct(
        self, mesh_a, mesh_b, tmp_path
    ):
        """Pre-sidecar checkpoints (or a lost meta dir) restore
        exactly as before -- opaquely, but correctly."""
        import shutil

        from tpu_hpc.ckpt import CheckpointManager
        from tpu_hpc.reshard.elastic import SIDECAR_DIR

        d = str(tmp_path / "ck")
        mgr = CheckpointManager(d, async_save=False)
        saved = self._state(mesh_a, P("data"))
        mgr.save(saved, step=7)
        mgr.wait()
        shutil.rmtree(os.path.join(d, SIDECAR_DIR))
        template = self._state(mesh_b, P(None, "model"))
        restored = mgr.restore_latest(template)
        assert mgr.last_restore_info == {"step": 7, "elastic": False}
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(saved["w"])
        )
        mgr.close()

    def test_structural_mismatch_raises_typed_error(
        self, mesh_a, mesh_b, tmp_path
    ):
        """Satellite pin: a wrong-model relaunch surfaces a
        TopologyMismatchError naming source vs. live topology and the
        elastic-resume docs, not a generic orbax traceback."""
        from tpu_hpc.ckpt import CheckpointManager, TopologyMismatchError

        mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
        mgr.save(self._state(mesh_a, P("data")), step=7)
        mgr.wait()
        bad_template = {
            "w": _put(mesh_b, P(None, "model"), jnp.zeros((16, 4))),
            "step": _put(mesh_b, P(), jnp.int32(0)),
        }
        with pytest.raises(TopologyMismatchError) as e:
            mgr.restore_latest(bad_template)
        msg = str(e.value)
        assert "{'data': 4}" in msg           # source topology
        assert "{'data': 2, 'model': 2}" in msg  # live topology
        assert "resharding.md" in msg
        mgr.close()

    def test_elastic_restore_lands_every_leaf_on_the_live_mesh(
        self, mesh_a, mesh_b, tmp_path
    ):
        """Replicated leaves (state.step) are assignment-equivalent
        across the throwaway source mesh and the live mesh; a naive
        passthrough would keep them COMMITTED to the source mesh, the
        next save's sidecar would record the stale topology, and the
        restart after THAT would mis-route. Pin the full round trip:
        restore -> all leaves on the live mesh -> save -> sidecar
        names the live mesh -> next restore takes the direct path."""
        from tpu_hpc.ckpt import CheckpointManager
        from tpu_hpc.reshard import read_sidecar

        d1, d2 = str(tmp_path / "ck1"), str(tmp_path / "ck2")
        mgr = CheckpointManager(d1, async_save=False)
        mgr.save(self._state(mesh_a, P("data")), step=7)
        mgr.wait()
        template = self._state(mesh_b, P(None, "model"))
        restored = mgr.restore_latest(template)
        for leaf in jax.tree.leaves(restored):
            assert leaf.sharding.mesh == mesh_b, leaf.sharding
        mgr.close()
        # The resumed run saves; its sidecar must name the LIVE mesh.
        mgr2 = CheckpointManager(d2, async_save=False)
        mgr2.save(restored, step=8)
        mgr2.wait()
        meta = read_sidecar(d2, 8)
        assert meta["mesh"] == {"data": 2, "model": 2}
        again = mgr2.restore_latest(template)
        assert mgr2.last_restore_info == {"step": 8, "elastic": False}
        np.testing.assert_array_equal(
            np.asarray(again["w"]), np.asarray(restored["w"])
        )
        mgr2.close()

    def test_dtype_switch_casts_like_the_direct_path(
        self, mesh_a, mesh_b, tmp_path
    ):
        """A dtype change on relaunch (the fp32->bf16 moments unlock)
        is a legal config change, not a structural mismatch: the
        elastic path restores into the LIVE dtype (orbax casts at
        restore time, exactly as the direct path does) and reshards
        the cast bytes."""
        from tpu_hpc.ckpt import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
        saved = self._state(mesh_a, P("data"))  # float32 w
        mgr.save(saved, step=7)
        mgr.wait()
        template = {
            "w": _put(mesh_b, P(None, "model"),
                      jnp.zeros((8, 4), jnp.bfloat16)),
            "step": _put(mesh_b, P(), jnp.int32(0)),
        }
        restored = mgr.restore_latest(template)
        assert mgr.last_restore_info["elastic"] is True
        assert restored["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(restored["w"]),
            np.asarray(saved["w"]).astype(jnp.bfloat16),
        )
        mgr.close()

    def test_sidecar_pruned_with_checkpoints(self, mesh_a, tmp_path):
        from tpu_hpc.ckpt import CheckpointManager
        from tpu_hpc.reshard.elastic import SIDECAR_DIR

        d = str(tmp_path / "ck")
        mgr = CheckpointManager(d, max_to_keep=2, async_save=False)
        for s in (1, 2, 3, 4):
            mgr.save(self._state(mesh_a, P("data")), step=s)
            mgr.wait()
        names = sorted(os.listdir(os.path.join(d, SIDECAR_DIR)))
        kept = {f"{s}.json" for s in mgr.all_steps()}
        # The topology-history file shares the dir but is GC'd by
        # entry, not by file -- it never matches the per-step scan.
        assert set(names) - {"topology_history.json"} == kept
        mgr.close()

    def test_topology_history_pruned_with_checkpoints(
        self, mesh_a, tmp_path
    ):
        """The morph-history file is GC'd alongside the sidecars:
        ``save`` entries for collected checkpoints vanish, morph
        entries older than the oldest retained checkpoint vanish,
        and everything at or past the retention floor survives --
        the history cannot grow without bound on a long run."""
        from tpu_hpc.ckpt import CheckpointManager
        from tpu_hpc.reshard.elastic import (
            append_topology_history,
            read_topology_history,
        )

        d = str(tmp_path / "ck")
        mgr = CheckpointManager(d, max_to_keep=2, async_save=False)
        # Interleave saves with coordinator-style morph entries, the
        # shape a real elastic run writes.
        for s in (1, 2, 3, 4):
            mgr.save(self._state(mesh_a, P("data")), step=s)
            mgr.wait()
            append_topology_history(
                d, s, {"axes": {"data": 4}},
                reason="morph-shrink" if s % 2 else "morph-grow",
            )
        kept_steps = set(mgr.all_steps())
        assert kept_steps == {3, 4}
        history = read_topology_history(d)
        assert history, "history must survive pruning, trimmed"
        floor = min(kept_steps)
        for entry in history:
            if entry["reason"] == "save":
                assert entry["step"] in kept_steps
            else:
                assert entry["step"] >= floor
        # Both retained saves and both retained morphs are present.
        assert {
            e["step"] for e in history if e["reason"] == "save"
        } == kept_steps
        assert {
            e["reason"] for e in history if e["reason"] != "save"
        } == {"morph-shrink", "morph-grow"}
        # Stale entries are genuinely gone, not just shadowed.
        assert all(e["step"] >= floor for e in history)
        mgr.close()


# ---------------------------------------------------------------------
# THE acceptance run: supervised kill -> restart onto a DIFFERENT mesh
# ---------------------------------------------------------------------
ELASTIC_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("TPU_VISIBLE_DEVICES", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                "TPU_WORKER_HOSTNAMES"):
        os.environ.pop(var, None)
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_hpc import resilience
    from tpu_hpc.ckpt import CheckpointManager
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.train import Trainer

    # THE elastic contract: attempt 0 trains on data=4; every restart
    # lands on a 2x2 data x model mesh over the same chips -- the
    # preempted-pod-comes-back-smaller/reshaped scenario.
    attempt = int(os.environ.get("TPU_HPC_ATTEMPT", "0"))
    devs = jax.devices()
    if attempt == 0:
        mesh = build_mesh(
            MeshSpec(axes={"data": 4}), devices=devs[:4]
        )
        pspecs = {"w": P("data", None)}
    else:
        mesh = build_mesh(
            MeshSpec(axes={"data": 2, "model": 2}), devices=devs[:4]
        )
        pspecs = {"w": P(None, "model")}

    class DS:
        # Deterministic per-step batches keyed on the step index, so
        # the stream is mesh-shape independent.
        def batch_at(self, step, bs):
            k = jax.random.key(int(step) % 97)
            x = jax.random.normal(k, (bs, 8), jnp.float32)
            y = x @ jnp.arange(16.0, dtype=jnp.float32).reshape(8, 2)
            return x, y

    def forward(params, model_state, batch, step_rng):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2), model_state, {}

    ckpt_dir = os.environ["WORK_CKPT"]
    cfg = TrainingConfig(
        epochs=3, steps_per_epoch=2, global_batch_size=8,
        learning_rate=1e-2, save_every=1, checkpoint_dir=ckpt_dir,
        metrics_path=os.environ.get("WORK_METRICS", ""),
    )
    mgr = CheckpointManager(ckpt_dir, async_save=False)
    trainer = Trainer(
        cfg, mesh, forward, {"w": jnp.zeros((8, 2), jnp.float32)},
        param_pspecs=pspecs, checkpoint_manager=mgr,
    )
    if attempt >= 1:
        # Bit-exactness evidence BEFORE training continues: the
        # elastic restore of the newest step must byte-equal a direct
        # explicit-step restore of the same data.
        restored = mgr.restore_latest(trainer.state)
        info = mgr.last_restore_info
        assert info is not None and info["elastic"], info
        step = info["step"]
        ref = mgr.restore(step, restored)
        for a, b in zip(jax.tree.leaves(restored),
                        jax.tree.leaves(ref)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            )
        print("ELASTIC_BITEXACT step", step,
              "src", info["src_mesh"], "tgt", info["tgt_mesh"],
              flush=True)
    result = trainer.fit(DS())
    print("FINAL_STEP", int(jax.device_get(trainer.state.step)),
          flush=True)
    sys.exit(resilience.exit_code_for(result["preempted"]))
""")


class TestElasticSupervised:
    def test_kill_restart_resumes_on_different_mesh(self, tmp_path):
        """Train on data=4, SIGKILL at step 4 via TPU_HPC_FAULTS;
        the supervisor restarts onto data=2 x model=2; the elastic
        reshard path restores step 2 bit-exact; training completes;
        the metrics JSONL carries ONE resumed run (2 run_starts, 1
        run_end at attempt 1, resumed_from_step 2) plus the
        elastic_restore event with its plan record."""
        worker = tmp_path / "worker.py"
        worker.write_text(ELASTIC_WORKER)
        sup_dir = str(tmp_path / "sup")
        env = dict(os.environ)
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = REPO + (os.pathsep + prev if prev else "")
        env["WORK_CKPT"] = str(tmp_path / "ckpts")
        env["WORK_METRICS"] = str(tmp_path / "run.jsonl")
        env["TPU_HPC_FAULTS"] = "kill_at_step=4"
        proc = subprocess.run(
            [
                sys.executable, "-m", "tpu_hpc.resilience.supervisor",
                "--max-restarts", "2", "--log-dir", sup_dir,
                "--backoff", "0.1", "--",
                sys.executable, str(worker),
            ],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]

        events = [
            json.loads(x)
            for x in open(os.path.join(sup_dir, "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == [137, 0]

        a1 = open(os.path.join(sup_dir, "run.attempt1.log")).read()
        assert "ELASTIC_BITEXACT step 2" in a1
        assert "FINAL_STEP 6" in a1

        recs = [json.loads(x) for x in open(tmp_path / "run.jsonl")]
        # Schema discipline: the whole run log (elastic_restore
        # included) validates.
        from tpu_hpc.obs.schema import validate_file

        validate_file(str(tmp_path / "run.jsonl"))
        starts = [r for r in recs if r["event"] == "run_start"]
        assert len(starts) == 2
        assert starts[0]["start_step"] == 0
        assert starts[1]["start_step"] == 2
        elastic = [r for r in recs if r["event"] == "elastic_restore"]
        assert elastic, "elastic_restore event missing from run log"
        e = elastic[-1]
        assert e["from_step"] == 2
        assert e["src_mesh"] == {"data": 4}
        assert e["tgt_mesh"] == {"data": 2, "model": 2}
        assert e["plan"]["steps"] >= 2
        run_ends = [r for r in recs if r["event"] == "run_end"]
        assert len(run_ends) == 1  # a SINGLE resumed run
        end = run_ends[0]
        assert end["attempt"] == 1
        assert end["resumed_from_step"] == 2
        assert end["step"] == 6
        assert end["preempted"] is False
        assert end["goodput"]["restore_s"] > 0.0
