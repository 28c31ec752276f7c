"""Disaggregated prefill/decode: two engine tiers, one request stream.

Prefill and decode want different hardware economics: prefill is a
compute-bound batch job over a whole prompt, decode a latency-bound
single-token tick whose batch the continuous batcher keeps full. Run
them on the SAME chips and every admission's prefill stalls the decode
batch for a full prompt's worth of FLOPs. The disaggregated tier
(the splitwise/distserve deployment shape) gives each phase its own
mesh slice:

* the **prefill tier** runs the bucketed prefill programs and writes
  the prompt's K/V into its own (transient) cache rows;
* the KV block then crosses to the **decode tier** as an explicit
  :mod:`tpu_hpc.reshard` plan -- planned once per bucket at warmup,
  executed with cached programs (zero steady-state recompiles),
  bounded by ``max_inflight_bytes``, and span-bracketed as
  ``kv_transfer`` so TTFT decomposes into prefill-tier time + hop
  time on the same obs spine the meter uses;
* the **decode tier** owns the resident KV cache and the per-tick
  decode program, exactly as in the single-tier engine.

:class:`DisaggEngine` presents the single-tier :class:`Engine`
interface (``prefill``/``decode``/``warmup``/``compile_count``), so
the continuous batcher and the replay server drive it unchanged, and
the token-exactness oracle in tests/test_serve.py applies verbatim:
greedy decode through the disaggregated path must equal the no-cache
forward pass token for token.

**Paged mode** (``paged=PagedConfig(...)``): both tiers run the
block-table cache (serve/paging.py), and the KV hop ships **block
tables plus the referenced pages only** -- per-bucket gather programs
read exactly the pages a request's table names on the prefill tier,
the bounded reshard plan moves them, and per-bucket scatter programs
land them at the decode tier's own page ids (each tier has its own
allocator; physical ids never have to agree across tiers). Prompts
longer than the largest bucket (chunked prefill) hop as a sequence of
bucket-sized page groups through the same fixed-shape programs, so
the zero-recompile pin survives. Prefix reuse lives on the prefill
tier (a trie hit skips the prefill FLOPs; the pages still hop --
the decode tier holds no copy).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_hpc.models import hybrid_ssm_moe, latent_moe, llama2, sparse_moe
from tpu_hpc.obs import get_registry, span
from tpu_hpc.serve.engine import Engine, ServeConfig


def split_serving_meshes(
    n_devices: int,
    cfg: llama2.LlamaConfig,
    prefill_devices: Optional[int] = None,
) -> Tuple[Mesh, Mesh]:
    """Disjoint (prefill_mesh, decode_mesh) tiers over the visible
    chips: the first ``prefill_devices`` (default: half) prefill, the
    rest decode. Each tier uses the same auto TP-capped split policy
    as the single-tier serving mesh (tp.auto_mesh_axes), so per-tier
    collective signatures match what the flat engine would run."""
    from tpu_hpc.parallel import tp
    from tpu_hpc.runtime import MeshSpec, build_mesh

    if n_devices < 2:
        raise ValueError(
            f"disaggregated serving needs >= 2 devices (one per "
            f"tier), got {n_devices}"
        )
    k = prefill_devices if prefill_devices is not None else n_devices // 2
    if not 1 <= k < n_devices:
        raise ValueError(
            f"prefill tier of {k} device(s) leaves "
            f"{n_devices - k} for decode (need >= 1 each of "
            f"{n_devices})"
        )
    devs = jax.devices()[:n_devices]
    prefill_mesh = build_mesh(
        MeshSpec(axes=tp.auto_mesh_axes(
            k, cfg.n_heads, cfg.kv_heads, cap=4
        )),
        devices=devs[:k],
    )
    decode_mesh = build_mesh(
        MeshSpec(axes=tp.auto_mesh_axes(
            n_devices - k, cfg.n_heads, cfg.kv_heads, cap=4
        )),
        devices=devs[k:],
    )
    return prefill_mesh, decode_mesh


def _kv_rows_pspec(mesh: Mesh, kv_heads: int) -> P:
    """Layout for one request's extracted KV rows
    ``[layers, 1, bucket, kv_heads, head_dim]``: KV heads over
    ``model`` where that axis exists and divides (matching the cache),
    everything else whole."""
    names = set(mesh.axis_names)
    model = (
        "model"
        if "model" in names and mesh.shape["model"] > 1
        and kv_heads % mesh.shape["model"] == 0
        else None
    )
    return P(None, None, None, model, None)


class DisaggEngine:
    """Prefill on one mesh tier, decode on another, KV blocks moved by
    per-bucket reshard plans. Drop-in for :class:`Engine` from the
    batcher's point of view."""

    def __init__(
        self,
        params: Any,
        cfg: llama2.LlamaConfig,
        serve_cfg: ServeConfig,
        prefill_mesh: Mesh,
        decode_mesh: Mesh,
        max_inflight_bytes: "Optional[int | str]" = None,
        paged=None,
    ):
        shared = set(prefill_mesh.devices.flat) & set(
            decode_mesh.devices.flat
        )
        if shared:
            raise ValueError(
                f"prefill and decode tiers share {len(shared)} "
                "device(s); disaggregation needs disjoint tiers"
            )
        sparse_moe.refuse(
            cfg, "disaggregated serving (serve/disagg.py)",
            "the cross-tier hop ships keys and values only",
        )
        latent_moe.refuse(
            cfg, "disaggregated serving (serve/disagg.py)",
            "the cross-tier hop ships per-head keys and values, not "
            "latent rows",
        )
        hybrid_ssm_moe.refuse(
            cfg, "disaggregated serving (serve/disagg.py)",
            "the cross-tier hop ships pages, not the recurrent state "
            "a prefilled sequence also leaves",
        )
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.max_inflight_bytes = max_inflight_bytes
        self.paged = paged
        self.is_paged = paged is not None
        # Both tiers place the same param tree onto their own mesh --
        # the decode tier is the latency-critical one and keeps the
        # single-tier layout; the prefill tier is throughput-bound and
        # uses the same TP split on its own chips.
        if paged is not None:
            from tpu_hpc.serve.paging import PagedEngine

            self.prefill_engine = PagedEngine(
                params, cfg, serve_cfg, prefill_mesh, paged
            )
            self.decode_engine = PagedEngine(
                params, cfg, serve_cfg, decode_mesh, paged
            )
            # Two pools in one process: distinct gauge names, or the
            # tiers overwrite each other's page readings (the
            # process-wide-registry blending class the hop quantiles
            # already dodge via engine-local samples).
            for eng, suffix in (
                (self.prefill_engine, "_prefill"),
                (self.decode_engine, "_decode"),
            ):
                eng.gauge_suffix = suffix
                eng._set_block_gauges()
        else:
            self.prefill_engine = Engine(params, cfg, serve_cfg,
                                         prefill_mesh)
            self.decode_engine = Engine(params, cfg, serve_cfg,
                                        decode_mesh)
        self.mesh = decode_mesh  # the resident (decode) tier
        self.prefill_mesh = prefill_mesh
        self.decode_mesh = decode_mesh
        # max_inflight_bytes="auto": size the page-group transfers
        # from the topology's cost tables (comm/planner.py) -- the
        # chunk that amortizes the cross-tier launch latency, bounded
        # by the largest bucket's actual KV leaf. The operator knob
        # (--disagg-max-inflight-mb N) still overrides.
        self.inflight_source = None
        if max_inflight_bytes == "auto":
            import math as _math

            from tpu_hpc.comm.planner import Planner

            rows = self._rows_shape(max(serve_cfg.prefill_buckets))
            leaf_bytes = int(
                _math.prod(rows)
                * jnp.dtype(self.prefill_engine.ks.dtype).itemsize
            )
            planner = Planner.for_devices(
                list(prefill_mesh.devices.flat)
                + list(decode_mesh.devices.flat)
            )
            self.max_inflight_bytes = planner.chunk_bytes(leaf_bytes)
            self.inflight_source = "planner"
        self.cache_bytes = (
            self.prefill_engine.cache_bytes
            + self.decode_engine.cache_bytes
        )
        self._aot_builds = 0
        self._extract: Dict[int, Any] = {}
        self._insert: Dict[int, Any] = {}
        self._plans: Dict[int, Any] = {}
        self.transfer_stats = {
            "kv_transfers": 0, "kv_transfer_bytes": 0,
        }
        # Per-ENGINE hop samples for the summary quantiles: the obs
        # registry histogram is process-wide (a second replay in the
        # same process would blend runs), so the engine owns its own
        # window. Warmup's dummy transfers bypass prefill() and stay
        # out of it.
        self._hop_s: list = []
        get_registry().describe(
            "serve_kv_transfer_s",
            "Prefill->decode tier KV hop, dispatch until the decode "
            "cache holds the rows (s)",
        )

    # -- executable/plans table ---------------------------------------
    @property
    def compile_count(self) -> int:
        """Every compiled program across both tiers and the transfer
        path: the two engines' executable tables, this tier's AOT
        extract/insert programs, and the reshard plans' cached
        programs. After :meth:`warmup` it must stay put -- the same
        zero-recompile guard the single-tier engine pins."""
        return (
            self.prefill_engine.compile_count
            + self.decode_engine.compile_count
            + self._aot_builds
            + sum(
                p.compiled_program_count for p in self._plans.values()
            )
        )

    def _rows_shape(self, bucket: int) -> Tuple[int, ...]:
        c = self.cfg
        if self.is_paged:
            # A page run in the pool's own page layout.
            return (
                c.n_layers, bucket // self.paged.block_size,
                *self.prefill_engine.ks.shape[2:],
            )
        return (c.n_layers, 1, bucket, c.kv_heads, c.head_dim)

    def _build_bucket_paged(self, bucket: int) -> None:
        """Paged hop programs for one bucket: gather exactly the pages
        a table slice names on the prefill tier, plan the bounded
        cross-tier move, scatter at the decode tier's own page ids --
        block tables + referenced pages only, nothing else crosses."""
        from tpu_hpc import reshard

        pe, de = self.prefill_engine, self.decode_engine
        nb = bucket // self.paged.block_size
        rows = self._rows_shape(bucket)
        # Page runs are slices of a pool along its block dim: each
        # tier's rows shard exactly as its pool does.
        src_sh, tgt_sh = pe._cache_sharding, de._cache_sharding
        cache_p = pe._cache_abstract()
        cache_d = de._cache_abstract()
        ids_p = jax.ShapeDtypeStruct((nb,), jnp.int32, sharding=pe._rep)
        ids_d = jax.ShapeDtypeStruct((nb,), jnp.int32, sharding=de._rep)

        def extract(ks, vs, ids):
            return ks[:, ids], vs[:, ids]

        self._extract[bucket] = jax.jit(
            extract, out_shardings=(src_sh, src_sh)
        ).lower(cache_p, cache_p, ids_p).compile()
        self._aot_builds += 1

        def insert(ks, vs, k_rows, v_rows, ids):
            return ks.at[:, ids].set(k_rows), vs.at[:, ids].set(v_rows)

        rows_abs = jax.ShapeDtypeStruct(
            rows, de.ks.dtype, sharding=tgt_sh
        )
        self._insert[bucket] = jax.jit(
            insert,
            donate_argnums=(0, 1),
            out_shardings=(de._cache_sharding, de._cache_sharding),
        ).lower(cache_d, cache_d, rows_abs, rows_abs, ids_d).compile()
        self._aot_builds += 1

        abstract = {
            "k": jax.ShapeDtypeStruct(rows, pe.ks.dtype,
                                      sharding=src_sh),
            "v": jax.ShapeDtypeStruct(rows, pe.ks.dtype,
                                      sharding=src_sh),
        }
        self._plans[bucket] = reshard.plan_reshard(
            abstract, {"k": tgt_sh, "v": tgt_sh},
            max_inflight_bytes=self.max_inflight_bytes,
            label=f"kv_pages_b{bucket}",
        )

    def _build_bucket(self, bucket: int) -> None:
        """Extract (prefill tier), transfer plan (cross-tier), insert
        (decode tier) for one prefill bucket, all AOT so steady state
        never compiles."""
        from tpu_hpc import reshard

        c = self.cfg
        pe, de = self.prefill_engine, self.decode_engine
        rows = self._rows_shape(bucket)
        src_sh = NamedSharding(
            self.prefill_mesh,
            _kv_rows_pspec(self.prefill_mesh, c.kv_heads),
        )
        tgt_sh = NamedSharding(
            self.decode_mesh,
            _kv_rows_pspec(self.decode_mesh, c.kv_heads),
        )
        cache_p = pe._cache_abstract()
        cache_d = de._cache_abstract()
        slot_p = jax.ShapeDtypeStruct((), jnp.int32, sharding=pe._rep)
        slot_d = jax.ShapeDtypeStruct((), jnp.int32, sharding=de._rep)

        def extract(ks, vs, slot):
            size = (c.n_layers, 1, bucket, c.kv_heads, c.head_dim)
            start = (0, slot, 0, 0, 0)
            return (
                jax.lax.dynamic_slice(ks, start, size),
                jax.lax.dynamic_slice(vs, start, size),
            )

        self._extract[bucket] = jax.jit(
            extract, out_shardings=(src_sh, src_sh)
        ).lower(cache_p, cache_p, slot_p).compile()
        self._aot_builds += 1

        def insert(ks, vs, k_rows, v_rows, slot):
            start = (0, slot, 0, 0, 0)
            return (
                jax.lax.dynamic_update_slice(ks, k_rows, start),
                jax.lax.dynamic_update_slice(vs, v_rows, start),
            )

        rows_abs = jax.ShapeDtypeStruct(
            rows, de.ks.dtype, sharding=tgt_sh
        )
        self._insert[bucket] = jax.jit(
            insert,
            donate_argnums=(0, 1),
            out_shardings=(de._cache_sharding, de._cache_sharding),
        ).lower(cache_d, cache_d, rows_abs, rows_abs, slot_d).compile()
        self._aot_builds += 1

        abstract = {
            "k": jax.ShapeDtypeStruct(rows, pe.ks.dtype,
                                      sharding=src_sh),
            "v": jax.ShapeDtypeStruct(rows, pe.ks.dtype,
                                      sharding=src_sh),
        }
        self._plans[bucket] = reshard.plan_reshard(
            abstract, {"k": tgt_sh, "v": tgt_sh},
            max_inflight_bytes=self.max_inflight_bytes,
            label=f"kv_transfer_b{bucket}",
        )

    def warmup(self) -> int:
        """Compile both tiers' program tables, the per-bucket
        extract/insert executables, and (by a dummy zero-block
        transfer) every reshard-plan program. Returns the total
        compiled-program count; after this ``compile_count`` must
        never move."""
        self.prefill_engine.warmup()
        self.decode_engine.warmup()
        for b in self.serve_cfg.prefill_buckets:
            if self.is_paged:
                self._build_bucket_paged(b)
                # Dummy move of all-scratch page ids: compiles every
                # plan program now, writes scratch garbage over
                # scratch garbage.
                nb = b // self.paged.block_size
                zeros = np.zeros((nb,), np.int32)
                self._move_kv_paged(b, zeros, zeros)
            else:
                self._build_bucket(b)
                # Dummy transfer of the (all-zero) slot-0 rows:
                # compiles every plan program now, writes zeros over
                # zeros.
                self._move_kv(b, 0)
        return self.compile_count

    # -- serving ops ---------------------------------------------------
    def _move_kv(self, bucket: int, slot: int) -> int:
        """One request's KV rows: prefill cache -> decode cache, via
        the bucket's cached reshard plan. Returns bytes moved."""
        pe, de = self.prefill_engine, self.decode_engine
        k, v = self._extract[bucket](
            pe.ks, pe.vs, pe._rep_arr(slot)
        )
        moved = self._plans[bucket].execute({"k": k, "v": v})
        de.ks, de.vs = self._insert[bucket](
            de.ks, de.vs, moved["k"], moved["v"], de._rep_arr(slot)
        )
        # Block until the decode cache actually holds the rows: every
        # hop timer (the kv_transfer span, the _hop_s quantiles)
        # wraps this call, and async dispatch would otherwise read as
        # a microsecond hop while the real copy cost leaked into the
        # next decode tick's ITL -- the same dispatch-to-result
        # bracketing Engine.prefill and comm/bench.py use.
        de.ks.block_until_ready()
        de.vs.block_until_ready()
        return int(k.nbytes + v.nbytes)

    def _move_kv_paged(
        self, bucket: int, src_ids: np.ndarray, tgt_ids: np.ndarray
    ) -> int:
        """One bucket-sized page group: gather ``src_ids`` pages on the
        prefill tier, reshard, scatter at ``tgt_ids`` on the decode
        tier. Same dispatch-to-result blocking as :meth:`_move_kv`."""
        pe, de = self.prefill_engine, self.decode_engine
        k, v = self._extract[bucket](
            pe.ks, pe.vs, pe._rep_arr(np.asarray(src_ids, np.int32))
        )
        moved = self._plans[bucket].execute({"k": k, "v": v})
        de.ks, de.vs = self._insert[bucket](
            de.ks, de.vs, moved["k"], moved["v"],
            de._rep_arr(np.asarray(tgt_ids, np.int32)),
        )
        de.ks.block_until_ready()
        de.vs.block_until_ready()
        return int(k.nbytes + v.nbytes)

    def _hop_pieces(self, prompt_len: int):
        """Bucket-sized page groups covering the prompt region --
        fixed shapes only, so a chunked prompt longer than the
        largest bucket hops through the same compiled programs."""
        bs = self.paged.block_size
        largest = max(self.serve_cfg.prefill_buckets)
        total = -(-prompt_len // bs) * bs
        pieces = []
        pos = 0
        while pos < total:
            rem = total - pos
            b = largest if rem >= largest \
                else self.serve_cfg.bucket_for(rem)
            pieces.append((pos // bs, b))
            pos += b
        return pieces

    def prefill(self, slot: int, prompt: Sequence[int]) -> int:
        """Prefill on the prefill tier, then ship the slot's KV block
        to the decode tier. The hop rides in a ``kv_transfer`` span
        (tier-tagged), so TTFT = prefill span + kv_transfer span on
        one timeline."""
        import time

        tok = self.prefill_engine.prefill(slot, prompt)
        bucket = self.serve_cfg.bucket_for(len(prompt))
        t0 = time.perf_counter()
        with span(
            "kv_transfer", tier="transfer",
            hist="serve_kv_transfer_s", n=bucket,
        ):
            nbytes = self._move_kv(bucket, slot)
        self._hop_s.append(time.perf_counter() - t0)
        self.transfer_stats["kv_transfers"] += 1
        self.transfer_stats["kv_transfer_bytes"] += nbytes
        return tok

    # -- the paged protocol (serve/paging.py), tier-split -------------
    def validate_request(
        self, prompt_len: int, max_new: int, rid: str = "?"
    ) -> None:
        # The decode tier holds prompt + generation; the prefill tier
        # only ever holds the prompt (plus its one-token admit pad).
        self.decode_engine.validate_request(prompt_len, max_new, rid)
        self.prefill_engine.validate_request(prompt_len, 1, rid)

    def admit(
        self, slot: int, prompt: Sequence[int], max_new: int
    ) -> dict:
        """Reserve pages on BOTH tiers (all-or-nothing: a request must
        never hold prefill-tier pages it can't decode). The decode
        tier goes FIRST: its admit is stat-free (no trie), so a
        failure there never leaves the prefill tier's prefix-hit
        counters inflated by a rolled-back admission (review
        finding)."""
        self.decode_engine.admit(
            slot, prompt, max_new, run_prefill=False
        )
        try:
            return self.prefill_engine.admit(slot, prompt, 1)
        except Exception:
            self.decode_engine.release(slot)
            raise

    def prefill_step(self, slot: int):
        """Advance one chunk on the prefill tier; on prompt completion
        ship the referenced pages to the decode tier's page ids and
        release the prefill tier's reservation (its trie keeps the
        prompt pages for future hits)."""
        import time

        tok = self.prefill_engine.prefill_step(slot)
        if tok is None:
            return None
        pe, de = self.prefill_engine, self.decode_engine
        plen = len(pe.slot_state(slot).prompt)
        src_table = pe.slot_table(slot)
        tgt_table = de.slot_table(slot)
        t0 = time.perf_counter()
        nbytes = 0
        pieces = self._hop_pieces(plen)
        with span(
            "kv_transfer", tier="transfer",
            hist="serve_kv_transfer_s", n=plen,
        ):
            for start_blk, b in pieces:
                nb = b // self.paged.block_size
                nbytes += self._move_kv_paged(
                    b,
                    src_table[start_blk:start_blk + nb],
                    tgt_table[start_blk:start_blk + nb],
                )
        self._hop_s.append(time.perf_counter() - t0)
        self.transfer_stats["kv_transfers"] += len(pieces)
        self.transfer_stats["kv_transfer_bytes"] += nbytes
        pe.release(slot)
        return tok

    def release(self, slot: int) -> None:
        self.decode_engine.release(slot)

    def planned_prefill_tokens(self, slot: int) -> int:
        return self.prefill_engine.planned_prefill_tokens(slot)

    @property
    def block_occupancy(self) -> float:
        return max(
            self.prefill_engine.block_occupancy,
            self.decode_engine.block_occupancy,
        )

    @property
    def prefill_forwarded_total(self) -> int:
        return self.prefill_engine.prefill_forwarded_total

    @property
    def paged_stats(self) -> dict:
        pe = self.prefill_engine.paged_stats
        de = self.decode_engine.paged_stats
        return {k: pe[k] + de[k] for k in pe}

    def paged_summary(self) -> dict:
        """Pool description for the serve summary: the decode tier's
        resident pool, the prefill tier's prefix/chunk activity."""
        out = self.decode_engine.paged_summary()
        src = self.prefill_engine.paged_summary()
        for k in ("prefix_lookups", "prefix_hits", "prefix_hit_blocks",
                  "prefix_hit_rate", "prefill_chunks"):
            out[k] = src[k]
        out["cow_copies"] = (
            src["cow_copies"] + out["cow_copies"]
        )
        return out

    def decode(
        self,
        tokens: Sequence[int],
        positions: Sequence[int],
        active: Optional[Sequence[bool]] = None,
    ) -> np.ndarray:
        if self.is_paged:
            # Synchronous across the tiers: a step's own tokens.
            return self.decode_engine.decode_now(tokens, positions, active)
        return self.decode_engine.decode(tokens, positions)

    def describe(self) -> dict:
        """The summary block the replay server reports per tier,
        hop-latency quantiles included (this engine's own samples)."""
        from tpu_hpc.obs import quantile

        plans = {
            b: p.summary() for b, p in sorted(self._plans.items())
        }
        hops = sorted(self._hop_s)
        return {
            "kv_transfer_ms_p50": round(
                quantile(hops, 0.50) * 1e3, 3
            ) if hops else 0.0,
            "kv_transfer_ms_p95": round(
                quantile(hops, 0.95) * 1e3, 3
            ) if hops else 0.0,
            "prefill_mesh": {
                k: int(v) for k, v in self.prefill_mesh.shape.items()
            },
            "decode_mesh": {
                k: int(v) for k, v in self.decode_mesh.shape.items()
            },
            "max_inflight_bytes": self.max_inflight_bytes,
            "inflight_source": self.inflight_source,
            "kv_transfers": self.transfer_stats["kv_transfers"],
            "kv_transfer_bytes": self.transfer_stats[
                "kv_transfer_bytes"
            ],
            "kv_plans": plans,
        }
