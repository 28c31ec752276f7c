"""Host-DRAM KV page tier: million-session residency behind one pool.

Servable sessions per chip are capped by HBM pages. The paged pool
(serve/paging.py) and the prefix trie already know which pages are
warm-but-parked -- a finished chat turn's prompt K/V, held only by the
trie, waiting for the user to come back -- but parked pages still burn
HBM, so a returning user forces either a shed or a full re-prefill.
This module adds the memory-hierarchy step behind the allocator: the
vLLM PagedAttention thesis (arXiv 2309.06180) extended one tier down.

* **Spill**: under pool pressure, admission asks the tier for pages
  *before* falling back to trie eviction. The tier takes the coldest
  parked pages the trie can give up without breaking a live request
  (``PrefixTrie.spillable``: refcount 1, children already spilled),
  gathers them through an AOT page-gather program -- the PR 6/12
  disagg KV-hop machinery pointed at host instead of a peer mesh --
  and lands them in host numpy buffers. The allocator moves the
  page's accounting across tiers (``spill``), so the cross-tier
  invariant ``scratch + free + referenced + host == total`` holds at
  every step.
* **Prefetch/refill**: a router affinity hit or the scheduler's
  admit path calls :meth:`prefetch` with the incoming prompt *before*
  the request is seated, so the host->device hop hides behind
  queueing instead of stretching TTFT. Spilled chain nodes refill in
  chain order (``match`` stops at the first still-spilled node, so a
  partial refill still lengthens the served prefix) through a
  ``device_put`` + AOT page-scatter with a donated cache.

Transfers move in bounded groups: ``max_inflight_bytes="auto"`` sizes
the group from the topology's cost tables (comm/planner.py), exactly
the disagg hop's sizing rule. Both programs compile through the
engine's executable table at :meth:`warmup` (same table, same
counter), so the zero-steady-state-recompile pins cover the tier, and
every hop rides a ``kv_transfer`` span plus ring-only ``kv_spill`` /
``kv_refill`` events -- the fleet-scale diagnosability discipline of
arXiv 2510.20171."""
from __future__ import annotations

from typing import Any, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpu_hpc.models import hybrid_ssm_moe, latent_moe, sparse_moe
from tpu_hpc.obs import get_bus, get_registry, span
from tpu_hpc.serve.paging import SCRATCH_BLOCK, BlockBudgetError


class HostTier:
    """Host-memory page tier attached to one :class:`PagedEngine`.

    Owns the host-side K/V buffers (numpy, ``[layers, host_blocks,
    *page]`` in the device pool's own page layout, slot 0 scratch like
    the device pool's block 0) and the two
    AOT programs that move page groups across the HBM/DRAM boundary.
    All *accounting* lives on the engine's :class:`BlockAllocator` and
    :class:`PrefixTrie`; this class only moves bytes and keeps the
    tier's telemetry."""

    def __init__(self, engine: Any, max_inflight_bytes="auto"):
        if engine.trie is None:
            raise ValueError(
                "HostTier needs the prefix trie (prefix_cache=True): "
                "parked trie pages are the only thing worth spilling"
            )
        sparse_moe.refuse(
            engine.cfg, "the host KV tier (serve/tier.py)",
            "it spills and refills a page's keys and values, and a "
            "page now has three arrays",
        )
        latent_moe.refuse(
            engine.cfg, "the host KV tier (serve/tier.py)",
            "it spills and refills a page's keys and values, and a "
            "latent page is one array of rows with no head axis",
        )
        hybrid_ssm_moe.refuse(
            engine.cfg, "the host KV tier (serve/tier.py)",
            "it spills and refills pages, and a page refilled without "
            "the recurrent state at its position serves nothing",
        )
        self.engine = engine
        c = engine.cfg
        bs = engine.paged.block_size
        page = engine.ks.shape[2:]  # the pool's page layout, verbatim
        self.host_blocks = engine.paged.host_blocks
        dtype = np.dtype(jnp.dtype(engine.ks.dtype).name)
        # One K + one V host buffer, page-granular like the device
        # pool. Plain (pageable) numpy: the pinned-buffer upgrade is a
        # jax.device_put detail the transfer path already routes
        # through, not an accounting concern.
        shape = (c.n_layers, self.host_blocks, *page)
        self._host_k = np.zeros(shape, dtype)
        self._host_v = np.zeros(shape, dtype)
        self.host_bytes = int(self._host_k.nbytes + self._host_v.nbytes)
        # int8 pools: a page is its bytes PLUS its f32 scale -- a
        # spilled page that came back without its scale would
        # dequantize to garbage, so the scale rows ride every hop in
        # mirrored host side arrays.
        self._quant = (
            getattr(engine.paged, "kv_quant", "none") == "int8"
        )
        self._host_ksc = self._host_vsc = None
        if self._quant:
            sc_shape = (c.n_layers, self.host_blocks)
            self._host_ksc = np.zeros(sc_shape, np.float32)
            self._host_vsc = np.zeros(sc_shape, np.float32)
            self.host_bytes += int(
                self._host_ksc.nbytes + self._host_vsc.nbytes
            )
        # One page's K (or V) leaf: the transfer-group unit.
        self._page_bytes = int(
            c.n_layers * np.prod(page) * dtype.itemsize
        )
        # Bounded streams: group pages so one hop moves about
        # max_inflight_bytes. "auto" asks the topology cost tables for
        # the chunk that amortizes launch latency (the disagg hop's
        # sizing rule), capped at the largest bucket's page count so
        # the group program stays bucket-shaped.
        max_group = max(engine.serve_cfg.prefill_buckets) // bs
        self.inflight_source = None
        if max_inflight_bytes == "auto":
            from tpu_hpc.comm.planner import Planner

            planner = Planner.for_devices(
                list(engine.mesh.devices.flat)
            )
            max_inflight_bytes = planner.chunk_bytes(
                self._page_bytes * max_group
            )
            self.inflight_source = "planner"
        self.max_inflight_bytes = int(max_inflight_bytes)
        self.group = max(
            1, min(max_group, self.max_inflight_bytes // self._page_bytes)
        )
        self._rows_shape = (c.n_layers, self.group, *page)
        # A page group is a slice of the pool along its block dim: it
        # shards exactly as the pool does.
        self._rows_sharding = engine._cache_sharding
        # The gather/scatter builders register in the ENGINE's
        # executable table: _build dispatches here, the shared
        # compile counter ticks, and the zero-recompile pins cover
        # the tier for free.
        engine._tier_builders["spill_gather"] = self._build_gather
        engine._tier_builders["refill_scatter"] = self._build_scatter
        self.stats = {
            "kv_spills": 0, "kv_spill_pages": 0,
            "kv_spill_wire_bytes": 0,
            "kv_refills": 0, "kv_refill_pages": 0,
            "kv_refill_wire_bytes": 0,
        }
        # Engine-local hop samples for the summary quantiles (the
        # registry histogram is process-wide; a second pool in the
        # same process would blend runs -- the disagg lesson).
        self._hop_s: List[float] = []
        reg = get_registry()
        reg.describe(
            "serve_kv_transfer_s",
            "Cross-tier KV hop, dispatch until the destination holds "
            "the rows (s)",
        )
        reg.describe(
            "serve_kv_spill_pages_total",
            "KV pages spilled from HBM to the host-DRAM tier",
        )
        reg.describe(
            "serve_kv_refill_pages_total",
            "KV pages refilled from the host-DRAM tier into HBM",
        )

    # -- AOT programs (built through the engine's table) ---------------
    def _build_gather(self, key):
        eng = self.engine
        cache = eng._cache_abstract()
        ids = jax.ShapeDtypeStruct(
            (self.group,), jnp.int32, sharding=eng._rep
        )

        if self._quant:
            sc = eng._scale_abstract()

            def gather_q(ks, vs, ksc, vsc, page_ids):
                return (
                    ks[:, page_ids], vs[:, page_ids],
                    ksc[:, page_ids], vsc[:, page_ids],
                )

            return jax.jit(
                gather_q,
                out_shardings=(
                    self._rows_sharding, self._rows_sharding,
                    eng._rep, eng._rep,
                ),
            ).lower(cache, cache, sc, sc, ids).compile()

        def gather(ks, vs, page_ids):
            return ks[:, page_ids], vs[:, page_ids]

        return jax.jit(
            gather,
            out_shardings=(self._rows_sharding, self._rows_sharding),
        ).lower(cache, cache, ids).compile()

    def _build_scatter(self, key):
        eng = self.engine
        cache = eng._cache_abstract()
        ids = jax.ShapeDtypeStruct(
            (self.group,), jnp.int32, sharding=eng._rep
        )
        rows = jax.ShapeDtypeStruct(
            self._rows_shape, eng.ks.dtype, sharding=self._rows_sharding
        )

        if self._quant:
            sc = eng._scale_abstract()
            sc_rows = jax.ShapeDtypeStruct(
                (eng.cfg.n_layers, self.group), jnp.float32,
                sharding=eng._rep,
            )

            def scatter_q(ks, vs, ksc, vsc, k_rows, v_rows, ksc_rows,
                          vsc_rows, page_ids):
                return (
                    ks.at[:, page_ids].set(k_rows),
                    vs.at[:, page_ids].set(v_rows),
                    ksc.at[:, page_ids].set(ksc_rows),
                    vsc.at[:, page_ids].set(vsc_rows),
                )

            return jax.jit(
                scatter_q,
                donate_argnums=(0, 1, 2, 3),
                out_shardings=(
                    eng._cache_sharding, eng._cache_sharding,
                    eng._rep, eng._rep,
                ),
            ).lower(
                cache, cache, sc, sc, rows, rows, sc_rows, sc_rows, ids
            ).compile()

        def scatter(ks, vs, k_rows, v_rows, page_ids):
            return (
                ks.at[:, page_ids].set(k_rows),
                vs.at[:, page_ids].set(v_rows),
            )

        return jax.jit(
            scatter,
            donate_argnums=(0, 1),
            out_shardings=(eng._cache_sharding, eng._cache_sharding),
        ).lower(cache, cache, rows, rows, ids).compile()

    def warmup(self) -> None:
        """Compile the gather/scatter programs and run one dummy
        all-scratch round trip, so the device_get/device_put transfer
        paths are warm too. Scratch garbage over scratch garbage:
        both tiers' slot 0 absorb it."""
        self.engine._get_exec(("spill_gather",))
        self.engine._get_exec(("refill_scatter",))
        pad = [SCRATCH_BLOCK] * self.group
        self._move_out(pad, [0] * self.group)
        self._move_in([0] * self.group, pad)

    # -- byte movement -------------------------------------------------
    def _pad_ids(self, blocks: Sequence[int]) -> np.ndarray:
        """Fixed-shape page-id vector: real ids first, scratch padding
        after (gather padding reads block 0, scatter padding writes
        garbage over block 0 -- both absorbed by design)."""
        ids = np.full((self.group,), SCRATCH_BLOCK, np.int32)
        ids[:len(blocks)] = blocks
        return ids

    def _move_out(
        self, blocks: Sequence[int], slots: Sequence[int]
    ) -> int:
        """One page group, device pages -> host slots. Returns wire
        bytes (the padded group buffer -- what actually crosses)."""
        eng = self.engine
        n = len(blocks)
        ex = eng._get_exec(("spill_gather",))
        ids = eng._rep_arr(self._pad_ids(blocks))
        if self._quant:
            k, v, ksc, vsc = ex(
                eng.ks, eng.vs, eng.k_scales, eng.v_scales, ids
            )
            ksc_np, vsc_np = jax.device_get((ksc, vsc))
            self._host_ksc[:, list(slots)] = ksc_np[:, :n]
            self._host_vsc[:, list(slots)] = vsc_np[:, :n]
        else:
            k, v = ex(eng.ks, eng.vs, ids)
            ksc = vsc = None
        # device_get blocks until the rows are host-side -- the same
        # dispatch-to-result bracketing every hop timer relies on.
        k_np, v_np = jax.device_get((k, v))
        self._host_k[:, list(slots)] = k_np[:, :n]
        self._host_v[:, list(slots)] = v_np[:, :n]
        nbytes = int(k.nbytes + v.nbytes)
        if self._quant:
            nbytes += int(ksc.nbytes + vsc.nbytes)
        return nbytes

    def _move_in(
        self, slots: Sequence[int], blocks: Sequence[int]
    ) -> int:
        """One page group, host slots -> device pages, through a
        donated-cache scatter. Returns wire bytes."""
        eng = self.engine
        n = len(blocks)
        k_np = np.zeros(self._rows_shape, self._host_k.dtype)
        v_np = np.zeros(self._rows_shape, self._host_v.dtype)
        k_np[:, :n] = self._host_k[:, list(slots)]
        v_np[:, :n] = self._host_v[:, list(slots)]
        k_dev = jax.device_put(k_np, self._rows_sharding)
        v_dev = jax.device_put(v_np, self._rows_sharding)
        ex = eng._get_exec(("refill_scatter",))
        ids = eng._rep_arr(self._pad_ids(blocks))
        nbytes = int(k_dev.nbytes + v_dev.nbytes)
        if self._quant:
            sc_shape = (eng.cfg.n_layers, self.group)
            # Padding lanes write scale 0 over page 0's entry -- safe:
            # scale is only ever multiplied on read, and the decode
            # requantize floors its fresh scale (INT8_SCALE_FLOOR).
            ksc_np = np.zeros(sc_shape, np.float32)
            vsc_np = np.zeros(sc_shape, np.float32)
            ksc_np[:, :n] = self._host_ksc[:, list(slots)]
            vsc_np[:, :n] = self._host_vsc[:, list(slots)]
            ksc_dev = jax.device_put(ksc_np, eng._rep)
            vsc_dev = jax.device_put(vsc_np, eng._rep)
            eng.ks, eng.vs, eng.k_scales, eng.v_scales = ex(
                eng.ks, eng.vs, eng.k_scales, eng.v_scales,
                k_dev, v_dev, ksc_dev, vsc_dev, ids,
            )
            nbytes += int(ksc_dev.nbytes + vsc_dev.nbytes)
        else:
            eng.ks, eng.vs = ex(eng.ks, eng.vs, k_dev, v_dev, ids)
        eng.ks.block_until_ready()
        eng.vs.block_until_ready()
        return nbytes

    # -- tier operations -----------------------------------------------
    def spill_parked(self, n_needed: int) -> int:
        """Move up to ``n_needed`` of the coldest parked pages to the
        host tier, freeing their device pages. Called by admission
        BEFORE trie eviction: a spilled page is a cheap hop on return,
        an evicted one is a full re-prefill. Returns pages freed."""
        import time

        eng = self.engine
        alloc = eng.allocator
        t0 = time.perf_counter()
        taken = 0
        nbytes = 0
        with span(
            "kv_transfer", tier="host_spill",
            hist="serve_kv_transfer_s", n=n_needed,
        ):
            # spillable() only offers nodes whose children already
            # left HBM (leaf-first, the eviction rule), so spilling a
            # layer makes its parents spillable -- re-walk until the
            # quota is met or a pass makes no progress.
            while taken < n_needed:
                nodes = eng.trie.spillable(alloc)
                take = min(
                    n_needed - taken, len(nodes),
                    alloc.host_free_slots,
                )
                if take <= 0:
                    break
                nodes = nodes[:take]
                for i in range(0, take, self.group):
                    grp = nodes[i:i + self.group]
                    blocks = [n.block for n in grp]
                    # Accounting first, bytes second: spill() frees
                    # the device page before the gather reads it,
                    # which is safe single-threaded -- nothing
                    # allocates between here and the copy, so the
                    # freed page still holds its rows.
                    slots = [alloc.spill(b) for b in blocks]
                    nbytes += self._move_out(blocks, slots)
                    for node, slot in zip(grp, slots):
                        node.host = slot
                        node.block = -1
                taken += take
        self._hop_s.append(time.perf_counter() - t0)
        if not taken:
            return 0
        self.stats["kv_spills"] += 1
        self.stats["kv_spill_pages"] += taken
        self.stats["kv_spill_wire_bytes"] += nbytes
        get_registry().inc("serve_kv_spill_pages_total", taken)
        # Ring-only (no sink): spills happen at admission cadence,
        # flight-recorder forensics is the right volume tier.
        get_bus().emit(
            "kv_spill", pages=taken, bytes=nbytes,
            host_free=alloc.host_free_slots,
        )
        return taken

    def prefetch(self, prompt: Sequence[int]) -> int:
        """Refill ``prompt``'s host-resident chain nodes back into
        HBM, in chain order, before the request is seated. A partial
        refill (device pool filled up mid-way) is still progress:
        ``match`` serves the refilled prefix and the request
        re-prefills only the remainder. Returns pages refilled."""
        import time

        eng = self.engine
        alloc = eng.allocator
        nodes = eng.trie.spilled_chain(prompt)
        if not nodes:
            return 0
        short = len(nodes) - alloc.free_blocks
        if short > 0:
            # Make room by evicting cold DEVICE leaves; eviction may
            # also drop spilled leaves (possibly ours), so re-walk the
            # chain afterwards rather than trust stale node refs.
            eng.paged_stats["trie_evictions"] += eng.trie.evict(
                alloc, short
            )
            nodes = eng.trie.spilled_chain(prompt)
            if not nodes:
                return 0
        t0 = time.perf_counter()
        refilled = 0
        nbytes = 0
        with span(
            "kv_transfer", tier="host_refill",
            hist="serve_kv_transfer_s", n=len(nodes),
        ):
            for i in range(0, len(nodes), self.group):
                grp = nodes[i:i + self.group]
                got: List[Any] = []
                blocks: List[int] = []
                for node in grp:
                    try:
                        blocks.append(alloc.refill(node.host))
                    except BlockBudgetError:
                        break
                    got.append(node)
                if not got:
                    break
                # refill() already released the host slots, but the
                # rows are still in the buffers -- nothing writes
                # host memory between accounting and copy.
                slots = [n.host for n in got]
                nbytes += self._move_in(slots, blocks)
                for node, blk in zip(got, blocks):
                    node.host = None
                    node.block = int(blk)
                refilled += len(got)
                if len(got) < len(grp):
                    break
        self._hop_s.append(time.perf_counter() - t0)
        if refilled:
            self.stats["kv_refills"] += 1
            self.stats["kv_refill_pages"] += refilled
            self.stats["kv_refill_wire_bytes"] += nbytes
            get_registry().inc(
                "serve_kv_refill_pages_total", refilled
            )
            get_bus().emit(
                "kv_refill", pages=refilled, bytes=nbytes,
                host_free=alloc.host_free_slots,
            )
        return refilled

    # -- lifecycle / reporting -----------------------------------------
    def reset(self) -> None:
        """Forget everything (the reset_pool weight-swap contract):
        the buffers' contents become unreachable with the fresh
        allocator; only the telemetry needs clearing."""
        for k in self.stats:
            self.stats[k] = 0
        self._hop_s = []

    def summary(self) -> dict:
        from tpu_hpc.obs import quantile

        alloc = self.engine.allocator
        hops = sorted(self._hop_s)
        return {
            "kv_host_blocks": self.host_blocks,
            "kv_host_used": alloc.host_used_slots,
            "kv_host_free": alloc.host_free_slots,
            "kv_host_drops": alloc.host_drops,
            "kv_host_inflight_bytes": self.max_inflight_bytes,
            "kv_host_inflight_source": self.inflight_source,
            "kv_hop_ms_p50": round(
                quantile(hops, 0.50) * 1e3, 3
            ) if hops else 0.0,
            "kv_hop_ms_p95": round(
                quantile(hops, 0.95) * 1e3, 3
            ) if hops else 0.0,
            **self.stats,
        }
