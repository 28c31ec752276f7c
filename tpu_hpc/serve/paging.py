"""Paged KV cache: block-table attention, prefix reuse, chunked prefill.

The slab engine (serve/engine.py) preallocates one
``[layers, slots, seq, kv_heads, head_dim]`` cache where a 32-token
request pins the same HBM as a 4096-token one. This module carves that
HBM into fixed-size **pages** instead -- the vLLM insight ("Efficient
Memory Management for Large Language Model Serving with
PagedAttention", PAPERS.md), rebuilt on this repo's own discipline of
AOT executable tables and token-exact oracles:

* **cache** ``[layers, num_blocks, kv_heads, block_size, head_dim]``:
  one physical pool, heads ahead of rows inside a page (the layout
  kernels/paged_attention.py defines and its Mosaic kernels need), KV
  heads sharded over the ``model`` axis (pages are globally
  addressable, so the block dim stays unsharded -- a multi-slice
  deployment runs one pool per data-parallel replica);
* **BlockAllocator** (host side): LIFO free list + refcounts. A block
  is shared when several owners (request tables, the prefix trie)
  hold references; it returns to the free list only at refcount zero.
  Physical block 0 is the **scratch block**: padded-tail writes of a
  bucketed prefill land there instead of corrupting a neighbour, and
  the per-slot length mask keeps its garbage unreachable;
* **block tables**: per-slot ``int32`` rows of physical block ids, fed
  to the compiled programs as *data* -- shapes never change, so the
  zero-steady-state-recompile guarantee survives (the compile-counter
  pins in tests/test_paging.py hold with paging on);
* **PrefixTrie**: a hash-trie over full prompt token blocks with
  copy-on-write refcounts. A request whose prompt starts with an
  already-cached block chain resolves those pages physically and skips
  their prefill compute entirely -- shared system prompts across
  tenants cost their FLOPs once. Writes never target shared pages by
  construction (a request's writes start past its shared prefix);
  :meth:`BlockAllocator.cow` is the enforcing guard rail -- the decode
  path checks its write-target page and copies first if it is shared;
* **chunked prefill**: the scheduler admits a long prompt as a series
  of block-aligned chunks interleaved with decode steps, so a 4k-token
  admission no longer stalls every in-flight request's ITL. Each chunk
  runs through the same per-bucket program -- plain prefill is just
  the one-chunk case.

Attention reads the logical sequence one of two ways, selected by
``PagedConfig.kernel``: ``"gather"`` -- ONE gather over the stacked
pool by layer and block table (``ks[layer, table]``; never
``ks[layer][table]``, whose per-layer slice the TPU compiler
materialises before it gathers), the XLA-level reference formulation,
correct on every backend and token-exact against the no-cache forward (the
tests/test_serve.py oracle applies verbatim), which a chunk keeps and a
one-row decode step over a pool in the compute dtype replaces with a
kernel that walks each slot's table (``PagedAttention``) -- or
``"pallas"`` -- the
kernels/paged_attention.py kernels dropped into the SAME program
slots: block table walked in-kernel as a scalar-prefetch operand, one
HBM read per page, no gathered intermediate, run under ``shard_map``
over the serving mesh (compiled by Mosaic for TPU meshes, interpreted
on the simulated CPU mesh; token-exact vs gather by the parity suite
in tests/test_paged_kernels.py). ``PagedConfig.kv_quant="int8"`` stores
the pool as per-page symmetric int8 with f32 scale side arrays
(``k_scales``/``v_scales``, one scalar per page per layer): half the
pool HBM, ~2x the resident context at equal bytes, gated by a
bounded-divergence oracle instead of token-exactness.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_hpc.models import hybrid_ssm_moe, latent_moe, llama2, sparse_moe
from tpu_hpc.kernels.latent_paged_attention import latent_paged_decode
from tpu_hpc.kernels.paged_attention import (
    INT8_SCALE_FLOOR,
    dequantize_pages_int8,
    paged_decode_attention,
    paged_prefill_attention,
    pages_to_tokens,
    quantize_pages_int8,
    tokens_to_pages,
    write_tokens,
)
from tpu_hpc.kernels.sparse_paged_attention import sparse_paged_decode
from tpu_hpc.obs import get_bus, get_registry, span
from tpu_hpc.serve.decoder import (
    _embed,
    _grouped_attention,
    _grouped_attention_flat,
    _grouped_attention_paged,
    _logits_head,
    _rope_tables,
    _score_scale,
    decoder_layers,
)
from tpu_hpc.serve.engine import Engine, ServeConfig

SCRATCH_BLOCK = 0

# Rows of the decode program's one host-made argument ``[4, slots]``:
# a slot continues from the token the step before left on the device
# unless ``fresh`` says the host's is the one to read.
STEP_ROWS = ("token", "position", "active", "fresh")

# What the one-step-ahead dispatch counts, beside ``serve_compiles_total``
# (registry name, HELP); ``PagedEngine.paged_stats`` holds them too.
DECODE_COUNTERS: Tuple[Tuple[str, str], ...] = (
    ("serve_decode_overlapped_total",
     "Decode steps dispatched while the step before was still "
     "unfetched (over decode steps: how often the device had a step "
     "queued behind the one running)"),
    ("serve_decode_discarded_total",
     "Slot-steps computed past an end the host saw one step late "
     "(end of sequence) and dropped"),
    ("serve_decode_view_pages_read_total",
     "KV pages the dispatched decode programs read a layer: the live "
     "pages of the active slots where a kernel walks the tables (every "
     "pool in the compute dtype read through 'gather'), a shared page "
     "once a slot; else the flat rung's size, or slots x pages a slot "
     "on the rectangle"),
    ("serve_decode_view_pages_total",
     "KV pages the rectangle would have gathered a layer (slots x "
     "pages a slot, a decode step); read over this is how much of "
     "every slot's capacity the steps read"),
)

# What an engine WITH a ladder (``decode_rungs`` non-empty) counts
# beside them, in the same line of ``decode``; an engine without one
# holds neither name.
LADDER_COUNTERS: Tuple[Tuple[str, str], ...] = (
    ("serve_decode_ladder_steps_total",
     "Decode steps dispatched by an engine that holds flat rungs "
     "below the rectangle"),
    ("serve_decode_rectangle_steps_total",
     "Of those, the steps whose live pages no rung held and that ran "
     "the rectangle (every slot's whole capacity)"),
)

# The flat decode rungs of a dense engine over an int8 pool (the one
# read that still gathers: a pool in the compute dtype is walked), as
# shares of slots x pages a slot: a step whose live pages fit one reads
# that many pages and no more; above the last the rectangle reads every
# slot's whole capacity (at half of it the two cost the same a page on
# the v5e). Two, because each is a program to trace and an executable
# to load at start-up, 0.65 s on the v5e's host (PERF.md).
FLAT_RUNGS = (3 / 8, 1 / 2)

# What a decode step of a sparse-expert configuration counts: (the
# program's key for it, the registry's name, HELP), in the order the
# program packs them behind its tokens (one device fetch a step).
# ``*_total`` are summed over layers and steps, the one gauge is the
# largest seen. docs/guide/observability.md has the table of record.
SPARSE_COUNTERS: Tuple[Tuple[str, str, str], ...] = (
    ("assignments", "serve_moe_assignments_total",
     "Token-to-expert assignments routed by decode steps (active "
     "slots x experts a token x layers)"),
    ("experts_touched", "serve_moe_experts_touched_total",
     "Distinct experts with at least one token, summed over layers "
     "and decode steps"),
    ("max_tokens_per_expert", "serve_moe_max_tokens_per_expert",
     "Most tokens one expert of one layer got in one decode step"),
    ("dropped", "serve_moe_dropped_total",
     "Assignments to held experts the expert layer did not compute "
     "(must stay 0)"),
    ("selected", "serve_sparse_selected_tokens_total",
     "Cached tokens attention read after selection (active slots x "
     "layers, min(context, indexer_topk) each)"),
    ("candidates", "serve_sparse_candidate_tokens_total",
     "Cached tokens the indexer scored (active slots x layers, the "
     "whole context each)"),
)

# The same for a latent-attention configuration
# (``models/latent_moe.py``): its expert layers' counts, no indexer's.
LATENT_COUNTERS: Tuple[Tuple[str, str, str], ...] = SPARSE_COUNTERS[:4] + (
    ("assignments_held", "serve_moe_assignments_held_total",
     "Assignments that landed on experts held here (the rest are "
     "other chips' of the expert-parallel deployment, and no drop)"),
)

# Kept on the host from what the step's fetch already holds: which of
# the two it adds is the decode program's static shape
# (``sparse_moe.grouped_by_shape``), so the packed vector stays as wide
# as it was.
MOE_EXPERTS_READ = (
    "serve_moe_experts_read_total",
    "Experts whose weights the decode steps' expert products read, "
    "summed over layers and steps: the experts touched where the "
    "step's shape has the product visit those alone, every held "
    "expert of every expert layer where it reads the whole stack",
)

# Kept on the host where pages are taken and released, added up once a
# decode step (``_LivePages``).
LATENT_PAGES_LIVE = (
    "serve_latent_pages_live_total",
    "Distinct latent pages that at least one active slot read, summed "
    "over decode steps (a page several slots share counts once)",
)


# What an engine with a recurrent state beside its pages counts
# (``models/hybrid_ssm_moe.py``), on the host where it admits, snapshots
# and dispatches; and its two gauges.
SSM_COUNTERS: Tuple[Tuple[str, str], ...] = (
    ("serve_ssm_slot_steps_total",
     "Slots whose recurrent state a decode step advanced (the active "
     "ones), summed over decode steps"),
    ("serve_ssm_snapshots_total",
     "Copies of a slot's recurrent state the prefix trie took (a "
     "finished prompt's last full block, or the end of a page match "
     "no snapshot covered)"),
    ("serve_ssm_restores_total",
     "Admissions whose recurrent state was restored from a snapshot "
     "in the prefix trie"),
    ("serve_ssm_restored_tokens_total",
     "Prompt tokens whose recurrent state came from a snapshot "
     "(the snapshot's depth, an admission)"),
    ("serve_ssm_snapshot_evictions_total",
     "Snapshots dropped to stay under the snapshot byte budget"),
)
# The same count as ``LATENT_PAGES_LIVE`` over the pages of such an
# engine's attention layers: what the floor of its decode step reads.
KV_PAGES_LIVE = (
    "serve_kv_pages_live_total",
    "Distinct KV pages that at least one active slot read, summed over "
    "decode steps (a page several slots share counts once)",
)
SSM_GAUGES: Tuple[Tuple[str, str], ...] = (
    ("serve_ssm_state_bytes",
     "Bytes of recurrent state the engine holds for its slots"),
    ("serve_ssm_snapshot_bytes",
     "Bytes of recurrent-state snapshots the prefix trie holds"),
)


def step_counters(cfg) -> Tuple[Tuple[str, str, str], ...]:
    """What the configuration's decode step packs behind its tokens."""
    if sparse_moe.is_sparse_moe(cfg):
        return SPARSE_COUNTERS
    if latent_moe.is_latent_moe(cfg) or hybrid_ssm_moe.has_experts(cfg):
        return LATENT_COUNTERS
    return ()


class BlockBudgetError(RuntimeError):
    """Transient: the allocator cannot seat this request *right now*.
    The batcher keeps the request queued and retries next tick (free
    blocks appear as in-flight requests finish)."""


class UnservableRequestError(ValueError):
    """Permanent: the request can never fit the configured page budget
    (prompt + max_new exceeds what the whole pool holds). Raised at
    submit() so one oversized request cannot abort a mid-flight
    drain."""


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Static paged-cache shape: everything the pool layout and the
    compiled programs depend on.

    ``block_size``: tokens per page. ``num_blocks``: physical pages in
    the pool, INCLUDING the reserved scratch block 0 (usable pages =
    ``num_blocks - 1``). ``prefill_chunk``: chunked-prefill stride in
    tokens (0 = whole-prompt bucketed prefill); must be block-aligned
    so every chunk starts on a page boundary. ``prefix_cache``: keep
    finished prompts' full pages in the prefix trie for reuse.
    ``host_blocks``: host-DRAM page slots behind the HBM pool
    (serve/tier.py; 0 = no tier). Like ``num_blocks`` it INCLUDES a
    reserved scratch slot 0, so a non-zero tier needs >= 2 slots.
    ``kernel``: how attention reads the pool -- ``"gather"`` (the XLA
    data-indexed gather, the oracle) or ``"pallas"``
    (kernels/paged_attention.py: block table walked in-kernel, one HBM
    read per page). ``kv_quant``: pool storage -- ``"none"``
    (cache_dtype as configured) or ``"int8"`` (per-page symmetric int8
    with f32 scale side arrays; half the pool bytes).

    Legal page sizes for ``kernel="pallas"``: ``block_size >= 2``. A
    one-row page fails the Mosaic lowering for bfloat16 and int8 pools
    (the ``(1, 128)`` page leaves the matmul a degenerate operand);
    every size from 2 to 128 lowers, and on a v5e matches the
    gather-then-dense oracle, for float32, bfloat16 and int8 alike
    (PERF.md, PR 21 sweep). HBM stores a page short of a tile
    unpadded -- XLA narrows the tiling -- so no dtype needs a larger
    minimum; which size is FASTEST is not measured.

    ``ssm_snapshot_bytes``: what the prefix trie may hold in snapshots
    of a recurrent state (a configuration with state-space layers
    only: ``models/hybrid_ssm_moe.py``); ``None`` is as many snapshots
    as the engine has slots."""

    block_size: int = 16
    num_blocks: int = 64
    prefill_chunk: int = 0
    prefix_cache: bool = True
    host_blocks: int = 0
    kernel: str = "gather"
    kv_quant: str = "none"
    ssm_snapshot_bytes: Optional[int] = None

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}"
            )
        if self.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (scratch + at least one "
                f"usable page), got {self.num_blocks}"
            )
        if self.host_blocks < 0 or self.host_blocks == 1:
            raise ValueError(
                f"host_blocks must be 0 (no host tier) or >= 2 "
                f"(scratch + at least one resident slot), got "
                f"{self.host_blocks}"
            )
        if self.host_blocks and not self.prefix_cache:
            raise ValueError(
                "host_blocks needs prefix_cache=True: the host tier "
                "spills TRIE-parked pages (a pool with no trie has "
                "nothing parked to spill)"
            )
        if self.prefill_chunk < 0 or (
            self.prefill_chunk % self.block_size
        ):
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} must be a "
                f"multiple of block_size {self.block_size} (chunks "
                "start on page boundaries)"
            )
        if self.kernel not in ("gather", "pallas"):
            raise ValueError(
                f"kernel must be 'gather' or 'pallas', got "
                f"{self.kernel!r}"
            )
        if self.kernel == "pallas" and self.block_size < 2:
            raise ValueError(
                f"kernel='pallas' needs block_size >= 2, got "
                f"{self.block_size}: a one-row page does not lower "
                "through Mosaic for bfloat16 and int8 pools"
            )
        if self.kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant must be 'none' or 'int8', got "
                f"{self.kv_quant!r}"
            )

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1

    def blocks_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` cache positions."""
        return -(-tokens // self.block_size)


DEFAULT_BLOCK_SIZE = 16


def derive_paged_config(
    slots: int,
    max_seq: int,
    buckets: Sequence[int],
    block_size: Optional[int] = None,
    num_blocks: Optional[int] = None,
    prefill_chunk: Optional[int] = None,
    align_capacity: bool = False,
    host_blocks: Optional[int] = None,
    kernel: Optional[str] = None,
    kv_quant: Optional[str] = None,
) -> Tuple["PagedConfig", int]:
    """CLI-shared sizing: ``(PagedConfig, capacity)`` from the flag
    values, with every invalid combination raising ``ValueError``
    BEFORE any backend bring-up. One derivation for server.py and
    bench.py, so the bench rows and the serving CLI can never
    silently diverge on the default block size, the page-rounding
    rule, or the slab-equivalent pool default.

    ``align_capacity=True`` rounds a DERIVED capacity up to a whole
    number of pages; an explicitly chosen capacity must align itself
    (callers pass False so the mismatch errors loudly)."""
    bs = block_size or DEFAULT_BLOCK_SIZE
    if align_capacity:
        max_seq = -(-max_seq // bs) * bs
    misaligned = [n for n in (max_seq, *buckets) if n % bs]
    if misaligned:
        raise ValueError(
            f"kv block size {bs} must divide the cache capacity and "
            f"every prefill bucket; {misaligned} are not multiples"
        )
    if (prefill_chunk or 0) > max(buckets):
        raise ValueError(
            f"prefill chunk {prefill_chunk} exceeds the largest "
            f"bucket {max(buckets)} (chunks run through the compiled "
            "bucket programs)"
        )
    cfg = PagedConfig(
        block_size=bs,
        num_blocks=(
            num_blocks if num_blocks is not None
            # Slab-equivalent HBM by default: same token capacity,
            # plus the scratch page.
            else slots * max_seq // bs + 1
        ),
        prefill_chunk=prefill_chunk or 0,
        host_blocks=host_blocks or 0,
        kernel=kernel or "gather",
        kv_quant=kv_quant or "none",
    )
    return cfg, max_seq


def rope_pack(cfg, block_size: int) -> int:
    """Tokens that share one row of a latent configuration's pool
    arrays (``models/latent_moe.py``): as many rotary keys as fill the
    chip's 128 lanes and divide a page (2 of 64 numbers at a page of
    16: pages of ``[8, 128]`` rotary keys and ``[8, 1024]`` latents).
    A row narrower than the lanes makes the TPU runtime lay the pool
    out pages-minor, and every program then copies the whole pool in
    and out to reach a page (PERF.md, PR 31); the latents keep the
    rotary keys' packing so that the decode kernel scores both as they
    lie (``kernels/latent_paged_attention.py``)."""
    return math.gcd(block_size, max(1, 128 // cfg.rope_dim))


def _write_packed(pool, layer, page_ids, offsets, rows, pack: int):
    """``write_tokens`` for a latent pool ``[layers, pages, block_size
    / pack, pack * width]``, ``pack`` tokens a row: token ``offsets[s]``
    of page ``page_ids[s]`` is lanes ``(off % pack) * width ..`` of row
    ``off // pack``. The same page-granular read-modify-write, and the
    same rule: one writer a page a call."""
    pages = pool[layer, page_ids]
    width = pages.shape[-1] // pack
    row = jnp.arange(pages.shape[1])[None, :, None]
    lane = jnp.arange(pages.shape[2])[None, None, :]
    at = (row == offsets[:, None, None] // pack) \
        & (lane // width == offsets[:, None, None] % pack)
    new = jnp.tile(rows.astype(pool.dtype), (1, pack))[:, None, :]
    return pool.at[layer, page_ids].set(jnp.where(at, new, pages))


def paged_kv_cache_pspec(mesh: Mesh, kv_heads: int) -> P:
    """Pool layout: KV heads over ``model`` (when the axis exists,
    divides, and is wider than 1); the block dim stays unsharded --
    any slot may reference any page, and a data-sharded pool would
    turn every table gather into a cross-replica collective."""
    names = set(mesh.axis_names)
    model = (
        "model"
        if "model" in names and mesh.shape["model"] > 1
        and kv_heads % mesh.shape["model"] == 0
        else None
    )
    return P(None, None, model, None, None)


def _on_mesh(kernel, mesh: Mesh, kv_heads: int, heads_dim: int, **static):
    """A paged kernel with its static arguments bound, run per KV-head
    shard of the serving mesh. XLA has no SPMD partitioning rule for a
    Mosaic call, so inside a GSPMD program the kernel runs under
    ``shard_map`` (the tp.make_tp_flash_attn_fn pattern): q, the pool
    and the output split over ``model`` on their KV-head dim exactly as
    :func:`paged_kv_cache_pspec` lays the pool out; tables, positions
    and scales are whole on every shard. ``heads_dim``: which dim of q
    and of the output indexes KV heads. Interpreted when the mesh is
    not made of TPU devices."""
    if mesh is None:
        raise ValueError("kernel='pallas' needs the serving mesh")
    fn = functools.partial(
        kernel,
        interpret=mesh.devices.flat[0].platform != "tpu",
        **static,
    )
    if mesh.size == 1:
        return fn
    model = paged_kv_cache_pspec(mesh, kv_heads)[2]
    q_spec = P(*(model if i == heads_dim else None for i in range(4)))
    pool_spec = P(None, model, None, None)

    def call(q, k_pages, v_pages, *scalars, **scales):
        # Tables/positions and the (possibly None) scales ride as two
        # whole-on-every-shard pytrees.
        return jax.shard_map(
            lambda q, k, v, scalars, scales: fn(
                q, k, v, *scalars, **scales
            ),
            mesh=mesh,
            in_specs=(q_spec, pool_spec, pool_spec, P(), P()),
            out_specs=q_spec, check_vma=False,
        )(q, k_pages, v_pages, scalars, scales)

    return call


def _walk_on_mesh(walk, mesh: Optional[Mesh], kv_heads: int):
    """A row step's table-walking read (``sparse_paged_decode`` with
    its static arguments bound) where the program runs: as
    ``sparse_moe._on_mesh`` has it, or, where the pool's KV heads are
    split over ``model`` (:func:`paged_kv_cache_pspec`: a dense
    configuration on a tensor-parallel mesh), under ``shard_map`` a
    KV-head shard at a time: each chip walks its own heads' pages with
    its share of the queries, tables, positions and the rest whole on
    every chip. Interpreted when the mesh is not made of TPU
    devices."""
    model = None if mesh is None else paged_kv_cache_pspec(mesh, kv_heads)[2]
    if model is None:
        return sparse_moe._on_mesh(walk, mesh)
    call = functools.partial(
        walk, interpret=mesh.devices.flat[0].platform != "tpu"
    )
    heads = P(None, model, None, None)
    pool = P(None, None, model, None, None)

    def walk_shards(q, ks, vs, *rest):
        return jax.shard_map(
            lambda q, ks, vs, rest: call(q, ks, vs, *rest), mesh=mesh,
            in_specs=(heads, pool, pool, P()), out_specs=heads,
            check_vma=False,
        )(q, ks, vs, rest)

    return walk_shards


# ---------------------------------------------------------------------
# Host-side page accounting
# ---------------------------------------------------------------------


class BlockAllocator:
    """Free-list + refcount accounting over the physical page pool.

    Invariant (pinned by the property suite in tests/test_paging.py):
    ``1 (scratch) + len(free) + len(referenced) == num_blocks`` at all
    times -- no page is ever both free and referenced, double-freed,
    or leaked. ``retain``/``release`` move refcounts; a page frees
    only at refcount zero, which is what lets the prefix trie keep a
    finished request's prompt pages alive for future hits.

    With ``host_blocks > 0`` (the host-DRAM tier, serve/tier.py) the
    identity extends across tiers: device scratch + free + referenced
    plus host scratch + free + resident must equal
    ``num_blocks + host_blocks`` -- a page lives in exactly one tier
    at a time. ``spill``/``refill`` move a page's accounting between
    tiers; the device<->host copies themselves are the tier's job."""

    def __init__(self, num_blocks: int, host_blocks: int = 0):
        if num_blocks < 2:
            raise ValueError(f"num_blocks {num_blocks} must be >= 2")
        if host_blocks < 0 or host_blocks == 1:
            raise ValueError(
                f"host_blocks {host_blocks} must be 0 or >= 2"
            )
        self.num_blocks = num_blocks
        self.host_blocks = host_blocks
        # LIFO: the most recently freed page is the next handed out --
        # it is the page most likely still warm in HBM caches.
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        # Host tier: slot 0 mirrors the device scratch block (refill
        # padding gathers from it, spill padding scatters to it).
        self._host_free: List[int] = (
            list(range(host_blocks - 1, 0, -1)) if host_blocks else []
        )
        self._host_used: set = set()
        self.host_drops = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._ref)

    @property
    def host_free_slots(self) -> int:
        return len(self._host_free)

    @property
    def host_used_slots(self) -> int:
        return len(self._host_used)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` fresh pages at refcount 1."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} blocks")
        if n > len(self._free):
            raise BlockBudgetError(
                f"need {n} free pages, have {len(self._free)} "
                f"(pool {self.num_blocks}, {len(self._ref)} in use)"
            )
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def retain(self, blocks: Sequence[int]) -> None:
        """Add one reference to each (already-referenced) page."""
        for b in blocks:
            if b not in self._ref:
                raise ValueError(
                    f"retain of unreferenced block {b} (free or "
                    "scratch) -- a share must start from a live page"
                )
            self._ref[b] += 1

    def release(self, blocks: Sequence[int]) -> int:
        """Drop one reference from each page; pages reaching zero
        return to the free list. Returns how many pages freed."""
        freed = 0
        for b in blocks:
            n = self._ref.get(b)
            if n is None:
                raise ValueError(
                    f"double free of block {b} (not referenced)"
                )
            if n == 1:
                del self._ref[b]
                self._free.append(b)
                freed += 1
            else:
                self._ref[b] = n - 1
        return freed

    def cow(self, block: int) -> Tuple[int, bool]:
        """Copy-on-write: writing into ``block`` is safe only while
        this owner holds the sole reference. Returns ``(block,
        False)`` when exclusive; otherwise drops this owner's
        reference, allocates a fresh page, and returns ``(new_block,
        True)`` -- the caller must copy the page contents device-side
        before writing."""
        n = self._ref.get(block)
        if n is None:
            raise ValueError(f"cow of unreferenced block {block}")
        if n == 1:
            return block, False
        self._ref[block] = n - 1
        try:
            new = self.alloc(1)[0]
        except BlockBudgetError:
            self._ref[block] = n  # roll back: caller keeps its ref
            raise
        return new, True

    # -- host-tier accounting (serve/tier.py moves the bytes) ----------
    def spill(self, block: int) -> int:
        """Move one device page's accounting to the host tier: frees
        the device page, returns the host slot now holding it.

        Refuses pages any live request still shares (refcount above
        the spiller's single trie reference) -- the PR-8 shared-leaf
        eviction lesson applied to spill: a page a live request still
        reads through its block table must stay in HBM, or the next
        decode gather reads a recycled page."""
        n = self._ref.get(block)
        if n is None:
            raise ValueError(f"spill of unreferenced block {block}")
        if n != 1:
            raise ValueError(
                f"spill of shared block {block} (refcount {n}): a "
                "page a live request still reads must stay in HBM"
            )
        if not self._host_free:
            raise BlockBudgetError(
                f"host tier full ({len(self._host_used)} of "
                f"{self.host_blocks} slot(s) resident)"
            )
        slot = self._host_free.pop()
        self._host_used.add(slot)
        del self._ref[block]
        self._free.append(block)
        return slot

    def refill(self, host_slot: int) -> int:
        """Bring one host-resident page's accounting back: allocates a
        device page at refcount 1, frees the host slot. Raises
        :class:`BlockBudgetError` when the device pool is full (the
        caller's spill/evict pass must free pages first)."""
        if host_slot not in self._host_used:
            raise ValueError(
                f"refill of non-resident host slot {host_slot}"
            )
        block = self.alloc(1)[0]
        self._host_used.remove(host_slot)
        self._host_free.append(host_slot)
        return block

    def host_drop(self, host_slot: int) -> None:
        """Discard a host-resident page (host-tier eviction, or a
        trie re-insert adopting a freshly recomputed device copy)."""
        if host_slot not in self._host_used:
            raise ValueError(
                f"host drop of non-resident slot {host_slot}"
            )
        self._host_used.remove(host_slot)
        self._host_free.append(host_slot)
        self.host_drops += 1

    def check_invariant(self) -> None:
        """Raises if the accounting identity is violated (the property
        suite calls this after every random operation)."""
        free = set(self._free)
        held = set(self._ref)
        if len(free) != len(self._free):
            raise AssertionError("duplicate pages on the free list")
        if free & held:
            raise AssertionError(
                f"pages both free and referenced: {sorted(free & held)}"
            )
        if SCRATCH_BLOCK in free or SCRATCH_BLOCK in held:
            raise AssertionError("scratch block leaked into the pool")
        if any(n < 1 for n in self._ref.values()):
            raise AssertionError("zero/negative refcount retained")
        total = 1 + len(free) + len(held)
        if total != self.num_blocks:
            raise AssertionError(
                f"page accounting broken: scratch + {len(free)} free "
                f"+ {len(held)} held = {total} != {self.num_blocks}"
            )
        hfree = set(self._host_free)
        if len(hfree) != len(self._host_free):
            raise AssertionError(
                "duplicate slots on the host free list"
            )
        if hfree & self._host_used:
            raise AssertionError(
                f"host slots both free and resident: "
                f"{sorted(hfree & self._host_used)}"
            )
        if self.host_blocks and (0 in hfree or 0 in self._host_used):
            raise AssertionError(
                "host scratch slot leaked into the tier"
            )
        htotal = (
            1 + len(hfree) + len(self._host_used)
            if self.host_blocks else 0
        )
        if self.host_blocks and htotal != self.host_blocks:
            raise AssertionError(
                f"host tier accounting broken: scratch + "
                f"{len(hfree)} free + {len(self._host_used)} resident "
                f"= {htotal} != {self.host_blocks}"
            )
        # The cross-tier identity the host tier extends the pool
        # with: scratch + free + referenced + host == total pages.
        if total + htotal != self.num_blocks + self.host_blocks:
            raise AssertionError(
                f"cross-tier accounting broken: device {total} + "
                f"host {htotal} != "
                f"{self.num_blocks + self.host_blocks}"
            )


@dataclasses.dataclass
class _TrieNode:
    block: int
    children: Dict[Tuple[int, ...], "_TrieNode"] = dataclasses.field(
        default_factory=dict
    )
    last_used: int = 0
    # Host-tier residency (serve/tier.py): the host slot holding this
    # block's K/V while it is spilled out of HBM; None = device-
    # resident (block is the live page id; spilled nodes park -1).
    host: Optional[int] = None
    # The recurrent state of a sequence that has read the chain down
    # to this block, where the trie holds one (a configuration with
    # state-space layers only).
    snapshot: Optional["_Snapshot"] = None


@dataclasses.dataclass
class _Snapshot:
    """A copy of one slot's recurrent state at a block boundary, the
    trie's: ``state`` are the device arrays, ``cost`` the prompt tokens
    whoever left it had prefilled since the state IT started from (what
    a later request saves by finding it), ``credit`` its standing in
    the eviction order."""

    state: Tuple[Any, ...]
    nbytes: int
    cost: int
    credit: float = 0.0


class PrefixTrie:
    """Hash-trie over full prompt token blocks.

    Each edge is one block's worth of token ids; each node owns one
    reference on a physical page holding that block's K/V. A lookup
    walks the longest cached chain for a new prompt; an insert
    registers a finished prefill's full prompt blocks. Eviction is
    LRU leaf-first (an inner node's page is only reachable through
    its chain, so leaves must go first), and releasing the trie's
    reference frees the page only when no live request still holds
    it -- which is exactly why a prefix hit stays token-exact after
    the original owner was evicted.

    A configuration with state-space layers keeps a recurrent state a
    sequence beside its pages, and a shared page is only worth having
    where the state AT THAT POSITION is there too: a node may hold a
    **snapshot** of it (:meth:`put_snapshot`), and an admission uses a
    page match only down to :meth:`deepest_snapshot`. Snapshots live
    under ``snapshot_budget`` bytes and die with their node. Over the
    budget the one worth least goes: each carries ``credit = floor +
    cost``, its cost the tokens it saves, refreshed when it is restored
    from, and ``floor`` rises to the credit of whatever was last
    dropped (GreedyDual: plain LRU where every cost is the same). A
    document's snapshot at 28672 tokens so outlasts hundreds of
    never-reused 300-token ones of the questions asked about it, where
    LRU would drop it for the first that finds the budget full."""

    def __init__(self, block_size: int, snapshot_budget: int = 0):
        self.block_size = block_size
        self._root: Dict[Tuple[int, ...], _TrieNode] = {}
        self._clock = 0
        self.nodes = 0
        self.snapshot_budget = snapshot_budget
        self.snapshot_bytes = 0
        self.snapshot_evictions = 0
        self._snapshots: Dict[int, _TrieNode] = {}   # id(node) -> node
        self._floor = 0.0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _full_blocks(
        self, prompt: Sequence[int]
    ) -> List[Tuple[int, ...]]:
        bs = self.block_size
        n_full = len(prompt) // bs
        return [
            tuple(prompt[i * bs:(i + 1) * bs]) for i in range(n_full)
        ]

    def match(self, prompt: Sequence[int]) -> List[int]:
        """Physical pages of the longest cached full-block prefix of
        ``prompt`` (possibly empty). Bumps LRU clocks; takes no
        references -- the caller retains what it keeps. Stops at the
        first HOST-resident node: a spilled page has no device id to
        share until a prefetch (serve/tier.py) refills it."""
        blocks: List[int] = []
        level = self._root
        now = self._tick()
        for key in self._full_blocks(prompt):
            node = level.get(key)
            if node is None or node.host is not None:
                break
            node.last_used = now
            blocks.append(node.block)
            level = node.children
        return blocks

    def _node_at(
        self, prompt: Sequence[int], n_blocks: int
    ) -> Optional[_TrieNode]:
        level, node = self._root, None
        for key in self._full_blocks(prompt)[:n_blocks]:
            node = level.get(key)
            if node is None:
                return None
            level = node.children
        return node

    def deepest_snapshot(
        self, prompt: Sequence[int], n_blocks: int
    ) -> Tuple[int, Optional[_Snapshot]]:
        """``(depth in blocks, snapshot)`` of the deepest node among
        the leading ``n_blocks`` of ``prompt``'s chain that holds one;
        ``(0, None)`` where none does. The caller restores from it, so
        its standing is refreshed."""
        found: Tuple[int, Optional[_Snapshot]] = (0, None)
        level = self._root
        for depth, key in enumerate(
            self._full_blocks(prompt)[:n_blocks], 1
        ):
            node = level.get(key)
            if node is None:
                break
            if node.snapshot is not None:
                found = (depth, node.snapshot)
            level = node.children
        if found[1] is not None:
            found[1].credit = self._floor + found[1].cost
        return found

    def put_snapshot(
        self, prompt: Sequence[int], n_blocks: int, state: Tuple[Any, ...],
        nbytes: int, cost: int,
    ) -> bool:
        """Leave ``state``, a sequence's recurrent state after the
        leading ``n_blocks`` full blocks of ``prompt``, at that node.
        An existing snapshot wins (it is the same state); a chain the
        trie no longer holds, or a budget too small for one, takes
        none. Then drops snapshots, least credit first, until the
        budget holds. Returns whether the node took it."""
        node = self._node_at(prompt, n_blocks) if n_blocks else None
        if node is None or node.snapshot is not None \
                or nbytes > self.snapshot_budget:
            return False
        node.snapshot = _Snapshot(
            state, nbytes, cost, credit=self._floor + cost
        )
        self._snapshots[id(node)] = node
        self.snapshot_bytes += nbytes
        while self.snapshot_bytes > self.snapshot_budget:
            victim = min(
                self._snapshots.values(),
                key=lambda n: (n.snapshot.credit, n.last_used),
            )
            self._floor = victim.snapshot.credit
            self._drop_snapshot(victim)
            self.snapshot_evictions += 1
        return node.snapshot is not None

    def _drop_snapshot(self, node: _TrieNode) -> None:
        if node.snapshot is not None:
            self.snapshot_bytes -= node.snapshot.nbytes
            del self._snapshots[id(node)]
            node.snapshot = None

    def spilled_chain(
        self, prompt: Sequence[int]
    ) -> List[_TrieNode]:
        """The HOST-resident nodes along ``prompt``'s cached chain, in
        chain order -- what a prefetch must refill before
        :meth:`match` can serve the full prefix. Read-only: no LRU
        bump (the refill itself is the evidence of heat)."""
        out: List[_TrieNode] = []
        level = self._root
        for key in self._full_blocks(prompt):
            node = level.get(key)
            if node is None:
                break
            if node.host is not None:
                out.append(node)
            level = node.children
        return out

    def spillable(
        self, allocator: BlockAllocator
    ) -> List[_TrieNode]:
        """Device-resident nodes whose page only the trie holds and
        whose children (if any) are all host-resident already --
        the pages a host-tier spill may take without breaking a
        chain's device-prefix/host-suffix shape. LRU first, so the
        coldest suffixes leave HBM first (evict's leaf-first rule,
        applied to spill)."""
        cands: List[Tuple[int, _TrieNode]] = []

        def walk(level: Dict) -> None:
            for node in level.values():
                walk(node.children)
                if (
                    node.host is None
                    and all(
                        c.host is not None
                        for c in node.children.values()
                    )
                    and allocator.refcount(node.block) == 1
                ):
                    cands.append((node.last_used, node))

        walk(self._root)
        cands.sort(key=lambda t: t[0])
        return [node for _, node in cands]

    def insert(
        self,
        prompt: Sequence[int],
        blocks: Sequence[int],
        allocator: BlockAllocator,
    ) -> int:
        """Register a finished prefill's full prompt blocks
        (``blocks[i]`` holds tokens ``[i*bs, (i+1)*bs)``). Existing
        nodes win (a concurrent identical prompt already cached the
        chain; the caller keeps its private copy). Returns how many
        new nodes (trie references) were created."""
        level = self._root
        now = self._tick()
        created = 0
        for i, key in enumerate(self._full_blocks(prompt)):
            node = level.get(key)
            if node is None:
                node = _TrieNode(block=int(blocks[i]), last_used=now)
                allocator.retain([node.block])
                level[key] = node
                self.nodes += 1
                created += 1
            else:
                if node.host is not None:
                    # The prefill just recomputed this block's K/V
                    # into the request's own device page (match
                    # stopped at the spilled node, so the chunk plan
                    # covered it): adopt that page and drop the now-
                    # redundant host copy -- a chain demonstrably hot
                    # again belongs in HBM, not behind a refill hop.
                    allocator.retain([int(blocks[i])])
                    allocator.host_drop(node.host)
                    node.host = None
                    node.block = int(blocks[i])
                node.last_used = now
            level = node.children
        return created

    def evict(
        self, allocator: BlockAllocator, n_needed: int
    ) -> int:
        """Drop LRU leaf nodes until ``n_needed`` pages came FREE (a
        released page still referenced by a live request frees
        nothing) or nothing evictable remains. Returns pages freed.

        One walk collects the current leaves; the whole batch drains
        in LRU order before re-walking (a re-walk is only needed when
        evicting a batch exposed parents as new leaves), so freeing
        ``n`` pages costs O(depth) walks, not O(n) -- evict runs
        inside admit() on every page-short admission, the hot path of
        a saturated pool.

        Leaves whose page is SHARED with a live request (refcount
        above the trie's own reference) are skipped: releasing them
        frees nothing toward the shortage, and deleting the node
        would throw away a demonstrably-hot prefix -- the next
        same-prompt request would pay the full prefill again (review
        finding: one unsatisfiable shortage must not wipe the warm
        cache)."""
        freed = 0
        while freed < n_needed:
            leaves: List[Tuple[int, Dict, Tuple, _TrieNode]] = []
            spilled: List[Tuple[int, Dict, Tuple, _TrieNode]] = []

            def walk(level: Dict) -> None:
                for key, node in level.items():
                    if node.children:
                        walk(node.children)
                    elif node.host is not None:
                        # Host-resident leaf: pins no HBM, but blocks
                        # the walk from exposing its device-resident
                        # ancestors as leaves.
                        spilled.append(
                            (node.last_used, level, key, node)
                        )
                    elif allocator.refcount(node.block) == 1:
                        leaves.append(
                            (node.last_used, level, key, node)
                        )

            walk(self._root)
            if leaves:
                leaves.sort(key=lambda t: t[0])
                for _, level, key, node in leaves:
                    del level[key]
                    self.nodes -= 1
                    self._drop_snapshot(node)
                    freed += allocator.release([node.block])
                    if freed >= n_needed:
                        break
            elif spilled:
                # Device leaves exhausted while still short: the pool-
                # pressure endgame. Dropping host-resident leaves
                # frees no HBM directly, but the re-walk then reaches
                # their (device-resident) parents -- without this the
                # eviction loop stalls on a full host tier while
                # parked pages still hold HBM.
                spilled.sort(key=lambda t: t[0])
                for _, level, key, node in spilled:
                    del level[key]
                    self.nodes -= 1
                    allocator.host_drop(node.host)
            else:
                break
        return freed


# ---------------------------------------------------------------------
# Compiled programs
# ---------------------------------------------------------------------


def _with_state(body, name: str, quant: bool, sparse: bool,
                recurrent: bool = False):
    """A program body ``(params, ks, vs, ksc, vsc, xs, rec, *args) ->
    (ks, vs, ksc, vsc, xs, *results)`` under the signature the engine's
    state has: ``(params, ks, vs, *args)``, with ``ksc, vsc`` after
    ``vs`` for an int8 pool, ``xs`` after those for a sparse-expert
    configuration, and the recurrent state's two arrays (``rec``; the
    body returns them ahead of its results) after those for one with
    state-space layers, in the arguments and in the results alike.
    ``name`` is the program's (the jitted module's, which the trace
    reports)."""

    def program(params, ks, vs, *rest):
        n = 2 * quant + sparse
        extra, args = rest[:n], rest[n:]
        ksc, vsc = extra[:2] if quant else (None, None)
        xs = extra[-1] if sparse else None
        rec = None
        if recurrent:
            rec, args = args[:2], args[2:]
        ks, vs, ksc, vsc, xs, *out = body(
            params, ks, vs, ksc, vsc, xs, rec, *args
        )
        return (
            *(a for a in (ks, vs, ksc, vsc, xs) if a is not None), *out
        )

    program.__name__ = program.__qualname__ = name
    return program


def _check_read_path(cfg, kernel: str, kv_quant: str) -> None:
    """A sparse-expert configuration reads under ``gather`` from a
    pool in the compute dtype: ``kernels/paged_attention.py`` walks the
    whole table (no selection) and the int8 page write has no indexer
    key."""
    if kernel != "gather" or kv_quant != "none":
        sparse_moe.refuse(
            cfg, f"kernel={kernel!r} / kv_quant={kv_quant!r}",
            "kernels/paged_attention.py applies no selection and the "
            "int8 page write quantises no indexer key; the selected "
            "decode step already walks the tables in a kernel of its "
            "own (kernels/sparse_paged_attention.py) under 'gather', "
            "over an unquantised pool",
        )
        latent_moe.refuse(
            cfg, f"kernel={kernel!r} / kv_quant={kv_quant!r}",
            "kernels/paged_attention.py contracts per-head K and V "
            "pages and the int8 page write quantises them; a latent "
            "page has neither, and its decode step already walks the "
            "tables in a kernel of its own "
            "(kernels/latent_paged_attention.py) under 'gather'",
        )
        hybrid_ssm_moe.refuse(
            cfg, f"kernel={kernel!r} / kv_quant={kv_quant!r}",
            "the Pallas kernels scale scores by head_dim ** -0.5, not "
            "by the configuration's multiplier, and neither they nor "
            "the int8 pool have been held to this decoder's reference",
        )


class PagedAttention:
    """The attention state of every program over the page pool: the
    pool's arrays inside one call (``ks, vs``, an int8 pool's scales
    ``ksc, vsc``, a sparse-expert configuration's indexer keys ``xs``)
    and the one place that knows how a page is written and read --
    ``kv_quant``, ``kernel`` and the indexer's selection are decided
    here and in no program. ``serve/decoder.py``'s layer loop calls it
    once a layer; what stays the program's is what differs between
    programs: positions and the mask, and which pages and rows this
    step writes.

    A factory builds one for its program's kind (``chunk``: one
    sequence's block-aligned run of tokens; else one row a slot, or a
    slot's candidate rows); the traced body takes a copy over its
    arrays (:meth:`on`), says what the step reads (:meth:`view`) and
    writes (:meth:`pages` or :meth:`rows`), hands it to the loop and
    returns :meth:`state`. A layer is then, by stage name:

    * ``kv_write`` -- a chunk's K/V as whole pages into ``blk_ids``; a
      row's into page ``pb`` at offset ``off`` through ``write_tokens``
      (one writer a page a call, so a slot's candidate rows, which
      share pages, go one call each). An int8 pool quantizes whole
      pages (per-page f32 scale into ``ksc`` / ``vsc``), so its row
      write is a page REQUANTIZE: dequantize the target page, insert
      the row, zero the not-yet-written tail (so stale garbage cannot
      leak into the scale), requantize with a fresh per-page amax
      scale. The page's scale is monotone non-decreasing over a
      request's decode (amax only grows among live positions), so
      requantization drift of earlier tokens is bounded -- the int8
      oracle's contract.
    * ``indexer`` (a sparse-expert configuration only,
      ``models/sparse_moe.py``) -- project, put the tokens' keys into
      ``xs`` under the page ids and rows their K/V went to, score every
      column of the view against each query and keep the exact top
      ``indexer_topk`` of the columns the program's mask allows. The
      read runs under THAT mask, over whole pages: a CHUNK's gathered
      view as it is, a ROW step's through
      ``kernels/sparse_paged_attention.py``, which takes the tables,
      ``pos``, ``active`` and the selection and walks each slot's live
      pages itself: every K and V page once, the mask applied to the
      block that has landed, no gathered view, no score tensor in HBM,
      pages past ``pos`` and inactive slots unread; all of the walk
      under ``kv_read``, what is left under ``attention`` the rounding
      of its float32 result. Chosen by the program's kind and by
      nothing a user sets; compiled by Mosaic on a TPU, interpreted
      elsewhere (``sparse_moe._on_mesh``'s rule). (Whole pages under a
      mask, not the selected rows: rows of 256 B move at 16 GB/s, and a
      token-granular gather of them read 2.3 ms a step SLOWER than the
      gathered rectangle, PERF.md PR 27; the walk reads 1.0 ms a layer
      where the rectangle read 4.8, PR 38.) A row step also keeps each
      layer's selection (``picked``, for the benchmark's probe) and
      counts what the indexer scored and what attention read
      (``counts``, by ``SPARSE_COUNTERS``' keys).
    * ``kv_read`` + ``attention`` -- ``kernel="gather"``: ONE
      data-indexed gather of the view's pages by layer and table
      (dequantized from an int8 pool) and the model's dense attention
      under the mask, page-major for row steps
      (:func:`_grouped_attention_paged`) and token-major for a chunk;
      so a chunk attends to every previously prefilled chunk and to the
      shared prefix pages it never computed. A ROW step handed ``pos,
      active`` (:meth:`view`) over a pool in the compute dtype -- a
      dense configuration's, and one with state-space layers
      (``models/hybrid_ssm_moe.py``) -- reads through the selected
      read's kernel with no selection (:meth:`walks` is the rule): the
      walk over every column ``<= pos`` at the configuration's score
      scale, each live page of K and V once, under ``kv_read``, and the
      rounding of its result under ``attention``; pages of 32 KiB and
      up have their block's copies issued in rounds of a few pages (the
      kernel's page-bytes rule: ``sparse_paged_attention.ROUND_BYTES``).
      On a mesh whose ``model`` axis splits the pool's KV heads the
      walk runs a shard a chip (:func:`_walk_on_mesh`). A speculative
      program's rows gather their view. A dense one-row step over an
      int8 pool whose caller knows how many pages are live gathers only
      those, dequantized, as one flat list over all slots
      (:meth:`live_pages`, :func:`_grouped_attention_flat`), in place
      of every slot's whole capacity. ``kernel="pallas"``: the
      table handed to kernels/paged_attention.py, which walks it
      in-kernel under ``shard_map`` over ``mesh`` (required for this
      kernel), all of it under ``kv_read``. Both read paths dequantize,
      so they always see the identical pool state.

    A latent configuration (``models/latent_moe.py``) keeps ONE row a
    token and nothing per head, in two arrays without a head axis,
    :func:`rope_pack` tokens side by side in a row of either: ``ks
    [layers, pages, block_size / pack, pack * kv_lora_rank]`` holds the
    normed latent ``c`` and ``vs [layers, pages, block_size / pack,
    pack * rope_dim]`` the rotated key ``kR`` (packed because a row
    that does not fill the 128 lanes -- 576 numbers, or 64 -- is laid
    out pages-minor by the runtime and costs a copy of the whole pool
    a program: PERF.md, PR 31; both alike so that a kernel reads the
    ``j``-th token of every row of both as lane-aligned slices). A
    page's rows end to end are still its tokens end to end. The layer
    loop hands both over as ``k, v`` and ``kv_write`` puts them down
    under the same page ids and offsets (:func:`_write_packed`). What
    reads them is decided by the program's kind and by nothing a user
    sets:

    * a ROW step reads in the ABSORBED form -- the query carried into
      the latent space under ``qkv``, the rows as the one shared key
      AND value of every head -- through
      ``kernels/latent_paged_attention.py``, which takes the tables,
      ``pos`` and ``active`` and walks each slot's live pages itself:
      every page once, no gathered view, no score tensor in HBM, pages
      past ``pos`` and inactive slots unread. All of the walk is under
      ``kv_read`` (the rule for a table-walking kernel); the head's
      output is brought out by ``W_UV`` under ``attention``. Compiled by
      Mosaic on a TPU, interpreted elsewhere
      (``sparse_moe._on_mesh``'s rule). The same products over a
      gathered view (``decoder._latent_attention``) are the oracle the
      tests hold it to; no serving program reaches them.
    * a CHUNK, with hundreds of query rows to spend them on, gathers
      its ONE view and expands its rows into every head's key and value
      first (57 ms a 512-row chunk on the v5e where the absorbed form
      took 135: PERF.md, PR 31).

    The flat list of live pages is not for it: a page's owner's query
    is twice the page (32 heads x 576 against 16 rows x 576), and a
    flat rung ran slower than the rectangle at any occupancy.
    """

    def __init__(self, cfg, block_size: int, max_blocks: int,
                 kernel: str = "gather", kv_quant: str = "none",
                 mesh: Optional[Mesh] = None, chunk: bool = False):
        _check_read_path(cfg, kernel, kv_quant)
        self.cfg = cfg
        self.block_size, self.max_blocks = block_size, max_blocks
        self.chunk = chunk
        self.own = None                 # set by live_pages()
        self.walk = None                # a row step's table-walking read
        self.quant = kv_quant == "int8"
        self.sparse = sparse_moe.is_sparse_moe(cfg)
        self.latent = latent_moe.is_latent_moe(cfg)
        # A stack with state-space layers: pages for the others only.
        self.hybrid = hybrid_ssm_moe.is_hybrid_ssm_moe(cfg)
        if self.latent:
            self.pack = rope_pack(cfg, block_size)
            # A row step's read (a chunk gathers its one view).
            self.walk = sparse_moe._on_mesh(functools.partial(
                latent_paged_decode, scale=cfg.qk_head_dim ** -0.5
            ), mesh)
        elif self.walks(kernel, kv_quant) and not chunk:
            # A row step's read of K and V pages, under the selection or
            # over the columns ``<= pos`` (a chunk attends over its one
            # gathered view).
            self.walk = _walk_on_mesh(functools.partial(
                sparse_paged_decode, scale=_score_scale(cfg)
            ), mesh, cfg.kv_heads)
        self.kernel = None
        if kernel == "pallas":
            self.kernel = _on_mesh(
                paged_prefill_attention if chunk
                else paged_decode_attention,
                mesh, cfg.kv_heads, 0 if chunk else 1,
                block_size=block_size, max_blocks=max_blocks,
            )

    @staticmethod
    def walks(kernel: str, kv_quant: str) -> bool:
        """Whether a row step reads by walking its slots' page tables in
        a kernel, and gathers no view: every configuration's does under
        ``kernel="gather"`` over a pool in the compute dtype (the only
        read path a latent, a sparse-selection and a state-space
        configuration have). An int8 pool's dense read gathers and
        dequantizes; ``kernel="pallas"`` walks in
        ``kernels/paged_attention.py``. The engine counts a walked read
        by the live pages and holds no flat rung for it."""
        return kernel == "gather" and kv_quant == "none"

    def on(self, ks, vs, ksc=None, vsc=None, xs=None):
        """This state over one call's arrays (a copy: the factory's
        own is traced once for every shape)."""
        pool = copy.copy(self)
        pool.ks, pool.vs, pool.ksc, pool.vsc, pool.xs = ks, vs, ksc, vsc, xs
        return pool

    def state(self):
        return self.ks, self.vs, self.ksc, self.vsc, self.xs

    def view(self, tables, *where):
        """What attention reads: the first ``max_blocks`` pages of each
        slot's table (``[slots, width]``), or of one sequence's
        (``[width]``). ``where`` is what a table-walking kernel takes
        after the table: ``pos, active`` of a row step, ``start`` of a
        chunk."""
        self.tables, self.where = tables, where
        self.view_ids = tables[..., :self.max_blocks]

    def live_pages(self, tables, pos, active, n_pages):
        """What a one-row step reads in place of :meth:`view` when its
        caller knows that the live pages of all slots fit ``n_pages``:
        a flat list of them, slot after slot. Slot ``s`` has ``pos[s]
        // block_size + 1`` live pages if ``active[s]``, else none;
        entry ``j`` is page ``j - start[s]`` of the slot ``s`` whose
        run ``[start[s], end[s])`` of the cumulative count holds ``j``
        (``own [n_pages, slots]``: one slot a row, none past the
        total, whose entries name the scratch page and are read by
        nobody). Derived once a step from what the step is handed
        anyway, under ``kv_read``; the layers gather by ``flat_ids``
        and attend under ``flat_mask [n_pages, block_size]`` (a page's
        rows at positions ``<= pos`` of its owner)."""
        bs = self.block_size
        with jax.named_scope("kv_read"):
            n_live = jnp.where(active > 0, pos // bs + 1, 0)
            end = jnp.cumsum(n_live)
            start = end - n_live
            j = jnp.arange(n_pages)[:, None]
            self.own = (j >= start) & (j < end)
            owner = jnp.argmax(self.own, axis=1)
            page = jnp.sum(jnp.where(self.own, j - start, 0), axis=1)
            owned = jnp.any(self.own, axis=1)
            # The owner's position; -1 where there is none, so that no
            # row of the entry is read.
            last = jnp.where(
                owned, jnp.sum(jnp.where(self.own, pos, 0), axis=1), -1
            )
            self.flat_ids = jnp.where(
                owned, tables[owner, page], SCRATCH_BLOCK
            )
            self.flat_mask = (
                page[:, None] * bs + jnp.arange(bs)[None, :]
                <= last[:, None]
            )

    def pages(self, blk_ids, qpos, mask):
        """What a chunk writes: its tokens at positions ``qpos`` fill
        the pages ``blk_ids``; ``mask [1, 1, 1, rows, columns]`` is
        what each may read."""
        self.blk_ids, self.mask = blk_ids, mask
        if self.sparse:
            self.icos, self.isin = self._indexer_rope(qpos)

    def rows(self, pb, off, mask, slot=None):
        """What a row step writes: page ``pb`` at offset ``off`` for
        each slot's row (``[slots]``), or for each of a slot's
        candidate rows (``[slots, n]``); ``mask [slots, 1, 1, rows,
        columns]`` is what each may read. ``slot`` is
        ``arange(slots)``, which an int8 pool's page insert indexes
        by."""
        cfg = self.cfg
        self.pb, self.off, self.mask, self.slot = pb, off, mask, slot
        self.counts = {}
        if self.quant:
            idx = jnp.arange(self.block_size)
            # Rows of the write-target page already live, broadcast
            # over the page's [kv_heads, block_size, head_dim].
            self.written = (
                idx[None, :] <= off[:, None]
            )[:, None, :, None]
        if self.sparse:
            pos, active = self.where
            icos, isin = self._indexer_rope(pos)
            self.icos, self.isin = icos[:, None, :], isin[:, None, :]
            self.valid = mask[:, 0, 0]            # [slots, 1, columns]
            self.counted = active > 0
            self.picked = []
            self.counts = {
                "selected": 0,
                "candidates": cfg.n_layers * jnp.sum(
                    jnp.where(self.counted, pos + 1, 0)
                ),
            }

    def _indexer_rope(self, positions):
        return llama2.rope_cos_sin(
            1, self.cfg.indexer_rope_dim, self.cfg.rope_theta,
            positions=positions,
        )

    def __call__(self, layer, h, lp, q, k, v):
        if self.hybrid:
            # The pool has a row for each layer that attends.
            layer = self.cfg.state_layer(layer, "attention")
        if self.latent:
            self._write_latent(layer, k, v)
            return self._read_latent(layer, lp, q)
        self._write(layer, k, v)
        mask = self.mask
        if self.sparse:
            mask = self._select(layer, h, lp)
            if not self.chunk:
                return self._read_walked(layer, q, mask)
        elif self.walk is not None and self.where:
            # A row step handed ``pos, active`` (a speculative program's
            # rows are not: they gather their view), with no selection:
            # the walk attends to the columns ``<= pos``.
            return self._read_walked(layer, q)
        return self._read(layer, q, mask)

    def _target(self, j):
        """Row ``j``'s page and offset."""
        if self.pb.ndim == 1:
            return self.pb, self.off
        return self.pb[:, j], self.off[:, j]

    def _write(self, layer, k, v):
        bs = self.block_size
        with jax.named_scope("kv_write"):
            if self.chunk:
                ids = self.blk_ids
                k_pages = tokens_to_pages(k[0], bs)
                v_pages = tokens_to_pages(v[0], bs)
            elif self.quant:
                ids = self.pb
                k_pages = dequantize_pages_int8(
                    self.ks[layer, ids], self.ksc[layer, ids]
                )
                v_pages = dequantize_pages_int8(
                    self.vs[layer, ids], self.vsc[layer, ids]
                )
                k_pages = k_pages.at[self.slot, :, self.off].set(
                    k[:, 0].astype(jnp.float32)
                )
                v_pages = v_pages.at[self.slot, :, self.off].set(
                    v[:, 0].astype(jnp.float32)
                )
                k_pages = jnp.where(self.written, k_pages, 0.0)
                v_pages = jnp.where(self.written, v_pages, 0.0)
            else:
                for j in range(k.shape[1]):
                    self.ks = write_tokens(
                        self.ks, layer, *self._target(j), k[:, j]
                    )
                    self.vs = write_tokens(
                        self.vs, layer, *self._target(j), v[:, j]
                    )
                return
            if self.quant:
                kq, k_sc = quantize_pages_int8(k_pages)
                vq, v_sc = quantize_pages_int8(v_pages)
                self.ks = self.ks.at[layer, ids].set(kq)
                self.vs = self.vs.at[layer, ids].set(vq)
                self.ksc = self.ksc.at[layer, ids].set(k_sc)
                self.vsc = self.vsc.at[layer, ids].set(v_sc)
            else:
                self.ks = self.ks.at[layer, ids].set(
                    k_pages.astype(self.ks.dtype)
                )
                self.vs = self.vs.at[layer, ids].set(
                    v_pages.astype(self.vs.dtype)
                )

    def _write_latent(self, layer, latents, k_rope):
        """``latents [b, s, rank]`` into ``ks``, ``k_rope [b, s, rope]``
        into ``vs``: a chunk's as whole pages (its tokens end to end
        ARE a page's rows end to end), a row step's one call a
        candidate row (one writer a page a call) into its place in a
        row of :func:`rope_pack` tokens."""
        with jax.named_scope("kv_write"):
            if self.chunk:
                def put(pool, rows):
                    return pool.at[layer, self.blk_ids].set(
                        rows[0].reshape(-1, *pool.shape[2:])
                        .astype(pool.dtype)
                    )

                self.ks, self.vs = put(self.ks, latents), put(self.vs, k_rope)
                return
            for j in range(latents.shape[1]):
                pb, off = self._target(j)
                self.ks = _write_packed(
                    self.ks, layer, pb, off, latents[:, j], self.pack
                )
                self.vs = _write_packed(
                    self.vs, layer, pb, off, k_rope[:, j], self.pack
                )

    def _read_latent(self, layer, lp, q):
        cfg = self.cfg
        scope = jax.named_scope
        if not self.chunk:
            # A row step: the absorbed read, by the kernel that walks
            # the tables. No view is gathered and no score leaves fast
            # memory; all of the walk under ``kv_read`` (the rule for
            # a table-walking kernel), ``W_UV`` under ``attention``.
            pack, rope = self.pack, cfg.rope_dim
            with scope("qkv"):
                q, q_rope = latent_moe.absorb(q, lp, cfg)
                # The rotary query where each of a row's tokens keeps
                # its key: lanes j * rope .. of pack * rope, else 0.
                q_rope = jnp.stack([
                    jnp.pad(q_rope[:, 0], (
                        (0, 0), (0, 0), (j * rope, (pack - 1 - j) * rope)
                    )) for j in range(pack)
                ], axis=1)
            with scope("kv_read"):
                pos, active = self.where
                u = self.walk(
                    q[:, 0], q_rope, self.ks, self.vs,
                    jnp.asarray(layer, jnp.int32), self.view_ids, pos,
                    active,
                )
            with scope("attention"):
                return latent_moe.unabsorb(u[:, None], lp, cfg)
        with scope("kv_read"):
            # [1, tokens, width]: a page's rows end to end are its
            # tokens, so unpacking them is a reshape.
            latents, k_rope = (
                pool[layer, self.view_ids].astype(cfg.dtype).reshape(
                    q.shape[0], -1, width
                ) for pool, width in (
                    (self.ks, cfg.kv_lora_rank), (self.vs, cfg.rope_dim)
                )
            )
        with scope("attention"):
            k, v = latent_moe.expand(latents, k_rope, lp, cfg)
            return _grouped_attention(
                q, k, v, self.mask, cfg, scale=cfg.qk_head_dim ** -0.5
            )

    def _select(self, layer, h, lp):
        """The layer's selection as attention's mask. ``h [b, s,
        dim]``; a chunk's ``s`` rows share one view and each keeps its
        own top ``indexer_topk`` of the columns ``<= start + q``."""
        cfg = self.cfg
        valid = self.mask[0, 0] if self.chunk else self.valid
        with jax.named_scope("indexer"):
            qi, ki, w = sparse_moe.indexer_project(
                h, lp, cfg, self.icos, self.isin
            )
            if self.chunk:
                self.xs = self.xs.at[layer, self.blk_ids].set(
                    ki[0].reshape(
                        self.blk_ids.shape[0], self.block_size, -1
                    ).astype(self.xs.dtype)
                )
            else:
                self.xs = write_tokens(
                    self.xs, layer, self.pb, self.off, ki[:, 0]
                )
            keys = self.xs[layer, self.view_ids]
            keys = keys.reshape(
                *keys.shape[:-3], -1, cfg.indexer_head_dim
            )
            if not self.chunk:              # a view a slot
                keys = keys[:, None]
            scores = sparse_moe.indexer_scores(qi, w, keys, cfg)
            chosen = sparse_moe.select_topk(
                scores, valid, cfg.indexer_topk
            )
        if self.chunk:
            return chosen[:, None, None]
        self.picked.append(chosen[:, 0])
        mask = chosen[:, None, None]
        with jax.named_scope("indexer"):
            self.counts["selected"] += jnp.sum(
                jnp.where(self.counted[:, None, None], chosen, False)
            )
        return mask

    def _read_walked(self, layer, q, mask=None):
        """A row step's read by the kernel that walks the tables, under
        the layer's selection (``mask``), or over every column ``<=
        pos`` without one: every live page of a slot once, no gathered
        view, no score in HBM. All of the walk under ``kv_read`` (the
        rule for a table-walking kernel); what is left under
        ``attention`` is the rounding of its float32 result."""
        cfg = self.cfg
        slots = q.shape[0]
        with jax.named_scope("kv_read"):
            pos, active = self.where
            out = self.walk(
                q[:, 0].astype(cfg.dtype).reshape(
                    slots, cfg.kv_heads, -1, cfg.head_dim
                ),
                self.ks, self.vs, jnp.asarray(layer, jnp.int32),
                self.view_ids, pos, active,
                None if mask is None else mask[:, 0, 0, 0],
            )
        with jax.named_scope("attention"):
            return out.astype(cfg.dtype).reshape(
                slots, 1, cfg.n_heads, cfg.head_dim
            )

    def _read(self, layer, q, mask):
        cfg, quant = self.cfg, self.quant
        b, s = q.shape[0], q.shape[1]
        if self.kernel is not None:
            with jax.named_scope("kv_read"):
                # Queries grouped by KV head: [kv_heads, rows, ...] for
                # one sequence's chunk, [slots, kv_heads, ...] for rows.
                qg = q[0] if self.chunk else q[:, 0]
                qg = qg.astype(cfg.dtype).reshape(
                    -1, cfg.kv_heads, cfg.n_heads // cfg.kv_heads,
                    cfg.head_dim,
                )
                if self.chunk:
                    qg = qg.transpose(1, 0, 2, 3)
                ctx = self.kernel(
                    qg, self.ks[layer], self.vs[layer], self.tables,
                    *self.where,
                    k_scale=self.ksc[layer] if quant else None,
                    v_scale=self.vsc[layer] if quant else None,
                )
                if self.chunk:
                    ctx = ctx.transpose(1, 0, 2, 3)
                return ctx.reshape(b, s, cfg.n_heads, cfg.head_dim)
        ids = self.view_ids if self.own is None else self.flat_ids
        with jax.named_scope("kv_read"):
            k_view = self.ks[layer, ids]
            v_view = self.vs[layer, ids]
            if quant:
                k_view = dequantize_pages_int8(
                    k_view, self.ksc[layer, ids]
                )
                v_view = dequantize_pages_int8(
                    v_view, self.vsc[layer, ids]
                )
            if self.chunk:
                # Token-major for the chunk: its view is ONE slot's
                # pages (a hundredth of the 512-row scores), and the
                # chip ran this contraction 7 % faster than the
                # page-major one at 8 KV heads (PERF.md, PR 26).
                k_view = pages_to_tokens(k_view)[None]
                v_view = pages_to_tokens(v_view)[None]
        with jax.named_scope("attention"):
            if self.own is not None:
                return _grouped_attention_flat(
                    q, k_view.astype(cfg.dtype), v_view.astype(cfg.dtype),
                    self.own, self.flat_mask, cfg,
                )
            attend = _grouped_attention if self.chunk \
                else _grouped_attention_paged
            return attend(
                q, k_view.astype(cfg.dtype), v_view.astype(cfg.dtype),
                mask, cfg,
            )


class RecurrentState:
    """The recurrent state of every program of a configuration with
    state-space layers (``models/hybrid_ssm_moe.py``), beside the page
    pool: ``ss [ssm layers, slots, heads * head_dim, state]``, the
    recurrence's ``S``, and ``sc [ssm layers, slots, (taps - 1) *
    conv_dim]``, the pre-convolution rows the next token's convolution
    comes behind, both in ``ssm_state_dtype``
    (``HybridSSMMoEConfig.state_shapes`` says why both lie flat). A
    fixed size a SLOT, not a row a
    token: no table names it, nothing of it is shared between slots,
    and what the prefix trie keeps of it is a copy at one position
    (``PrefixTrie.put_snapshot``).

    ``serve/decoder.py``'s layer loop calls it once a state-space
    layer, with the layer's projected rows, where it calls
    :class:`PagedAttention` for an attention layer; it is built, copied
    over a call's arrays (:meth:`on`) and handed back (:meth:`state`)
    the same way. A layer is, by stage name:

    * ``ssm_conv`` -- the causal convolution behind the slot's kept
      rows, which are replaced by the last ``taps - 1`` REAL rows;
    * ``ssm_scan`` -- the step size and decay, the state's read, the
      recurrence, ``y = S C + D x`` and the state's write.

    A chunk (:meth:`run`: one slot's run of rows) uses the chunked form
    and masks its bucket's padded rows out (``dt`` 0: they decay
    nothing and leave nothing), so the state it leaves is the state
    after ``true_len`` rows; it also keeps each layer's state after the
    leading ``snap_len`` rows (:meth:`snapshot`), which the engine
    hands the trie where it wants one at that position and drops
    otherwise: the state at a block boundary INSIDE a chunk, for the
    price of one more small product and with no chunk cut short for
    it. A row step (:meth:`rows`: every slot, one token) uses the
    one-step form and leaves a slot that is not ``active`` as it was,
    bit for bit: a free slot's, and one in the middle of its prompt,
    whose chunks ride between decode steps."""

    def __init__(self, cfg, chunk: bool = False):
        self.cfg, self.chunk = cfg, chunk

    def on(self, ss, sc):
        state = copy.copy(self)
        state.ss, state.sc = ss, sc
        state.snaps = []
        return state

    def state(self):
        return self.ss, self.sc

    def run(self, slot, true_len, snap_len):
        """What a chunk advances: ``slot``'s state, by the leading
        ``true_len`` of its rows."""
        self.slot, self.true_len, self.snap_len = slot, true_len, snap_len

    def rows(self, active):
        """What a row step advances: the ``active`` slots' states."""
        self.active = active > 0

    def snapshot(self):
        """Every layer's state after the chunk's leading ``snap_len``
        rows, laid out as a slot's: ``([ssm layers, heads * head_dim,
        state], [ssm layers, (taps - 1) * conv_dim])``."""
        s, conv = zip(*self.snaps)
        return jnp.stack(s), jnp.stack(conv)

    def __call__(self, layer, lp, xbc, dt):
        cfg, scope = self.cfg, jax.named_scope
        j = cfg.state_layer(layer, "mamba")
        d_skip = lp["ssm"]["D"].astype(jnp.float32)[:, None]
        heads = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
        taps = (cfg.ssm_conv - 1, cfg.conv_dim)

        def flat_s(a):      # [..., heads, head_dim, state], as kept
            return a.reshape(*a.shape[:-3], -1, a.shape[-1]).astype(
                self.ss.dtype
            )

        def flat_rows(a):   # [..., taps - 1, conv_dim], as kept
            return a.reshape(*a.shape[:-2], -1).astype(self.sc.dtype)

        if self.chunk:
            n = xbc.shape[1]
            with scope("ssm_conv"):
                conv, kept, snap_rows = hybrid_ssm_moe.conv_chunk(
                    xbc[0], self.sc[j, self.slot].reshape(taps), lp,
                    self.true_len, self.snap_len,
                )
                self.sc = self.sc.at[j, self.slot].set(flat_rows(kept))
            with scope("ssm_scan"):
                x, b, c = hybrid_ssm_moe.split_xbc(conv, cfg)
                step, a = hybrid_ssm_moe.discretise(dt[0], lp)
                step = jnp.where(
                    (jnp.arange(n) < self.true_len)[:, None], step, 0.0
                )
                y, after, snap = hybrid_ssm_moe.scan_chunk(
                    x, step, a, b, c, self.ss[j, self.slot].reshape(heads),
                    cfg.ssm_chunk, self.snap_len,
                )
                self.ss = self.ss.at[j, self.slot].set(flat_s(after))
                self.snaps.append(
                    (flat_s(snap), flat_rows(snap_rows))
                )
                return (y + d_skip * x)[None]
        slots = xbc.shape[0]
        with scope("ssm_conv"):
            conv, kept = hybrid_ssm_moe.conv_step(
                xbc[:, 0], self.sc[j].reshape(slots, *taps), lp
            )
            self.sc = self.sc.at[j].set(jnp.where(
                self.active[:, None], flat_rows(kept), self.sc[j]
            ))
        with scope("ssm_scan"):
            x, b, c = hybrid_ssm_moe.split_xbc(conv, cfg)
            step, a = hybrid_ssm_moe.discretise(dt[:, 0], lp)
            y, after = hybrid_ssm_moe.scan_step(
                x, step, a, b, c, self.ss[j].reshape(slots, *heads)
            )
            self.ss = self.ss.at[j].set(jnp.where(
                self.active[:, None, None], flat_s(after), self.ss[j]
            ))
            return (y + d_skip * x)[:, None]


def make_chunk_logits_fn(
    cfg: llama2.LlamaConfig,
    bucket: int,
    block_size: int,
    max_blocks: int,
    table_width: int,
    kernel: str = "gather",
    kv_quant: str = "none",
    mesh: Optional[Mesh] = None,
):
    """One prefill **chunk** at a padded bucket length -- the paged
    generalisation of the slab prefill program (whole-prompt prefill
    is the ``start=0`` single-chunk case). Returns the raw logits row
    (``[vocab]``) at ``true_len - 1``; :func:`make_chunk_prefill_fn`
    argmaxes it (greedy serving) and serve/spec.py's sampled prefill
    applies the seeded temperature/top-p head instead -- one layer
    loop, two token rules.

    ``(params, ks, vs, tokens [1, bucket], start, true_len,
    table [table_width])`` -> ``(ks, vs, logits)``: the chunk's
    K/V goes into the pages ``table[start/bs :]`` names, then
    attention runs over the WHOLE logical sequence view under the
    global causal mask ``key_pos <= start + q``
    (:class:`PagedAttention` has the stages, and what ``kernel`` and
    ``kv_quant`` change in them). The row at ``true_len - 1`` is
    meaningful on the final chunk only.

    The pool's arrays follow ``vs`` in the arguments and the results
    alike (:func:`_with_state`): ``ksc, vsc`` for ``kv_quant="int8"``,
    ``xs`` for a sparse-expert configuration (``models/sparse_moe.py``).
    A configuration with state-space layers
    (``models/hybrid_ssm_moe.py``) has its recurrent state's two arrays
    there too, takes ``slot, snap_len`` after ``table`` (whose state
    the chunk advances; the row count at which it also keeps a copy)
    and returns that copy's two arrays after the logits
    (:class:`RecurrentState`).

    ``table_width > max_blocks``: the trailing entries are scratch
    padding, so a bucket-padded write near the capacity edge can
    never clamp (jax dynamic_slice clamps out-of-range starts, which
    would silently misalign the scatter) nor touch a real page.
    """
    nb_chunk = bucket // block_size
    cache_cap = max_blocks * block_size
    attention = PagedAttention(
        cfg, block_size, max_blocks, kernel, kv_quant, mesh, chunk=True
    )
    recurrent = RecurrentState(cfg, chunk=True) \
        if hybrid_ssm_moe.is_hybrid_ssm_moe(cfg) else None

    def body(params, ks, vs, ksc, vsc, xs, rec, tokens, start, true_len,
             table, *where):
        scope = jax.named_scope
        pool = attention.on(ks, vs, ksc, vsc, xs)
        recur = None
        if recurrent is not None:
            recur = recurrent.on(*rec)
            recur.run(where[0], true_len, where[1])
        with scope("embed"):
            x = _embed(params, tokens, cfg)
        qpos = start + jnp.arange(bucket)
        cos, sin = _rope_tables(cfg, bucket, qpos)
        col = jnp.arange(cache_cap)
        mask = (col[None, :] <= qpos[:, None])[None, None, None, :, :]
        blk_ids = jax.lax.dynamic_slice(
            table, (start // block_size,), (nb_chunk,)
        )
        pool.view(table, start)
        pool.pages(blk_ids, qpos, mask)
        x, _ = decoder_layers(
            params, cfg, x, cos, sin, pool, recur=recur, mesh=mesh
        )
        with scope("head"):
            last = jax.lax.dynamic_slice(
                x, (0, true_len - 1, 0), (1, 1, cfg.dim)
            )
            logits = _logits_head(last, params, cfg)
        if recur is None:
            return *pool.state(), logits[0, 0]
        return *pool.state(), *recur.state(), logits[0, 0], \
            *recur.snapshot()

    return _with_state(
        body, "chunk_logits_q" if attention.quant else "chunk_logits",
        attention.quant, attention.sparse, recurrent is not None,
    )


def make_chunk_prefill_fn(
    cfg: llama2.LlamaConfig,
    bucket: int,
    block_size: int,
    max_blocks: int,
    table_width: int,
    kernel: str = "gather",
    kv_quant: str = "none",
    mesh: Optional[Mesh] = None,
):
    """The greedy chunk-prefill program: :func:`make_chunk_logits_fn`
    with the argmax token rule (meaningful on the final chunk only)."""
    inner = make_chunk_logits_fn(
        cfg, bucket, block_size, max_blocks, table_width,
        kernel=kernel, kv_quant=kv_quant, mesh=mesh,
    )

    # What follows the logits: a recurrent state's snapshot.
    after = 2 * hybrid_ssm_moe.is_hybrid_ssm_moe(cfg)

    def chunk_prefill(params, *args):
        out = inner(params, *args)
        at = len(out) - 1 - after
        with jax.named_scope("head"):
            tok = jnp.argmax(out[at], axis=-1).astype(jnp.int32)
        return (*out[:at], tok, *out[at + 1:])

    if kv_quant == "int8":
        chunk_prefill.__name__ = "chunk_prefill_q"
    return chunk_prefill


def make_paged_decode_fn(
    cfg: llama2.LlamaConfig,
    block_size: int,
    max_blocks: int,
    table_width: int,
    kernel: str = "gather",
    kv_quant: str = "none",
    mesh: Optional[Mesh] = None,
    probe: bool = False,
    flat_pages: Optional[int] = None,
):
    """The single-token decode program over every slot, block-table
    edition.

    ``(params, ks, vs, prev [slots], step [4, slots],
    tables [slots, table_width])`` -> ``(ks, vs, next_tokens)``.
    ``step``'s rows are ``STEP_ROWS``: the host's token, the position,
    the active mask and ``fresh`` for every slot, in ONE array (one
    transfer a step). ``prev`` is this program's own last result, left
    on the device: a slot with ``fresh`` 0 continues from
    ``prev[s]`` (the token the step before computed for it, which the
    host may not have fetched yet), a slot with ``fresh`` 1 (it joins
    this step: its first token came from its prompt's last chunk) from
    the host's ``step[0, s]``. So step k+1 can be dispatched before
    step k's tokens reach the host, and one step is always queued
    behind the one running.

    Each active slot's token K/V goes into page
    ``tables[s, pos/bs]`` at offset ``pos % bs``; inactive slots (free,
    or still prefilling their prompt) are redirected to the scratch
    block so their garbage write cannot corrupt a live page. Attention
    reads each slot's logical view through its table and masks columns
    ``> pos`` -- stale pages from an evicted tenant are unreachable,
    which is what makes page reuse safe (the slab engine's slot-reuse
    invariant, per page). The same mask covers a step dispatched past
    an end-of-sequence the host had not seen yet (it sees one a step
    late): that step wrote row ``pos`` of a page that was still the
    slot's own when it was dispatched (pages are reserved at admission
    for prompt + max_new, and a step in flight holds its own copy of
    the table), the device runs programs in dispatch order, so a later
    tenant's chunk lands after it, and a row past a tenant's length is
    never read.

    :class:`PagedAttention` has the stages, and what ``kernel`` and
    ``kv_quant`` change in them; the pool's arrays follow ``vs``
    (:func:`_with_state`). A sparse-expert configuration
    (``models/sparse_moe.py``) runs the SAME program with the
    indexer's selection inside attention and
    ``serve/decoder.py``'s router and experts for a feed-forward, and
    its result is ``tokens ++ counts`` in one int32 vector
    (``SPARSE_COUNTERS``' order), so the counts cost no second fetch
    (and ``prev`` is that vector: its first ``slots`` entries are read).
    A latent configuration (``models/latent_moe.py``) runs it with the
    absorbed read over its headless pool (a kernel that walks the
    tables: no view, and no mask but ``pos``) and packs its expert
    layers' counts the same way (``LATENT_COUNTERS``).
    A configuration with state-space layers
    (``models/hybrid_ssm_moe.py``) runs it with its recurrent state's
    two arrays behind the pool's (:class:`RecurrentState`): an active
    slot's state advances by its token, any other slot's stays as it
    was; its attention reads K and V through the selected read's kernel
    with no mask; its expert layers' counts ride as a latent
    configuration's.
    ``probe=True`` (such configurations only) also returns each layer's
    selection ``[layers, slots, columns]``: the benchmark's check
    reads it, no serving path does.

    A dense configuration over a pool in the compute dtype reads K and
    V through the same kernel with no mask, as one with state-space
    layers does.

    ``flat_pages`` (a dense configuration's int8 pool through
    ``gather`` only): the same program reading ``flat_pages`` pages in
    all, the live pages of every slot end to end
    (:meth:`PagedAttention.live_pages`),
    where the plain one reads ``max_blocks`` pages of every slot
    whatever is live. Same arguments, same results; the caller
    promises that the step's live pages fit (``PagedEngine.decode``
    counts them from the positions it hands over and picks the
    program by them).
    """
    cache_cap = max_blocks * block_size
    counters = step_counters(cfg)
    attention = PagedAttention(
        cfg, block_size, max_blocks, kernel, kv_quant, mesh
    )
    recurrent = RecurrentState(cfg) \
        if hybrid_ssm_moe.is_hybrid_ssm_moe(cfg) else None
    if flat_pages is not None and (
        PagedAttention.walks(kernel, kv_quant)
        or attention.kernel is not None
    ):
        raise ValueError(
            "flat_pages is the gather read of a dense configuration over "
            "an int8 pool: an indexer ranks the columns of each slot's "
            "own view, a latent page is smaller than its owner's query, "
            "and a table-walking kernel (kernel='pallas', and the row "
            "step of a dense, a sparse-selection, a latent or a "
            "state-space configuration over a pool in the compute "
            "dtype) reads no view at all"
        )

    def body(params, ks, vs, ksc, vsc, xs, rec, prev, step, tables):
        scope = jax.named_scope
        pool = attention.on(ks, vs, ksc, vsc, xs)
        host_tokens, pos, active, fresh = step
        slots = step.shape[1]
        with scope("embed"):
            tokens = jnp.where(fresh > 0, host_tokens, prev[:slots])
            x = _embed(params, tokens[:, None], cfg)
        cos, sin = _rope_tables(cfg, 1, pos)
        cos, sin = cos[:, None, :], sin[:, None, :]
        mask = None
        if flat_pages is None:
            col = jnp.arange(cache_cap)
            mask = (col[None, :] <= pos[:, None])[:, None, None, None, :]
        rows = jnp.arange(slots)
        blk = pos // block_size
        off = pos % block_size
        pb = jnp.where(
            active > 0, tables[rows, blk], SCRATCH_BLOCK
        )
        if flat_pages is None:
            pool.view(tables, pos, active)
        else:
            pool.live_pages(tables, pos, active, flat_pages)
        pool.rows(pb, off, mask, slot=rows)
        recur = None
        if recurrent is not None:
            recur = recurrent.on(*rec)
            recur.rows(active)
        x, counts = decoder_layers(
            params, cfg, x, cos, sin, pool, weight=active, recur=recur,
            mesh=mesh,
        )
        with scope("head"):
            logits = _logits_head(x, params, cfg)
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            if counters:
                # The step's counts ride behind its tokens: one array,
                # one fetch (order: step_counters).
                counts.update(pool.counts)
                tok = jnp.concatenate([tok, jnp.stack([
                    counts[key] for key, _, _ in counters
                ]).astype(jnp.int32)])
        if probe:
            # The selection each layer made, for the benchmark's
            # check against the reference: [layers, slots, columns].
            return *pool.state(), tok, jnp.stack(pool.picked)
        if recur is not None:
            return *pool.state(), *recur.state(), tok
        return *pool.state(), tok

    return _with_state(
        body, "decode_q" if attention.quant else "decode",
        attention.quant, attention.sparse, recurrent is not None,
    )


def make_copy_block_fn():
    """``(*state, src, dst)``: copy one physical page (all layers) of
    every array the pool is made of -- the device half of
    copy-on-write. Keys and values, an int8 pool's scale entries (a
    copied page that kept the source's bytes but not its scale would
    dequantize to garbage) and a sparse-expert configuration's indexer
    keys all index pages on axis 1, so one rule moves them all. (A
    slot's recurrent state is nobody else's: nothing of it is ever
    copied on a write.)"""

    def copy_block(*args):
        *state, src, dst = args
        pages = [
            jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1) for a in state
        ]
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(a, page, dst, axis=1)
            for a, page in zip(state, pages)
        )

    return copy_block


def make_restore_state_fn():
    """``(ss, sc, snap_s, snap_conv, slot)``: put one sequence's
    recurrent state (a snapshot out of the prefix trie, or zeros) into
    ``slot``'s rows of the engine's (:class:`RecurrentState`) -- the
    device half of an admission."""

    def ssm_restore(ss, sc, snap_s, snap_conv, slot):
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                a, snap[:, None].astype(a.dtype), slot, axis=1
            ) for a, snap in ((ss, snap_s), (sc, snap_conv))
        )

    return ssm_restore


# ---------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------


@dataclasses.dataclass
class _PagedSlot:
    """Host-side request state behind one batch slot."""

    prompt: List[int]
    max_new: int
    blocks: List[int]          # pages this request references, in order
    n_shared: int              # leading pages resolved from the trie
    plan: List[Tuple[int, int, int]]   # (start, run, bucket) chunks
    next_chunk: int = 0
    forwarded: int = 0         # padded tokens actually forwarded
    # Per-request sampling contract (serve/spec.py): the seeded
    # temperature/top-p head of the spec prefill program reads these.
    seed: int = 0
    temperature: float = 0.0
    top_p: float = 1.0
    # A configuration with a recurrent state: the prompt tokens whose
    # state came out of a snapshot, and where (in tokens; 0: nowhere)
    # the request's page match ran past that snapshot, so that its
    # prefill leaves one there.
    restored: int = 0
    branch: int = 0


class _LivePages:
    """The distinct pages the decoding slots read, kept where pages
    are taken and released instead of recounted each step: a slot
    reads the leading ``pos // block_size + 1`` pages of its table, a
    page several slots share (a prefix) is one page. ``len()`` is the
    count a step adds to ``serve_latent_pages_live_total``."""

    def __init__(self, block_size: int):
        self.block_size = block_size
        self.readers: Dict[int, int] = {}       # page -> slots reading it
        self.upto: Dict[int, int] = {}          # slot -> pages counted

    def __len__(self) -> int:
        return len(self.readers)

    def _add(self, page: int, n: int) -> None:
        left = self.readers.get(page, 0) + n
        if left:
            self.readers[page] = left
        else:
            del self.readers[page]

    def reach(self, slot: int, blocks: Sequence[int], pos: int) -> None:
        """``slot`` decodes at ``pos``: count the pages it has grown
        into since it was last seen (one every ``block_size`` steps)."""
        have, need = self.upto.get(slot, 0), pos // self.block_size + 1
        for page in blocks[have:need]:
            self._add(page, 1)
        self.upto[slot] = max(have, need)

    def moved(self, slot: int, index: int, old: int, new: int) -> None:
        """Copy-on-write gave ``slot`` page ``new`` for ``old``."""
        if index < self.upto.get(slot, 0):
            self._add(old, -1)
            self._add(new, 1)

    def leave(self, slot: int, blocks: Sequence[int]) -> None:
        for page in blocks[:self.upto.pop(slot, 0)]:
            self._add(page, -1)


class PagedEngine(Engine):
    """AOT prefill/decode over a paged KV pool.

    Presents the slab :class:`Engine`'s compile/warmup surface plus the
    paged protocol the scheduler drives (``is_paged`` marks it):

    * :meth:`validate_request` -- submit-time page-budget check (typed
      :class:`UnservableRequestError` for never-servable requests);
    * :meth:`admit` -- prefix-trie lookup, conservative page
      reservation for prompt + max_new (no mid-flight OOM: a request
      that admits always finishes), chunk plan; raises
      :class:`BlockBudgetError` when the pool is transiently full
      (after trying to reclaim trie-only pages). A configuration with
      a recurrent state (:class:`RecurrentState`) shares pages only as
      deep as the trie also holds that state, restores the slot's from
      the snapshot there (else zeroes it), and its prefill leaves
      snapshots behind for the next;
    * :meth:`prefill_step` -- run the next chunk; returns the first
      greedy token once the prompt is fully prefilled (and registers
      the prompt's full pages in the trie);
    * :meth:`decode` -- dispatch one token for every slot, block tables
      and the active mask riding as data, and hand back the tokens of
      the step BEFORE (``decode_lag``): the step's own stay on the
      device and feed the next; :meth:`flush` takes the last;
    * :meth:`release` -- drop the request's page references (trie
      references survive, so its prompt stays hit-able).
    """

    is_paged = True

    def __init__(
        self,
        params: Any,
        cfg: llama2.LlamaConfig,
        serve_cfg: ServeConfig,
        mesh: Mesh,
        paged: PagedConfig,
        param_pspecs: Any = None,
    ):
        bs = paged.block_size
        if serve_cfg.max_seq_len % bs:
            raise ValueError(
                f"max_seq_len {serve_cfg.max_seq_len} must be a "
                f"multiple of block_size {bs} (the logical view is a "
                "whole number of pages)"
            )
        bad = [b for b in serve_cfg.prefill_buckets if b % bs]
        if bad:
            raise ValueError(
                f"prefill buckets {bad} are not multiples of "
                f"block_size {bs} (chunk writes are page-aligned)"
            )
        if paged.prefill_chunk > max(serve_cfg.prefill_buckets):
            raise ValueError(
                f"prefill_chunk {paged.prefill_chunk} exceeds the "
                f"largest compiled bucket "
                f"{max(serve_cfg.prefill_buckets)}"
            )
        if paged.kv_quant == "int8" and serve_cfg.cache_dtype is not None:
            raise ValueError(
                "kv_quant='int8' fixes the pool storage dtype; drop "
                f"cache_dtype={serve_cfg.cache_dtype!r} (the scale "
                "side arrays are always f32)"
            )
        _check_read_path(cfg, paged.kernel, paged.kv_quant)
        if "model" in mesh.axis_names and mesh.shape["model"] > 1:
            latent_moe.refuse(
                cfg, "a serving mesh with a tensor axis",
                "the latent page has no head axis to shard and the "
                "latent projections have no tensor-parallel plan",
            )
            hybrid_ssm_moe.refuse(
                cfg, "a serving mesh with a tensor axis",
                "the recurrent state's heads, the state-space mixer's "
                "projections and the held experts have no "
                "tensor-parallel plan",
            )
        per_seq = serve_cfg.max_seq_len // bs
        # A pool SMALLER than one full-capacity sequence is legal --
        # it simply cannot serve max-length requests, and
        # validate_request() rejects those at submit with the typed
        # page-budget error (the whole point of paging is that HBM no
        # longer has to be provisioned for worst-case length).
        self.paged = paged
        # Read by the loadgen cost model and the bench metric-family
        # suffixing; mirrors paged_summary()'s kv_kernel / kv_quant.
        self.kv_kernel = paged.kernel
        self.kv_quant = paged.kv_quant
        self.max_blocks_per_seq = per_seq
        # Table rows carry extra scratch entries past capacity so a
        # bucket-padded chunk write at the capacity edge stays
        # in-range (see make_chunk_prefill_fn).
        self.table_width = per_seq + max(serve_cfg.prefill_buckets) // bs
        # What the rectangle gathers a layer, and the flat rungs below
        # it (``decode_rungs``).
        self.view_pages = serve_cfg.slots * per_seq
        self._flat_rungs: Tuple[int, ...] = ()
        self._recurrent = hybrid_ssm_moe.is_hybrid_ssm_moe(cfg)
        # Whether the decode program's row step walks the tables in a
        # kernel, reading the live pages of the active slots and no view.
        self._walks = PagedAttention.walks(paged.kernel, paged.kv_quant)
        if paged.kernel == "gather" and not self._walks:
            self._flat_rungs = tuple(sorted(
                {int(self.view_pages * r) for r in FLAT_RUNGS} - {0}
            ))
        super().__init__(params, cfg, serve_cfg, mesh, param_pspecs)

        # Speculative decoding (serve/spec.py): attach_spec sets the
        # runner + the extra program builders the executable table
        # dispatches to; None means plain greedy single-token decode.
        self.spec = None
        self._spec_builders: Dict[str, Any] = {}
        self._tier_builders: Dict[str, Any] = {}
        self.allocator = BlockAllocator(
            paged.num_blocks, host_blocks=paged.host_blocks
        )
        self.trie: Optional[PrefixTrie] = (
            self._new_trie() if paged.prefix_cache else None
        )
        # Host-DRAM page tier (serve/tier.py): parked pages spill to
        # host buffers under pool pressure and prefetch back on a
        # returning prompt. Attached AFTER the base engine exists (the
        # tier compiles its gather/scatter through THIS executable
        # table, so the zero-recompile pins cover it).
        self.host_tier = None
        if paged.host_blocks:
            from tpu_hpc.serve.tier import HostTier

            self.host_tier = HostTier(self)
        self._tables = np.full(
            (serve_cfg.slots, self.table_width), SCRATCH_BLOCK,
            np.int32,
        )
        self._tables_dev = None  # rebuilt lazily after table edits
        self._slot_state: Dict[int, _PagedSlot] = {}
        # The step in flight (decode()): the decode program's last
        # result, on the device; whether the host has yet to take it;
        # and the slots whose NEXT input token is in it and nowhere
        # else (active in that step and not released since).
        self._step_counters = step_counters(cfg)
        self._toks = self._rep_arr(np.zeros(
            serve_cfg.slots + len(self._step_counters), np.int32,
        ))
        self._unfetched = False
        self._on_device = np.zeros(serve_cfg.slots, bool)
        self.prefill_forwarded_total = 0
        # Registry gauge names are process-global: a multi-pool
        # process (the disagg tiers) must suffix them or the pools
        # overwrite each other's readings (DisaggEngine sets
        # "_prefill"/"_decode").
        self.gauge_suffix = ""
        self.paged_stats = {
            "prefix_lookups": 0, "prefix_hits": 0,
            "prefix_hit_blocks": 0, "prefill_chunks": 0,
            "cow_copies": 0, "trie_evictions": 0, "decode_steps": 0,
        }
        counters = DECODE_COUNTERS \
            + tuple(c[1:] for c in self._step_counters)
        if self._flat_rungs:
            counters += LADDER_COUNTERS
        # What a decode step's expert products read where they read
        # every held expert (None: the experts the step touched).
        self._experts_read: Optional[int] = None
        if self._step_counters:
            counters += (MOE_EXPERTS_READ,)
            if not sparse_moe.grouped_by_shape(serve_cfg.slots, cfg):
                self._experts_read = cfg.n_held * sum(
                    "moe" in params[f"layers_{i}"]
                    for i in range(cfg.n_layers)
                )
        # The distinct pages the active slots read (a latent
        # configuration, and one with a recurrent state: their
        # rooflines count a shared page once).
        self._live_pages: Optional[_LivePages] = None
        if latent_moe.is_latent_moe(cfg) or self._recurrent:
            self._live_pages = _LivePages(bs)
            self._live_pages_total = (
                KV_PAGES_LIVE if self._recurrent else LATENT_PAGES_LIVE
            )
            counters += (self._live_pages_total,)
        if self._recurrent:
            counters += SSM_COUNTERS
            for name, help_ in SSM_GAUGES:
                get_registry().describe(name, help_)
            get_registry().set_gauge(
                "serve_ssm_state_bytes", self.ssm_state_bytes
            )
        for name, help_ in counters:
            self.paged_stats[name] = 0
            get_registry().describe(name, help_)
        self._blocks_free_min = self.allocator.free_blocks
        # HELP once at construction (the ServeMeter.__init__
        # discipline); the suffix-dependent pool gauges re-describe
        # only when the suffix actually changes (disagg re-labels the
        # tiers after construction).
        self._described_suffix: Optional[str] = None
        get_registry().describe(
            "serve_prefix_hit_total",
            "Admissions whose prompt prefix was served from the trie "
            "(prefill FLOPs skipped)",
        )
        get_registry().describe(
            "serve_prefix_hit_blocks_total",
            "KV pages reused from the prefix trie",
        )
        self._set_block_gauges()

    # -- cache layout overrides ----------------------------------------
    def _cache_shape(self) -> Tuple[int, ...]:
        # A layer that attends keeps pages; a state-space layer keeps
        # a state a slot (``_init_cache``).
        layers = self.cfg.n_attention_layers if self._recurrent \
            else self.cfg.n_layers
        return (
            layers, self.paged.num_blocks,
            self.cfg.kv_heads, self.paged.block_size,
            self.cfg.head_dim,
        )

    def _new_trie(self) -> PrefixTrie:
        budget = 0
        if self._recurrent:
            budget = self.paged.ssm_snapshot_bytes
            if budget is None:
                budget = self.serve_cfg.slots * self._snapshot_nbytes
        return PrefixTrie(self.paged.block_size, snapshot_budget=budget)

    def _cache_pspec(self) -> P:
        if latent_moe.is_latent_moe(self.cfg):
            return P()      # no head axis to shard
        return paged_kv_cache_pspec(self.mesh, self.cfg.kv_heads)

    def _init_cache(self) -> None:
        """int8 pools override the slab allocation: int8 payload pages
        plus replicated f32 per-page scale side arrays
        ``[n_layers, num_blocks]`` for K and V (scales are scalars per
        page -- sharding them would turn every page write into a
        collective for 4 bytes). ``cache_bytes`` counts both, which is
        what makes the fit-report capacity claim honest."""
        self.xs = self.ssm_s = self.ssm_conv = None
        if self._recurrent:
            # The recurrent state beside the pool: ``S`` and the
            # convolution rows of every state-space layer, a slot; and
            # the state of a sequence that has read nothing, which an
            # admission with no snapshot to restore from puts there.
            cfg = self.cfg
            dtype = jnp.dtype(cfg.ssm_state_dtype)
            shapes = cfg.state_shapes(self.serve_cfg.slots)
            one = tuple(
                shape[:1] + shape[2:] for shape in cfg.state_shapes(1)
            )
            made = jax.jit(
                lambda: tuple(
                    jnp.zeros(shape, dtype) for shape in shapes + one
                ),
                out_shardings=(self._rep,) * 4,
            )()
            self.ssm_s, self.ssm_conv = made[:2]
            self._no_state = made[2:]
            self.ssm_state_bytes = cfg.state_bytes(self.serve_cfg.slots)
            self._snapshot_nbytes = cfg.state_bytes()
        if latent_moe.is_latent_moe(self.cfg):
            # One row a token and nothing per head: the latent in
            # ``ks``, its rotary key in ``vs``, ``rope_pack`` tokens
            # side by side in a row of either.
            dtype = jnp.dtype(self.serve_cfg.cache_dtype or self.cfg.dtype)
            bs = self.paged.block_size
            pack = rope_pack(self.cfg, bs)
            page = (self.cfg.n_layers, self.paged.num_blocks)
            shapes = (
                (*page, bs // pack, pack * self.cfg.kv_lora_rank),
                (*page, bs // pack, pack * self.cfg.rope_dim),
            )
            self._cache_sharding = NamedSharding(
                self.mesh, self._cache_pspec()
            )
            self.ks, self.vs = jax.jit(
                lambda: tuple(jnp.zeros(shape, dtype) for shape in shapes),
                out_shardings=(self._cache_sharding,) * 2,
            )()
            self.k_scales = self.v_scales = None
            self.cache_bytes = sum(map(math.prod, shapes)) * dtype.itemsize
            return
        if getattr(self.paged, "kv_quant", "none") != "int8":
            super()._init_cache()
            self.k_scales = self.v_scales = None
            if sparse_moe.is_sparse_moe(self.cfg):
                # The indexer's key: a third per-token array under the
                # same page ids and rows (one key head, so replicated
                # where the K/V heads are split).
                shape = (
                    self.cfg.n_layers, self.paged.num_blocks,
                    self.paged.block_size, self.cfg.indexer_head_dim,
                )
                self.xs = jax.jit(
                    lambda: jnp.zeros(shape, self.ks.dtype),
                    out_shardings=self._rep,
                )()
                self.cache_bytes += math.prod(shape) \
                    * self.ks.dtype.itemsize
            return
        shape = self._cache_shape()
        sc_shape = (self.cfg.n_layers, self.paged.num_blocks)
        self._cache_sharding = NamedSharding(
            self.mesh, self._cache_pspec()
        )
        alloc = jax.jit(
            lambda: (
                jnp.zeros(shape, jnp.int8),
                jnp.zeros(shape, jnp.int8),
                # Floor, not zero: a never-written page must
                # dequantize to exact zeros without a 0/0 hazard on
                # the requantize round trip.
                jnp.full(sc_shape, INT8_SCALE_FLOOR, jnp.float32),
                jnp.full(sc_shape, INT8_SCALE_FLOOR, jnp.float32),
            ),
            out_shardings=(
                self._cache_sharding, self._cache_sharding,
                self._rep, self._rep,
            ),
        )
        self.ks, self.vs, self.k_scales, self.v_scales = alloc()
        self.cache_bytes = (
            2 * int(np.prod(shape)) + 2 * int(np.prod(sc_shape)) * 4
        )

    def _scale_abstract(self):
        return jax.ShapeDtypeStruct(
            self.k_scales.shape, self.k_scales.dtype, sharding=self._rep
        )

    # -- the pool's arrays, in the programs' argument order ------------
    _PAGED = ("ks", "vs", "k_scales", "v_scales", "xs")
    _STATE = _PAGED + ("ssm_s", "ssm_conv")

    def _state(self, names: Tuple[str, ...] = _STATE) -> List[Any]:
        """Keys, values (a latent pool: latents, rotary keys), then an
        int8 pool's two scale arrays, then a sparse-expert
        configuration's indexer keys, then the recurrent state's two
        arrays of one with state-space layers: what every paged program
        takes after the weights, donates and returns first. ``_PAGED``
        names the arrays that are indexed by page."""
        return [
            a for a in (getattr(self, n) for n in names) if a is not None
        ]

    def _set_state(self, out) -> Any:
        """Take the pool's arrays back from a program's results;
        returns what follows them (the token)."""
        names = [n for n in self._STATE if getattr(self, n) is not None]
        for name, value in zip(names, out):
            setattr(self, name, value)
        rest = out[len(names):]
        return rest[0] if rest else None

    # -- executable table ----------------------------------------------
    def _build(self, key):
        self._count_compile()
        # Speculative programs (spec_verify / spec_draft /
        # spec_prefill) are built by the attached SpecRunner against
        # THIS engine's cache and param abstracts -- same table, same
        # counter, so the zero-recompile pins cover them too.
        if key[0] in self._spec_builders:
            return self._spec_builders[key[0]](key)
        # Host-tier programs (serve/tier.py spill gather / refill
        # scatter) build against this engine's cache abstracts --
        # same table, same counter, so the zero-recompile pins cover
        # the tier too.
        if key[0] in self._tier_builders:
            return self._tier_builders[key[0]](key)
        params_abs = self._params_abstract()
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=self._rep)
        slots = self.serve_cfg.slots
        # int8 mode threads the f32 scale side arrays through every
        # paged program, a sparse-expert configuration its indexer
        # keys: (ks, vs) grows in both args and results, all
        # engine-resident and donated.
        state_shardings = (self._cache_sharding, self._cache_sharding) \
            + (self._rep,) * (len(self._state()) - 2)
        state = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a, sharding in zip(self._state(), state_shardings)
        )
        if key[0] == "prefill":
            bucket = key[1]
            fn = make_chunk_prefill_fn(
                self.cfg, bucket, self.paged.block_size,
                self.max_blocks_per_seq, self.table_width,
                kernel=self.paged.kernel, kv_quant=self.paged.kv_quant,
                mesh=self.mesh,
            )
            tokens = jax.ShapeDtypeStruct(
                (1, bucket), jnp.int32, sharding=self._rep
            )
            table = jax.ShapeDtypeStruct(
                (self.table_width,), jnp.int32, sharding=self._rep
            )
            args = (params_abs,) + state + (tokens, scalar, scalar,
                                            table)
            if self._recurrent:
                args += (scalar, scalar)        # slot, snap_len
        elif key[0] in ("decode", "decode_probe"):
            # ("decode",) is the rectangle, ("decode", pages) a flat
            # rung: one name in the trace, so the per-scope readers
            # average over whichever ran.
            fn = make_paged_decode_fn(
                self.cfg, self.paged.block_size,
                self.max_blocks_per_seq, self.table_width,
                kernel=self.paged.kernel, kv_quant=self.paged.kv_quant,
                mesh=self.mesh, probe=key[0] == "decode_probe",
                flat_pages=key[1] if len(key) > 1 else None,
            )
            prev = jax.ShapeDtypeStruct(
                self._toks.shape, jnp.int32, sharding=self._rep
            )
            step = jax.ShapeDtypeStruct(
                (len(STEP_ROWS), slots), jnp.int32, sharding=self._rep
            )
            tables = jax.ShapeDtypeStruct(
                (slots, self.table_width), jnp.int32, sharding=self._rep
            )
            args = (params_abs,) + state + (prev, step, tables)
        elif key[0] == "ssm_restore":
            snap = tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=self._rep)
                for a in self._no_state
            )
            return jax.jit(
                make_restore_state_fn(), donate_argnums=(0, 1),
                out_shardings=(self._rep,) * 2,
            ).lower(*state[-2:], *snap, scalar).compile()
        else:  # ("copy_block",)
            fn = make_copy_block_fn()
            n_paged = len(self._state(self._PAGED))
            jitted = jax.jit(
                fn,
                donate_argnums=tuple(range(n_paged)),
                out_shardings=state_shardings[:n_paged],
            )
            return jitted.lower(*state[:n_paged], scalar, scalar).compile()
        jitted = jax.jit(
            fn,
            donate_argnums=tuple(range(1, 1 + len(state))),
            # After the state: the token(s); a probe's selection, or a
            # chunk's snapshot of the recurrent state.
            out_shardings=state_shardings + (self._rep,) * (
                2 if key[0] == "decode_probe"
                else 3 if key[0] == "prefill" and self._recurrent else 1
            ),
        )
        return jitted.lower(*args).compile()

    def warmup(self) -> int:
        if self.spec is not None:
            # Speculative steady state: the sampled prefill variant
            # per bucket, the batched verify step, CoW -- and the
            # draft side's programs. The plain greedy decode program
            # is deliberately NOT compiled (the verify step IS the
            # decode step here); a stray call would count as a
            # recompile and trip the pins, keeping the table honest.
            for b in self.serve_cfg.prefill_buckets:
                self._get_exec(("spec_prefill", b))
            self._get_exec(("spec_verify",))
            self._get_exec(("copy_block",))
            self.spec.warmup_draft()
            if self.host_tier is not None:
                self.host_tier.warmup()
            return self.compile_count_total
        for b in self.serve_cfg.prefill_buckets:
            self._get_exec(("prefill", b))
        for pages in self.decode_rungs:
            self._get_exec(("decode", pages))
        self._get_exec(("decode",))
        self._get_exec(("copy_block",))
        if self._recurrent:
            self._get_exec(("ssm_restore",))
        if self.host_tier is not None:
            self.host_tier.warmup()
        return self.compile_count

    @property
    def decode_rungs(self) -> Tuple[int, ...]:
        """The flat decode programs this engine holds below the
        rectangle, by the pages each reads (``FLAT_RUNGS`` of ``slots x
        pages a slot``), smallest first: a dense engine over an int8
        pool through ``gather`` only. None where the row step walks the
        tables in a kernel and reads no view (every pool in the compute
        dtype through ``gather``: :meth:`PagedAttention.walks`), under
        ``kernel="pallas"``, and for a speculative engine, whose step
        is the verify program."""
        return () if self.spec is not None else self._flat_rungs

    @property
    def compile_count_total(self) -> int:
        """Executable builds across the WHOLE serving unit: this
        engine plus the attached draft engine -- the number the
        recompile guards must pin (a draft-side rebuild is just as
        much a steady-state violation as a target one)."""
        n = self.compile_count
        if self.spec is not None:
            n += self.spec.draft_compile_count
        return n

    # -- page bookkeeping ----------------------------------------------
    def _set_block_gauges(self) -> None:
        free = self.allocator.free_blocks
        self._blocks_free_min = min(self._blocks_free_min, free)
        reg = get_registry()
        if self._described_suffix != self.gauge_suffix:
            self._described_suffix = self.gauge_suffix
            reg.describe(
                f"serve_kv_blocks_free{self.gauge_suffix}",
                "KV pages on the free list (trie-parked pages are "
                "reclaimable and not counted free)",
            )
            reg.describe(
                f"serve_kv_blocks_used{self.gauge_suffix}",
                "KV pages referenced by live requests or the "
                "prefix trie",
            )
        reg.set_gauge(
            f"serve_kv_blocks_free{self.gauge_suffix}", free
        )
        reg.set_gauge(
            f"serve_kv_blocks_used{self.gauge_suffix}",
            self.allocator.used_blocks,
        )
        if self._recurrent and self.trie is not None:
            # (snapshots die with evicted nodes too: read here, where
            # pages are taken and released)
            reg.set_gauge(
                "serve_ssm_snapshot_bytes", self.trie.snapshot_bytes
            )

    @property
    def block_occupancy(self) -> float:
        """Fraction of the pool held by LIVE requests. Trie-parked
        pages are deliberately excluded: they are a reclaimable cache
        (admit evicts them on demand), and counting them would drive
        the admission policy's occupancy input to permanent
        saturation as the trie warms -- shedding requests the pool
        could seat fine."""
        usable = self.paged.usable_blocks
        if not usable:
            return 0.0
        live: set = set()
        for st in self._slot_state.values():
            live.update(st.blocks)
        return len(live) / usable

    def slot_table(self, slot: int) -> np.ndarray:
        """Host copy of one slot's block-table row (disagg reads it to
        ship exactly the referenced pages)."""
        return self._tables[slot].copy()

    def slot_state(self, slot: int) -> _PagedSlot:
        return self._slot_state[slot]

    def _tables_device(self):
        if self._tables_dev is None:
            self._tables_dev = self._rep_arr(self._tables)
        return self._tables_dev

    def _write_table(self, slot: int, blocks: Sequence[int]) -> None:
        row = np.full((self.table_width,), SCRATCH_BLOCK, np.int32)
        row[:len(blocks)] = blocks
        self._tables[slot] = row
        self._tables_dev = None

    # -- the paged protocol --------------------------------------------
    def validate_request(
        self, prompt_len: int, max_new: int, rid: str = "?"
    ) -> None:
        """Submit-time discipline: reject only the truly unservable.
        With chunked prefill any prompt length up to capacity chunks
        through the compiled buckets; without it, the whole remainder
        must fit one bucket, so the slab-era bucket check remains."""
        need = self.paged.blocks_for(prompt_len + max_new)
        usable = self.paged.usable_blocks
        if need > usable:
            raise UnservableRequestError(
                f"request {rid!r}: prompt {prompt_len} + max_new "
                f"{max_new} needs {need} pages of "
                f"{self.paged.block_size} tokens, but the pool budget "
                f"is {usable} usable pages "
                f"({self.paged.num_blocks} minus scratch)"
            )
        if not self.paged.prefill_chunk:
            # Worst case (no prefix hit) the whole prompt is one chunk.
            self.serve_cfg.bucket_for(prompt_len)

    def _chunk_plan(
        self, start: int, prompt_len: int, split: int = 0
    ) -> List[Tuple[int, int, int]]:
        """``(start, run, bucket)`` chunks over ``[start,
        prompt_len)``; one of them ends at ``split`` where that lies
        inside (a block boundary: chunks still start on pages)."""
        plan = []
        pos = start
        stride = self.paged.prefill_chunk or None
        while pos < prompt_len:
            run = prompt_len - pos
            if stride is not None:
                run = min(stride, run)
            if pos < split:
                run = min(run, split - pos)
            plan.append((pos, run, self.serve_cfg.bucket_for(run)))
            pos += run
        return plan

    def admit(
        self,
        slot: int,
        prompt: Sequence[int],
        max_new: int,
        run_prefill: bool = True,
        sampling: Optional[Tuple[int, float, float]] = None,
    ) -> Dict[str, int]:
        """Reserve pages and build the chunk plan for one request.

        Conservative reservation: ``ceil((prompt + max_new) / bs)``
        pages up front (minus prefix hits), so decode can never hit an
        empty free list mid-request -- admission is the only place the
        pool says no. ``run_prefill=False`` (the disagg decode tier)
        reserves the same pages but skips the trie and the chunk plan:
        page contents arrive via the cross-tier hop.

        ``sampling`` (``(seed, temperature, top_p)``, spec engines
        only) is the request's seeded-sampling contract; the spec
        prefill program's first-token head reads it, and the attached
        draft pool mirrors the admission one-for-one.
        """
        if slot in self._slot_state:
            raise ValueError(f"slot {slot} already admitted")
        plen = len(prompt)
        need = self.paged.blocks_for(plen + max_new)
        shared: List[int] = []
        if run_prefill and self.trie is not None:
            shared = self.trie.match(prompt)
            # Keep at least one prompt token to (re-)prefill: the
            # first greedy token comes from the last prompt position's
            # logits, which a fully-cached prompt would never compute.
            while shared and len(shared) * self.paged.block_size >= plen:
                shared.pop()
        snapshot, branch = None, 0
        if self._recurrent and shared:
            # Pages are worth sharing only as deep as the trie also
            # holds the recurrent state at that very position: cut the
            # match back to the deepest snapshot on its chain, and have
            # the prefill leave one where the match ended, so that the
            # next request on this branch shares all of it.
            depth, snapshot = self.trie.deepest_snapshot(
                prompt, len(shared)
            )
            if depth < len(shared):
                branch = len(shared) * self.paged.block_size
            shared = shared[:depth]
        self.allocator.retain(shared)
        fresh_needed = need - len(shared)
        short = fresh_needed - self.allocator.free_blocks
        if short > 0 and self.host_tier is not None:
            # Spill beats evict: a parked page moved to host DRAM is a
            # cheap hop on return, an evicted page is a full
            # re-prefill. Only pages the tier could not place fall
            # through to the trie eviction below.
            short -= self.host_tier.spill_parked(short)
        if short > 0 and self.trie is not None:
            self.paged_stats["trie_evictions"] += self.trie.evict(
                self.allocator, short
            )
        try:
            fresh = self.allocator.alloc(fresh_needed)
        except BlockBudgetError:
            self.allocator.release(shared)
            raise
        start = len(shared) * self.paged.block_size
        plan = self._chunk_plan(start, plen, branch) if run_prefill else []
        seed, temperature, top_p = sampling or (0, 0.0, 1.0)
        state = _PagedSlot(
            prompt=list(int(t) for t in prompt),
            max_new=max_new,
            blocks=shared + fresh,
            n_shared=len(shared),
            plan=plan,
            seed=int(seed), temperature=float(temperature),
            top_p=float(top_p),
            restored=start if snapshot is not None else 0, branch=branch,
        )
        self._slot_state[slot] = state
        self._write_table(slot, state.blocks)
        if self._recurrent:
            self._restore_state(slot, snapshot, state.restored)
        if self.spec is not None:
            self.spec.on_admit(slot, prompt, max_new)
        bus = get_bus()
        # Ring-only page telemetry (no sink): allocation happens at
        # admission cadence, flight-recorder forensics is the right
        # volume tier (the lg_token discipline).
        bus.emit("kv_block", action="alloc", n=len(fresh), slot=slot)
        # Hit-rate stats count SEATED admissions only, and only after
        # alloc succeeded: a block-stalled request is re-queued and
        # retried every tick, and counting each retry as a lookup
        # would deflate prefix_hit_rate by stall count -- failing the
        # cache-efficiency gate on pool pressure, not trie behavior
        # (review finding).
        if run_prefill and self.trie is not None:
            self.paged_stats["prefix_lookups"] += 1
        if shared:
            self.paged_stats["prefix_hits"] += 1
            self.paged_stats["prefix_hit_blocks"] += len(shared)
            get_registry().inc("serve_prefix_hit_total")
            get_registry().inc(
                "serve_prefix_hit_blocks_total", len(shared)
            )
            bus.emit(
                "kv_block", action="prefix_hit", n=len(shared),
                slot=slot,
            )
        self._set_block_gauges()
        return {
            "shared_blocks": len(shared),
            "shared_tokens": start,
            "chunks": len(plan),
            "planned_prefill_tokens": sum(b for _, _, b in plan),
        }

    def _restore_state(
        self, slot: int, snapshot: Optional[_Snapshot], tokens: int
    ) -> None:
        """``slot``'s recurrent state for a new tenant: the snapshot
        its shared pages end at, else that of a sequence that has read
        nothing. Dispatched behind whatever step is in flight, so a
        step computed past the last tenant's end (``decode``) advanced
        a state nobody reads again."""
        with span("admit.restore"):
            self.ssm_s, self.ssm_conv = self._get_exec(("ssm_restore",))(
                self.ssm_s, self.ssm_conv,
                *(self._no_state if snapshot is None else snapshot.state),
                self._rep_arr(slot),
            )
        if snapshot is not None:
            self._count("serve_ssm_restores_total")
            self._count("serve_ssm_restored_tokens_total", tokens)

    def _leave_snapshot(self, st: _PagedSlot, tokens: int, state) -> None:
        """Hand the trie ``state``, the recurrent state of ``st``'s
        prompt after its leading ``tokens`` (a block boundary)."""
        with span("prefill.snapshot"):
            dropped = self.trie.snapshot_evictions
            if self.trie.put_snapshot(
                st.prompt, tokens // self.paged.block_size, state,
                self._snapshot_nbytes, cost=tokens - st.restored,
            ):
                self._count("serve_ssm_snapshots_total")
            self._count(
                "serve_ssm_snapshot_evictions_total",
                self.trie.snapshot_evictions - dropped,
            )
            self._set_block_gauges()

    def prefetch_prompt(self, prompt: Sequence[int]) -> int:
        """Refill host-spilled prefix pages for ``prompt`` back into
        HBM *before* the request is seated, so the host→device hop
        hides behind queueing instead of stretching TTFT. No-op (0)
        without a host tier. Returns pages refilled."""
        if self.host_tier is None:
            return 0
        return self.host_tier.prefetch(prompt)

    def admission_headroom(self, prompt: Sequence[int], max_new: int) -> bool:
        """Cheap pre-check: could ``admit()`` plausibly succeed for
        this request right now? Counts free pages, trie-matched pages,
        and parked pages reclaimable by spill or eviction. Heuristic
        only -- ``admit()``'s ``BlockBudgetError`` stays the
        authority -- but it lets the scheduler skip the prefetch hop
        for a request that is about to block-stall anyway."""
        need = self.paged.blocks_for(len(prompt) + max_new)
        matched = 0
        if self.trie is not None:
            matched = len(self.trie.match(list(int(t) for t in prompt)))
        reclaimable = 0
        if self.trie is not None:
            # Parked exclusive pages: spillable or evictable on demand.
            reclaimable = sum(
                1
                for b, c in self.allocator._ref.items()
                if c == 1 and b != SCRATCH_BLOCK
            ) - self._held_by_live_slots()
        avail = self.allocator.free_blocks + matched + max(0, reclaimable)
        return avail >= need

    def _held_by_live_slots(self) -> int:
        """Pages referenced by seated requests (refcount floor: these
        can never be spilled or evicted)."""
        live = set()
        for st in self._slot_state.values():
            live.update(st.blocks)
        return len(live)

    def planned_prefill_tokens(self, slot: int) -> int:
        return sum(b for _, _, b in self._slot_state[slot].plan)

    def prefill_step(self, slot: int) -> Optional[int]:
        """Run the next prefill chunk for ``slot``. Returns the first
        greedy token when the prompt is complete, else ``None``.
        Span-bracketed like the slab prefill (the token fetch rides
        inside, so the span measures dispatch-to-result)."""
        st = self._slot_state[slot]
        if st.next_chunk >= len(st.plan):
            raise ValueError(f"slot {slot} has no prefill pending")
        start, run, bucket = st.plan[st.next_chunk]
        bs = self.paged.block_size
        n_full = len(st.prompt) // bs
        # Where this chunk also keeps a copy of the recurrent state
        # (rows into it): at the prompt's last full block, which lies
        # in the last chunk, and at the end of a chunk that ends where
        # the page match did (``admit``).
        snap_len = 0
        if st.next_chunk == len(st.plan) - 1:
            snap_len = n_full * bs - start
        elif start + run == st.branch:
            snap_len = run
        with span("prefill", hist="serve_prefill_s", n=bucket):
            with span("prefill.prep"):
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :run] = st.prompt[start:start + run]
                args = [self.params, *self._state(),
                    self._rep_arr(padded), self._rep_arr(start),
                    self._rep_arr(run),
                    self._rep_arr(self._tables[slot]),
                ]
                if self._recurrent:
                    args += [self._rep_arr(slot), self._rep_arr(snap_len)]
                if self.spec is not None:
                    # The sampled prefill variant: same layer loop,
                    # seeded temperature/top-p first-token head (only
                    # the final chunk's token is consumed). Greedy
                    # requests (temp 0) get exactly the argmax token
                    # -- the oracle's contract.
                    exec_ = self._get_exec(("spec_prefill", bucket))
                    args += [
                        self._rep_arr(st.seed),
                        self._rep_arr(st.temperature, jnp.float32),
                        self._rep_arr(st.top_p, jnp.float32),
                    ]
                else:
                    exec_ = self._get_exec(("prefill", bucket))
            with span("prefill.dispatch"):
                out = exec_(*args)
                tok = self._set_state(out)
            if st.branch == start + run and self.trie is not None:
                self._leave_snapshot(st, st.branch, tuple(out[-2:]))
            st.next_chunk += 1
            st.forwarded += bucket
            self.prefill_forwarded_total += bucket
            self.paged_stats["prefill_chunks"] += 1
            if st.next_chunk < len(st.plan):
                return None
            with span("prefill.fetch"):
                first = int(tok)
        if self.trie is not None and n_full:
            self.trie.insert(
                st.prompt, st.blocks[:n_full], self.allocator
            )
            if self._recurrent:
                self._leave_snapshot(st, n_full * bs, tuple(out[-2:]))
        if self.spec is not None:
            self.spec.on_prefill_done(slot)
        return first

    def _cow_write_target(self, slot: int, pos: int) -> None:
        """Guard rail before a decode write: the target page must be
        exclusively ours. By construction it always is (writes start
        past the shared prefix, and the trie only references FULL
        prompt pages while decode writes land after the prompt) --
        but if a reference appeared (a test, a future sharing policy),
        copy the page first instead of corrupting the other owner."""
        st = self._slot_state[slot]
        idx = pos // self.paged.block_size
        blk = st.blocks[idx]
        if self.allocator.refcount(blk) <= 1:
            return
        new, copied = self.allocator.cow(blk)
        if copied:
            self._set_state(self._get_exec(("copy_block",))(
                *self._state(self._PAGED), self._rep_arr(blk),
                self._rep_arr(new),
            ))
            st.blocks[idx] = new
            if self._live_pages is not None:
                self._live_pages.moved(slot, idx, blk, new)
            self._write_table(slot, st.blocks)
            self.paged_stats["cow_copies"] += 1
            get_bus().emit(
                "kv_block", action="cow", block=int(new), slot=slot
            )
            self._set_block_gauges()

    @property
    def decode_lag(self) -> int:
        """Steps between a :meth:`decode` call and the call that
        returns its tokens: 1 (one step stays in flight), or 0 where
        something reads a step's tokens on the host before the next
        can go (speculative decoding drafts from them; the host tier
        stays as it was measured). The scheduler emits by it."""
        return int(self.spec is None and self.host_tier is None)

    def _step_inputs(self, tokens, positions, active):
        """What the decode program reads for a step on these
        arguments: ``(prev, step)`` (``make_paged_decode_fn``). A slot
        that was active in the step in flight and has not been
        released since reads the DEVICE's token (``prev``; the host's
        entry for it is stale by a step and ignored); every other slot
        reads the host's ``tokens[s]``: one that joins this step (its
        first token came from ``prefill_step``), and all of them when
        nothing is in flight. Changes nothing, so :meth:`decode` and
        :meth:`probe_selection` on the same arguments read the same."""
        return self._toks, self._rep_arr(np.stack([
            np.asarray(x, np.int32)
            for x in (tokens, positions, active, ~self._on_device)
        ]))

    def decode(
        self,
        tokens: Sequence[int],
        positions: Sequence[int],
        active: Optional[Sequence[bool]] = None,
    ) -> Optional[np.ndarray]:
        """Dispatch one decode step for every slot and return the
        tokens of the step BEFORE it (``decode_lag`` 1; ``None`` when
        none was in flight), so that step k+1 is queued on the device
        before the host waits for step k. The step's own tokens stay
        on the device and are the next step's input
        (:meth:`_step_inputs` says which slots read them and which
        the host's ``tokens``); :meth:`flush` takes them. With
        ``decode_lag`` 0 the step's own tokens come back at once.

        An engine whose row step walks the tables runs its one decode
        program, which reads the live pages of the active slots; one
        with a ladder runs the smallest program of it that holds them
        (:attr:`decode_rungs`; the rectangle above the last). Either
        way the live pages are counted here from ``positions`` and
        ``active`` as the program counts them: what it reads follows
        the occupancy, and ``serve_decode_view_pages_read_total`` over
        ``_total`` says how far.

        ``active[s]`` False redirects slot ``s``'s write to the scratch
        page (free slots, and slots still mid-chunked-prefill, must not
        dirty live pages). An end the host can only see in a token (end
        of sequence) is seen one step late: the slot ran one step more,
        the caller drops that token, and :meth:`release` counts it
        (``serve_decode_discarded_total``); ``make_paged_decode_fn``
        says why its write harms nobody."""
        if active is None:
            active = [True] * self.serve_cfg.slots
        with span("decode", hist="serve_decode_s"):
            with span("decode.prep"):
                for s, (is_on, pos) in enumerate(zip(active, positions)):
                    if is_on and s in self._slot_state:
                        self._cow_write_target(s, int(pos))
                        if self._live_pages is not None:
                            self._live_pages.reach(
                                s, self._slot_state[s].blocks, int(pos)
                            )
                # The smallest rung that holds the step's live pages
                # (exactly what the program will count from the same
                # positions), else the rectangle.
                live = int(np.sum(np.where(
                    np.asarray(active, bool),
                    np.asarray(positions) // self.paged.block_size + 1, 0,
                )))
                pages = next(
                    (p for p in self.decode_rungs if p >= live), None
                )
                # What the program reads a layer: a flat rung's pages,
                # a table-walking kernel's walk (exactly the live
                # pages, a shared one once a slot), else every slot's
                # whole capacity.
                if pages is not None:
                    read = pages
                else:
                    read = live if self._walks else self.view_pages
                exec_ = self._get_exec(
                    ("decode",) if pages is None else ("decode", pages)
                )
                args = (
                    *self._step_inputs(tokens, positions, active),
                    self._tables_device(),
                )
            with span("decode.dispatch"):
                before = self._toks if self._unfetched else None
                self._toks = self._set_state(
                    exec_(self.params, *self._state(), *args)
                )
                self._unfetched = True
                self._on_device = np.array(active, bool)
                if before is not None:
                    self._count("serve_decode_overlapped_total")
                self._count("serve_decode_view_pages_read_total", read)
                self._count(
                    "serve_decode_view_pages_total", self.view_pages
                )
                if self.decode_rungs:
                    self._count("serve_decode_ladder_steps_total")
                    self._count(
                        "serve_decode_rectangle_steps_total",
                        int(pages is None),
                    )
                if self._live_pages is not None:
                    self._count(
                        self._live_pages_total[0], len(self._live_pages)
                    )
                if self._recurrent:
                    self._count(
                        "serve_ssm_slot_steps_total",
                        int(self._on_device.sum()),
                    )
            if not self.decode_lag:
                return self.flush()
            return None if before is None else self._take(before)

    def flush(self) -> Optional[np.ndarray]:
        """The tokens of the step in flight (``None`` if there is
        none): after it the host's tokens are current, and the next
        step reads every slot's from the host."""
        if not self._unfetched:
            return None
        self._unfetched = False
        self._on_device[:] = False
        return self._take(self._toks)

    def decode_now(self, tokens, positions, active=None) -> np.ndarray:
        """One step, synchronously: dispatch it and take its own
        tokens (what a caller that is not the lagged tick wants)."""
        out = self.decode(tokens, positions, active)
        return self.flush() if self.decode_lag else out

    def probe_selection(
        self,
        tokens: Sequence[int],
        positions: Sequence[int],
        active: Sequence[bool],
    ) -> np.ndarray:
        """What the indexer of a sparse-expert configuration selects
        for the decode step :meth:`decode` would run on these
        arguments (its input tokens resolved by the same
        :meth:`_step_inputs`): bool ``[layers, slots, capacity]``. The
        decode program itself, compiled once more with its per-layer
        masks as a result (so: built on first use, outside any timed
        window); it writes what the step writes, and a :meth:`decode`
        on the same arguments afterwards writes the same again. No
        counts, no token: the step in flight stays as it is."""
        if self.xs is None:
            raise ValueError("probe_selection needs an indexer")
        out = self._get_exec(("decode_probe",))(
            self.params, *self._state(),
            *self._step_inputs(tokens, positions, active),
            self._tables_device(),
        )
        self._set_state(out)
        return np.asarray(out[-1])

    def _count(self, name: str, n: int = 1) -> None:
        self.paged_stats[name] += n
        get_registry().inc(name, n)

    def _take(self, toks) -> np.ndarray:
        """Fetch one step's result: its tokens, and the counts an
        expert configuration's step packs behind them
        (``step_counters``' order) into ``paged_stats`` and the
        registry, with the experts that step's products read
        (``MOE_EXPERTS_READ``)."""
        with span("decode.fetch"):
            fetched = np.asarray(toks)
        slots = self.serve_cfg.slots
        stats = self.paged_stats
        stats["decode_steps"] += 1
        for (key, name, _), value in zip(
            self._step_counters, fetched[slots:]
        ):
            if name.endswith("_total"):
                self._count(name, int(value))
            else:
                stats[name] = max(stats[name], int(value))
                get_registry().set_gauge(name, stats[name])
            if key == "experts_touched":
                self._count(
                    MOE_EXPERTS_READ[0],
                    int(value) if self._experts_read is None
                    else self._experts_read,
                )
        return fetched[:slots]

    def release(self, slot: int) -> None:
        """Drop the request's page references (the trie keeps its own,
        so the prompt stays reusable) and reset the table row."""
        st = self._slot_state.pop(slot, None)
        if st is None:
            return
        if self._on_device[slot]:
            # Released with its next token still in the step in
            # flight: that slot-step ran past the request's end.
            self._on_device[slot] = False
            self._count("serve_decode_discarded_total")
        if self._live_pages is not None:
            self._live_pages.leave(slot, st.blocks)
        freed = self.allocator.release(st.blocks)
        self._write_table(slot, [])
        get_bus().emit("kv_block", action="free", n=freed, slot=slot)
        self._set_block_gauges()
        if self.spec is not None:
            self.spec.on_release(slot)

    def reset_pool(self, force: bool = False) -> None:
        """Drop ALL cached KV state: allocator, prefix trie, block
        tables, slot bookkeeping. The weight-swap half of the fleet's
        drain-and-swap contract (serve/fleet.py): every cached page
        and trie chain encodes K/V computed under the OLD weights, so
        a hot-swapped replica must flush before serving resumes --
        and a restarted replica must flush whatever its crashed
        predecessor left admitted. The device pool buffers keep their
        (now garbage) contents; a fresh allocator plus scratch-reset
        tables make every stale row unreachable, exactly the slot-
        reuse safety argument, applied pool-wide.

        ``force=False`` (the swap path) refuses while requests are
        still admitted -- swapping under a live request would corrupt
        its stream, and the caller's drain logic is what must be
        fixed. ``force=True`` (the dead-replica restart path)
        abandons the admitted state deliberately: those requests were
        already redispatched to surviving replicas."""
        if self._slot_state and not force:
            raise RuntimeError(
                f"reset_pool on an undrained engine ({len(self._slot_state)} "
                "slot(s) still admitted); drain first, or force=True "
                "on the dead-replica restart path"
            )
        if self.spec is not None:
            raise NotImplementedError(
                "reset_pool with an attached SpecRunner: the mirrored "
                "draft pool would desync (the fleet runs plain paged "
                "engines)"
            )
        self._slot_state = {}
        if self._live_pages is not None:
            self._live_pages = _LivePages(self.paged.block_size)
        self._unfetched = False
        self._on_device[:] = False
        self.allocator = BlockAllocator(
            self.paged.num_blocks, host_blocks=self.paged.host_blocks
        )
        if self.trie is not None:
            self.trie = self._new_trie()
        if self.host_tier is not None:
            # Host pages also encode old-weight K/V: flush them too.
            self.host_tier.reset()
        self._tables[:] = SCRATCH_BLOCK
        self._tables_dev = None
        self._set_block_gauges()

    def spec_decode(self, *args, **kwargs):
        """One speculative decode step (serve/spec.py): draft k
        candidates per slot, verify all k+1 positions in one batched
        target forward. A named method (not a bare runner call) so
        the loadgen cost-model proxy can intercept and charge the
        modeled draft + verify costs on the virtual clock."""
        if self.spec is None:
            raise ValueError(
                "spec_decode on an engine with no attached SpecRunner"
            )
        return self.spec.decode(*args, **kwargs)

    def prefill(self, slot: int, prompt: Sequence[int]) -> int:
        raise NotImplementedError(
            "PagedEngine is driven through admit()/prefill_step(); "
            "the one-shot prefill surface belongs to the slab Engine"
        )

    # -- reporting ------------------------------------------------------
    def paged_summary(self) -> Dict[str, Any]:
        """The serve-summary block describing this pool: layout, hit
        rate, page headroom -- what the obs report's serving section
        and the regress gate read."""
        s = self.paged_stats
        lookups = s["prefix_lookups"]
        return {
            "kv_layout": "paged",
            "kv_kernel": self.paged.kernel,
            "kv_quant": self.paged.kv_quant,
            "kv_block_size": self.paged.block_size,
            "kv_blocks": self.paged.num_blocks,
            "kv_blocks_usable": self.paged.usable_blocks,
            "kv_blocks_free": self.allocator.free_blocks,
            "kv_blocks_free_min": self._blocks_free_min,
            "prefix_lookups": lookups,
            "prefix_hits": s["prefix_hits"],
            "prefix_hit_blocks": s["prefix_hit_blocks"],
            "prefix_hit_rate": (
                s["prefix_hits"] / lookups if lookups else 0.0
            ),
            "prefill_chunks": s["prefill_chunks"],
            "cow_copies": s["cow_copies"],
            "trie_evictions": s["trie_evictions"],
            **({
                "ssm_state_bytes": self.ssm_state_bytes,
                "ssm_snapshot_bytes": self.trie.snapshot_bytes
                if self.trie is not None else 0,
                **{name[len("serve_"):]: s[name]
                   for name, _ in SSM_COUNTERS},
            } if self._recurrent else {}),
            **(
                self.host_tier.summary()
                if self.host_tier is not None else {}
            ),
        }
