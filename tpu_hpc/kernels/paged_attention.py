"""Pallas paged-attention kernels over the serving engine's KV pool.

The paged engine (serve/paging.py) stores KV cache as a pool of
fixed-size pages addressed through per-slot block tables. Its original
programs express the table read as a data-indexed XLA gather that
materialises each slot's whole KV view in HBM before a single FLOP of
attention runs -- correct, and kept as the oracle + CPU path, but it
costs one full extra copy of the working set per decode tick. This
module is the vLLM PagedAttention insight (arXiv 2309.06180) done
natively: the block table rides into the kernel as a scalar-prefetch
operand, the BlockSpec index map resolves the page id per grid step, and
each page is streamed HBM->VMEM exactly once with no gathered
intermediate.

Two kernels, sharing the flash online-softmax core of
``kernels/attention.py`` (fp32 VMEM accumulators, MASK_VALUE masking,
``pick_block_sizes`` block selection):

  * ``paged_decode_attention`` -- one query token per slot. Grid
    (slot, kv_head, page); inactive slots and tail pages redirect to
    scratch page 0 in the index map, exactly as the gather path does,
    so the pool is never indexed out of bounds and dead programs cost
    one dummy page read.
  * ``paged_prefill_attention`` -- a chunked-prefill flash kernel that
    takes the block-table *view* directly: q-block x table-indexed
    kv-page grid, global causal mask built from the chunk ``start``
    carried as data (no per-bucket mask tensors).

**Why these two have only lost on the chip** (PERF.md, PR 27 / PR 30:
187 ms a decode tick at ``serve-decode-deepseek7b``'s shape where the
gather path takes 23). Both take ONE page a grid step: the page is a
``BlockSpec`` block, so every page of every (slot, KV head) pays a grid
step's fixed cost (~0.35 us) to bring in ``block_size x head_dim``
numbers (4 KiB at a page of 16) and multiply them against a handful of
query rows, and neither was ever tiled for the chip. A kernel that
wants to win there brings MANY pages of a slot into fast memory a step
with copies of its own and runs the online softmax over the block:
``kernels/latent_paged_attention.py`` does that for a latent
configuration's headless page (PR 36); the per-head form of it (jax's
``paged_attention``: ``pages_per_compute_block``) is what ROADMAP A3.2
still asks for.

Both kernels optionally dequantize int8 pages in-register: per-page
scales live in a small side array allocated with the pool
(``quantize_pages_int8`` below is the single write-side definition),
ride in through scalar prefetch, and multiply the page after the
int8->f32 cast -- so int8 halves pool HBM *and* halves kernel read
bytes. Quantize-on-write stays in the engine's XLA scatter; the kernels
are read-only consumers.

Page layout (the one definition; ``tokens_to_pages`` /
``pages_to_tokens`` / ``write_tokens`` below are its only spellings):
a page is ``[kv_heads, block_size, head_dim]``, heads AHEAD of rows, so
one layer's pool is ``[num_blocks, kv_heads, block_size, head_dim]``
(what a kernel here takes; the engine stacks the layers in one array
ahead of that and names layer and pages in ONE index,
``pool[layer, page_ids]`` -- a ``pool[layer]`` on its own is a copy of
a layer's pool wherever the compiler cannot fuse it away) and one
kernel block ``(1, 1, block_size, head_dim)`` spans the full last two
dims of the array -- the shape Mosaic's block rule accepts (its last
two block dims must divide by the (8, 128) tile or equal the array's).
Rows-first pages ``[block_size, kv_heads, head_dim]`` put a size-1 head
block in the second-minor dim and are refused by the TPU lowering.

The caller says whether to interpret: the serving engine compiles for
the devices of its mesh (Mosaic on TPU, real or virtual topology; the
Pallas interpreter on the simulated CPU mesh, which lowers to plain XLA
ops). Parity contract: greedy decode through these kernels is
token-exact vs the gather oracle for fp16/bf16 pools (same
online-softmax identity, fp32 accumulation); int8 mode is gated by a
bounded-divergence oracle whose tolerance is pinned from the
deterministic ``int8_logit_rmse`` probe.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hpc.kernels.attention import MASK_VALUE, pick_block_sizes

# Page 0 of the pool is the scratch page: never allocated, absorbs
# writes/reads from inactive slots and dead table entries. Must match
# serve.paging.SCRATCH_BLOCK (asserted in tests; not imported to keep
# kernels/ free of serve/ dependencies).
SCRATCH_PAGE = 0

# Per-page int8 scale floor: an all-zero page (fresh pool) would
# otherwise produce scale 0 and NaNs on dequantize-divide round trips.
INT8_SCALE_FLOOR = 1e-8


# ---------------------------------------------------------------------------
# Page layout: [kv_heads, block_size, head_dim]
# ---------------------------------------------------------------------------

def tokens_to_pages(rows: jax.Array, block_size: int) -> jax.Array:
    """``[n * block_size, kv_heads, head_dim]`` token rows -> ``[n,
    kv_heads, block_size, head_dim]`` pages."""
    t, h, d = rows.shape
    return rows.reshape(t // block_size, block_size, h, d).transpose(
        0, 2, 1, 3
    )


def pages_to_tokens(pages: jax.Array) -> jax.Array:
    """``[..., n, kv_heads, block_size, head_dim]`` pages -> the
    ``[..., n * block_size, kv_heads, head_dim]`` token view (what a
    gather over a block table reads)."""
    *lead, n, h, bs, d = pages.shape
    return jnp.swapaxes(pages, -3, -2).reshape(*lead, n * bs, h, d)


def write_tokens(
    pool: jax.Array, layer, page_ids: jax.Array, offsets: jax.Array,
    rows: jax.Array,
) -> jax.Array:
    """Put token rows ``[slots, kv_heads, head_dim]`` into the stacked
    pool ``[layers, num_blocks, kv_heads, block_size, head_dim]`` at row
    ``offsets`` of pages ``page_ids`` (both ``[slots]``) of ``layer``.

    Page-granular read-modify-write: gather the target pages, select
    the row in, scatter the pages back -- two indexed operations whose
    window is a whole page, which the TPU compiler runs on the donated
    pool in the pool's own layout. (The token-granular
    ``pool.at[layer, page_ids, :, offsets].set(rows)`` scatters the row
    dimension under a ``(kv_heads, head_dim)`` window; for that the
    compiler re-lays the WHOLE pool rows-ahead-of-heads on the way in
    and back on the way out, 6 GiB moved a step to write 32 rows.)

    ONE WRITER PER PAGE: of two entries that name the same page, one
    page write wins and the other's row is lost. The engine holds that
    rule for every live page -- a slot's write-target page is its own
    (``PagedEngine._cow_write_target`` copies a shared one first) --
    and sends every inactive slot to the scratch page, whose content
    nothing reads. A caller with several rows for one page in one step
    (the speculative verify program) writes them in successive calls.
    """
    pages = pool[layer, page_ids]
    at_row = jnp.arange(pool.shape[-2]) == offsets[:, None]
    if pool.ndim == 4:
        # A pool with no head axis (``[layers, num_blocks, block_size,
        # width]``, rows ``[slots, width]``: the sparse-expert
        # decoder's indexer keys), same rule.
        pages = jnp.where(
            at_row[:, :, None], rows.astype(pool.dtype)[:, None, :], pages
        )
        return pool.at[layer, page_ids].set(pages)
    pages = jnp.where(
        at_row[:, None, :, None],
        rows.astype(pool.dtype)[:, :, None, :],
        pages,
    )
    return pool.at[layer, page_ids].set(pages)


# ---------------------------------------------------------------------------
# Per-page int8 quantization (single write-side definition)
# ---------------------------------------------------------------------------

def page_scales_int8(pages: jax.Array) -> jax.Array:
    """Per-page symmetric int8 scale: amax over the page's
    (kv_heads, block_size, head_dim) trailing dims / 127, floored."""
    amax = jnp.max(jnp.abs(pages.astype(jnp.float32)), axis=(-3, -2, -1))
    return jnp.maximum(amax / 127.0, INT8_SCALE_FLOOR)


def quantize_pages_int8(pages: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Quantize ``[..., kv_heads, block_size, head_dim]`` pages to int8
    with one f32 scale per page. Round-half-even, clipped to +-127
    (symmetric; -128 unused so dequant is sign-symmetric)."""
    sc = page_scales_int8(pages)
    q = jnp.clip(
        jnp.round(pages.astype(jnp.float32) / sc[..., None, None, None]),
        -127.0,
        127.0,
    ).astype(jnp.int8)
    return q, sc


def dequantize_pages_int8(q: jax.Array, sc: jax.Array) -> jax.Array:
    """Inverse of ``quantize_pages_int8`` (f32 out)."""
    return q.astype(jnp.float32) * sc[..., None, None, None]


# ---------------------------------------------------------------------------
# Decode kernel: one query token per slot, block table walked in-kernel
# ---------------------------------------------------------------------------

def _decode_kernel(
    # scalar prefetch (SMEM)
    tbl_ref,   # (slots, table_width) int32 block tables
    pos_ref,   # (slots,) int32 position being written this tick
    act_ref,   # (slots,) int32 active mask
    *rest,
    block_size: int,
    n_pages: int,
    sm_scale: float,
    quant: bool,
):
    if quant:
        ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ksc_ref = vsc_ref = None
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
    s_id = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    p_pos = pos_ref[s_id]
    live = jnp.logical_and(j * block_size <= p_pos, act_ref[s_id] > 0)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]                       # (g, d)
        k = k_ref[0, 0]                       # (block_size, d)
        v = v_ref[0, 0]
        if quant:
            page = tbl_ref[s_id, j]
            k = k.astype(jnp.float32) * ksc_ref[page]
            v = v.astype(jnp.float32) * vsc_ref[page]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                          # (g, block_size)
        cols = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where(cols <= p_pos, s, MASK_VALUE)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new <= MASK_VALUE * 0.5, 0.0, m_new)
        p = jnp.where(s > MASK_VALUE * 0.5, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.exp(m_prev - m_safe)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = alpha * acc_ref[:] + pv
        m_ref[:] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,        # (slots, kv_heads, group, head_dim)
    k_pages: jax.Array,  # (num_blocks, kv_heads, block_size, head_dim)
    v_pages: jax.Array,
    tables: jax.Array,   # (slots, table_width) int32
    pos: jax.Array,      # (slots,) int32 position written this tick
    active: jax.Array,   # (slots,) int32
    *,
    block_size: int,
    max_blocks: int,
    k_scale: Optional[jax.Array] = None,  # (num_blocks,) f32 (int8 pools)
    v_scale: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token paged attention: returns (slots, kv_heads, group,
    head_dim) context in q.dtype. Each grid program (slot, kv_head, j)
    streams table[slot, j]'s page once; pages past pos and inactive
    slots redirect to SCRATCH_PAGE in the index map and are skipped by
    predication (inactive slots output zeros)."""
    slots, hkv, g, d = q.shape
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    if sm_scale is None:
        sm_scale = d ** -0.5
    scalars = [tables.astype(jnp.int32), pos.astype(jnp.int32),
               active.astype(jnp.int32)]
    if quant:
        scalars += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    def kv_map(s, h, j, tbl, pos_r, act_r, *_):
        live = jnp.logical_and(j * block_size <= pos_r[s], act_r[s] > 0)
        page = jnp.where(live, tbl[s, j], SCRATCH_PAGE)
        return page, h, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(slots, hkv, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda s, h, j, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, block_size, d), kv_map),
            pl.BlockSpec((1, 1, block_size, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda s, h, j, *_: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        block_size=block_size,
        n_pages=max_blocks,
        sm_scale=sm_scale,
        quant=quant,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, hkv, g, d), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(*scalars, q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Chunked-prefill kernel: flash over the block-table view
# ---------------------------------------------------------------------------

def _prefill_kernel(
    # scalar prefetch (SMEM)
    tbl_ref,    # (table_width,) int32: this slot's table row
    start_ref,  # (1,) int32: global position of the chunk's first token
    *rest,
    block_size: int,
    block_q: int,
    n_pages: int,
    group: int,
    sm_scale: float,
    quant: bool,
):
    if quant:
        ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ksc_ref = vsc_ref = None
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    j = pl.program_id(2)
    start = start_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal page skip: the page's first key position is past the last
    # query row of this block.
    live = j * block_size <= start + (qi + 1) * block_q - 1

    @pl.when(live)
    def _step():
        rows = block_q * group
        q = q_ref[0].reshape(rows, q_ref.shape[-1])  # (bq*g, d), row-major
        k = k_ref[0, 0]                              # (block_size, d)
        v = v_ref[0, 0]
        if quant:
            page = tbl_ref[j]
            k = k.astype(jnp.float32) * ksc_ref[page]
            v = v.astype(jnp.float32) * vsc_ref[page]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                 # (bq*g, block_size)
        # Global causal mask from data: q row r of this block sits at
        # position start + qi*block_q + r//group.
        qpos = start + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0
        ) // group
        cols = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where(cols <= qpos, s, MASK_VALUE)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new <= MASK_VALUE * 0.5, 0.0, m_new)
        p = jnp.where(s > MASK_VALUE * 0.5, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.exp(m_prev - m_safe)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = alpha * acc_ref[:] + pv
        m_ref[:] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[:] / l_safe
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def paged_prefill_attention(
    q: jax.Array,        # (kv_heads, bucket, group, head_dim)
    k_pages: jax.Array,  # (num_blocks, kv_heads, block_size, head_dim)
    v_pages: jax.Array,
    table: jax.Array,    # (table_width,) int32: one slot's table row
    start: jax.Array,    # scalar int32: chunk's first global position
    *,
    block_size: int,
    max_blocks: int,
    block_q: int = 128,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill flash attention over the block-table view:
    returns (kv_heads, bucket, group, head_dim) context in q.dtype.
    The kv grid walks table[j] for j < max_blocks (the engine's full
    view, trailing entries scratch-padded); the causal mask is global,
    from ``start`` carried as data, so one compiled program serves
    every chunk of every slot at this bucket."""
    hkv, bucket, g, d = q.shape
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    if sm_scale is None:
        sm_scale = d ** -0.5
    block_q, _ = pick_block_sizes(block_q, block_size, bucket, block_size)
    block_q = min(block_q, bucket)
    if bucket % block_q:
        block_q = bucket  # odd bucket: one q block, no padding games
    scalars = [table.astype(jnp.int32),
               jnp.asarray(start, jnp.int32).reshape(1)]
    if quant:
        scalars += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    def kv_map(h, i, j, tbl, start_r, *_):
        live = j * block_size <= start_r[0] + (i + 1) * block_q - 1
        page = jnp.where(live, tbl[j], SCRATCH_PAGE)
        return page, h, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(hkv, bucket // block_q, max_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, g, d), lambda h, i, j, *_: (h, i, 0, 0)),
            pl.BlockSpec((1, 1, block_size, d), kv_map),
            pl.BlockSpec((1, 1, block_size, d), kv_map),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, g, d), lambda h, i, j, *_: (h, i, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q * g, d), jnp.float32),
            pltpu.VMEM((block_q * g, 1), jnp.float32),
            pltpu.VMEM((block_q * g, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        block_size=block_size,
        block_q=block_q,
        n_pages=max_blocks,
        group=g,
        sm_scale=sm_scale,
        quant=quant,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, bucket, g, d), q.dtype),
        interpret=interpret,
        name="paged_prefill",
    )(*scalars, q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Gather-then-dense references (the oracle the kernels are checked against)
# ---------------------------------------------------------------------------

def _gathered_view(pages, ids, scale):
    """``pages[ids]`` as f32 token rows ``[..., T, kv_heads, d]``,
    dequantized when the pool is int8."""
    view = pages[ids]
    if scale is not None:
        view = dequantize_pages_int8(view, scale[ids])
    return pages_to_tokens(view.astype(jnp.float32))


def _dense_attention(q, k, v, mask, eq_qk, eq_pv):
    """Masked softmax attention in f32 at full matmul precision (the
    TPU default would round the f32 operands to bf16). Callers zero the
    V rows no query may see, so a dead table entry may point at any
    page, a NaN-poisoned one included."""
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum(
        eq_qk, q.astype(jnp.float32), k, precision=hi
    ) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum(eq_pv, p, v, precision=hi)


def paged_decode_reference(
    q, k_pages, v_pages, tables, pos, active, *, max_blocks,
    k_scale=None, v_scale=None,
):
    """What :func:`paged_decode_attention` must compute, the way the
    engine's ``kernel="gather"`` path does it: gather each slot's first
    ``max_blocks`` table entries into a dense view, then masked softmax
    attention. Inactive slots give zeros."""
    ids = tables[:, :max_blocks]
    k = _gathered_view(k_pages, ids, k_scale)     # (slots, T, hkv, d)
    v = _gathered_view(v_pages, ids, v_scale)
    live = jnp.arange(k.shape[1])[None, :] <= pos[:, None]
    v = jnp.where(live[:, :, None, None], v, 0.0)
    out = _dense_attention(
        q, k, v, live[:, None, None, :], "shgd,sthd->shgt",
        "shgt,sthd->shgd",
    )
    return jnp.where(
        active[:, None, None, None] > 0, out, 0.0
    ).astype(q.dtype)


def paged_prefill_reference(
    q, k_pages, v_pages, table, start, *, max_blocks,
    k_scale=None, v_scale=None,
):
    """What :func:`paged_prefill_attention` must compute: one slot's
    table row gathered dense, global causal mask from ``start``."""
    ids = table[:max_blocks]
    k = _gathered_view(k_pages, ids, k_scale)     # (T, hkv, d)
    v = _gathered_view(v_pages, ids, v_scale)
    qpos = start + jnp.arange(q.shape[1])
    causal = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    v = jnp.where(causal[-1][:, None, None], v, 0.0)
    return _dense_attention(
        q, k, v, causal[None, :, None, :], "hqgd,thd->hqgt",
        "hqgt,thd->hqgd",
    ).astype(q.dtype)


# ---------------------------------------------------------------------------
# int8 divergence probe (pins the bounded-divergence tolerance)
# ---------------------------------------------------------------------------

def int8_logit_rmse(
    *,
    head_dim: int,
    kv_heads: int,
    n_heads: Optional[int] = None,
    seq_len: int = 256,
    block_size: int = 16,
    seed: int = 0,
) -> float:
    """Deterministic measure of the int8 page representational error at
    a model's attention dims: RMSE between exact-fp decode attention
    logits (pre-softmax scores of the last query against the full
    context) and the same scores computed from per-page
    quantize->dequantize K. This is what the bounded-divergence oracle
    tolerance is pinned from -- it needs no engine, no weights, and no
    clock, so the pin is stable across machines."""
    if seq_len % block_size:
        raise ValueError("seq_len must be a multiple of block_size")
    n_heads = n_heads or kv_heads
    if n_heads % kv_heads:
        raise ValueError("n_heads must be a multiple of kv_heads")
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (n_heads, head_dim), jnp.float32)
    k = jax.random.normal(kk, (seq_len, kv_heads, head_dim), jnp.float32)
    kq8, ksc = quantize_pages_int8(tokens_to_pages(k, block_size))
    k_hat = pages_to_tokens(dequantize_pages_int8(kq8, ksc))
    g = n_heads // kv_heads
    qg = q.reshape(kv_heads, g, head_dim)
    scale = head_dim ** -0.5
    exact = jnp.einsum("hgd,shd->hgs", qg, k) * scale
    approx = jnp.einsum("hgd,shd->hgs", qg, k_hat) * scale
    return float(jnp.sqrt(jnp.mean((exact - approx) ** 2)))
