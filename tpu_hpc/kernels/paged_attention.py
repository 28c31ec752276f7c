"""The serving engine's KV page format, its int8 form, and the
gather-then-dense read every table-walking kernel is held to.

The paged engine (serve/paging.py) stores KV cache as a pool of
fixed-size pages addressed through per-slot block tables. This module
is the one definition of what a page is and how it is quantized; the
kernels that read pages live beside it (``sparse_paged_attention.py``
and ``latent_paged_attention.py``, over ``page_walk.py``).

Page layout (``tokens_to_pages`` / ``pages_to_tokens`` /
``write_tokens`` below are its only spellings): a page is ``[kv_heads,
block_size, head_dim]``, heads AHEAD of rows, so one layer's pool is
``[num_blocks, kv_heads, block_size, head_dim]`` and the engine stacks
the layers in one array ahead of that and names layer and pages in ONE
index, ``pool[layer, page_ids]`` -- a ``pool[layer]`` on its own is a
copy of a layer's pool wherever the compiler cannot fuse it away. A
K or V page is then one contiguous copy for a kernel that walks the
tables.

int8 pages: one f32 scale a page, in a small side array allocated with
the pool (``quantize_pages_int8`` is the single write-side definition),
half the pool's HBM. Their read gathers and dequantizes. int8 mode is
gated by a bounded-divergence oracle whose tolerance is pinned from
the deterministic ``int8_logit_rmse`` probe.

``paged_decode_reference`` is what a one-row decode step over the pool
must compute: each slot's view gathered dense, then masked softmax
attention at full f32 precision. The walk
(``sparse_paged_attention.sparse_paged_decode`` with no mask) is
checked against it on the chip (``chip_smoke.py``).

This module once held two Pallas kernels of its own, a decode and a
chunked-prefill flash kernel, that took the block table as a
scalar-prefetch operand and ONE page a grid step. They lost every
reading on a TPU v5e: 187 ms a decode tick at
``serve-decode-deepseek7b``'s shape where the gathered read took 23-39,
and 12.2 against 8.7 ms at four slots (PERF.md). Every
page of every (slot, KV head) paid a grid step's fixed cost (~0.35 us)
to bring in ``block_size x head_dim`` numbers and multiply them
against a handful of query rows. They went, with the option that
picked them. A row step now reads through the walk, which brings many
pages of a slot into fast memory a step with copies of its own; a
chunk, a speculative step and an int8 row step gather their view.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

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
# Gather-then-dense reference (the oracle the walk is checked against)
# ---------------------------------------------------------------------------

def _gathered_view(pages, ids):
    """``pages[ids]`` as f32 token rows ``[..., T, kv_heads, d]``."""
    return pages_to_tokens(pages[ids].astype(jnp.float32))


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
):
    """What a one-row decode step over the pool must compute: gather
    each slot's first ``max_blocks`` table entries into a dense view,
    then masked softmax attention over the columns ``<= pos``. q is
    ``[slots, kv_heads, groups, head_dim]``; inactive slots give
    zeros."""
    ids = tables[:, :max_blocks]
    k = _gathered_view(k_pages, ids)     # (slots, T, hkv, d)
    v = _gathered_view(v_pages, ids)
    live = jnp.arange(k.shape[1])[None, :] <= pos[:, None]
    v = jnp.where(live[:, :, None, None], v, 0.0)
    out = _dense_attention(
        q, k, v, live[:, None, None, :], "shgd,sthd->shgt",
        "shgt,sthd->shgd",
    )
    return jnp.where(
        active[:, None, None, None] > 0, out, 0.0
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
