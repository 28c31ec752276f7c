"""A selected decode step's read: the kernel walks each slot's page
table and reads every live K and V page once, under the indexer's
mask.

A sparse-selection configuration (``models/sparse_moe.py``) attends,
a layer and query row, over the ``indexer_topk`` cached tokens its
indexer chose. Through a gathered view a one-row decode step paid for
every column of every slot's capacity to read them: the gather reads
and writes both views (K and V of ``max_blocks`` pages a slot), the
scores read K's, ``sum p v`` reads V's, and a float32 score tensor over
every column goes through HBM in between.

Here the slot's row of the block table, its position and whether it is
active ride in as scalar-prefetch operands (``kernels/page_walk.py``
has the walk: the grid over slots, ``pos // block_size + 1`` live pages
in blocks of :data:`PAGES_PER_BLOCK`, one async copy a page and array
into one of two fast-memory buffers, the next block's copies in flight
while this one is scored). A K or V page is ``pool[layer, page]``,
``[kv_heads, block_size, head_dim]``: one contiguous copy. For each KV
head the block's ``pages * block_size`` keys are scored against the
head's group of query rows, float32 scores times ``scale``, the
layer's selection applied BEFORE the softmax (the block's slice of the
slot's mask row: column ``c`` of a view is page ``c // block_size``,
row ``c % block_size``, so it is contiguous), a float32 running max,
sum and attended value, the output written once at the slot's end. No
view of the pool is written, no score leaves fast memory, pages past a
slot's position and every page of an inactive slot are not read.

The selection is a subset of the columns ``<= pos`` (the indexer ranks
only those), so the mask is the one limit on what is attended; a block
none of whose columns is selected adds nothing, whatever it is the
first. Without a mask (``mask=None``: a caller with no selection) the
columns ``<= pos`` are attended.

Products in the queries' dtype with float32 accumulation, a float32
softmax over float32 scores (the gathered form rounds its scores to
the compute dtype first), the output unrounded in float32: the gathered
form's numbers in another summation order.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hpc.kernels.page_walk import Paged, page_walk

# Pages a compute block: what one buffer holds and one round of the
# online softmax scores. Swept on the v5e at serve-docqa-keye30b's
# shape (docs/guide/sparse_moe.md has the table).
PAGES_PER_BLOCK = 64


def _kernel(
    layer_ref, tables_ref, pos_ref, active_ref,       # scalar prefetch
    q_ref, *refs, block_size, width, pages, scale, masked,
):
    mask_ref = refs[0] if masked else None
    ks_ref, vs_ref, o_ref, k_buf, v_buf, sems = refs[masked:]
    s = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[s]
    n_live = jnp.where(active_ref[s] > 0, pos // block_size + 1, 0)
    kv_heads, groups, head_dim = q_ref.shape
    columns = pages * block_size               # tokens a block

    # A page is one row of a buffer, ``[kv_heads, block_size,
    # head_dim]`` as it lies in the pool.
    run = page_walk(s, n_live, tables_ref, width, pages, 1, (
        Paged(lambda page: ks_ref.at[layer, pl.ds(page, 1)], k_buf),
        Paged(lambda page: vs_ref.at[layer, pl.ds(page, 1)], v_buf),
    ), sems)

    nt = (((1,), (1,)), ((), ()))              # a @ b.T

    def score(block, buf, carry):
        """One round of the online softmax over ``block``, landed in
        buffer ``buf``: a KV head at a time, its ``groups`` query rows
        against the block's keys."""
        if masked:
            keep = mask_ref[pl.ds(block, 1), :] > 0        # [1, columns]
        else:
            keep = block * columns + jax.lax.broadcasted_iota(
                jnp.int32, (1, columns), 1
            ) <= pos
        out = []
        for h, (top, total, acc) in enumerate(carry):
            q = q_ref[h]                       # [groups, head_dim]
            # The head's rows of the block's pages, page after page:
            # the block's tokens in order.
            k = k_buf[buf, :, h].reshape(columns, head_dim).astype(q.dtype)
            v = v_buf[buf, :, h].reshape(columns, head_dim).astype(q.dtype)
            scores = jnp.where(keep, jax.lax.dot_general(
                q, k, nt, preferred_element_type=jnp.float32
            ) * scale, -jnp.inf)
            new_top = jnp.maximum(
                top, jnp.max(scores, axis=1, keepdims=True)
            )
            # Nothing selected so far: any finite number, for exp(-inf
            # - top) to be 0 and not exp(-inf + inf).
            safe = jnp.where(new_top == -jnp.inf, 0.0, new_top)
            shrink = jnp.exp(top - safe)
            p = jnp.exp(scores - safe)
            out.append((
                new_top,
                shrink * total + jnp.sum(p, axis=1, keepdims=True),
                shrink * acc + jnp.dot(
                    p.astype(q.dtype), v,
                    preferred_element_type=jnp.float32,
                ),
            ))
        return tuple(out)

    heads = run(score, lambda: tuple((
        jnp.full((groups, 1), -jnp.inf, jnp.float32),
        jnp.zeros((groups, 1), jnp.float32),
        jnp.zeros((groups, head_dim), jnp.float32),
    ) for _ in range(kv_heads)))
    for h, (_, total, acc) in enumerate(heads):
        # A slot that read nothing (inactive) comes out 0, not 0 / 0.
        o_ref[h] = acc / jnp.where(total > 0, total, 1.0)


def sparse_paged_decode(
    q: jax.Array,        # [slots, kv_heads, groups, head_dim]
    ks: jax.Array,       # [layers, pages, kv_heads, block_size, head_dim]
    vs: jax.Array,       # the same
    layer: jax.Array,    # [] int32
    tables: jax.Array,   # [slots, width] int32 page ids
    pos: jax.Array,      # [slots] int32: the step's token's position
    active: jax.Array,   # [slots] int32
    mask: Optional[jax.Array] = None,   # [slots, columns] bool
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """What every slot's query rows attend to, ``[slots, kv_heads,
    groups, head_dim]`` float32: ``softmax_t(q . k_t * scale) v_t`` a
    query head, against its KV head's keys and values, over the tokens
    ``t`` of the pages ``tables[s, :pos[s] // block_size + 1]`` of layer
    ``layer`` that ``mask[s, t]`` selects (``mask`` names only tokens
    ``t <= pos[s]``; its columns are the view's, ``columns <= width *
    block_size``), or over every ``t <= pos[s]`` without a mask; 0 for
    a slot that is not active, and for one with nothing selected."""
    pages = min(PAGES_PER_BLOCK, tables.shape[1])
    if mask is not None:
        # A row a block of the walk, whole blocks only (what a table
        # wider than the view names past it is selected by nobody).
        block = pages * ks.shape[3]
        mask = jnp.pad(mask, (
            (0, 0), (0, -mask.shape[1] % block)
        )).astype(jnp.int32).reshape(mask.shape[0], -1, block)
    return _walk(
        q, ks, vs, layer, tables, pos, active, mask, scale=scale,
        interpret=interpret, pages=pages,
    )


# One trace and one lowering of the kernel for all the layers of a
# program (it names its layer in an operand): the decode program's
# build is what ``setup_s`` pays.
@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "pages")
)
def _walk(q, ks, vs, layer, tables, pos, active, mask, *, scale,
          interpret, pages):
    slots, kv_heads, groups, head_dim = q.shape
    block_size = ks.shape[3]
    width = tables.shape[1]
    itemsize = jnp.dtype(ks.dtype).itemsize
    live = slots * width * block_size
    masked = mask is not None

    def slot(*shape):
        return pl.BlockSpec(
            (None, *shape), lambda s, *_: (s, *(0,) * len(shape))
        )

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    block = (pages, kv_heads, block_size, head_dim)   # one buffer
    buffers = 4 * pages * kv_heads * block_size * head_dim * itemsize
    return pl.pallas_call(
        functools.partial(
            _kernel, block_size=block_size, width=width, pages=pages,
            scale=scale, masked=masked,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots,),
            in_specs=[
                slot(kv_heads, groups, head_dim),
                *([slot(*mask.shape[1:])] if masked else []),
                anywhere, anywhere,
            ],
            out_specs=slot(kv_heads, groups, head_dim),
            scratch_shapes=[
                pltpu.VMEM((2, *block), ks.dtype),
                pltpu.VMEM((2, *block), vs.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + (32 << 20),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * kv_heads * groups * live * head_dim,
            transcendentals=kv_heads * groups * live,
            bytes_accessed=2 * live * kv_heads * head_dim * itemsize,
        ),
        interpret=interpret,
        name="sparse_paged_decode",
    )(
        layer.astype(jnp.int32).reshape(1),
        tables.astype(jnp.int32).reshape(-1),
        pos.astype(jnp.int32), active.astype(jnp.int32),
        q, *([mask] if masked else []), ks, vs,
    )
