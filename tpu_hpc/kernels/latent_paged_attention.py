"""A latent decode step's read: the kernel walks each slot's page table
and reads every live latent page once.

A latent configuration (``models/latent_moe.py``) caches ONE row a
token and nothing per head: the normed latent ``c`` (``rank`` numbers)
and the rotated key ``kR`` (``rope`` numbers). A one-row decode step
reads them in the absorbed form: ``score = (q . c + qR . kR) * scale``
under every head, a float32 softmax over the slot's tokens, and the
attended latent ``u = sum p c`` a head. Through a gathered view that is
four passes over the cache (the gather reads and writes it, the scores
read it, ``sum p c`` reads it again) and a float32 score tensor over
every column of every slot.

Here the slot's row of the block table, its position and whether it is
active ride in as scalar-prefetch operands. The grid walks the slots;
for a slot the kernel walks its ``pos // block_size + 1`` live pages in
blocks of :data:`PAGES_PER_BLOCK`: it starts one async copy a page and
array (``ks[layer, page]``, ``vs[layer, page]``) into one of two
fast-memory buffers, and while the next block's copies fly it scores
the block that has landed against the slot's absorbed queries, keeps a
float32 running max, sum and attended latent (the online softmax of
``kernels/attention.py``), and writes ``u`` once at the slot's end. No
view of the pool is written, no score leaves fast memory, pages past a
slot's position and every page of an inactive slot are not read, and
the columns past ``pos`` inside the last page are masked. (The technique
is the one of jax's own ``paged_attention`` kernel: many pages a
compute block, brought in by the kernel's own copies.)

**What the chip asked for** (TPU v5 lite at 16 slots x ~29k tokens;
docs/guide/latent_moe.md has the table). A layer's walk is ~59k copies
of 16 KB and 2 KB, and what bounds it is how fast they are ISSUED, not
the bandwidth: ``kernels/page_walk.py`` (the walk itself, which a
sparse-selection configuration's read shares) says what that made of
the copies, the waits and the loop over blocks. The kernel is traced
and lowered ONCE for all the layers of a program (:func:`_walk` is
jitted and takes its layer as an operand): a decode program's build is
part of what a server's start pays.

**Tokens as they lie.** Both pool arrays hold ``pack`` tokens a row
(``paging.rope_pack``: two, at a rotary key of 64 numbers and the
chip's 128 lanes), the latents as ``[rows, pack * rank]`` and the
rotary keys as ``[rows, pack * rope]``: row ``r`` of a page is tokens
``pack * r .. pack * r + pack - 1`` side by side. A softmax does not
care in what order its columns come, so the kernel never puts them in
token order: stream ``j`` (the ``j``-th token of every row) reads its
latents as the lane-aligned slice ``[:, j * rank:(j + 1) * rank]`` of
the block, and its rotary scores as the product of the whole packed
rows with the query's rotary part zero-padded to ``pack * rope`` lanes
at offset ``j * rope`` (``q_rope`` arrives so padded, ``pack`` times).
No row is split, shuffled or unpacked on the chip.

Products in the queries' dtype with float32 accumulation, a float32
softmax, ``u`` unrounded in float32: the gathered form's numbers in
another summation order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hpc.kernels.page_walk import Paged, page_walk

# Pages a compute block: what one buffer holds and one round of the
# online softmax scores. Swept on the v5e at serve-docqa-joyai-flash's
# shape (docs/guide/latent_moe.md has the table).
PAGES_PER_BLOCK = 64


def _kernel(
    layer_ref, tables_ref, pos_ref, active_ref,       # scalar prefetch
    q_ref, qr_ref, ks_ref, vs_ref, o_ref,
    c_buf, r_buf, sems,
    *, block_size, pack, rank, width, pages, scale,
):
    s = pl.program_id(0)
    rows = block_size // pack                  # pool rows a page
    layer = layer_ref[0]
    pos = pos_ref[s]
    n_live = jnp.where(active_ref[s] > 0, pos // block_size + 1, 0)

    run = page_walk(s, n_live, tables_ref, width, pages, rows, (
        Paged(lambda page: ks_ref.at[layer, page], c_buf),
        Paged(lambda page: vs_ref.at[layer, page], r_buf),
    ), sems)

    q = q_ref[...]                             # [heads, rank]
    heads = q.shape[0]
    nt = (((1,), (1,)), ((), ()))              # a @ b.T

    def score(block, buf, carry):
        """One round of the online softmax over ``block``, landed in
        buffer ``buf``."""
        top, total, acc = carry
        # [pages * rows, pack * rank] and [.., pack * rope]
        c, r = c_buf[buf].astype(q.dtype), r_buf[buf].astype(q.dtype)
        # Row i of the block is tokens first + pack * i + j.
        first = block * pages * block_size
        token = first + pack * jax.lax.broadcasted_iota(
            jnp.int32, (heads, pages * rows), 1
        )
        # Stream j: the j-th token of every row.
        latents = [c[:, j * rank:(j + 1) * rank] for j in range(pack)]
        scores = []
        for j, c_j in enumerate(latents):
            s_j = jax.lax.dot_general(
                q, c_j, nt, preferred_element_type=jnp.float32
            ) + jax.lax.dot_general(
                qr_ref[j], r, nt, preferred_element_type=jnp.float32
            )
            scores.append(
                jnp.where(token + j <= pos, s_j * scale, -jnp.inf)
            )
        new_top = functools.reduce(jnp.maximum, [
            top, *(jnp.max(s_j, axis=1, keepdims=True) for s_j in scores)
        ])
        keep = jnp.exp(top - new_top)
        total, acc = keep * total, keep * acc
        for s_j, c_j in zip(scores, latents):
            p_j = jnp.exp(s_j - new_top)
            total += jnp.sum(p_j, axis=1, keepdims=True)
            acc += jnp.dot(
                p_j.astype(q.dtype), c_j,
                preferred_element_type=jnp.float32,
            )
        return new_top, total, acc

    _, total, acc = run(score, lambda: (
        jnp.full((heads, 1), -jnp.inf, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, rank), jnp.float32),
    ))
    # A slot that read nothing (inactive) comes out 0, not 0 / 0.
    o_ref[...] = acc / jnp.where(total > 0, total, 1.0)


def latent_paged_decode(
    q: jax.Array,        # [slots, heads, rank], the compute dtype
    q_rope: jax.Array,   # [slots, pack, heads, pack * rope]
    ks: jax.Array,       # [layers, pages, block_size / pack, pack * rank]
    vs: jax.Array,       # [layers, pages, block_size / pack, pack * rope]
    layer: jax.Array,    # [] int32
    tables: jax.Array,   # [slots, width] int32 page ids
    pos: jax.Array,      # [slots] int32: the step's token's position
    active: jax.Array,   # [slots] int32
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """The attended latent of every slot and head, ``u [slots, heads,
    rank]`` float32: ``softmax_t((q . c_t + qR . kR_t) * scale) c_t``
    over the tokens ``t <= pos[s]`` of the pages ``tables[s, :pos[s] //
    block_size + 1]`` of layer ``layer``; 0 for a slot that is not
    active. ``q_rope[s, j]`` is the slot's rotary query in lanes ``j *
    rope .. (j + 1) * rope`` and zero elsewhere."""
    return _walk(
        q, q_rope, ks, vs, layer, tables, pos, active, scale=scale,
        interpret=interpret, pages=min(PAGES_PER_BLOCK, tables.shape[1]),
    )


# One trace and one lowering of the kernel for all the layers of a
# program (it names its layer in an operand): the decode program's
# build is what ``setup_s`` pays.
@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "pages")
)
def _walk(q, q_rope, ks, vs, layer, tables, pos, active, *, scale,
          interpret, pages):
    slots, heads, rank = q.shape
    pack = q_rope.shape[1]
    rows = ks.shape[2]
    block_size = rows * pack
    width = tables.shape[1]
    itemsize = jnp.dtype(ks.dtype).itemsize
    live = slots * width * block_size

    def slot(*shape):
        return pl.BlockSpec(
            (None, *shape), lambda s, *_: (s, *(0,) * len(shape))
        )

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    buffers = 2 * pages * rows * (ks.shape[3] + vs.shape[3]) * itemsize
    return pl.pallas_call(
        functools.partial(
            _kernel, block_size=block_size, pack=pack, rank=rank,
            width=width, pages=pages, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots,),
            in_specs=[
                slot(heads, rank), slot(pack, heads, q_rope.shape[3]),
                anywhere, anywhere,
            ],
            out_specs=slot(heads, rank),
            scratch_shapes=[
                pltpu.VMEM((2, pages * rows, ks.shape[3]), ks.dtype),
                pltpu.VMEM((2, pages * rows, vs.shape[3]), vs.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + (32 << 20),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * live * (2 * rank + q_rope.shape[3]),
            transcendentals=heads * live,
            bytes_accessed=live * (rank + vs.shape[3] // pack) * itemsize,
        ),
        interpret=interpret,
        name="latent_paged_decode",
    )(
        layer.astype(jnp.int32).reshape(1),
        tables.astype(jnp.int32).reshape(-1),
        pos.astype(jnp.int32), active.astype(jnp.int32),
        q, q_rope, ks, vs,
    )
