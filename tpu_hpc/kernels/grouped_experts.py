"""A grouped expert product: the experts a step's tokens chose, and no
other, each read once.

``models/sparse_moe.py::expert_ffn`` computes ``sum_e gate_e *
W2_e(silu(W1_e h) * W3_e h)`` over the experts a process holds. With a
dozen tokens a step over a hundred held experts most gates are zero,
and a product over the whole held stack reads every expert's three
matrices to multiply them by nothing. Here the step hands the kernel
the list of the experts it touched (``visit``, scalar prefetch) and how
many of its entries are real (``n_touched``); the grid walks the list,
the ``BlockSpec`` index maps name ``w1[visit[i]]``, ``w3[visit[i]]``,
``w2[visit[i]]`` as the blocks to stream, and every token row is
multiplied against each visited expert, scaled by its gate for it (zero
where the token did not choose it). The list is padded by repeating its
last entry: a repeated block index starts no new copy, and the
arithmetic of a padded step is predicated away, so a step costs the
experts it touched.

Per visited expert that is three whole matrices (contiguous in the
stack: full-speed copies) against a few rows: a weight read, tiled for
the copies and not for the matrix unit. Two experts are in fast memory
at a time (the one multiplied, the one arriving), which is more than
the compiler's default budget for a kernel, so the call states its own
(:func:`vmem_bytes`; :func:`fits` says whether a chip has that much).

The sum over experts is kept in float32 in one resident output block
and leaves the kernel unrounded; operands are the weights' dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of a bf16 tile: the token rows are padded to a multiple of it.
_ROWS = 16
# What a kernel may ask of a v5e core's 128 MiB of fast memory and
# leave the compiler room for its own.
_VMEM_CEILING = 96 << 20


def vmem_bytes(dim: int, hidden: int, itemsize: int) -> int:
    """Fast memory the call asks for: two experts of three ``dim x
    hidden`` matrices (double buffering), and room for the rows, the
    gates, the float32 sum and the products' temporaries."""
    return 2 * 3 * dim * hidden * itemsize + (8 << 20)


def fits(dim: int, hidden: int, itemsize: int) -> bool:
    """Whether two whole experts fit the chip's fast memory (a wider
    expert would want its matrices tiled; none of the served
    configurations has one)."""
    return vmem_bytes(dim, hidden, itemsize) <= _VMEM_CEILING


def _kernel(visit_ref, n_ref, x_ref, g_ref, w1_ref, w3_ref, w2_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _start():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _visit():
        x = x_ref[...]
        gate = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
        # This expert's column of the gates, [rows, 1].
        gates = g_ref[...]
        mine = jax.lax.broadcasted_iota(jnp.int32, gates.shape, 1) \
            == visit_ref[i]
        g = jnp.sum(jnp.where(mine, gates, 0.0), axis=1, keepdims=True)
        hidden = (jax.nn.silu(gate) * up * g).astype(x.dtype)
        o_ref[...] += jnp.dot(
            hidden, w2_ref[...], preferred_element_type=jnp.float32
        )


def grouped_expert_ffn(
    x: jax.Array,          # [tokens, dim], the weights' dtype
    gates: jax.Array,      # [tokens, experts] float32, 0 where not chosen
    visit: jax.Array,      # [n_visit] int32 rows of the stacks to read
    n_touched: jax.Array,  # [] int32: visit[:n_touched] are distinct
    w1: jax.Array,         # [experts, dim, hidden]
    w3: jax.Array,         # [experts, dim, hidden]
    w2: jax.Array,         # [experts, hidden, dim]
    *,
    interpret: bool = False,
) -> jax.Array:
    """``sum over i < n_touched of (silu(x W1_e) * (x W3_e) * gates[:,
    e]) W2_e`` with ``e = visit[i]`` -> float32 ``[tokens, dim]``.
    ``visit[n_touched:]`` must repeat ``visit[n_touched - 1]`` (any row
    where nothing was touched): those steps read and compute nothing."""
    tokens, dim = x.shape
    n_experts, _, hidden = w1.shape
    rows = -(-tokens // _ROWS) * _ROWS
    x = jnp.pad(x, ((0, rows - tokens), (0, 0)))
    gates = jnp.pad(gates.astype(jnp.float32), ((0, rows - tokens), (0, 0)))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, visit, n: (0, 0))

    def expert(shape):
        return pl.BlockSpec(
            (None, *shape), lambda i, visit, n: (visit[i], 0, 0)
        )

    itemsize = jnp.dtype(w1.dtype).itemsize
    n_visit = visit.shape[0]
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_visit,),
            in_specs=[
                whole((rows, dim)),
                whole((rows, n_experts)),
                expert((dim, hidden)),
                expert((dim, hidden)),
                expert((hidden, dim)),
            ],
            out_specs=whole((rows, dim)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(dim, hidden, itemsize),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * rows * dim * hidden * n_visit,
            transcendentals=rows * hidden * n_visit,
            bytes_accessed=3 * dim * hidden * itemsize * n_visit,
        ),
        interpret=interpret,
        name="grouped_experts",
    )(
        visit.astype(jnp.int32), n_touched.astype(jnp.int32).reshape(1),
        x, gates, w1, w3, w2,
    )
    return out[:tokens]
