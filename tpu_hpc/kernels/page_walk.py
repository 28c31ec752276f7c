"""One slot's walk of its row of the block table, inside a kernel: what
the table-walking decode reads share
(``kernels/latent_paged_attention.py``,
``kernels/sparse_paged_attention.py``).

The caller is a Pallas kernel whose grid walks the slots and whose
tables ride in as a scalar-prefetch operand. For slot ``s`` it hands
over how many of the slot's pages are live and, for each pool array it
reads, where a page comes from and where it lands in one of two
fast-memory buffers of ``pages`` pages; it gets back a function that
runs its ``score`` over the blocks of the walk, each block's copies in
flight while the block before is scored.

**What the chip asked for** (TPU v5 lite; docs/guide/latent_moe.md has
the table). What bounds such a walk is how fast its copies are ISSUED,
not the bandwidth. So the copies of a whole block are straight-line
code (a branch or a loop round between them costs more than the copy),
a whole block is waited for with one wait an array, only a slot's first
and last block go through a loop, and the loop over blocks runs two a
round so that each block's buffer is known where the kernel is
compiled: then the next block's copies are issued beside this block's
products.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class Paged(NamedTuple):
    """One pool array of a walk: ``page(p)`` is the pool's page ``p``,
    ``[rows, ...]``, and ``buffer`` its two fast-memory buffers, ``[2,
    pages * rows, ...]``."""

    page: Callable
    buffer: object


def page_walk(s, n_live, tables_ref, width, pages, rows, arrays, sems):
    """Start slot ``s``'s walk of its ``n_live`` leading pages
    (``tables_ref[s * width + ...]``) in blocks of ``pages`` pages of
    ``rows`` rows: the buffers are cleaned before the first slot, the
    first block's copies are started, and the walk's ``run(score,
    init)`` comes back. ``arrays`` is a :class:`Paged` a pool array;
    ``sems`` a DMA semaphore an array and buffer, ``[len(arrays), 2]``.

    ``run`` calls ``score(block, buf, carry) -> carry`` once a block
    in order (``init()`` makes the first carry; the last comes back),
    ``buf`` a Python int, when the block has landed in
    buffer ``buf`` of every array and the next block's copies are on
    their way; where the last block holds fewer than ``pages`` live
    pages the rest of its buffer holds an earlier block's rows (or the
    zeros of the cleaning), so ``score`` gives them a weight of zero."""
    n_blocks = pl.cdiv(n_live, pages)

    @pl.when(s == 0)
    def _clean():
        # What a buffer holds where no page landed is multiplied by a
        # probability of zero: it has to be a number.
        for array in arrays:
            array.buffer[...] = jnp.zeros_like(array.buffer)

    def copies(block, buf, i):
        page = tables_ref[s * width + block * pages + i]
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        return tuple(
            pltpu.make_async_copy(
                array.page(page), array.buffer.at[buf, at], sems.at[a, buf]
            ) for a, array in enumerate(arrays)
        )

    def in_a_loop(block, buf, what):
        """``what`` the copies of as many of ``block``'s pages as are
        live, a page a round of a loop."""
        def page(i, _):
            for copy in copies(block, buf, i):
                what(copy)

        jax.lax.fori_loop(
            0, jnp.minimum(n_live - block * pages, pages), page, None
        )

    def each_page(block, buf, what, whole=None):
        """``what`` every live page's copies of ``block``. Those of a
        WHOLE block are straight-line code, no branch and no loop
        between them (or ``whole``, if given, in their place): that is
        what lets the chip issue them beside the products of the block
        before (a branch a copy cost the v5e 0.4 ms a layer, a loop
        round of eight copies as much). A slot's LAST block, the one
        block that may hold fewer than ``pages`` live pages, takes the
        loop."""
        full = (block + 1) * pages <= n_live

        @pl.when(full)
        def _whole():
            if whole is not None:
                return whole()
            for i in range(pages):
                for copy in copies(block, buf, i):
                    what(copy)

        @pl.when(jnp.logical_not(full))
        def _last():
            in_a_loop(block, buf, what)

    def start(copy):
        copy.start()

    def finish(copy):
        copy.wait()

    def wait(block, buf):
        def whole():
            # A DMA semaphore counts bytes: one wait an array for a
            # whole buffer's worth is the wait for its ``pages`` copies.
            for a, array in enumerate(arrays):
                pltpu.make_async_copy(
                    array.buffer.at[1 - buf], array.buffer.at[buf],
                    sems.at[a, buf],
                ).wait()

        each_page(block, buf, finish, whole)

    # A slot's first block: once a slot, so the loop will do (and the
    # kernel is a third shorter to trace and lower).
    in_a_loop(0, 0, start)

    def run(score, init):
        def landed(block, buf, carry):
            """``score`` over ``block`` in buffer ``buf`` (a Python
            int: with the buffer known where the program is compiled,
            the next block's copies overlap this block's products;
            indexed by ``block % 2`` they did not, 1.45 against 1.16 ms
            a layer on the v5e)."""
            @pl.when(block + 1 < n_blocks)
            def _next():
                each_page(block + 1, 1 - buf, start)

            wait(block, buf)
            return score(block, buf, carry)

        def two_blocks(i, carry):
            carry = landed(2 * i, 0, carry)
            return jax.lax.cond(
                2 * i + 1 < n_blocks,
                lambda carry: landed(2 * i + 1, 1, carry),
                lambda carry: carry, carry,
            )

        return jax.lax.fori_loop(
            0, pl.cdiv(n_blocks, 2), two_blocks, init()
        )

    return run
