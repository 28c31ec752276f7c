"""Recomputation by memory budget: how a Trainer tells the model it
lowers how many of its blocks may keep their matmul outputs.

``remat=True`` on a model means "recompute what does not fit". What
fits is not something a configuration can know: the same model fills
its chips under one plan and leaves gigabytes empty under another. The
Trainer can see it (the device's limit, its placed state, the gradient
tree), the model can see what a keeping block costs (its widths, the
tokens it is handed), so the decision is made where both meet: while
the Trainer lowers its step it holds a :class:`RematBudget` open, the
model reads it as it is traced, decides, and writes the decision back.

A forward traced with no budget open (``evaluate``, a reference check,
``jax.grad`` in a test) keeps nothing: every block saves its input
alone, the program ``remat=True`` always meant.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Iterator, Optional

import jax

# The six matmul outputs a keeping block saves for its backward pass
# (models/llama2.py tags them): the rotated query and key and the
# value, the residual stream after the attention output projection,
# and the feed-forward's gate and up. Norms, the rotary tables,
# silu(gate) * up and the attention call itself are recomputed either
# way.
KEPT_PRODUCTS = (
    "proj_q", "proj_k", "proj_v", "attn_residual", "ffn_gate", "ffn_up",
)


# What a keeping block of ``models/conv_moe.py`` saves: the short
# convolution's input projection (``B``, ``C`` and ``X`` in one), the
# attention layer's three as above, the stream after the mixer, and a
# dense layer's gate and up. Nothing of an expert layer: its products
# live in a row buffer sized for the worst routing a step can produce,
# eight times the rows that carry an assignment at an even load.
CONV_MOE_PRODUCTS = (
    "conv_in", "proj_q", "proj_k", "proj_v", "attn_residual", "ffn_gate",
    "ffn_up",
)


def keep_products(names=KEPT_PRODUCTS):
    """The ``jax.checkpoint`` policy of a keeping block."""
    return jax.checkpoint_policies.save_only_these_names(*names)


# The share of a device's memory limit the budget leaves unspoken for:
# the activation model is a reckoning from shapes, the allocator
# fragments, and a step that is refused at run time (not at compile
# time) cannot fall back.
SAFETY_SHARE = 0.1


@dataclasses.dataclass
class RematBudget:
    """What one chip has left for activations, as the Trainer reckons
    it, and (after a trace) what the model made of it."""

    limit_bytes: int      # the device's own (memory_stats)
    resident_bytes: int   # what already lives there: the placed state
    grad_bytes: int       # the gradient tree the step will hold
    # Devices one (micro)batch's tokens are split over, as the forward
    # sees them: the batch sharding's extent, or 1 where the forward
    # runs inside a shard_map and is handed its own shard.
    batch_shards: int = 1
    # Extent of the mesh's ``model`` axis: under tensor parallelism and
    # the sequence-parallel constraint the kept products are split by it.
    model_shards: int = 1
    # The most blocks that may keep: lowered when a compile is refused.
    cap: Optional[int] = None
    # Written by the model as it is traced.
    n_blocks: int = 0
    blocks_kept: int = 0
    block_bytes: int = 0   # one keeping block's products, a chip
    room_bytes: int = 0    # free_bytes less full recomputation's own

    @property
    def free_bytes(self) -> int:
        """What activations of ANY kind may take."""
        return (
            self.limit_bytes - self.resident_bytes - self.grad_bytes
            - int(SAFETY_SHARE * self.limit_bytes)
        )

    @property
    def kept_bytes(self) -> int:
        return self.blocks_kept * self.block_bytes

    def decide(
        self, n_blocks: int, block_bytes: int, recompute_bytes: int
    ) -> int:
        """Called by the model: ``recompute_bytes`` is what its
        activations take a chip when every block recomputes. Blocks
        keep, in order, while the room holds one more."""
        return self.decide_each([block_bytes] * n_blocks, recompute_bytes)

    def decide_each(self, block_bytes, recompute_bytes: int) -> int:
        """:meth:`decide` for a stack whose blocks differ
        (``block_bytes``: one number a block, in order): leading blocks
        keep while the room holds the next one's products.
        ``block_bytes`` then reads the mean of those that keep."""
        self.n_blocks = len(block_bytes)
        self.room_bytes = self.free_bytes - recompute_bytes
        kept = spent = 0
        for cost in block_bytes:
            if spent + cost > self.room_bytes or kept == self.cap:
                break
            kept, spent = kept + 1, spent + cost
        self.blocks_kept = kept
        self.block_bytes = (
            spent // kept if kept else max(block_bytes, default=0)
        )
        return kept


_OPEN: contextvars.ContextVar[Optional[RematBudget]] = (
    contextvars.ContextVar("tpu_hpc_remat_budget", default=None)
)


@contextlib.contextmanager
def lowering_under(budget: Optional[RematBudget]) -> Iterator[None]:
    """Hold ``budget`` open for the models traced inside."""
    token = _OPEN.set(budget)
    try:
        yield
    finally:
        _OPEN.reset(token)


def open_budget() -> Optional[RematBudget]:
    """The budget of the Trainer lowering this trace, if any."""
    return _OPEN.get()
