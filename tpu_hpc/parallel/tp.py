"""Tensor parallelism: the Megatron column/row plan as PartitionSpecs.

Parity: scripts/03_tensor_parallel_tp (Colwise->Rowwise MLP pairing,
02_basic_tensor_parallel.py:64-71; ViT plan tensor_parallel_vit.py:
352-361) and the Llama block plan in scripts/06_hybrid_parallelism/
01_fsdp_tp_hybrid.py:110-152: wq/wk/wv/w1/w3 Colwise, wo/w2 Rowwise,
tok_embeddings Rowwise, output Colwise, norms SequenceParallel.

TPU-native: "Colwise" = shard the kernel's output-features dim on the
``model`` mesh axis; "Rowwise" = shard the input-features dim. XLA's
SPMD partitioner then places exactly one all-reduce (or
reduce-scatter under SP) per attention/FFN block -- the same comm
pattern DTensor produces, but fused into the jitted step and free to
overlap with compute. Megatron-SP is an *activation* layout (sequence
dim sharded on ``model`` between blocks), expressed here as a
with_sharding_constraint hook threaded through the model
(models/llama2.py ``constrain``) instead of DTensor Shard(1) plans.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_hpc.parallel.plans import Rule, pspec_tree


def llama_rules(axis: str = "model") -> List[Rule]:
    """Megatron TP plan for the Llama param tree (parity:
    01_fsdp_tp_hybrid.py:110-152, expressed as path-regex rules)."""
    return [
        # Rowwise embedding: vocab dim sharded; each shard owns a vocab
        # slice, XLA masks+psums the gather (reference tok_embeddings
        # Rowwise, :113-117).
        (r"tok_embeddings/embedding$", P(axis, None)),
        # Colwise attention inputs: heads shard across TP.
        (r"attention/w[qkv]/kernel$", P(None, axis)),
        # Rowwise attention output: input-features sharded, psum after.
        (r"attention/wo/kernel$", P(axis, None)),
        # SwiGLU: w1/w3 Colwise, w2 Rowwise (reference :144-150).
        (r"feed_forward/w[13]/kernel$", P(None, axis)),
        (r"feed_forward/w2/kernel$", P(axis, None)),
        # LM head Colwise (reference output plan :118-122).
        (r"^output/kernel$", P(None, axis)),
        # Norm scales replicated (SP shards their *activations*).
        (r"norm/scale$", P()),
    ]


def mlp_rules(axis: str = "model") -> List[Rule]:
    """Generic Colwise->Rowwise pairing for a 2-layer MLP stack:
    odd layers shard outputs, even layers shard inputs (parity:
    02_basic_tensor_parallel.py:64-71)."""
    return [
        # (^|/) anchors on a path-component boundary so e.g. a layer
        # named 'main' is not claimed by the 'in' rule.
        (r"(^|/)(up|fc1|in)/kernel$", P(None, axis)),
        (r"(^|/)(down|fc2|out)/kernel$", P(axis, None)),
    ]


def vit_rules(axis: str = "model") -> List[Rule]:
    """ViT block plan (parity: tensor_parallel_vit.py:352-361): q/k/v +
    fc1 Colwise, out_proj + fc2 Rowwise, patch embed + norms
    replicated."""
    return [
        (r"(^|/)[qkv]_proj/kernel$", P(None, axis)),
        (r"(^|/)out_proj/kernel$", P(axis, None)),
        (r"(^|/)fc1/kernel$", P(None, axis)),
        (r"(^|/)fc2/kernel$", P(axis, None)),
    ]


def param_pspecs(params: Any, rules: Sequence[Rule]) -> Any:
    """Rule list -> full PartitionSpec tree (unmatched leaves
    replicated)."""
    return pspec_tree(params, rules, default=P())


def sp_constrain(
    mesh: Mesh,
    dp_axis: Optional[str] = "data",
    sp_axis: str = "model",
) -> Callable[[jax.Array], jax.Array]:
    """Megatron-SP activation hook: pin [B, S, D] residual-stream
    activations to (dp, sp, None) -- sequence dim sharded on the TP
    axis between blocks. XLA turns the TP all-reduces into
    reduce-scatter + all-gather pairs around each block, cutting
    activation memory by the TP degree (parity: SequenceParallel norms
    + Shard(1) layouts, 01_fsdp_tp_hybrid.py:126-152).
    """
    spec = NamedSharding(mesh, P(dp_axis, sp_axis, None))

    def constrain(x: jax.Array) -> jax.Array:
        if x.ndim == 3:
            # The scope names the constraint's own resharding in a
            # trace (docs/guide/observability.md, "Stage names").
            with jax.named_scope("sp_constrain"):
                return jax.lax.with_sharding_constraint(x, spec)
        return x

    return constrain


def auto_tp_degree(
    n_devices: int, n_heads: int, kv_heads: int, cap: Optional[int] = None
) -> int:
    """Largest valid TP degree: divides the device count and both head
    counts (the constraint validate_tp_degree enforces), optionally
    capped (the reference caps TP at the 4-GPU node size,
    tensor_parallel_vit.py:273). Returns 1 when nothing fits -- callers
    then fall back to pure DP, the reference's world_size==1 pattern."""
    limit = min(n_devices, cap or n_devices)
    return max(
        d
        for d in range(1, limit + 1)
        if n_devices % d == 0 and n_heads % d == 0 and kv_heads % d == 0
    )


def auto_mesh_axes(
    n_devices: int, n_heads: int, kv_heads: int, cap: Optional[int] = 4
) -> "dict[str, int]":
    """The standard auto-split mesh shape: TP (capped, head-divisible)
    on ``model``, remaining chips on ``data``. One helper so the bench
    headline and the serving engine can never drift onto different
    policies while claiming the same split."""
    tp = (
        auto_tp_degree(n_devices, n_heads, kv_heads, cap=cap)
        if n_devices > 1 else 1
    )
    axes = {"data": n_devices // tp}
    if tp > 1:
        axes["model"] = tp
    return axes


def validate_tp_degree(
    n_heads: int, kv_heads: int, tp: int
) -> None:
    """Head-divisibility guard (parity: the reference's head-sharding
    constraint, tensor_parallel_vit.py:107-123 and the TP-degree rule
    docs/guide/06_tensor_parallel.md:79-101)."""
    if n_heads % tp != 0:
        raise ValueError(f"n_heads={n_heads} not divisible by tp={tp}")
    if kv_heads % tp != 0:
        raise ValueError(
            f"n_kv_heads={kv_heads} not divisible by tp={tp}; "
            "GQA requires kv_heads % tp == 0"
        )


def make_tp_flash_attn_fn(
    mesh: Mesh,
    dp_axis: Optional[str] = "data",
    tp_axis: Optional[str] = "model",
    *,
    causal: bool = True,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
    block_q_bwd: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    wrap: bool = True,
) -> Callable[[jax.Array, jax.Array, jax.Array], jax.Array]:
    """The Pallas flash kernel under tensor parallelism: heads shard
    over ``tp_axis``, batch over ``dp_axis``, full sequence per shard.

    XLA has no SPMD partitioning rule for a Pallas call, so inside a
    GSPMD-partitioned step the kernel must run under ``shard_map`` --
    each shard does full-sequence attention for its own heads (the
    head-parallel split of Megatron TP; parity: the reference's
    per-head SDPA sharding, tensor_parallel_vit.py:107-123). GQA is
    handled in-kernel (no KV repeat), so kv_heads only need to divide
    ``tp_axis`` -- validate with :func:`validate_tp_degree`.

    ``wrap=False`` returns the bare batch-local closure without the
    ``shard_map`` wrapper -- for callers whose whole forward already
    runs inside one ``shard_map`` over the same mesh (the manual
    comm-mode step, the PP stages), where nesting a second manual
    sharding would fail to trace. One factory either way, so every
    caller measures the same kernel configuration.

    The production attention path for hybrid FSDPxTP training: the
    XLA einsum attention materialises per-layer [B,H,S,S] score
    blocks that dominate HBM temps at seq 4096+ (a 70B/128-core
    topology compile overflows a 15.25 GiB core by ~0.6 GiB on
    scores alone); the flash kernel's online softmax removes them.
    """
    from tpu_hpc.kernels.attention import blockwise_attention

    def flash(q, k, v):
        out, _ = blockwise_attention(
            q, k, v, causal=causal, impl=impl,
            block_q=block_q, block_k=block_k,
            block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
        )
        return out

    if not wrap or mesh.size == 1:
        return flash
    tp_size = mesh.shape.get(tp_axis, 1) if tp_axis else 1
    spec = P(
        dp_axis if dp_axis and mesh.shape.get(dp_axis, 1) > 1 else None,
        None,
        tp_axis if tp_size > 1 else None,
        None,
    )
    return jax.shard_map(
        flash, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    )
