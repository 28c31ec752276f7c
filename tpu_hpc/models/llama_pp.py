"""Llama-2 through the pipeline engine: stage-split the flagship model.

Parity: the reference's pipeline example trains its own dedicated
model (scripts/04_pipeline_parallel_pp/03_pipeline_training.py:198-252,
stage cuts at named attribute boundaries :92-103). Here the flagship
Llama-2 itself runs under ``tpu_hpc.parallel.pp``: its transformer
blocks are homogeneous (the depth-scaled init of llama2.py affects
parameter VALUES, never the applied program), so ``n_layers/S``
consecutive blocks form one shape-preserving stage function and the
whole body pipelines as a single SPMD tick program.

Layout. ``split_params`` regroups ``init_llama``'s param tree into

- ``edges``: tok_embeddings + final norm + output head -- replicated
  over the pipe axis and applied OUTSIDE the pipelined body (a
  rounding error of the FLOPs; keeping the body homogeneous is what
  makes it one program, pp.py module docstring), and
- ``stages``: a [S, ...] stacked tree (stage s = layers
  ``s*lps .. s*lps+lps-1``) to be sharded ``P("pipe")`` so each device
  holds exactly its stage's weights.

``merge_params`` is the exact inverse, so the sequential oracle for
every pipelined run is ``llama2.apply_llama`` itself on the SAME
values -- the correctness anchor tests/test_pp_llama.py pins.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpu_hpc.models.llama2 import (
    AttnFn,
    LlamaConfig,
    RMSNorm,
    TransformerBlock,
    _make_embed_lookup,
)
from tpu_hpc.parallel import pp

EDGE_KEYS = ("tok_embeddings", "norm", "output")


def layers_per_stage(cfg: LlamaConfig, n_stages: int) -> int:
    from tpu_hpc.models import hybrid_ssm_moe, latent_moe, sparse_moe

    for model in (sparse_moe, latent_moe, hybrid_ssm_moe):
        model.refuse(
            cfg, "the pipeline split (models/llama_pp.py)",
            "its stages are llama2's dense blocks",
        )
    if n_stages < 1 or cfg.n_layers % n_stages:
        raise ValueError(
            f"pipeline needs n_layers {cfg.n_layers} divisible by "
            f"the stage count {n_stages}"
        )
    return cfg.n_layers // n_stages


def split_params(params: Dict, cfg: LlamaConfig, n_stages: int) -> Dict:
    """init_llama tree -> {"edges": {...}, "stages": [S, ...] stacked}.

    Stage s's subtree is {"layer_j": <params of layers_{s*lps+j}>}, so
    the stage function applies its layers in global order.
    """
    lps = layers_per_stage(cfg, n_stages)
    edges = {k: params[k] for k in EDGE_KEYS}
    per_stage = [
        {
            f"layer_{j}": params[f"layers_{s * lps + j}"]
            for j in range(lps)
        }
        for s in range(n_stages)
    ]
    return {"edges": edges, "stages": pp.stack_stage_params(per_stage)}


def merge_params(split: Dict, cfg: LlamaConfig) -> Dict:
    """Exact inverse of :func:`split_params` -- the tree
    ``llama2.apply_llama`` (the sequential oracle) consumes."""
    stages = split["stages"]
    S = jax.tree.leaves(stages)[0].shape[0]
    lps = layers_per_stage(cfg, S)
    out = dict(split["edges"])
    for s in range(S):
        stage = jax.tree.map(lambda a: a[s], stages)
        for j in range(lps):
            out[f"layers_{s * lps + j}"] = stage[f"layer_{j}"]
    return out


def split_params_interleaved(
    params: Dict, cfg: LlamaConfig, n_devices: int, n_chunks: int
) -> Dict:
    """Like :func:`split_params` but in the Megatron virtual-pipeline
    layout for the interleaved schedules: ``n_devices * n_chunks``
    global stages of ``n_layers/(S*v)`` layers each, stacked so device
    s holds chunks {s, S+s, 2S+s, ...} (pp.stack_interleaved_stage_
    params' round-robin placement). Pair with
    ``make_forward(schedule="interleaved"/"interleaved-1f1b",
    n_chunks=v)``."""
    split = split_params(params, cfg, n_devices * n_chunks)
    return {
        "edges": split["edges"],
        "stages": pp.interleave_stacked(split["stages"], n_devices),
    }


def merge_params_interleaved(
    split: Dict, cfg: LlamaConfig, n_devices: int, n_chunks: int
) -> Dict:
    """Exact inverse of :func:`split_params_interleaved` -- undo the
    round-robin placement, then the sequential split."""
    import numpy as np

    S, V = n_devices, n_chunks
    order = [j * S + s for s in range(S) for j in range(V)]
    inv = np.argsort(order)
    stages = jax.tree.map(lambda a: a[inv], split["stages"])
    return merge_params(
        {"edges": split["edges"], "stages": stages}, cfg
    )


def make_stage_fn(
    cfg: LlamaConfig,
    n_stages: int,
    attn_fn: AttnFn = None,
    positions: Optional[jax.Array] = None,
):
    """stage_fn(stage_params, x) for ``pp.pipelined``: applies this
    stage's ``n_layers/S`` TransformerBlocks in order. [B, L, D] ->
    [B, L, D] (shape-preserving, as the tick programs require).

    ``layer_id=0`` is deliberate: the block's layer_id only selects
    the depth-scaled INIT std (llama2.py TransformerBlock docstring);
    the applied computation is identical for every layer, which is
    exactly the homogeneity the single-program pipeline needs. The
    per-layer values arrive through ``stage_params``.
    """
    lps = layers_per_stage(cfg, n_stages)
    block = TransformerBlock(cfg, 0, attn_fn=attn_fn)

    def stage_fn(stage_params, x):
        for j in range(lps):
            x = block.apply(
                {"params": stage_params[f"layer_{j}"]}, x, positions
            )
        return x

    return stage_fn


def embed(edges: Dict, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """[.., L] int tokens -> [.., L, D] in cfg.dtype -- the same
    gather-forward / matmul-backward lookup as Llama.__call__ (the
    scatter-free embedding gradient, llama2.LlamaConfig.iota_embed)."""
    table = edges["tok_embeddings"]["embedding"]
    if cfg.iota_embed:
        lookup = _make_embed_lookup(
            cfg.vocab_size, jnp.dtype(cfg.dtype).name
        )
        return lookup(table.astype(cfg.dtype), tokens)
    return jnp.take(table.astype(cfg.dtype), tokens, axis=0)


def head(edges: Dict, x: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Final RMSNorm + LM head -> [.., L, vocab] logits in cfg.dtype
    (the loss upcasts inside its reductions, llama2.Llama.__call__)."""
    x = RMSNorm(cfg.norm_eps, cfg.param_dtype).apply(
        {"params": edges["norm"]}, x
    )
    return x @ edges["output"]["kernel"].astype(cfg.dtype)


def pp_pspecs(split: Dict, axis: str = "pipe") -> Dict:
    """PartitionSpec tree: edges replicated over every mesh axis,
    stages stage-sharded over ``axis`` (pp.stage_pspecs)."""
    return {
        "edges": jax.tree.map(lambda _: P(), split["edges"]),
        "stages": pp.stage_pspecs(split["stages"], axis=axis),
    }


def make_forward(
    cfg: LlamaConfig,
    mesh: Mesh,
    n_microbatches: int,
    axis: str = "pipe",
    schedule: str = "1f1b",
    backward: str = "remat",
    batch_spec: P = P(),
    attn_fn: AttnFn = None,
    positions: Optional[jax.Array] = None,
    remat_stage: bool = False,
    n_chunks: int = 1,
):
    """Trainer-contract forward for pipelined Llama training: embed ->
    pipelined stage body -> head -> next-token cross-entropy, with the
    batch microbatched [B, L] -> [M, B/M, L] around the tick program.
    ``batch_spec`` shards the microbatch rows (e.g. P(None, "data")
    for the PP x DP composition); the pipe axis itself never appears
    in it -- activations are replicated over stages by construction.
    ``remat_stage`` wraps the stage in jax.checkpoint on the autodiff
    schedules -- see pp.pipelined. ``n_chunks`` > 1 selects the
    Megatron virtual-pipeline placement (stack the params with
    :func:`split_params_interleaved`; interleaved schedules only).
    """
    from tpu_hpc.models.losses import cross_entropy

    S = mesh.shape[axis]
    pipe = pp.pipelined(
        make_stage_fn(cfg, S * n_chunks, attn_fn, positions),
        mesh, axis=axis,
        schedule=schedule, batch_spec=batch_spec, backward=backward,
        remat_stage=remat_stage, n_chunks=n_chunks,
    )

    def forward(params, model_state, batch, step_rng):
        inputs, targets = batch
        xs = embed(
            params["edges"], pp.microbatch(inputs, n_microbatches), cfg
        )
        ys = pipe(params["stages"], xs)
        logits = head(params["edges"], ys, cfg)
        loss = cross_entropy(logits, pp.microbatch(targets, n_microbatches))
        return loss, model_state, {}

    return forward


def mpmd_bundle(
    split: Dict,
    cfg: LlamaConfig,
    attn_fn: AttnFn = None,
    positions: Optional[jax.Array] = None,
):
    """Cut the flagship Llama for the MPMD pipeline runtime
    (``tpu_hpc.parallel.mpmd``): ``split_params``' stacked stage tree
    becomes per-stage trees, and the edges stop being replicated --
    tok_embeddings lives in stage 0's fault domain, norm+output (and
    the loss) in stage S-1's. Pair with the same sequential-stack
    layout ``split_params`` produces (the interleaved layouts are an
    SPMD bubble optimization; MPMD dispatch order is the runtime's
    own concern)."""
    from tpu_hpc.models.losses import cross_entropy
    from tpu_hpc.parallel.mpmd import StageBundle

    stages = split["stages"]
    S = jax.tree.leaves(stages)[0].shape[0]
    stage_params = tuple(
        jax.tree.map(lambda a: a[s], stages) for s in range(S)
    )
    edges = split["edges"]

    def embed_fn(ep, tokens):
        return embed(ep, tokens, cfg)

    def loss_fn(hp, y, targets):
        return cross_entropy(head(hp, y, cfg), targets)

    return StageBundle(
        n_stages=S,
        stage_fn=make_stage_fn(cfg, S, attn_fn, positions),
        embed_fn=embed_fn,
        loss_fn=loss_fn,
        stage_params=stage_params,
        embed_params={"tok_embeddings": edges["tok_embeddings"]},
        head_params={"norm": edges["norm"], "output": edges["output"]},
    )
