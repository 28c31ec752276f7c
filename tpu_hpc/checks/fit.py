"""North-star shard/fit analysis: does Llama-2 7B hybrid FSDPxTP fit a
TPU pod, and what does its compiled step look like?

Capability anchor: the reference's north-star workload is its hybrid
FSDPxTP Llama-2 example run at the full 7B ``ModelArgs`` defaults
(/root/reference/fsdp_tp/fsdp_tp_example.py:120-187 with
llama2_model.py:13-16), for which it offers only a planning table
("7B: TP4 x FSDP2", /root/reference/docs/guide/09_hybrid_parallelism.md:
118-137) -- it never demonstrates the memory budget. This module does,
TPU-style, without needing the pod:

  1. **Exact static accounting** -- ``jax.eval_shape`` of the real init
     + the real hybrid PartitionSpec plan give per-chip bytes for
     params, gradients and optimizer state, exactly (no model is
     materialized).
  2. **Analytic activation model** -- remat-per-block + Megatron-SP
     sequence-sharded residual checkpoints + flash attention (no S x S
     score materialization), the configuration bench.py runs.
  3. **AOT compile evidence** -- the *actual* Trainer step function
     (train.trainer.make_step_fn) is jit-lowered and XLA-compiled
     against a virtual pod mesh; the compiled HLO is scanned for the
     emitted collectives, proving the 2D sharding plan partitions
     end-to-end (GSPMD accepts it) rather than merely type-checking.

Run: ``python -m tpu_hpc.checks.fit --markdown REPORT_7b_v4-32.md``
(self-provisions a 32-device simulated mesh when needed).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_hpc.models import (
    conv_moe, hybrid_ssm_moe, latent_moe, llama2, sparse_moe,
)
from tpu_hpc.parallel import hybrid, tp
from tpu_hpc.parallel.plans import derived_pspecs, shardings_for

GIB = 1024 ** 3

# Collectives worth reporting from the compiled module (the comm
# signature of the plan; parity with reading NCCL_DEBUG=INFO logs,
# /root/reference/docs/guide/nccl_tuning.md:153-173). Single-sourced
# with the HLO counting helper so the fit report and the comm-guard
# tests can never disagree on what counts as a collective.
from tpu_hpc.checks.hlo import COLLECTIVE_OPS as _COLLECTIVES  # noqa: E402


def _leaf_bytes_per_chip(leaf, spec: P, mesh_axes: Dict[str, int]) -> int:
    """Bytes one chip holds of ``leaf`` under ``spec``: the full size
    divided by the product of the mesh-axis sizes the spec claims."""
    size = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    denom = 1
    for entry in spec:
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        for name in names:
            denom *= mesh_axes[name]
    return -(-size // denom)  # ceil: padding rounds up, never down


def tree_bytes_per_chip(abstract: Any, specs: Any, mesh_axes: Dict[str, int]) -> int:
    total = 0
    for leaf, spec in zip(
        jax.tree.leaves(abstract),
        jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
    ):
        total += _leaf_bytes_per_chip(leaf, spec, mesh_axes)
    return total


def kv_cache_bytes(
    cfg: llama2.LlamaConfig,
    batch_slots: int,
    max_seq_len: Optional[int] = None,
    cache_dtype: str = "bfloat16",
) -> int:
    """Per-POD bytes of a decode KV cache: batch_slots x seq x layers
    x kv_heads x head_dim x 2 (K and V) x dtype. The term the serving
    engine preallocates (tpu_hpc/serve/engine.py) and the memory-fit
    analysis previously ignored -- at 70B GQA with 4k context and 64
    slots this is ~80 GiB, not a rounding error. Divide by the mesh
    extents sharding the cache (slots over data, kv_heads over model)
    for the per-chip share; analyze() does that with its own mesh.
    A latent configuration (``models/latent_moe.py``) has no such
    cache: only the paged pool keeps its rows (:func:`kv_paged_bytes`)."""
    latent_moe.refuse(
        cfg, "the slab KV cache's size (checks/fit.py)",
        "the slab engine keeps per-head keys and values",
    )
    hybrid_ssm_moe.refuse(
        cfg, "the slab KV cache's size (checks/fit.py)",
        "the slab engine keeps keys and values for every layer and no "
        "recurrent state",
    )
    s = max_seq_len if max_seq_len is not None else cfg.max_seq_len
    itemsize = jnp.dtype(cache_dtype).itemsize
    return (
        batch_slots * s * cfg.n_layers * cfg.kv_heads * cfg.head_dim
        * 2 * itemsize
    )


def param_counts(cfg: llama2.LlamaConfig) -> Dict[str, int]:
    """``total`` parameters a chip must HOLD against ``active`` ones a
    token passes through. One number for a dense decoder; for a
    sparse-expert one (``models/sparse_moe.py``) memory is sized by the
    total (every held expert) and a decode step's products by the
    active (``experts_per_token`` experts a layer), sixteen times
    apart at Keye-VL-2.0-30B-A3B's 8 of 128. A latent configuration
    (``models/latent_moe.py``) counts by kind of layer: its leading
    dense layers, then expert layers with the experts HELD here; one
    with state-space layers (``models/hybrid_ssm_moe.py``) by each
    layer's own mixer. One that is TRAINED (``models/conv_moe.py``)
    counts the same way: the step's expert products follow the
    assignments computed, not the experts held, and ``active`` counts
    a token's ``experts_per_token`` wherever they are held."""
    counts = None
    if conv_moe.is_conv_moe(cfg):
        counts = conv_moe.count_params(cfg)
    elif sparse_moe.is_sparse_moe(cfg):
        counts = sparse_moe.count_params(cfg)
    elif latent_moe.is_latent_moe(cfg):
        counts = latent_moe.count_params(cfg)
    elif hybrid_ssm_moe.is_hybrid_ssm_moe(cfg):
        counts = hybrid_ssm_moe.count_params(cfg)
    if counts is not None:
        return {"total": counts["total"], "active": counts["active"]}
    n = llama2.count_params(cfg)
    return {"total": n, "active": n}


def kv_paged_bytes(
    cfg: llama2.LlamaConfig,
    num_blocks: int,
    block_size: int,
    cache_dtype: str = "bfloat16",
    kv_quant: str = "none",
) -> int:
    """Per-POD bytes of a PAGED decode KV cache
    (tpu_hpc/serve/paging.py): num_blocks pages x block_size tokens x
    layers x kv_heads x head_dim x 2 (K and V) x dtype. The paged
    engine provisions pages for the tokens traffic actually holds,
    not ``slots x max_seq`` worst case -- the difference against
    :func:`kv_cache_bytes` at the same traffic mix is the
    fragmentation/slack headroom paging reclaims, which
    ``analyze(kv_blocks=...)`` reports next to the slab term. The
    pool shards KV heads over the model axis only (pages are globally
    addressable, so the block dim stays whole per replica).

    ``kv_quant="int8"`` (tpu_hpc.kernels.paged_attention) stores
    pages at 1 byte/element plus a per-page fp32 scale side array
    (one scale per page per layer, K and V each) -- the halved pool
    the quantized-capacity report line budgets.

    A sparse-expert configuration (``models/sparse_moe.py``) keeps a
    third array under the same pages: the indexer's one key a token a
    layer, ``indexer_head_dim`` numbers (128 B in bf16 at 64), which
    the engine's ``cache_bytes`` counts too. A latent configuration
    (``models/latent_moe.py``) keeps ONE row a token a layer, the
    latent and the rotary key (``latent_dim`` numbers: 1152 B in bf16
    at 512 + 64), nothing per head, and has no int8 page. One with
    state-space layers (``models/hybrid_ssm_moe.py``) keeps pages for
    its ATTENTION layers only; the recurrent state of the others is a
    fixed size a slot (``cfg.state_bytes(slots)``), no part of the
    pool, and it has no int8 page either."""
    layers = cfg.n_layers
    if hybrid_ssm_moe.is_hybrid_ssm_moe(cfg):
        if kv_quant != "none":
            hybrid_ssm_moe.refuse(
                cfg, "an int8 page pool",
                "the int8 pool has not been held to this decoder's "
                "reference",
            )
        layers = cfg.n_attention_layers
    if latent_moe.is_latent_moe(cfg):
        if kv_quant != "none":
            latent_moe.refuse(
                cfg, "an int8 page pool",
                "the int8 page write quantises per-head K and V pages",
            )
        return num_blocks * block_size * cfg.n_layers * cfg.latent_dim \
            * jnp.dtype(cache_dtype).itemsize
    if sparse_moe.is_sparse_moe(cfg):
        if kv_quant != "none":
            sparse_moe.refuse(
                cfg, "an int8 page pool", "the indexer key is not quantised"
            )
        return num_blocks * block_size * cfg.n_layers * (
            2 * cfg.kv_heads * cfg.head_dim + cfg.indexer_head_dim
        ) * jnp.dtype(cache_dtype).itemsize
    if kv_quant == "int8":
        page_bytes = (
            num_blocks * block_size * cfg.n_layers * cfg.kv_heads
            * cfg.head_dim * 2
        )
        scale_bytes = num_blocks * cfg.n_layers * 2 * 4
        return page_bytes + scale_bytes
    itemsize = jnp.dtype(cache_dtype).itemsize
    return (
        num_blocks * block_size * layers * cfg.kv_heads
        * cfg.head_dim * 2 * itemsize
    )


@dataclasses.dataclass
class FitResult:
    cfg: llama2.LlamaConfig
    dp: int
    tp_size: int
    global_batch: int
    seq_len: int
    hbm_gib: float
    n_params: int
    param_bytes: int          # per chip, fp32 masters
    grad_bytes: int           # per chip, fp32, live during the step
    opt_bytes: int            # per chip, AdamW mu+nu fp32
    act_bytes: Dict[str, int]  # per chip, analytic model
    grad_accum: int = 1
    compiled: bool = False
    compile_seconds: float = 0.0
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    xla_argument_bytes: int = 0  # per chip, XLA's own accounting
    xla_temp_bytes: int = 0      # per chip, XLA scratch/live temps
    compile_backend: str = "cpu-sim"  # or "tpu-topology:<name>"
    attn: str = "xla"            # attention path the compile pass used
    moments_dtype: str = "float32"  # AdamW moment storage dtype
    layout: str = "tp"           # "tp" (FSDPxTP+SP) | "cp" (FSDP x ring)
    compiler_options: Dict[str, str] = dataclasses.field(
        default_factory=dict
    )
    kv_cache_bytes: int = 0      # per chip, decode-config KV cache
    kv_slots: int = 0            # decode batch slots the term assumes
    kv_block_bytes: int = 0      # per chip, PAGED decode KV pool
    kv_blocks: int = 0           # physical pages the paged term assumes
    kv_block_size: int = 0       # tokens per page
    kv_quant: str = "none"       # page storage: "none" (dtype) | "int8"
    # Host-DRAM KV page tier (serve/tier.py): parked prefixes spill
    # into host buffers, so this term is DRAM, not HBM -- reported
    # for sizing but never part of total_bytes or the fits verdict.
    kv_host_blocks: int = 0      # host tier slots incl. scratch
    kv_host_bytes: int = 0       # per host, full-width K+V buffers
    # Speculative-decode draft model (serve/spec.py): its params live
    # on the same chips and its KV pool mirrors the target's pages --
    # a draft that does not fit must fail THIS report, not OOM at
    # serving bring-up.
    draft_n_params: int = 0
    draft_param_bytes: int = 0   # per chip, serving-layout fp32
    draft_kv_block_bytes: int = 0  # per chip, mirrored paged pool

    @property
    def static_bytes(self) -> int:
        return self.param_bytes + self.grad_bytes + self.opt_bytes

    @property
    def total_bytes(self) -> int:
        # The paged pool REPLACES the slab cache when both are given
        # (you deploy one engine); the slab term stays reported for
        # the fragmentation-headroom comparison.
        kv = self.kv_block_bytes if self.kv_blocks \
            else self.kv_cache_bytes
        return (
            self.static_bytes + sum(self.act_bytes.values()) + kv
            + self.draft_param_bytes + self.draft_kv_block_bytes
        )

    @property
    def fits(self) -> bool:
        return self.total_bytes <= self.hbm_gib * GIB

    def to_json(self) -> Dict:
        d = {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if f.name != "cfg"
        }
        d.update(
            model=dict(
                dim=self.cfg.dim, n_layers=self.cfg.n_layers,
                n_heads=self.cfg.n_heads, vocab_size=self.cfg.vocab_size,
                ffn_hidden=self.cfg.ffn_hidden, remat=self.cfg.remat,
            ),
            static_bytes=self.static_bytes,
            total_bytes=self.total_bytes,
            fits=self.fits,
        )
        return d


def kept_block_bytes(
    cfg: llama2.LlamaConfig, tokens: int, tp_size: int = 1
) -> int:
    """Bytes ONE keeping block holds on a chip between its forward and
    its backward pass, beyond the block input every block saves: the
    six matmul outputs ``models/llama2.py`` tags (models/remat.py) --
    the rotated query and key and the value, the residual stream after
    the output projection, the feed-forward's gate and up -- in the
    compute dtype, for the ``tokens`` a chip holds of one microbatch.
    Tensor parallelism shards q/k/v by heads and gate/up by the hidden
    width, and the sequence-parallel constraint shards the residual
    stream, so all six are split ``tp_size`` ways.

    The Trainer's decision (how many blocks keep) and this module's
    fit report read the same number from here."""
    per_token = (
        (cfg.n_heads + 2 * cfg.kv_heads) * cfg.head_dim
        + cfg.dim + 2 * cfg.ffn_hidden
    )
    return tokens * per_token * jnp.dtype(cfg.dtype).itemsize // tp_size


def activation_bytes(
    cfg: llama2.LlamaConfig, tokens: int, tp_size: int = 1,
    keeping: int = 0,
) -> Dict[str, int]:
    """:func:`activation_model` in terms of the ``tokens`` a chip holds
    of one microbatch (rows x sequence, after data parallelism and
    gradient accumulation): the form the model itself can evaluate
    while it is traced (``llama2._blocks_keeping``)."""
    t_sp = tokens // tp_size         # SP-sharded residual stream
    d, hd = cfg.dim, cfg.head_dim
    h_loc = cfg.n_heads // tp_size   # TP shards heads
    kv_loc = max(cfg.kv_heads // tp_size, 1)
    ffn_loc = cfg.ffn_hidden // tp_size
    bf16, f32 = 2, 4

    # Saved between fwd and bwd: one residual checkpoint per block
    # (sequence-sharded thanks to SP) + embedding output.
    checkpoints = (cfg.n_layers + 1) * t_sp * d * bf16
    # Live while recomputing/backpropping ONE block (full seq per chip
    # -- the SP all-gather happens at the block boundary): input + QKV +
    # flash out/LSE + two SwiGLU hiddens, roughly doubled for the
    # matching gradient buffers.
    qkv = tokens * (h_loc + 2 * kv_loc) * hd * bf16
    attn_out = tokens * h_loc * hd * bf16
    lse = tokens * h_loc * f32
    mlp = 2 * tokens * ffn_loc * bf16
    block_live = 2 * (tokens * d * bf16 + qkv + attn_out + lse + mlp)
    # LM head: logits are vocab-sharded (output Colwise) and stay in
    # bf16 -- the loss upcasts inside its fused reductions, so no
    # [B, S, V] fp32 buffer exists (models/llama2.py Llama.__call__).
    # bf16 logits + bf16 logit-grad + one fp32 reduction pass that XLA
    # may materialise while fusing logsumexp.
    vocab_loc = cfg.vocab_size // tp_size
    head = tokens * vocab_loc * (2 * bf16 + f32)
    out = {
        "residual_checkpoints": checkpoints,
        "block_recompute_live": block_live,
        "lm_head_and_loss": head,
    }
    if keeping:
        out["kept_matmul_outputs"] = keeping * kept_block_bytes(
            cfg, tokens, tp_size
        )
    return out


def conv_moe_kept_block_bytes(
    cfg: "conv_moe.ConvMoEConfig", layer: int, tokens: int
) -> int:
    """:func:`kept_block_bytes` for block ``layer`` of a
    ``models/conv_moe.py`` stack (``remat.CONV_MOE_PRODUCTS``): the
    mixer's projections in the compute dtype, the float32 stream after
    the mixer, a dense layer's gate and up. An expert layer keeps
    nothing of its row buffer."""
    item = jnp.dtype(cfg.dtype).itemsize
    if cfg.is_attention_layer(layer):
        per_token = (cfg.n_heads + 2 * cfg.kv_heads) * cfg.head_dim * item
    else:
        per_token = 3 * cfg.dim * item
    per_token += 4 * cfg.dim
    if cfg.is_dense_layer(layer):
        per_token += 2 * cfg.dense_hidden * item
    return tokens * per_token


def conv_moe_activation_bytes(
    cfg: "conv_moe.ConvMoEConfig", tokens: int
) -> Dict[str, int]:
    """:func:`activation_bytes` for a ``models/conv_moe.py`` stack on
    one chip when every block recomputes: the float32 stream saved at
    each block's input, the most any one block holds while it is
    recomputed and differentiated, and the head. An expert layer's
    buffers are sized for the worst routing
    (``sparse_moe.ragged_rows``), whatever the step routes."""
    d, item, f32 = cfg.dim, jnp.dtype(cfg.dtype).itemsize, 4
    live = 0
    for i in range(cfg.n_layers):
        if cfg.is_attention_layer(i):
            mixer = tokens * (
                2 * (cfg.n_heads + cfg.kv_heads) * cfg.head_dim * item
                + cfg.n_heads * f32
            )
        else:
            mixer = tokens * 5 * d * item
        if cfg.is_dense_layer(i):
            ffn = 3 * tokens * cfg.dense_hidden * item
        else:
            rows = sparse_moe.ragged_rows(tokens, cfg)
            k = cfg.experts_per_token
            ffn = rows * (2 * d + 3 * cfg.expert_hidden) * item \
                + tokens * k * d * (item + f32)
        # The block's input and two stream-sized results, float32.
        # (Calibrated on the v5e compiler at the benchmark's cut, 5
        # layers x 32768 tokens: it reads 5.6 GiB of temporaries beside
        # the gradient tree where this reckons 6.5.)
        live = max(live, 3 * tokens * d * f32 + mixer + ffn)
    return {
        "residual_checkpoints": (cfg.n_layers + 1) * tokens * d * f32,
        "block_recompute_live": live,
        "lm_head_and_loss": tokens * cfg.vocab_size * (2 * item + f32),
    }


def activation_model(
    cfg: llama2.LlamaConfig, dp: int, tp_size: int,
    global_batch: int, seq_len: int, grad_accum: int = 1,
    keeping: int = 0,
) -> Dict[str, int]:
    """Per-chip activation bytes under the bench configuration:
    remat-per-block, Megatron-SP (residual stream sequence-sharded over
    the model axis between blocks), flash attention (O(S) saved state,
    no S x S scores), bf16 compute.

    ``keeping``: how many blocks keep their six matmul outputs for the
    backward pass (:func:`kept_block_bytes` each); the others save
    their input alone and recompute the rest. 0, the default, is full
    recomputation. A Trainer on a device that reports a memory limit
    chooses the count from this same model (train/trainer.py,
    models/remat.py) and reports it as ``train_remat_blocks_kept``.

    ``grad_accum > 1``: each microbatch's activations live only for its
    own forward/backward inside the accumulation scan, so every term
    scales by 1/grad_accum (the gradient-sum carry is accounted
    separately in analyze()).

    An analytic model, not a measurement: XLA's actual peak adds fusion
    temporaries, but the dominant terms (checkpointed residuals, one
    block's recompute live-set, the logits/CE head, the kept products)
    are all here.
    """
    # Per-chip, per-microbatch rows (DP shards the batch dim).
    bl = global_batch // dp // grad_accum
    return activation_bytes(cfg, bl * seq_len, tp_size, keeping)


def activation_model_cp(
    cfg: llama2.LlamaConfig, dp: int, cp: int,
    global_batch: int, seq_len: int, grad_accum: int = 1,
) -> Dict[str, int]:
    """Per-chip activation bytes for the long-context layout: FSDP
    over ``data``, ring-attention context parallelism over
    ``context`` (examples/05 --fsdp). The residual stream is
    sequence-sharded EVERYWHERE (cp_constrain), attention is the ring
    (O(S/cp) per chip: a device never holds more than its own Q chunk
    plus the KV chunk passing through), and there is no TP -- heads,
    FFN and vocab are full-width but only S/cp tokens deep.
    """
    bl = global_batch // dp // grad_accum
    s_loc = seq_len // cp
    d, hd = cfg.dim, cfg.head_dim
    h, kv = cfg.n_heads, cfg.kv_heads
    bf16, f32 = 2, 4

    checkpoints = (cfg.n_layers + 1) * bl * s_loc * d * bf16
    qkv = bl * s_loc * (h + 2 * kv) * hd * bf16
    # Ring state: the rotating K/V chunk is double-buffered (current +
    # in-flight ppermute), and the merge carries an fp32 output
    # accumulator + LSE.
    ring_kv = 2 * 2 * bl * s_loc * kv * hd * bf16
    out_acc = bl * s_loc * h * hd * f32
    lse = bl * h * s_loc * f32
    mlp = 2 * bl * s_loc * cfg.ffn_hidden * bf16
    block_live = 2 * (
        bl * s_loc * d * bf16 + qkv + ring_kv + out_acc + lse + mlp
    )
    head = bl * s_loc * cfg.vocab_size * (2 * bf16 + f32)
    return {
        "residual_checkpoints": checkpoints,
        "block_recompute_live": block_live,
        "lm_head_and_loss": head,
    }


def activation_model_pp(
    cfg: llama2.LlamaConfig, dp: int, stages: int,
    global_batch: int, seq_len: int, microbatches: int,
    pp_backward: str = "remat",
) -> Dict[str, int]:
    """Per-chip activation bytes for the pipeline layout (1F1B,
    pp.pipelined): each chip holds ONE stage's layers; at the 1F1B
    steady state up to ``stages`` microbatches are in flight per chip.
    ``pp_backward="remat"`` (the default): each in-flight microbatch
    contributes its stage's residual checkpoints only (the custom-vjp
    backward recomputes everything else). ``"stash"``: each in-flight
    slot instead holds the full vjp residuals -- every per-layer
    intermediate plus a compute-dtype copy of the stage params
    (pp.pipelined(backward="stash")). Sequence is NOT sharded
    (full seq per chip, flash attention assumed -- no S x S scores).
    """
    if global_batch % (dp * microbatches):
        raise ValueError(
            f"global_batch {global_batch} must divide into dp {dp} x "
            f"microbatches {microbatches} rows"
        )
    mbr = global_batch // dp // microbatches  # rows per microbatch
    d, hd = cfg.dim, cfg.head_dim
    h, kv = cfg.n_heads, cfg.kv_heads
    bf16, f32 = 2, 4
    layers_loc = cfg.n_layers // stages
    # The tick programs allocate their ring buffers at FIXED depth
    # 2S as scan carries (pp.py: D = 2 * n_stages), and XLA keeps a
    # scan carry resident for the whole scan -- capacity follows the
    # allocation, not the in-flight high-water mark.
    ring_depth = 2 * stages
    # Residuals per microbatch per layer-token: dim (input) +
    # q/k/v/attn-out + both SwiGLU hiddens (matches the roofline's
    # stash_residuals traffic term, checks/roofline.py).
    per_tok = d + (h + 2 * kv + h) * hd + 2 * cfg.ffn_hidden
    if pp_backward == "stash":
        # Every ring slot holds a full vjp residual set, including a
        # bf16 stage-param copy.
        checkpoints = ring_depth * (
            layers_loc * mbr * seq_len * per_tok * bf16
            + llama2.pp_worst_stage_params(cfg, stages) * bf16
        )
    else:
        # Remat: ring slots hold stage INPUTS only; the backward's
        # vjp materializes ONE microbatch's full stage residuals
        # transiently each tick.
        checkpoints = (
            ring_depth * mbr * seq_len * d * bf16
            + layers_loc * mbr * seq_len * per_tok * bf16
        )
    qkv = mbr * seq_len * (h + 2 * kv) * hd * bf16
    attn_out = mbr * seq_len * h * hd * bf16
    lse = mbr * h * seq_len * f32
    mlp = 2 * mbr * seq_len * cfg.ffn_hidden * bf16
    block_live = 2 * (
        mbr * seq_len * d * bf16 + qkv + attn_out + lse + mlp
    )
    head = mbr * seq_len * cfg.vocab_size * (2 * bf16 + f32)
    return {
        "inflight_stage_checkpoints": checkpoints,
        "block_recompute_live": block_live,
        "lm_head_and_loss": head,
    }


def _count_collectives(hlo: str) -> Dict[str, int]:
    """Collective op applications in compiled HLO, across backend
    spellings: plain ``op(``, the async pair form ``op-start(`` (the
    TPU latency-hiding scheduler splits collectives into start/done),
    and the TPU backend's fused reduce-scatter -- a kCustom fusion
    ``calls=%all-reduce-scatter`` that consumes the full gradient and
    emits the sharded shard directly (observed on v5e topology
    compiles; counting only ``reduce-scatter(`` would report 0 and
    understate the real lowering)."""
    counts = {}
    # Each %all-reduce-scatter computation *body* contains one
    # all-reduce op implementing it -- that op must not also count in
    # the all-reduce row (it IS the fused reduce-scatter).
    fused_defs = len(
        re.findall(r"(?m)^\s*%all-reduce-scatter[\w.\-]*\s+\(", hlo)
    )
    for op in _COLLECTIVES:
        n = len(re.findall(rf"\b{op}(?:-start)?\(", hlo))
        if op == "reduce-scatter":
            n += len(re.findall(r"calls=%all-reduce-scatter", hlo))
        elif op == "all-reduce":
            n = max(0, n - fused_defs)
        counts[op] = n
    return counts


def _resolve_devices(
    tpu_topology: Optional[str], n_dev: int, result: "FitResult"
) -> list:
    """Device list for the AOT-compile pass: the chips of a virtual
    TPU topology (no hardware needed -- libtpu compiles against the
    description) or this process's real/simulated devices."""
    if tpu_topology is not None:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=tpu_topology
        )
        devices = list(topo.devices)
        if len(devices) != n_dev:
            raise RuntimeError(
                f"topology {tpu_topology!r} has {len(devices)} chips, "
                f"mesh needs {n_dev}"
            )
        result.compile_backend = f"tpu-topology:{tpu_topology}"
    else:
        devices = jax.devices()
        if len(devices) < n_dev:
            raise RuntimeError(
                f"need {n_dev} devices for the compile pass, have "
                f"{len(devices)}; run under TPU_HPC_SIM_DEVICES={n_dev} "
                "or pass do_compile=False"
            )
    return devices


def _compile_and_record(
    result: "FitResult",
    step,
    state_abstract,
    state_shardings,
    batch_abstract,
    batch_shardings,
    compiler_options: Optional[Dict[str, str]],
) -> "FitResult":
    """The shared compile-and-record tail of every layout's AOT pass:
    jit/lower/compile the step, time it, and attach the collective
    table + the compiler's memory analysis to ``result``. One copy so
    the pp report can never drift from the tp/cp reports."""
    t0 = time.time()
    compiled = (
        jax.jit(
            step,
            in_shardings=(state_shardings, batch_shardings),
            donate_argnums=(0,),
        )
        .lower(state_abstract, batch_abstract)
        .compile(compiler_options=compiler_options or None)
    )
    result.compile_seconds = time.time() - t0
    result.compiled = True
    hlo = compiled.as_text()
    result.collectives = _count_collectives(hlo)
    mem = compiled.memory_analysis()
    if mem is not None:
        result.xla_argument_bytes = int(mem.argument_size_in_bytes)
        result.xla_temp_bytes = int(
            getattr(mem, "temp_size_in_bytes", 0) or 0
        )
    return result


def _compile_pp(
    result: "FitResult",
    cfg: llama2.LlamaConfig,
    dp: int,
    stages: int,
    global_batch: int,
    seq_len: int,
    microbatches: int,
    tpu_topology: Optional[str],
    attn: str,
    compiler_options: Optional[Dict[str, str]],
    moments_dtype: str,
    pp_backward: str,
) -> "FitResult":
    """AOT-compile the REAL stage-split Llama pipeline step (the 1F1B
    tick program of models/llama_pp.py + parallel/pp.py) over a
    {data: dp, pipe: stages} mesh, and attach the compiler's
    collective table + memory analysis to ``result`` -- the same
    evidence class the tp/cp layouts have always had."""
    from tpu_hpc.models import llama_pp
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.train.trainer import TrainState, make_adamw, make_step_fn

    n_dev = dp * stages
    devices = _resolve_devices(tpu_topology, n_dev, result)
    mesh = build_mesh(
        MeshSpec(axes={"data": dp, "pipe": stages}),
        devices=devices[:n_dev],
    )
    attn_fn = None
    if attn == "flash":
        from tpu_hpc.kernels.attention import blockwise_attention

        # Batch-local flash call (each stage owns its microbatch inside
        # pp's shard_map); impl pinned to "pallas" for topology
        # compiles, where "auto" would silently pick the XLA path.
        impl = "pallas" if tpu_topology else "auto"

        def attn_fn(q, k, v):
            out, _ = blockwise_attention(q, k, v, causal=True, impl=impl)
            return out

    abstract_split = jax.eval_shape(
        lambda: llama_pp.split_params(
            llama2.init_llama(jax.random.key(0), cfg), cfg, stages
        )
    )
    specs = llama_pp.pp_pspecs(abstract_split)
    forward = llama_pp.make_forward(
        cfg, mesh, n_microbatches=microbatches, schedule="1f1b",
        backward=pp_backward,
        batch_spec=P(None, "data") if dp > 1 else P(),
        attn_fn=attn_fn,
    )
    optimizer = make_adamw(3e-4, 0.1, moments_dtype)
    opt_abstract = jax.eval_shape(optimizer.init, abstract_split)
    opt_specs = derived_pspecs(opt_abstract, abstract_split, specs)
    step = make_step_fn(forward, optimizer, seed=0)

    state_abstract = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=abstract_split,
        opt_state=opt_abstract,
        model_state={},
    )
    state_shardings = TrainState(
        step=NamedSharding(mesh, P()),
        params=shardings_for(mesh, specs),
        opt_state=shardings_for(mesh, opt_specs),
        model_state={},
    )
    batch_abstract = tuple(
        jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)
        for _ in range(2)
    )
    # Batch replicated at the step boundary (the Trainer's pp
    # batch_pspec); pp's shard_map chops microbatch rows over data.
    batch_shardings = tuple(NamedSharding(mesh, P()) for _ in range(2))
    return _compile_and_record(
        result, step, state_abstract, state_shardings,
        batch_abstract, batch_shardings, compiler_options,
    )


def analyze(
    cfg: Optional[llama2.LlamaConfig] = None,
    dp: int = 4,
    tp_size: int = 8,
    global_batch: int = 8,
    seq_len: int = 4096,
    hbm_gib: float = 32.0,
    do_compile: bool = True,
    grad_accum: int = 1,
    tpu_topology: Optional[str] = None,
    attn: str = "xla",
    compiler_options: Optional[Dict[str, str]] = None,
    moments_dtype: str = "float32",
    layout: str = "tp",
    pp_backward: str = "remat",
    kv_slots: int = 0,
    kv_seq_len: Optional[int] = None,
    kv_cache_dtype: str = "bfloat16",
    kv_blocks: int = 0,
    kv_block_size: int = 16,
    kv_quant: str = "none",
    kv_host_blocks: int = 0,
    draft_cfg: Optional[llama2.LlamaConfig] = None,
) -> FitResult:
    """Shard/fit analysis of the hybrid FSDPxTP(+SP) train step.

    Defaults = the north star: 7B LlamaConfig defaults on a v4-32-shaped
    (data=4, model=8) mesh, 32 GiB HBM per chip. ``grad_accum`` analyzes
    (and compiles) the accumulated step -- the configuration large
    global batches actually run.

    ``tpu_topology`` (e.g. ``"v5e:4x8"``): AOT-compile against a
    *virtual TPU topology* via libtpu instead of the CPU-sim backend.
    No chips needed -- the TPU compiler itself partitions the step, so
    the collective table shows the REAL lowering (reduce-scatters stay
    reduce-scatters; the CPU simulator legalizes them to
    all-reduce+slice) and ``memory_analysis`` is the TPU compiler's own
    HBM accounting.

    ``attn="flash"`` compiles the production attention path -- the
    Pallas flash kernel under shard_map with heads on the TP axis
    (tp.make_tp_flash_attn_fn), or inside the KV ring with full-width
    heads under ``layout="cp"``. The default ``"xla"`` einsum path
    materialises per-layer score blocks whose HBM temps dominate at
    seq 4096+ and can overflow a real core's budget that the flash
    kernel's online softmax avoids.
    """
    if cfg is None:
        cfg = llama2.LlamaConfig(max_seq_len=seq_len, remat=True)
    if layout not in ("tp", "cp", "pp"):
        raise ValueError(f"unknown layout {layout!r} (tp|cp|pp)")
    axis2 = "model" if layout == "tp" else (
        "context" if layout == "cp" else "pipe"
    )
    if layout == "tp":
        tp.validate_tp_degree(cfg.n_heads, cfg.kv_heads, tp_size)
    elif layout == "cp" and seq_len % tp_size:
        raise ValueError(
            f"context parallelism needs seq_len {seq_len} divisible "
            f"by the ring degree {tp_size}"
        )
    elif layout == "pp" and cfg.n_layers % tp_size:
        raise ValueError(
            f"pipeline needs n_layers {cfg.n_layers} divisible by "
            f"the stage count {tp_size}"
        )
    if grad_accum < 1 or global_batch % grad_accum or (
        (global_batch // grad_accum) % dp
    ):
        raise ValueError(
            f"grad_accum {grad_accum} must divide global_batch "
            f"{global_batch} into microbatches divisible by dp {dp}"
        )

    # Decode-config KV-cache term (``kv_slots > 0``): what a serving
    # engine co-resident with this config would preallocate
    # (tpu_hpc/serve/engine.py). Sharded like the engine shards it --
    # slots over data, KV heads over the model axis -- when the
    # extents divide; otherwise that dimension is replicated.
    kv_bytes_chip = 0
    if kv_slots:
        full = kv_cache_bytes(cfg, kv_slots, kv_seq_len, kv_cache_dtype)
        denom = 1
        if dp > 1 and kv_slots % dp == 0:
            denom *= dp
        if layout == "tp" and tp_size > 1 \
                and cfg.kv_heads % tp_size == 0:
            denom *= tp_size
        kv_bytes_chip = -(-full // denom)

    # Paged pool term (``kv_blocks > 0``): what the paged engine
    # (tpu_hpc/serve/paging.py) would provision instead of the slab.
    # Sharded as the pool is: KV heads over the model axis when they
    # divide; the block dim replicates over data (pages are globally
    # addressable within a replica).
    kv_block_bytes_chip = 0
    if kv_quant not in ("none", "int8"):
        raise ValueError(
            f"unknown kv_quant {kv_quant!r} (none|int8)"
        )
    if kv_quant == "int8" and not kv_blocks:
        raise ValueError(
            "kv_quant='int8' needs the paged pool term (kv_blocks > "
            "0): only paged pages quantize "
            "(tpu_hpc.kernels.paged_attention)"
        )
    if kv_blocks:
        if kv_block_size < 1:
            raise ValueError(
                f"kv_block_size {kv_block_size} must be >= 1"
            )
        full = kv_paged_bytes(
            cfg, kv_blocks, kv_block_size, kv_cache_dtype, kv_quant
        )
        denom = 1
        if layout == "tp" and tp_size > 1 \
                and cfg.kv_heads % tp_size == 0:
            denom *= tp_size
        kv_block_bytes_chip = -(-full // denom)

    # Host-tier term (``kv_host_blocks > 0``, serve/tier.py): the
    # host-DRAM buffers parked prefixes spill into. Full-width per
    # host (the spill gather device_gets the sharded rows before the
    # numpy store), and host DRAM -- never part of the HBM verdict.
    kv_host_bytes = 0
    if kv_host_blocks:
        if not kv_blocks:
            raise ValueError(
                "a host KV tier needs the paged pool term too "
                "(kv_blocks > 0): the tier spills the paged pool's "
                "pages"
            )
        # The host buffers mirror the device pool's storage
        # (serve/tier.py allocates at the pool dtype, int8 included).
        kv_host_bytes = kv_paged_bytes(
            cfg, kv_host_blocks, kv_block_size, kv_cache_dtype,
            kv_quant,
        )

    # Speculative-draft term (``draft_cfg``, serve/spec.py): the
    # draft's serving params (fp32, TP-sharded over the model axis
    # where its heads divide, else replicated -- serve/weights.py's
    # layout, approximated at the whole-tree level) plus its mirrored
    # paged KV pool (same page COUNT as the target's -- the runner
    # mirrors admissions one-for-one -- but smaller pages: fewer
    # layers/heads).
    draft_params_chip = 0
    draft_kv_chip = 0
    draft_n_params = 0
    if draft_cfg is not None:
        if not kv_blocks:
            raise ValueError(
                "a speculative draft budget needs the paged pool "
                "term too (kv_blocks > 0): the draft's KV pool "
                "mirrors the target's pages"
            )
        draft_n_params = llama2.count_params(draft_cfg)
        tp_div = (
            tp_size
            if layout == "tp" and tp_size > 1
            and draft_cfg.n_heads % tp_size == 0 else 1
        )
        draft_params_chip = -(-draft_n_params * 4 // tp_div)
        # The mirror stores at the same discipline as the target pool
        # (a quantized deployment would quantize both or neither).
        full = kv_paged_bytes(
            draft_cfg, kv_blocks, kv_block_size, kv_cache_dtype,
            kv_quant,
        )
        kv_div = (
            tp_size
            if layout == "tp" and tp_size > 1
            and draft_cfg.kv_heads % tp_size == 0 else 1
        )
        draft_kv_chip = -(-full // kv_div)

    if layout == "pp":
        # The stage-shard byte accounting mirrors pp.stage_pspecs
        # (params stage-local, replicated over data -- the PP x DP
        # composition bench_llama_pp runs). With ``do_compile`` the
        # REAL stage-split Llama step (models/llama_pp.py through
        # pp.pipelined) is AOT-compiled on top, so the report carries
        # the compiler's own collective table and memory analysis like
        # the tp/cp layouts.
        f32 = 4
        mom = 2 if moments_dtype == "bfloat16" else 4
        p_stage = llama2.pp_worst_stage_params(cfg, tp_size)
        result = FitResult(
            cfg=cfg, dp=dp, tp_size=tp_size, global_batch=global_batch,
            seq_len=seq_len, hbm_gib=hbm_gib,
            n_params=llama2.count_params(cfg),
            param_bytes=p_stage * f32,
            grad_bytes=p_stage * f32,
            opt_bytes=p_stage * 2 * mom,
            act_bytes=activation_model_pp(
                cfg, dp, tp_size, global_batch, seq_len, grad_accum,
                pp_backward=pp_backward,
            ),
            grad_accum=grad_accum,
            moments_dtype=moments_dtype,
            layout="pp",
            attn=attn,
            kv_cache_bytes=kv_bytes_chip,
            kv_slots=kv_slots,
            kv_block_bytes=kv_block_bytes_chip,
            kv_blocks=kv_blocks,
            kv_block_size=kv_block_size if kv_blocks else 0,
            kv_quant=kv_quant if kv_blocks else "none",
            kv_host_blocks=kv_host_blocks,
            kv_host_bytes=kv_host_bytes,
            draft_n_params=draft_n_params,
            draft_param_bytes=draft_params_chip,
            draft_kv_block_bytes=draft_kv_chip,
        )
        result.compiler_options = dict(compiler_options or {})
        if not do_compile:
            return result
        return _compile_pp(
            result, cfg, dp, tp_size, global_batch, seq_len,
            microbatches=grad_accum, tpu_topology=tpu_topology,
            attn=attn, compiler_options=compiler_options,
            moments_dtype=moments_dtype, pp_backward=pp_backward,
        )

    abstract_params = jax.eval_shape(
        lambda: llama2.init_llama(jax.random.key(0), cfg)
    )
    n_params = llama2.count_params(cfg)
    mesh_axes = {"data": dp, axis2: tp_size}
    if layout == "cp":
        # Long-context layout: pure FSDP over data (the context axis
        # carries activations, not params).
        from tpu_hpc.parallel import fsdp as fsdp_mod

        specs = fsdp_mod.param_pspecs(
            abstract_params, axis="data", axis_size=dp
        )
    else:
        specs = hybrid.hybrid_pspecs(
            abstract_params, tp.llama_rules(), data_size=dp
        )
    # The Trainer's own AdamW construction (shared helper, so the fit
    # analysis can never drift from the step it certifies); bf16
    # moments halve the opt-state rows below -- the documented unlock
    # for 70B-class models on 16 GiB chips.
    from tpu_hpc.train.trainer import make_adamw

    optimizer = make_adamw(3e-4, 0.1, moments_dtype)
    opt_abstract = jax.eval_shape(optimizer.init, abstract_params)
    opt_specs = derived_pspecs(opt_abstract, abstract_params, specs)

    if layout == "cp":
        act = activation_model_cp(
            cfg, dp, tp_size, global_batch, seq_len, grad_accum
        )
    else:
        act = activation_model(
            cfg, dp, tp_size, global_batch, seq_len, grad_accum
        )
    grad_bytes = tree_bytes_per_chip(abstract_params, specs, mesh_axes)
    if grad_accum > 1:
        # The fp32 gradient-sum carry coexists with each microbatch's
        # freshly computed gradient inside the accumulation scan.
        act["grad_accum_sum_carry"] = grad_bytes
    result = FitResult(
        cfg=cfg, dp=dp, tp_size=tp_size, global_batch=global_batch,
        seq_len=seq_len, hbm_gib=hbm_gib, n_params=n_params,
        param_bytes=tree_bytes_per_chip(abstract_params, specs, mesh_axes),
        grad_bytes=grad_bytes,
        opt_bytes=tree_bytes_per_chip(opt_abstract, opt_specs, mesh_axes),
        act_bytes=act,
        grad_accum=grad_accum,
        moments_dtype=moments_dtype,
        layout=layout,
        kv_cache_bytes=kv_bytes_chip,
        kv_slots=kv_slots,
        kv_block_bytes=kv_block_bytes_chip,
        kv_blocks=kv_blocks,
        kv_block_size=kv_block_size if kv_blocks else 0,
        kv_quant=kv_quant if kv_blocks else "none",
        kv_host_blocks=kv_host_blocks,
        kv_host_bytes=kv_host_bytes,
        draft_n_params=draft_n_params,
        draft_param_bytes=draft_params_chip,
        draft_kv_block_bytes=draft_kv_chip,
    )
    if attn not in ("xla", "flash"):
        raise ValueError(f"unknown attn {attn!r} (xla|flash)")
    result.attn = attn
    result.compiler_options = dict(compiler_options or {})
    if not do_compile:
        return result

    # -- AOT compile the real step over the virtual pod mesh --
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.train.trainer import TrainState, make_step_fn

    n_dev = dp * tp_size
    devices = _resolve_devices(tpu_topology, n_dev, result)
    # build_mesh gives TPU device subsets (real or topology) ICI-aware
    # placement -- a flat reshape makes ring neighbors physically
    # distant, which v5e's limited ICI routing rejects outright for
    # async collective-permutes.
    mesh = build_mesh(
        MeshSpec(axes={"data": dp, axis2: tp_size}),
        devices=devices[:n_dev],
    )
    impl = "pallas" if tpu_topology else "auto"
    if layout == "cp":
        from tpu_hpc.parallel import ring_attention as ra

        constrain = ra.cp_constrain(mesh, "data", "context")
        attn_fn = ra.make_ring_attn_fn(
            mesh, "data", "context",
            impl=impl if attn == "flash" else "xla",
        )
        batch_spec = P("data", "context")
    else:
        constrain = tp.sp_constrain(
            mesh, dp_axis="data", sp_axis="model"
        )
        if attn == "flash":
            # impl pinned to "pallas": in a topology AOT compile no
            # backend is initialized, so blockwise_attention's "auto"
            # would pick the XLA path and silently defeat the point.
            attn_fn = tp.make_tp_flash_attn_fn(
                mesh, "data", "model", impl=impl,
            )
        else:
            attn_fn = None  # "xla": the model's einsum path
        batch_spec = P("data", None)
    forward = llama2.make_forward(cfg, constrain, attn_fn)
    micro_constrain = None
    if grad_accum > 1:
        from tpu_hpc.train.trainer import make_microbatch_constrain

        micro_constrain = make_microbatch_constrain(
            mesh, NamedSharding(mesh, batch_spec)
        )

    step = make_step_fn(
        forward, optimizer, seed=0,
        grad_accum=grad_accum, microbatch_constrain=micro_constrain,
    )

    state_abstract = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=abstract_params,
        opt_state=opt_abstract,
        model_state={},
    )
    state_shardings = TrainState(
        step=NamedSharding(mesh, P()),
        params=shardings_for(mesh, specs),
        opt_state=shardings_for(mesh, opt_specs),
        model_state={},
    )
    batch_abstract = tuple(
        jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)
        for _ in range(2)
    )
    batch_shardings = tuple(
        NamedSharding(mesh, batch_spec) for _ in range(2)
    )
    return _compile_and_record(
        result, step, state_abstract, state_shardings,
        batch_abstract, batch_shardings, compiler_options,
    )


def to_markdown(r: FitResult) -> str:
    cfg = r.cfg
    act_total = sum(r.act_bytes.values())
    chips = r.dp * r.tp_size
    size_b = f"{r.n_params/1e9:.0f}B"
    strategy = {
        "tp": "hybrid FSDPxTP(+SP)",
        "cp": "FSDP x ring-attention context parallel",
        "pp": "DP x pipeline (1F1B)",
    }[r.layout]
    axis2 = {"tp": "model", "cp": "context", "pp": "pipe"}[r.layout]
    lines = [
        f"# {size_b} shard/fit analysis -- Llama-2 {strategy} "
        f"on a {chips}-chip (data={r.dp} x {axis2}={r.tp_size}) mesh",
        "",
        "Produced by `python -m tpu_hpc.checks.fit`. Capability anchor "
        "(BASELINE.md): the reference's hybrid example "
        "(/root/reference/fsdp_tp/fsdp_tp_example.py:120-187) run at "
        "full scale (its ModelArgs ladder, llama2_model.py:13-16 and "
        "docs/guide/11_choosing_a_strategy.md:109-127), mapped to a "
        "TPU pod.",
        "",
        "## Configuration",
        "",
        f"- model: dim={cfg.dim}, layers={cfg.n_layers}, "
        f"heads={cfg.n_heads} (kv {cfg.kv_heads}), "
        f"ffn_hidden={cfg.ffn_hidden}, "
        f"vocab={cfg.vocab_size} -> **{r.n_params/1e9:.2f}B params**",
        f"- mesh: (data={r.dp}, {axis2}={r.tp_size}) = "
        f"{r.dp*r.tp_size} chips "
        + (
            "(FSDP over `data`, Megatron TP+SP over `model`)"
            if r.layout == "tp" else
            "(DP over `data`, stage-sharded layers over `pipe`: "
            f"each chip holds {cfg.n_layers//r.tp_size} of "
            f"{cfg.n_layers} layers)"
            if r.layout == "pp" else
            "(FSDP over `data`, ring attention over `context`: "
            f"each chip holds {r.seq_len//r.tp_size} of "
            f"{r.seq_len} tokens)"
        ),
        f"- batch: global {r.global_batch} sequences x {r.seq_len} "
        f"tokens (per-chip batch {r.global_batch//r.dp}"
        + (
            f", {r.grad_accum}-way gradient accumulation -> per-chip "
            f"microbatch {r.global_batch//r.dp//r.grad_accum}"
            if r.grad_accum > 1 else ""
        )
        + f"); remat={cfg.remat}, bf16 compute / fp32 params",
        "",
        "## Per-chip HBM budget",
        "",
        "| Component | Bytes | GiB |",
        "|---|---|---|",
        f"| params (fp32, "
        + {"tp": "FSDPxTP-sharded", "cp": "FSDP-sharded",
           "pp": "stage-sharded, worst stage"}[r.layout] + ") "
        f"| {r.param_bytes:,} | "
        f"{r.param_bytes/GIB:.2f} |",
        f"| gradients (fp32, same layout) | {r.grad_bytes:,} | "
        f"{r.grad_bytes/GIB:.2f} |",
        f"| AdamW mu+nu ({'bf16' if r.moments_dtype == 'bfloat16' else 'fp32'}, "
        f"same layout) | {r.opt_bytes:,} | "
        f"{r.opt_bytes/GIB:.2f} |",
    ]
    for name, b in r.act_bytes.items():
        lines.append(f"| activations: {name} | {b:,} | {b/GIB:.2f} |")
    if r.kv_cache_bytes and not r.kv_blocks:
        lines.append(
            f"| KV cache (decode, {r.kv_slots} slots) | "
            f"{r.kv_cache_bytes:,} | {r.kv_cache_bytes/GIB:.2f} |"
        )
    if r.kv_blocks:
        quant_tag = (
            ", int8 + fp32 scales" if r.kv_quant == "int8" else ""
        )
        lines.append(
            f"| KV cache (paged, {r.kv_blocks} pages x "
            f"{r.kv_block_size} tok{quant_tag}) | "
            f"{r.kv_block_bytes:,} | {r.kv_block_bytes/GIB:.2f} |"
        )
    if r.draft_param_bytes:
        # The speculative-draft budget (serve/spec.py): params + the
        # mirrored paged pool. Landing here means a too-big draft
        # flips the verdict below to DOES NOT FIT -- the whole point.
        lines.append(
            f"| spec draft params ({r.draft_n_params/1e9:.2f}B, "
            f"fp32 serving layout) | {r.draft_param_bytes:,} | "
            f"{r.draft_param_bytes/GIB:.2f} |"
        )
        lines.append(
            f"| spec draft KV pool (mirrored {r.kv_blocks} pages) | "
            f"{r.draft_kv_block_bytes:,} | "
            f"{r.draft_kv_block_bytes/GIB:.2f} |"
        )
    kv_live = r.kv_block_bytes if r.kv_blocks else r.kv_cache_bytes
    lines += [
        f"| **total** | **{r.total_bytes:,}** | "
        f"**{r.total_bytes/GIB:.2f}** |",
        "",
        f"Against **{r.hbm_gib:.0f} GiB** HBM per chip: "
        f"**{'FITS' if r.fits else 'DOES NOT FIT'}** "
        f"({r.total_bytes/ (r.hbm_gib*GIB) * 100:.1f}% of HBM; "
        f"static {r.static_bytes/GIB:.2f} GiB + activations "
        f"{act_total/GIB:.2f} GiB"
        + (
            f" + decode KV cache {kv_live/GIB:.2f} GiB"
            if kv_live else ""
        )
        + (
            f" + spec draft {(r.draft_param_bytes + r.draft_kv_block_bytes)/GIB:.2f} GiB"
            if r.draft_param_bytes else ""
        )
        + ").",
    ]
    if r.kv_blocks and r.kv_cache_bytes:
        # The fragmentation-headroom comparison: same traffic, two
        # cache disciplines, compared as LOGICAL capacity bytes --
        # per-chip numbers would mix different shardings (the slab
        # shards slots over data, the pool replicates per data
        # replica) and mislabel a correctly sized pool at dp > 1
        # (review finding). Reconstruct the unsharded totals from the
        # per-chip values and the denominators analyze() applied.
        tp_div = (
            r.tp_size
            if r.layout == "tp" and r.tp_size > 1
            and cfg.kv_heads % r.tp_size == 0 else 1
        )
        # Per DATA REPLICA: the slab's per-chip term already divides
        # by dp (slots shard over data) and tp; multiplying tp back
        # gives the replica's slab share. The pool IS per-replica by
        # construction, so the same multiply makes the two directly
        # comparable at every dp.
        slab_replica = r.kv_cache_bytes * tp_div
        paged_replica = r.kv_block_bytes * tp_div
        saved = slab_replica - paged_replica
        lines += [
            "",
            f"Fragmentation headroom (per data replica -- the slab "
            f"shards slots over data while each replica runs its own "
            f"pool, so raw per-chip numbers are not comparable): the "
            f"slab's replica share ({r.kv_slots} slots over "
            f"dp={r.dp}, worst-case length) pins {slab_replica:,} "
            f"bytes ({slab_replica/GIB:.2f} GiB); the paged pool "
            f"({r.kv_blocks} pages x {r.kv_block_size} tokens) holds "
            f"the same share in {paged_replica:,} bytes "
            f"({paged_replica/GIB:.2f} GiB) -- "
            + (
                f"**{saved:,} bytes ({saved/GIB:.2f} GiB) of "
                "slack/fragmentation reclaimed** for more concurrent "
                "requests at equal HBM."
                if saved >= 0 else
                f"**{-saved:,} bytes ({-saved/GIB:.2f} GiB) MORE** "
                "than the slab share -- this pool out-provisions the "
                "mix; shrink --kv-blocks."
            ),
        ]
    if r.kv_blocks and r.kv_quant == "int8":
        # The quantized-capacity line (tpu_hpc.kernels.paged_
        # attention): int8 pages + per-page fp32 scales vs the same
        # page count at bf16 -- the multiplier is how many MORE
        # resident tokens the same HBM seats, the number --kv-quant
        # exists to print. Full-pod bytes on both sides (one
        # sharding), so the ratio is sharding-independent.
        q_full = kv_paged_bytes(
            cfg, r.kv_blocks, r.kv_block_size, kv_quant="int8"
        )
        fp_full = kv_paged_bytes(
            cfg, r.kv_blocks, r.kv_block_size, "bfloat16"
        )
        q_pages_equal_hbm = fp_full * r.kv_blocks // q_full
        lines += [
            "",
            f"Quantized KV capacity (int8 pages + per-page fp32 "
            f"scales): the {r.kv_blocks:,}-page pool stores "
            f"{q_full:,} bytes ({q_full/GIB:.2f} GiB) vs {fp_full:,} "
            f"bytes ({fp_full/GIB:.2f} GiB) at bf16 -- the bf16 "
            f"pool's HBM seats **{q_pages_equal_hbm:,} int8 pages, "
            f"{fp_full/q_full:.1f}x the resident context at equal "
            f"HBM**.",
        ]
    if r.kv_host_blocks:
        # The tier's sizing line: host DRAM buys parked-session KV
        # capacity at ZERO HBM cost, so the multiplier is the page
        # ratio (minus each pool's scratch slot). This is the number
        # --kv-host-tier exists to print: how many more idle sessions
        # stay resident (return visits prefetch their prefix back
        # instead of re-prefilling) at the same device pool.
        dev_pages = max(r.kv_blocks - 1, 1)
        host_pages = max(r.kv_host_blocks - 1, 0)
        mult = (dev_pages + host_pages) / dev_pages
        lines += [
            "",
            f"Host KV tier (serve/tier.py): {r.kv_host_blocks} host "
            f"slots x {r.kv_block_size} tokens = "
            f"{r.kv_host_bytes:,} bytes ({r.kv_host_bytes/GIB:.2f} "
            f"GiB) of host DRAM per host -- NOT in the HBM total "
            f"above. Parked-session KV capacity: {dev_pages:,} "
            f"device pages HBM-only vs {dev_pages + host_pages:,} "
            f"pages with the tier -- **{mult:.1f}x the resident "
            f"sessions** at equal HBM.",
        ]
    lines += [
        "",
        "Static accounting is exact (eval_shape + the PartitionSpec "
        "plan); the activation rows are the analytic model described "
        + {
            "tp": "in `tpu_hpc/checks/fit.py:activation_model` "
            "(remat-per-block, SP-sharded residual checkpoints, flash "
            "attention).",
            "cp": "in `tpu_hpc/checks/fit.py:activation_model_cp` "
            "(remat-per-block, context-sharded residual stream, "
            "double-buffered KV ring, full-width FFN/vocab).",
            "pp": "in `tpu_hpc/checks/fit.py:activation_model_pp` "
            "(1F1B: up to `stages` in-flight microbatches of stage "
            "checkpoints, custom-vjp backward remat, full seq/chip).",
        }[r.layout],
    ]
    if r.compiled:
        lines += [
            "",
            "## Compile evidence",
            "",
            f"The real Trainer step (`train.trainer.make_step_fn`) was "
            f"AOT-lowered and XLA-compiled against the "
            f"{r.dp}x{r.tp_size} mesh in {r.compile_seconds:.1f}s "
            f"(SPMD partitioning enabled; backend: "
            f"**{r.compile_backend}**; attention path: {r.attn}"
            + (
                f"; compiler options: "
                + ", ".join(f"{k}={v}" for k, v in
                            sorted(r.compiler_options.items()))
                if r.compiler_options else ""
            )
            + "). XLA's per-chip argument "
            f"accounting: {r.xla_argument_bytes:,} bytes "
            f"({r.xla_argument_bytes/GIB:.2f} GiB) -- cross-checks the "
            "static rows above (params + opt state + batch)."
            + (
                f" Compiler temp/scratch accounting: "
                f"{r.xla_temp_bytes:,} bytes "
                f"({r.xla_temp_bytes/GIB:.2f} GiB) -- the compiler's "
                "own view of the activation/workspace footprint."
                if r.xla_temp_bytes else ""
            ),
            "",
            "Collectives in the compiled module (op applications):",
            "",
            "| op | count |",
            "|---|---|",
        ]
        for op, n in r.collectives.items():
            lines.append(f"| {op} | {n} |")
        # State only what this compile evidenced: on the CPU simulator
        # XLA legalizes reduce-scatter to all-reduce+slice, so a
        # reduce-scatter count of 0 there is a backend artifact, and
        # the fixed "matches the plan" sentence would overstate it.
        plan = (
            "all-gathers for FSDP param gathering + SP boundary "
            "gathers, reduce-scatter/all-reduce pairs for the TP "
            "block reductions and FSDP gradient scatter."
            if r.layout == "tp" else
            "collective-permutes for the KV ring rotation, "
            "all-gathers for FSDP param gathering, "
            "reduce-scatter/all-reduce for the FSDP gradient "
            "reduction."
        )
        if r.collectives.get("reduce-scatter", 0) > 0:
            conclusion = (
                "The signature matches the plan: " + plan
                + (
                    " This is the real TPU lowering (libtpu compiled "
                    "against the virtual topology), so the "
                    "reduce-scatter form is directly evidenced."
                    if r.compile_backend.startswith("tpu-topology")
                    else ""
                )
            )
        else:
            conclusion = (
                "The planned signature is: " + plan
                + " Every reduction was legalized to all-reduce by "
                "this backend (reduce-scatter: 0 -- on the CPU "
                "simulator XLA lowers reduce-scatter to "
                "all-reduce+slice, so this compile does not evidence "
                "the reduce-scatter form; an on-TPU compile is "
                "needed for that)."
            )
        lines += ["", conclusion]
    return "\n".join(lines) + "\n"


# (model preset, dp, tp, grad_accum): the TPU version of the
# reference's planning ladder (docs/guide/11_choosing_a_strategy.md:
# 109-127, "7B: TP4xFSDP4 ... 70B: TP4xFSDP20"). TP stays within the
# head-divisibility limits; chips = dp*tp; per-chip batch 8 at seq
# 4096 (the REPORT_7b_v4-32.md working configuration).
_TABLE_ROWS = (
    ("7b", 2, 4, 1),     # 8 chips: the minimal-footprint 7B config
    ("7b", 4, 8, 1),     # v4-32, the north star (REPORT_7b_v4-32.md)
    ("13b", 4, 4, 1),    # 16 chips
    ("13b", 8, 8, 1),    # 64 chips, roomy
    ("70b", 8, 8, 1),    # 64 chips: minimal 70B footprint
    ("70b", 16, 8, 1),   # 128 chips (v4-256 class)
)


def sizing_table(
    seq_len: int = 4096, hbm_gib: float = 32.0
) -> str:
    """Computed (not hand-waved) strategy ladder: for each row the
    analytic shard/fit analysis runs at per-chip batch 8 (the
    REPORT_7b_v4-32.md working configuration), and the table records
    the verdict against ``hbm_gib``. Regenerate
    docs/guide/11_choosing_a_strategy.md with
    ``python -m tpu_hpc.checks.fit --table``."""
    lines = [
        "| Model | params | chips | mesh | per-chip state | "
        f"per-chip total | fits {hbm_gib:.0f} GiB? |",
        "|---|---|---|---|---|---|---|",
    ]
    for name, dp, tp_size, accum in _TABLE_ROWS:
        cfg = dataclasses.replace(
            llama2.PRESETS[name], max_seq_len=seq_len
        )
        r = analyze(
            cfg=cfg, dp=dp, tp_size=tp_size,
            global_batch=8 * dp * accum, seq_len=seq_len,
            hbm_gib=hbm_gib, do_compile=False, grad_accum=accum,
        )
        mesh = f"`{{data: {dp}, model: {tp_size}}}`" + (
            f" + accum {accum}" if accum > 1 else ""
        )
        lines.append(
            f"| {name} | {r.n_params/1e9:.1f}B | {dp*tp_size} | {mesh} "
            f"| {r.static_bytes/GIB:.1f} GiB | {r.total_bytes/GIB:.1f} "
            f"GiB | {'yes' if r.fits else 'NO'} |"
        )
    return "\n".join(lines)


def _parse_xla_opts(opts) -> Optional[Dict[str, str]]:
    parsed = {}
    for opt in opts:
        key, sep, val = opt.partition("=")
        if not sep or not key:
            raise SystemExit(
                f"--xla-opt expects KEY=VALUE, got {opt!r} "
                "(e.g. xla_tpu_enable_latency_hiding_scheduler=false)"
            )
        parsed[key] = val
    return parsed or None


def main(argv=None) -> int:
    import sys

    if argv is None:
        argv = sys.argv[1:]
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--dp", type=int, default=4)
    parser.add_argument("--tp", type=int, default=8)
    parser.add_argument("--cp", type=int, default=0,
                        help="context-parallel ring degree: switches "
                        "to the long-context layout (FSDP over data x "
                        "ring attention over context; no TP) and "
                        "replaces --tp as the second mesh axis")
    parser.add_argument("--pp", type=int, default=0,
                        help="pipeline stage count: switches to the "
                        "PP x DP layout (stage-sharded params, "
                        "--grad-accum = microbatch count); the compile "
                        "pass AOT-compiles the real stage-split Llama "
                        "1F1B step (models/llama_pp.py)")
    parser.add_argument("--global-batch", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=4096)
    parser.add_argument("--hbm-gib", type=float, default=32.0)
    parser.add_argument("--layers", type=int, default=None,
                        help="override n_layers (default: 7B's 32)")
    parser.add_argument("--dim", type=int, default=None,
                        help="override model dim (with --heads/"
                        "--vocab, analyzes arbitrary architectures, "
                        "e.g. the bench model: --dim 1024 --layers 8 "
                        "--heads 8)")
    parser.add_argument("--heads", type=int, default=None)
    parser.add_argument("--kv-heads", type=int, default=None)
    parser.add_argument("--vocab", type=int, default=None)
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="analyze the N-way accumulated step")
    parser.add_argument("--model", type=str, default=None,
                        choices=sorted(llama2.PRESETS),
                        help="model preset (default: 7B)")
    parser.add_argument("--table", action="store_true",
                        help="print the computed 7B..70B sizing table "
                        "(analytic only, no compile) and exit")
    parser.add_argument("--no-compile", action="store_true")
    parser.add_argument("--markdown", type=str, default=None,
                        help="write the report to this path")
    parser.add_argument("--json", action="store_true",
                        help="print one JSON line instead of the report")
    parser.add_argument("--tpu-topology", type=str, default=None,
                        help="AOT-compile against a virtual TPU "
                        "topology (e.g. v5e:4x8) via libtpu -- no "
                        "chips needed; collective counts show the "
                        "real TPU lowering incl. reduce-scatters")
    parser.add_argument("--attn", choices=("xla", "flash"),
                        default="xla",
                        help="attention path for the compile pass: "
                        "'flash' = the production Pallas kernel under "
                        "shard_map (heads on the TP axis; under --cp "
                        "it runs inside the KV ring with full-width "
                        "heads)")
    parser.add_argument("--moments-dtype",
                        choices=("float32", "bfloat16"),
                        default="float32",
                        help="AdamW moment storage dtype; bfloat16 "
                        "halves optimizer-state HBM")
    parser.add_argument("--pp-backward", choices=("remat", "stash"),
                        default="remat",
                        help="1f1b backward for --pp accounting: remat "
                        "saves stage inputs only; stash adds the vjp-"
                        "residual buffers (Megatron-style) to the HBM "
                        "model")
    parser.add_argument("--kv-slots", type=int, default=0,
                        help="add a decode-config KV-cache term: "
                        "batch slots of a co-resident serving engine "
                        "(0 = no serving, the training-only budget)")
    parser.add_argument("--kv-seq-len", type=int, default=None,
                        help="KV-cache capacity per slot "
                        "(default: the model's max_seq_len)")
    parser.add_argument("--kv-cache-dtype",
                        choices=("bfloat16", "float32"),
                        default="bfloat16",
                        help="KV-cache storage dtype")
    parser.add_argument("--kv-blocks", type=int, default=0,
                        help="add a PAGED decode KV-cache term "
                        "instead of the slab: physical pages of a "
                        "co-resident paged serving engine "
                        "(tpu_hpc/serve/paging.py); with --kv-slots "
                        "also given, the report adds the "
                        "fragmentation-headroom comparison line")
    parser.add_argument("--kv-block-size", type=int, default=16,
                        help="tokens per page for --kv-blocks "
                        "(default 16)")
    parser.add_argument("--kv-quant", choices=("none", "int8"),
                        default=None,
                        help="paged page storage "
                        "(tpu_hpc.kernels.paged_attention): 'int8' "
                        "budgets 1-byte pages + per-page fp32 scales "
                        "-- about half the pool bytes, ~2x the "
                        "resident context at equal HBM; the report "
                        "adds the quantized-capacity line (requires "
                        "--kv-blocks)")
    parser.add_argument("--kv-host-tier", type=int, default=0,
                        metavar="N",
                        help="budget a host-DRAM KV page tier "
                        "(serve/tier.py): N host slots incl. scratch "
                        "that parked session prefixes spill into; "
                        "reported as host DRAM next to the HBM "
                        "verdict with the resident-sessions "
                        "multiplier (requires --kv-blocks)")
    parser.add_argument("--spec-draft", type=str, default=None,
                        choices=("half", *sorted(llama2.PRESETS)),
                        help="budget a speculative-decode draft model "
                        "(serve/spec.py) co-resident with this "
                        "config: its fp32 serving params + a KV pool "
                        "mirroring --kv-blocks. 'half' = the target "
                        "at half depth (the dev default); a draft "
                        "that does not fit fails this report instead "
                        "of OOMing at serving bring-up (requires "
                        "--kv-blocks)")
    parser.add_argument("--xla-opt", action="append", default=[],
                        metavar="KEY=VALUE",
                        help="extra XLA compiler option for the "
                        "compile pass (repeatable), e.g. "
                        "--xla-opt xla_tpu_enable_latency_hiding_"
                        "scheduler=false to trade collective overlap "
                        "for a lower HBM temp watermark")
    args = parser.parse_args(argv)

    if args.table:
        print(sizing_table(seq_len=args.seq_len, hbm_gib=args.hbm_gib))
        return 0

    # Self-provision the virtual pod for the compile pass: flip this
    # process to the simulated CPU backend if it's still pluripotent,
    # else re-exec in a child that comes up simulated. A TPU-topology
    # compile needs no devices at all -- libtpu compiles against the
    # topology description -- so skip provisioning entirely.
    if args.pp and args.cp:
        parser.error("--pp and --cp are mutually exclusive")
    if not args.no_compile and args.tpu_topology is None:
        from tpu_hpc.runtime import sim

        n_dev = args.dp * (args.pp or args.cp or args.tp)
        if not sim.backends_initialized():
            sim.force_sim_devices(n_dev)
        elif len(jax.devices()) < n_dev:
            proc = sim.run_in_sim_subprocess(
                ["-m", "tpu_hpc.checks.fit", *argv], n_dev
            )
            print(proc.stdout, end="")
            print(proc.stderr, end="", file=sys.stderr)
            return proc.returncode

    if args.model is not None:
        cfg = dataclasses.replace(
            llama2.PRESETS[args.model], max_seq_len=args.seq_len
        )
    else:
        cfg = llama2.LlamaConfig(max_seq_len=args.seq_len, remat=True)
    overrides = {
        k: v for k, v in (
            ("n_layers", args.layers), ("dim", args.dim),
            ("n_heads", args.heads), ("n_kv_heads", args.kv_heads),
            ("vocab_size", args.vocab),
        ) if v is not None
    }
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if args.kv_host_tier and not args.kv_blocks:
        parser.error(
            "--kv-host-tier needs --kv-blocks: the tier spills the "
            "paged pool's pages"
        )
    if args.kv_quant is not None and not args.kv_blocks:
        parser.error(
            "--kv-quant needs --kv-blocks: only paged pages quantize "
            "(tpu_hpc.kernels.paged_attention)"
        )
    draft_cfg = None
    if args.spec_draft is not None:
        if not args.kv_blocks:
            parser.error(
                "--spec-draft needs --kv-blocks: the draft's KV pool "
                "mirrors the target's paged pool"
            )
        if args.spec_draft == "half":
            from tpu_hpc.serve.spec import default_draft_config

            draft_cfg = default_draft_config(cfg)
        else:
            draft_cfg = dataclasses.replace(
                llama2.PRESETS[args.spec_draft],
                max_seq_len=args.seq_len,
            )
    r = analyze(
        cfg=cfg, dp=args.dp, tp_size=args.pp or args.cp or args.tp,
        global_batch=args.global_batch, seq_len=args.seq_len,
        hbm_gib=args.hbm_gib, do_compile=not args.no_compile,
        grad_accum=args.grad_accum, tpu_topology=args.tpu_topology,
        attn=args.attn,
        compiler_options=_parse_xla_opts(args.xla_opt),
        moments_dtype=args.moments_dtype,
        layout="pp" if args.pp else ("cp" if args.cp else "tp"),
        pp_backward=args.pp_backward,
        kv_slots=args.kv_slots,
        kv_seq_len=args.kv_seq_len,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_blocks=args.kv_blocks,
        kv_block_size=args.kv_block_size,
        kv_quant=args.kv_quant or "none",
        kv_host_blocks=args.kv_host_tier,
        draft_cfg=draft_cfg,
    )
    md = to_markdown(r)
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(md)
    if args.json:
        print(json.dumps(r.to_json()))
    else:
        print(md)
    return 0 if r.fits else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
