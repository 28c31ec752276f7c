"""Llama-2 transformer: the north-star LLM workload.

Capability parity with the reference's Llama-2 implementation
(fsdp_tp/llama2_model.py, identical copy in scripts/06_hybrid_parallelism/):
ModelArgs surface (:12-27), RoPE (:30-100), GQA via grouped KV heads
(:103-112), RMSNorm (:115-142), causal attention (:145-228), SwiGLU
FeedForward with the 2/3 rule + multiple_of rounding (:231-272),
depth-scaled residual-output init (:275-345), trunc-normal output head
(:348-448).

TPU-first design (not a translation):
  * flax.linen functional modules; params are an explicit pytree so TP
    is a PartitionSpec plan over param paths (parallel/tp.py), not a
    module-wrapping pass.
  * bf16 compute / fp32 params + fp32 RoPE and softmax; matmuls land on
    the MXU in bf16, reductions stay fp32.
  * RoPE carried as real cos/sin pairs (complex64 never touches the
    TPU vector unit well); computed at trace time, constant-folded.
  * separate wq/wk/wv projections (same deliberate choice as the
    reference's ViT :93-110 -- head-dim sharding stays clean under TP).
  * an optional ``constrain`` hook threads activation sharding
    constraints (Megatron-SP sequence sharding) through the block
    structure without the model knowing about meshes.
  * optional ``remat`` (jax.checkpoint) per block: every block saves
    its input and recomputes the rest in the backward pass, except the
    leading blocks whose six matmul outputs a Trainer finds room to
    keep (models/remat.py: the count comes from the bytes the chip has
    left, through the trace, never from a field here).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tpu_hpc.models import remat

Constrain = Callable[[jax.Array], jax.Array]
# (q [B,S,Hq,D], k [B,S,Hkv,D], v) -> [B,S,Hq,D]; plugs ring/Ulysses
# sequence-parallel attention (parallel/ring_attention.py,
# parallel/sp_ulysses.py) into the block without the model knowing
# about meshes. None -> local full attention.
AttnFn = Optional[Callable[[jax.Array, jax.Array, jax.Array], jax.Array]]


def _identity(x: jax.Array) -> jax.Array:
    return x


# Long-lived processes (the serving engine, multi-config sweeps) keep
# hitting these module-level caches with fresh keys; unbounded, they
# grow for the life of the process. The bounds are sized far above any
# real working set (a server runs ONE config; a sweep runs a handful),
# so steady state never evicts -- and eviction is SAFE anyway: each
# entry is recomputed from its key alone. The one subtlety is
# _make_embed_lookup, whose cache also provides function identity --
# an evicted-and-rebuilt lookup is a new callable, so a jit tracing it
# recompiles (correctness unaffected; tests/test_models.py pins both
# properties).
_CACHE_MAXSIZE = 64


@functools.lru_cache(maxsize=_CACHE_MAXSIZE)
def _make_embed_lookup(vocab: int, table_dtype: str):
    """table[tokens] with a scatter-free backward (see
    LlamaConfig.iota_embed). Factory keyed on the static (vocab,
    dtype) so the custom_vjp residual is just the token array AND so
    repeated traces see the same callable (stable jit cache keys)."""

    @jax.custom_vjp
    def lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
        return jnp.take(table, tokens, axis=0)

    def fwd(table, tokens):
        return lookup(table, tokens), tokens

    def bwd(tokens, g):
        # dtable[v] = sum over positions with token v of g --
        # expressed as one MXU matmul (one-hot rows are exact
        # selectors) instead of the gather-transpose scatter-add.
        # The (B, S) dims are contracted in place rather than
        # flattened first: under Megatron-SP the cotangent arrives
        # sharded (data, model, None), and a flattening reshape merges
        # two differently-sharded dims -- SPMD can only resolve that by
        # replicating the whole tensor (involuntary full
        # rematerialization). Contracting dims never merge, so each
        # device keeps its (batch, seq) tile and the partial dtables
        # meet in one psum.
        onehot = jax.nn.one_hot(tokens, vocab, dtype=g.dtype)
        batch_dims = tuple(range(g.ndim - 1))
        dtable = jax.lax.dot_general(
            onehot, g,
            ((batch_dims, batch_dims), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dtable.astype(table_dtype), None

    lookup.defvjp(fwd, bwd)
    return lookup


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Parity with ModelArgs (fsdp_tp/llama2_model.py:12-27); defaults
    are the 7B configuration, examples run it tiny."""

    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None  # None -> MHA; < n_heads -> GQA
    vocab_size: int = 32000
    multiple_of: int = 256
    ffn_dim_multiplier: Optional[float] = None
    norm_eps: float = 1e-5
    max_seq_len: int = 32768
    depth_init: bool = True
    dtype: Any = jnp.bfloat16       # compute dtype (the reference's
    param_dtype: Any = jnp.float32  # use_amp/amp_dtype pair, utils/config.py:40-44)
    # False: every activation is kept for the backward pass. True:
    # recompute what does not fit -- blocks keep their matmul outputs
    # while the Trainer lowering the step finds room (models/remat.py);
    # traced anywhere else, every block recomputes.
    remat: bool = False
    # Matmul-backward embedding lookup: forward is a plain gather
    # (cheap on TPU), but the gradient is computed as one_hot^T @ g on
    # the MXU instead of the gather's transpose scatter-add (TPU
    # scatters serialize; ~5x step slowdown measured). Forward-side
    # one-hot (the naive iota-embed trick) would burn an extra
    # 2*d*vocab FLOPs/token and a [B, S, V] buffer for no benefit.
    iota_embed: bool = True

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def ffn_hidden(self) -> int:
        """SwiGLU 2/3 rule + multiple_of rounding (reference :231-272)."""
        hidden = int(2 * (4 * self.dim) / 3)
        if self.ffn_dim_multiplier is not None:
            hidden = int(self.ffn_dim_multiplier * hidden)
        return self.multiple_of * (
            (hidden + self.multiple_of - 1) // self.multiple_of
        )

    def flops_per_token(self, seq_len: Optional[int] = None) -> int:
        """Training FLOPs/token (forward matmul count x 3 for fwd+bwd,
        the 6ND convention) including the causal attention-score/AV
        term at ``seq_len`` (defaults to max_seq_len) -- the
        denominator of MFU accounting."""
        s = seq_len if seq_len is not None else self.max_seq_len
        d, h = self.dim, self.ffn_hidden
        per_layer = (
            2 * d * (self.n_heads + 2 * self.kv_heads) * self.head_dim  # qkv
            + 2 * d * d  # wo
            + 3 * 2 * d * h  # w1,w3,w2
            # QK^T + AV: 2 x 2*S*dim per token, halved by causal mask.
            + 2 * s * d
        )
        embed = 2 * d * self.vocab_size
        return 3 * (self.n_layers * per_layer + embed)


# Llama-2 family shapes (public architecture constants; the reference
# ships only the 7B defaults, fsdp_tp/llama2_model.py:13-16, but its
# planning tables reason about 7B..70B -- docs/guide/
# 11_choosing_a_strategy.md:109-127). 70B is GQA (8 KV heads) with the
# 1.3x/4096-rounded SwiGLU -> ffn_hidden 28672. max_seq_len 4096 = the
# Llama-2 context window; remat on, the configuration large models run.
PRESETS: Dict[str, LlamaConfig] = {
    "7b": LlamaConfig(max_seq_len=4096, remat=True),
    "13b": LlamaConfig(
        dim=5120, n_layers=40, n_heads=40, max_seq_len=4096, remat=True
    ),
    "70b": LlamaConfig(
        dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        ffn_dim_multiplier=1.3, multiple_of=4096,
        max_seq_len=4096, remat=True,
    ),
}


@functools.lru_cache(maxsize=_CACHE_MAXSIZE)
def count_params(cfg: "LlamaConfig") -> int:
    """Total trainable parameters for ``cfg``, via eval_shape of the
    real init (no arrays materialized). The single source both
    checks/fit.py (HBM accounting) and checks/roofline.py (memory
    bound) divide by -- two copies would silently disagree the day
    the param tree changes."""
    import numpy as np

    abstract = jax.eval_shape(
        lambda: init_llama(jax.random.key(0), cfg)
    )
    return sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(abstract)
    )


@functools.lru_cache(maxsize=_CACHE_MAXSIZE)
def count_params_by_part(cfg: "LlamaConfig") -> "Mapping[str, int]":
    """Param counts split by pipeline role: one transformer layer
    (``per_layer``), the token embedding (``embed``), the LM head
    (``head``), and everything else (``other``, the final norm).
    Source for the pipeline-parallel stage-shard accounting in
    checks/fit.py and checks/roofline.py -- derived from the same
    eval_shape tree as count_params, so
    ``per_layer * n_layers + embed + head + other == count_params``.
    Returns an immutable view: the lru_cache hands every caller the
    same object, so a mutable dict would let one caller poison
    pp_worst_stage_params for all later calls."""
    import types

    import numpy as np

    abstract = jax.eval_shape(
        lambda: init_llama(jax.random.key(0), cfg)
    )
    parts = {"per_layer": 0, "embed": 0, "head": 0, "other": 0}
    for key, sub in abstract.items():
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(sub))
        if key == "layers_0":
            parts["per_layer"] = n
        elif key.startswith("layers_"):
            pass  # identical to layers_0 by construction
        elif key == "tok_embeddings":
            parts["embed"] = n
        elif key == "output":
            parts["head"] = n
        else:
            parts["other"] += n
    return types.MappingProxyType(parts)


def pp_worst_stage_params(cfg: "LlamaConfig", stages: int) -> int:
    """Params the fullest pipeline stage holds: its share of the
    layers plus the embed/head edge weights (BOTH on one chip when
    stages == 1; otherwise the bigger of the two, since embed and
    head live on opposite ends of the pipe). The single source for
    the pp byte accounting in checks/fit.py and checks/roofline.py --
    two copies would silently disagree on per-chip bytes."""
    if stages < 1 or cfg.n_layers % stages:
        raise ValueError(
            f"pipeline needs n_layers {cfg.n_layers} divisible by "
            f"the stage count {stages}"
        )
    parts = count_params_by_part(cfg)
    edge = (
        parts["embed"] + parts["head"] if stages == 1
        else max(parts["embed"], parts["head"])
    )
    return (
        parts["per_layer"] * (cfg.n_layers // stages)
        + edge + parts["other"]
    )


def rope_cos_sin(
    seq_len: int,
    head_dim: int,
    theta: float = 10000.0,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """RoPE tables as fp32 (cos, sin) of shape [seq, head_dim//2].

    Parity: precompute_freqs_cis (reference :30-55); real-pair form
    instead of complex64 -- the rotation is two fused multiply-adds.
    ``positions`` overrides the default 0..seq_len-1 ramp: slot p gets
    the rotation of global position positions[p]. This is what lets a
    permuted token layout (zigzag ring sharding, packed sequences)
    keep exact RoPE without un-permuting activations per layer.
    """
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if positions is None:
        t = jnp.arange(seq_len, dtype=jnp.float32)
    else:
        t = positions.astype(jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [B, S, H, D] by position. Adjacent-pair convention, fp32
    rotation, result cast back (parity: apply_rotary_emb :58-100).
    ``cos``/``sin`` are [S, D//2] tables shared across the batch, or
    [B, S, D//2] PER-ROW tables (the serving engine's decode step,
    where each batch slot sits at its own position)."""
    orig_dtype = x.dtype
    # Adjacent pairs via a trailing [D//2, 2] reshape -- identical
    # values to the x[..., 0::2]/[..., 1::2] formulation but with
    # contiguous (not lane-strided) access on the minor dim.
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1 = xf[..., 0]
    x2 = xf[..., 1]
    # [.., S, D/2] -> [.., S, 1, D/2]: broadcasts over heads either
    # way, and over batch for the shared-table shape.
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x1 * s + x2 * c
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(orig_dtype)


class RMSNorm(nn.Module):
    """RMSNorm computed in fp32 with a learned scale (parity:
    reference :115-142)."""

    eps: float = 1e-5
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],),
            self.param_dtype,
        ).astype(jnp.float32)
        xf = x.astype(jnp.float32)
        normed = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps
        )
        return (normed * scale).astype(x.dtype)


def _kept(x: jax.Array, name: str, keeps: bool) -> jax.Array:
    """Tag one of the products a keeping block saves (remat.py). Only a
    keeping block tags: every other trace holds no ``name`` equation
    and lowers to the text it always did."""
    return checkpoint_name(x, name) if keeps else x


def _dense(
    features: int, std: float, cfg: "LlamaConfig", name: str
) -> nn.Dense:
    """Bias-free projection with a given init std (the reference's
    nn.init.normal_/trunc_normal_ per-layer std scheme :275-345)."""
    return nn.Dense(
        features,
        use_bias=False,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=nn.initializers.normal(stddev=std),
        name=name,
    )


class Attention(nn.Module):
    """Causal self-attention with RoPE and grouped KV heads.

    Parity: reference Attention (:145-228). GQA is expressed as an
    einsum over a [B, S, Hkv, G, D] query view -- no materialised
    repeat_kv copy (:103-112); XLA broadcasts K/V over the group dim.
    """

    cfg: LlamaConfig
    out_std: float
    attn_fn: AttnFn = None
    keeps: bool = False  # see TransformerBlock.keeps

    @nn.compact
    def __call__(
        self, x: jax.Array, positions: Optional[jax.Array] = None
    ) -> jax.Array:
        cfg = self.cfg
        b, s, _ = x.shape
        hd = cfg.head_dim
        n_kv = cfg.kv_heads
        groups = cfg.n_heads // n_kv
        std = 0.02

        with jax.named_scope("qkv"):
            q = _dense(cfg.n_heads * hd, std, cfg, "wq")(x)
            k = _dense(n_kv * hd, std, cfg, "wk")(x)
            v = _dense(n_kv * hd, std, cfg, "wv")(x)

            cos, sin = rope_cos_sin(s, hd, positions=positions)
            # Kept AFTER the rotation: a keeping block then recomputes
            # neither the product nor the rotary arithmetic, whose own
            # backward needs only the tables.
            q = _kept(
                apply_rope(q.reshape(b, s, cfg.n_heads, hd), cos, sin),
                "proj_q", self.keeps,
            )
            k = _kept(
                apply_rope(k.reshape(b, s, n_kv, hd), cos, sin),
                "proj_k", self.keeps,
            )
            v = _kept(v.reshape(b, s, n_kv, hd), "proj_v", self.keeps)

        with jax.named_scope("attention"):
            if self.attn_fn is not None:
                out = self.attn_fn(q, k, v)
            else:
                # scores [B, Hkv, G, S, S], fp32 softmax, causal mask;
                # GQA via a grouped query view -- no materialised
                # repeat_kv.
                q = q.reshape(b, s, n_kv, groups, hd)
                scale = hd ** -0.5
                scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
                scores = scores.astype(jnp.float32)
                causal = jnp.tril(jnp.ones((s, s), dtype=bool))
                scores = jnp.where(causal, scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
                out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        with jax.named_scope("attn_out"):
            out = out.reshape(b, s, cfg.n_heads * hd)
            return _dense(cfg.dim, self.out_std, cfg, "wo")(out)


class FeedForward(nn.Module):
    """SwiGLU MLP: w2(silu(w1 x) * w3 x) (parity: reference :231-272)."""

    cfg: LlamaConfig
    out_std: float
    keeps: bool = False  # see TransformerBlock.keeps

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        hidden = cfg.ffn_hidden
        gate = _kept(
            _dense(hidden, 0.02, cfg, "w1")(x), "ffn_gate", self.keeps
        )
        up = _kept(
            _dense(hidden, 0.02, cfg, "w3")(x), "ffn_up", self.keeps
        )
        return _dense(cfg.dim, self.out_std, cfg, "w2")(
            nn.silu(gate) * up
        )


class TransformerBlock(nn.Module):
    """Pre-norm residual block with depth-scaled output init.

    Parity: reference TransformerBlock (:275-345) -- residual-path
    projections (wo, w2) get std 0.02/sqrt(2*(layer_id+1)) when
    depth_init, else 0.02/sqrt(2*n_layers).
    """

    cfg: LlamaConfig
    layer_id: int
    constrain: Constrain = _identity
    attn_fn: AttnFn = None
    # Set by ``Llama`` on the blocks it runs under the keeping policy
    # (models/remat.py): the six matmul outputs are tagged for it.
    keeps: bool = False

    @nn.compact
    def __call__(
        self, x: jax.Array, positions: Optional[jax.Array] = None
    ) -> jax.Array:
        cfg = self.cfg
        depth = (
            self.layer_id + 1 if cfg.depth_init else cfg.n_layers
        )
        out_std = 0.02 / (2 * depth) ** 0.5
        # Stage names shared with the serving programs
        # (serve/paging.py), read off the profiler's trace by scope.
        with jax.named_scope("qkv"):
            normed = RMSNorm(
                cfg.norm_eps, cfg.param_dtype, name="attention_norm"
            )(x)
        # The residual sum, not the projection's own output: the same
        # size, one add less to recompute, and behind the constraint,
        # so under sequence parallelism what is kept is a chip's share.
        h = _kept(
            x + self.constrain(
                Attention(
                    cfg, out_std, self.attn_fn, self.keeps,
                    name="attention",
                )(normed, positions)
            ),
            "attn_residual", self.keeps,
        )
        with jax.named_scope("mlp"):
            ffn = FeedForward(
                cfg, out_std, self.keeps, name="feed_forward"
            )(
                RMSNorm(cfg.norm_eps, cfg.param_dtype, name="ffn_norm")(h)
            )
        return h + self.constrain(ffn)


class Llama(nn.Module):
    """Parity: reference Transformer (:348-448): token embedding,
    n_layers blocks, final RMSNorm, trunc-normal lm head."""

    cfg: LlamaConfig
    constrain: Constrain = _identity
    attn_fn: AttnFn = None

    @nn.compact
    def __call__(
        self, tokens: jax.Array, positions: Optional[jax.Array] = None
    ) -> jax.Array:
        cfg = self.cfg
        emb = nn.Embed(
            cfg.vocab_size,
            cfg.dim,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(stddev=1.0),
            name="tok_embeddings",
        )
        with jax.named_scope("embed"):
            if cfg.iota_embed:
                # Gather forward, matmul backward (no scatter, no
                # forward one-hot); values identical to emb(tokens) up
                # to the compute-dtype cast.
                lookup = _make_embed_lookup(
                    cfg.vocab_size, jnp.dtype(cfg.dtype).name
                )
                x = lookup(emb.embedding.astype(cfg.dtype), tokens)
            else:
                x = emb(tokens)
        x = self.constrain(x)
        block = keeping_block = TransformerBlock
        keeping = 0
        if cfg.remat:
            block = nn.remat(TransformerBlock)
            keeping = _blocks_keeping(cfg, tokens.size)
            keeping_block = nn.remat(
                TransformerBlock, policy=remat.keep_products()
            )
        for i in range(cfg.n_layers):
            keeps = i < keeping
            x = (keeping_block if keeps else block)(
                cfg, i, self.constrain, self.attn_fn, keeps,
                name=f"layers_{i}",
            )(x, positions)
        with jax.named_scope("head"):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="norm")(x)
            logits = nn.Dense(
                cfg.vocab_size,
                use_bias=False,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.truncated_normal(stddev=0.02),
                name="output",
            )(x)
        # Logits stay in compute dtype: the loss upcasts INSIDE its
        # reductions (losses.cross_entropy), so XLA fuses the fp32
        # cast instead of materialising a [B, S, V] fp32 array in HBM
        # (~1 GiB/step at bench shapes). Value-exact either way -- the
        # matmul output is already rounded to cfg.dtype before any
        # cast.
        return logits


def _blocks_keeping(cfg: LlamaConfig, n_tokens: int) -> int:
    """How many leading blocks keep their matmul outputs: what the
    budget of the Trainer lowering this trace holds, 0 with no budget
    open. ``n_tokens`` is the (micro)batch as this trace sees it."""
    budget = remat.open_budget()
    if budget is None:
        return 0
    # checks/fit.py imports this module; its activation model is the
    # ONE reckoning the planner's report and this decision share.
    from tpu_hpc.checks import fit

    tokens = n_tokens // budget.batch_shards
    return budget.decide(
        cfg.n_layers,
        fit.kept_block_bytes(cfg, tokens, budget.model_shards),
        sum(fit.activation_bytes(
            cfg, tokens, budget.model_shards
        ).values()),
    )


def _dense_only(cfg: LlamaConfig, who: str) -> None:
    """``Llama`` is the dense decoder: a configuration that names
    experts (``models/sparse_moe.py``) or layers of another kind
    (``models/hybrid_ssm_moe.py``) is refused, never run as a dense
    model of its widths."""
    from tpu_hpc.models import hybrid_ssm_moe, latent_moe, sparse_moe

    why = "models/llama2.py builds the dense block only"
    if getattr(cfg, "n_experts", 0):
        sparse_moe.refuse(cfg, who, why)
        latent_moe.refuse(cfg, who, why)
    hybrid_ssm_moe.refuse(cfg, who, why)


def init_llama(
    rng: jax.Array, cfg: LlamaConfig, constrain: Constrain = _identity
) -> Dict:
    _dense_only(cfg, "llama2.init_llama")
    # attn_fn never affects the param tree (the attention op itself is
    # parameter-free), so init always uses the local-attention path --
    # a mesh-bound attn_fn could not run on the tiny init sample anyway.
    model = Llama(cfg, constrain)
    sample = jnp.zeros((1, min(8, cfg.max_seq_len)), jnp.int32)
    return model.init(rng, sample)["params"]


def apply_llama(
    params: Dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    constrain: Constrain = _identity,
    attn_fn: AttnFn = None,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """[B, S] int tokens -> [B, S, vocab] logits in cfg.dtype (the
    loss upcasts to fp32 inside its reductions; see Llama.__call__).
    ``positions`` [S]: global RoPE position of each slot, for permuted
    token layouts (zigzag ring); None = the usual 0..S-1."""
    _dense_only(cfg, "llama2.apply_llama")
    return Llama(cfg, constrain, attn_fn).apply(
        {"params": params}, tokens, positions
    )


def make_forward(
    cfg: LlamaConfig,
    constrain: Constrain = _identity,
    attn_fn: AttnFn = None,
    positions: Optional[jax.Array] = None,
):
    """Trainer-contract forward: next-token cross-entropy on (inputs,
    targets) token batches (datasets.TokenStream). ``positions`` as in
    :func:`apply_llama` -- pass the dataset's layout positions (e.g.
    ``TokenStream.positions()`` in zigzag mode) so RoPE stays exact
    under a permuted token layout; per-token mean cross-entropy is
    itself permutation-invariant."""
    from tpu_hpc.models.losses import cross_entropy

    _dense_only(cfg, "llama2.make_forward (the Trainer's forward)")

    def forward(params, model_state, batch, step_rng):
        inputs, targets = batch
        logits = apply_llama(
            params, inputs, cfg, constrain, attn_fn, positions
        )
        with jax.named_scope("head"):
            loss = cross_entropy(logits, targets)
        return loss, model_state, {}

    return forward
