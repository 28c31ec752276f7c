"""A decoder with latent attention and a sigmoid-routed expert layer.

The language model of ``jdopensource/JoyAI-LLM-Flash`` (preset
:data:`JOYAI_LLM_FLASH`; the DeepSeek-V3 layer at another size): the
residual stack of ``models/llama2.py`` with these departures, each a
field here:

* **latent attention** (MLA). The query is low-rank: ``cq =
  RMSNorm(h W_DQ)`` (``q_lora_rank``), ``[qN | qR] = cq W_UQ`` a head
  (``qk_nope_head_dim + qk_rope_head_dim``), ``qR`` rotated. Keys and
  values come from ONE latent a token: ``[c | kR] = h W_DKV``
  (``kv_lora_rank + qk_rope_head_dim``), ``c = RMSNorm(c)``, ``kR``
  rotated, one rotary key for all heads; ``kN_i = c W_UK,i``, ``v_i =
  c W_UV,i``. A cache keeps ``c`` and ``kR`` (512 + 64 numbers a token
  a layer at the published sizes) and nothing per head. ``score = (qN . kN +
  qR . kR) * (qk_nope_head_dim + qk_rope_head_dim) ** -0.5``.
  :func:`absorb` carries a query into the latent space (``qA = qN
  W_UK^T``), where ``qN . kN = qA . c``, and :func:`unabsorb` brings
  the attended latent out of it (``o = (sum_s p_s c_s) W_UV``): the
  same numbers in another order, with no per-head key or value ever
  built. :func:`expand` builds them, for a read that has many query
  rows to spend them on;
* the first ``first_dense_layers`` layers have a dense SwiGLU of width
  ``dense_hidden``; every later one has ``n_experts`` routed experts of
  width ``expert_hidden`` and ``n_shared_experts`` shared ones that
  every token passes. The router is a SIGMOID: ``s = sigmoid(h W_R)``,
  the ``experts_per_token`` largest of ``s + b`` are chosen (``b`` a
  selection bias, ties to the lower id), gated by ``s`` WITHOUT ``b``,
  renormalised over the chosen (``norm_topk_prob``) and scaled by
  ``routed_scaling_factor``. The dispatch over the experts HELD here
  (``held_experts``) is ``sparse_moe.expert_ffn``: the router keeps its
  width, an absent expert adds nothing, and the shares of an
  expert-parallel deployment add up to the whole once the shared expert
  and the dense layer, which every chip computes alike, are counted
  once (tests/test_latent_moe.py).

Only the paged server runs it (``serve/paging.py``: the page holds the
latent row). The slab engine, the speculative runner, the host tier,
disaggregation, the Pallas read path, int8 pages, a tensor axis, the
pipeline split and the trainer refuse it by name (:func:`refuse`). The
functions below are the stages ``serve/decoder.py``'s layer loop and
the page pool's attention state call; the weights are a plain dict.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_hpc.models import llama2
from tpu_hpc.models.sparse_moe import _is_shape


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig(llama2.LlamaConfig):
    """Defaults are JoyAI-LLM-Flash's published sizes (config.json)."""

    name: str = "latent-moe-decoder"
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 32
    vocab_size: int = 129280
    norm_eps: float = 1e-6
    max_seq_len: int = 131072
    rope_theta: float = 32e6
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_hidden: int = 7168
    first_dense_layers: int = 1
    n_experts: int = 256
    experts_per_token: int = 8
    expert_hidden: int = 768
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    held_experts: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        held = self.held_experts
        if held is not None:
            held = tuple(int(e) for e in held)
            object.__setattr__(self, "held_experts", held)
            if len(set(held)) != len(held) or not all(
                0 <= e < self.n_experts for e in held
            ):
                raise ValueError(
                    f"held_experts {held} must be distinct ids below "
                    f"{self.n_experts}"
                )
        if not 0 < self.experts_per_token <= self.n_experts:
            raise ValueError("experts_per_token out of range")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held_experts is None \
            else len(self.held_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Numbers a token a layer leaves in a cache: ``c`` and
        ``kR``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def rope_dim(self) -> int:
        return self.qk_rope_head_dim

    @property
    def residual_dtype(self):
        """What is ADDED UP stays float32 while every matrix product
        keeps operands in ``dtype``: the residual stream (the embedding
        row and each layer's two additions), the logits (the head's
        product accumulates in float32 either way) and the router's 256
        scores. With the dense decoder's compute-dtype stream its
        rounding moved the router's scores enough to swap a token's
        eighth expert for its ninth (each swap of a held expert a few
        per cent of the hidden state), and the arg-max over 129280
        logits was decided by their own 8 bits: the regret against the
        float32 reference came within 1.6 x of its limit (PERF.md, PR
        31)."""
        return jnp.float32

    def is_dense_layer(self, layer: int) -> bool:
        return layer < self.first_dense_layers


JOYAI_LLM_FLASH = LatentMoEConfig(name="joyai-llm-flash")


def is_latent_moe(cfg: Any) -> bool:
    return isinstance(cfg, LatentMoEConfig)


def refuse(cfg: Any, who: str, why: str) -> None:
    """One clear error, by name, from every path that has not learned
    this decoder: never a silent run of per-head keys and values on
    its weights."""
    if is_latent_moe(cfg):
        raise NotImplementedError(
            f"{who} does not run {cfg.name!r} ({type(cfg).__name__}: "
            f"latent attention, sigmoid-routed experts with a shared "
            f"expert, a leading dense layer): {why}. Serve it through "
            "serve.paging.PagedEngine (unquantised "
            "pages, no tensor axis)."
        )


def refuse_weights(params: Any, who: str, why: str) -> None:
    """:func:`refuse` for a path that sees weights and no
    configuration (the trainer): the tree of :func:`param_shapes` is
    told by its latent projections."""
    layer = params.get("layers_0") if isinstance(params, dict) else None
    if isinstance(layer, dict) and "wkv_a" in layer.get("attention", {}):
        refuse(JOYAI_LLM_FLASH, who, why)


# ---------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------


def _ffn_shapes(d: int, hidden: int) -> Dict:
    return {
        "w1": {"kernel": (d, hidden)},
        "w3": {"kernel": (d, hidden)},
        "w2": {"kernel": (hidden, d)},
    }


def param_shapes(cfg: LatentMoEConfig) -> Dict:
    """The weights' tree as shapes, ``[in, out]`` like ``llama2``.
    ``wq_b``'s columns are a head's ``[qN | qR]``, ``wkv_a``'s are ``[c
    | kR]``, ``wkv_b``'s a head's ``[kN | v]``; routed experts are
    stacked on a leading axis of the experts HELD, and the router's
    ``bias`` is the selection bias ``b``."""
    d, h, e = cfg.dim, cfg.n_heads, cfg.n_held
    attention = {
        "wq_a": {"kernel": (d, cfg.q_lora_rank)},
        "q_norm": {"scale": (cfg.q_lora_rank,)},
        "wq_b": {"kernel": (cfg.q_lora_rank, h * cfg.qk_head_dim)},
        "wkv_a": {"kernel": (d, cfg.latent_dim)},
        "kv_norm": {"scale": (cfg.kv_lora_rank,)},
        "wkv_b": {"kernel": (
            cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        )},
        "wo": {"kernel": (h * cfg.v_head_dim, d)},
    }
    moe = {
        "router": {"kernel": (d, cfg.n_experts), "bias": (cfg.n_experts,)},
        "w1": (e, d, cfg.expert_hidden),
        "w3": (e, d, cfg.expert_hidden),
        "w2": (e, cfg.expert_hidden, d),
        "shared": _ffn_shapes(d, cfg.n_shared_experts * cfg.expert_hidden),
    }
    tree = {}
    for i in range(cfg.n_layers):
        layer = {
            "attention_norm": {"scale": (d,)},
            "attention": attention,
            "ffn_norm": {"scale": (d,)},
        }
        if cfg.is_dense_layer(i):
            layer["feed_forward"] = _ffn_shapes(d, cfg.dense_hidden)
        else:
            layer["moe"] = moe
        tree[f"layers_{i}"] = layer
    tree["tok_embeddings"] = {"embedding": (cfg.vocab_size, d)}
    tree["norm"] = {"scale": (d,)}
    tree["output"] = {"kernel": (d, cfg.vocab_size)}
    return tree


def _size(tree) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(tree, is_leaf=_is_shape))


def count_params(cfg: LatentMoEConfig) -> Dict[str, int]:
    """``total`` held here, ``active`` a token passes through (its
    ``experts_per_token`` routed experts of each expert layer, no
    embedding row but its own), and the kinds of layer both are made
    of."""
    shapes = param_shapes(cfg)
    n_dense = min(cfg.first_dense_layers, cfg.n_layers)
    n_expert = cfg.n_layers - n_dense
    dense = _size(shapes["layers_0"]) if n_dense else 0
    expert = _size(shapes[f"layers_{cfg.n_layers - 1}"]) if n_expert else 0
    one_expert = 3 * cfg.dim * cfg.expert_hidden
    routed = cfg.n_held * one_expert
    edge = _size(shapes["tok_embeddings"]) + _size(shapes["output"])
    return {
        "attention_per_layer": _size(shapes["layers_0"]["attention"]),
        "dense_layer": dense,
        "expert_layer": expert,
        "experts_per_layer": routed,
        "embed_and_head": edge,
        "total": n_dense * dense + n_expert * expert + edge + cfg.dim,
        "active": n_dense * dense + n_expert * (
            expert - routed + cfg.experts_per_token * one_expert
        ) + edge // 2 + cfg.dim,
    }


def init_latent_moe(rng: jax.Array, cfg: LatentMoEConfig) -> Dict:
    """Seeded weights in ``cfg.param_dtype``, made where they are used
    (jit this). ``llama2``'s scheme as ``sparse_moe.init_sparse_moe``
    has it: a unit-normal embedding, Normal(0.02) matrices, the
    residual output projections (``wo``, every ``w2``) scaled by depth,
    unit norm scales; the router's selection bias is Normal(0.1), small
    and not zero as a trained one is (a zero bias would leave the
    selection rule untested)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    keys = jax.random.split(rng, len(leaves))
    dtype = cfg.param_dtype
    out = []
    for key, (path, shape) in zip(keys, leaves):
        names = [getattr(p, "key", None) for p in path]
        if names[-1] == "scale":
            out.append(jnp.ones(shape, dtype))
            continue
        std = {"embedding": 1.0, "bias": 0.1}.get(names[-1], 0.02)
        if cfg.depth_init and ("wo" in names or "w2" in names):
            layer = int(names[0].split("_")[1])
            std = 0.02 / (2 * (layer + 1)) ** 0.5
        out.append(
            (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
        )
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------
# Stages (functional, over the raw dict, like sparse_moe.py's)
# ---------------------------------------------------------------------


def _dense(x, leaf, dtype):
    """``nn.Dense(use_bias=False, dtype=dtype)`` over a weight leaf."""
    return jax.lax.dot_general(
        x.astype(dtype), leaf["kernel"].astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
    )


def _rmsnorm(x, leaf, eps):
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
    )
    return (normed * leaf["scale"].astype(jnp.float32)).astype(x.dtype)


def project(h, lp, cfg: LatentMoEConfig, cos, sin):
    """The projection stage: the normed input ``h [b, s, dim]`` -> the
    queries ``[b, s, heads, qk_head_dim]`` (``[qN | qR]``, ``qR``
    rotated by the ``cos`` / ``sin`` tables over ``rope_dim``) and what
    a cache keeps of the token, the normed latent ``c [b, s,
    kv_lora_rank]`` and the rotated key ``kR [b, s, rope_dim]``."""
    att = lp["attention"]
    b, s = h.shape[0], h.shape[1]
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    cq = _rmsnorm(_dense(h, att["wq_a"], cfg.dtype), att["q_norm"],
                  cfg.norm_eps)
    q = _dense(cq, att["wq_b"], cfg.dtype).reshape(
        b, s, cfg.n_heads, cfg.qk_head_dim
    )
    q = jnp.concatenate(
        [q[..., :nope], llama2.apply_rope(q[..., nope:], cos, sin)], axis=-1
    )
    ckv = _dense(h, att["wkv_a"], cfg.dtype)
    c = _rmsnorm(ckv[..., :rank], att["kv_norm"], cfg.norm_eps)
    kr = llama2.apply_rope(ckv[..., None, rank:], cos, sin)[..., 0, :]
    return q, c, kr


def _wkv_b(lp, cfg: LatentMoEConfig):
    """``W_UK [rank, heads, nope]`` and ``W_UV [rank, heads, v]``."""
    w = lp["attention"]["wkv_b"]["kernel"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
    )
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def absorb(q, lp, cfg: LatentMoEConfig):
    """Queries ``[..., heads, qk_head_dim]`` into the latent space:
    ``qN W_UK^T [..., heads, kv_lora_rank]`` and the rotary part ``qR``
    as it is; their products with a cached latent and its rotary key
    add up to the head's score."""
    w_uk, _ = _wkv_b(lp, cfg)
    nope = cfg.qk_nope_head_dim
    return jnp.einsum("...hn,rhn->...hr", q[..., :nope], w_uk), \
        q[..., nope:]


def unabsorb(u, lp, cfg: LatentMoEConfig):
    """The attended latent a head ``u [..., heads, kv_lora_rank]``
    (``sum_s p_s c_s``) -> the head's output ``u W_UV``, ``[..., heads,
    v_head_dim]``."""
    _, w_uv = _wkv_b(lp, cfg)
    return jnp.einsum("...hr,rhv->...hv", u.astype(cfg.dtype), w_uv)


def expand(latents, k_rope, lp, cfg: LatentMoEConfig):
    """Cached rows ``latents [..., n, kv_lora_rank]`` and ``k_rope
    [..., n, rope_dim]`` -> every head's key ``[..., n, heads,
    qk_head_dim]`` (``[c W_UK | kR]``, the one rotary key under every
    head) and value ``[..., n, heads, v_head_dim]``."""
    w_uk, w_uv = _wkv_b(lp, cfg)
    c = latents.astype(cfg.dtype)
    kn = jnp.einsum("...r,rhn->...hn", c, w_uk)
    k = jnp.concatenate([
        kn, jnp.broadcast_to(
            k_rope.astype(cfg.dtype)[..., None, :],
            (*kn.shape[:-1], cfg.qk_rope_head_dim),
        ),
    ], axis=-1)
    return k, jnp.einsum("...r,rhv->...hv", c, w_uv)


def route(h, lp, cfg: LatentMoEConfig):
    """``h [..., dim]`` -> ``(gates [..., k] float32, experts [..., k]
    int32)``: the ``k`` largest of ``sigmoid(h W_R) + b`` over ALL
    experts (ties to the lower id), gated by the sigmoid alone,
    renormalised over the chosen and scaled."""
    router = lp["moe"]["router"]
    # A float32 product of the float32 stream's normed row (the
    # published gate multiplies in float32 too): ``residual_dtype``.
    scores = jax.nn.sigmoid(jax.lax.dot_general(
        h.astype(jnp.float32), router["kernel"].astype(jnp.float32),
        (((h.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, experts = jax.lax.top_k(
        scores + router["bias"].astype(jnp.float32), cfg.experts_per_token
    )
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * cfg.routed_scaling_factor, experts.astype(jnp.int32)
