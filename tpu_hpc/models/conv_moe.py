"""A decoder of gated short convolutions, a few attention layers and a
sigmoid-routed expert layer, TRAINED through the ``Trainer``.

The language model of ``LiquidAI/LFM2-24B-A2B`` (``lfm2_moe``; preset
:data:`LFM2_24B_A2B`). With ``u`` the normed input of a block:

* block ``l``: ``h = x + Mixer_l(RMSNorm_operator(x))``, ``x' = h +
  FFN_l(RMSNorm_ffn(h))``;
* a ``conv`` mixer (30 of 40 layers): ``[B, C, X] = split3(u W_in)``,
  ``z = B * X``, ``c_t = sum_j k_j * z_{t - (taps - 1) + j}`` (a
  depthwise causal convolution of ``conv_taps`` taps, no bias, zeros
  before the sequence), ``y = (C * c) W_out``. No activation function;
* a ``full_attention`` mixer (10 of 40): grouped-query attention whose
  ``q`` and ``k`` pass a per-head RMSNorm BEFORE the rotation, rotary
  base ``rope_theta`` over the whole head in the rotate-half
  convention, causal softmax of ``q k^T / sqrt(head_dim)``;
* the FFN of the first ``first_dense_layers`` layers is a SwiGLU of
  width ``dense_hidden``; every later one has ``n_experts`` routed
  SwiGLU experts of width ``expert_hidden``, ``experts_per_token`` a
  token: ``s = sigmoid(u W_r)`` in float32, the largest of ``s + b``
  chosen (``b`` a selection bias that takes no gradient: a buffer, in
  the model STATE here), gates ``s[chosen] / sum(s[chosen])`` x
  ``routed_scaling_factor``. No shared expert. The router is
  ``latent_moe.route``; the product over the experts HELD here
  (``held_experts``) is ``sparse_moe.ragged_expert_ffn``: rows sorted
  by expert, ragged grouped products, no capacity and no dropped
  token, an absent expert adds nothing, so the layer computes its
  share of an expert-parallel deployment's result and the shares add
  up to the whole (tests/test_conv_moe.py);
* one final RMSNorm, then the head, which is the embedding table
  (``tie_word_embeddings``); the loss is the mean token cross-entropy.

What is ADDED UP is float32 (``residual_dtype``: the residual stream,
the router's product and scores, the loss); every other matrix product
has operands in ``dtype`` and a float32 accumulator.

Only the ``Trainer`` runs it (:func:`make_forward`, the forward of its
contract). ``serve/`` refuses it by name (:func:`refuse`): a page pool
keeps no convolution state of rows alone.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tpu_hpc.models import latent_moe, llama2, remat, sparse_moe
from tpu_hpc.models.losses import cross_entropy
from tpu_hpc.models.sparse_moe import _is_shape

# The published pattern: the first two layers convolutions, then one
# attention layer in four.
_LFM2_LAYERS = tuple(
    "full_attention" if i % 4 == 2 else "conv" for i in range(40)
)

# What each expert layer counts a step (``sparse_moe.ragged_expert_ffn``):
# the name the Trainer folds it over a chunk by, and what it says.
COUNTERS = {
    "assignments": (
        "train_moe_assignments_total",
        "Token-expert assignments the trained steps' routers made",
    ),
    "assignments_held": (
        "train_moe_assignments_held_total",
        "Those of them on experts held here: rows that carry an "
        "assignment",
    ),
    "rows_computed": (
        "train_moe_rows_computed_total",
        "Rows of the row tiles the ragged expert products visited",
    ),
    "max_rows_per_expert": (
        "train_moe_max_rows_per_expert",
        "Most rows one held expert got in one layer of one step",
    ),
    "dropped": (
        "train_moe_dropped_total",
        "Assignments to held experts the products did not cover (0 by "
        "construction)",
    ),
}


@dataclasses.dataclass(frozen=True)
class ConvMoEConfig(llama2.LlamaConfig):
    """Defaults are LFM2-24B-A2B's published sizes (config.json)."""

    name: str = "conv-moe-decoder"
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 32
    n_kv_heads: Optional[int] = 8
    vocab_size: int = 65536
    norm_eps: float = 1e-5
    max_seq_len: int = 128000
    rope_theta: float = 1e6
    # The kinds of layer, by index; the run is the leading ``n_layers``.
    layer_types: Tuple[str, ...] = _LFM2_LAYERS
    conv_taps: int = 3
    dense_hidden: int = 11776
    first_dense_layers: int = 2
    n_experts: int = 64
    experts_per_token: int = 4
    expert_hidden: int = 1536
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    held_experts: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
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
        if self.n_layers > len(self.layer_types) or any(
            kind not in ("conv", "full_attention")
            for kind in self.layer_types
        ):
            raise ValueError(
                f"layer_types must name {self.n_layers} layers, each "
                "'conv' or 'full_attention'"
            )
        if self.head_dim % 2:
            raise ValueError("the rotation needs an even head_dim")

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held_experts is None \
            else len(self.held_experts)

    @property
    def residual_dtype(self):
        """The stream every layer adds to stays float32: its rounding
        would move the router's scores enough to swap a token's fourth
        expert for its fifth (PERF.md, PR 31 and PR 37)."""
        return jnp.float32

    def is_dense_layer(self, layer: int) -> bool:
        return layer < self.first_dense_layers

    def is_attention_layer(self, layer: int) -> bool:
        return self.layer_types[layer] == "full_attention"

    @property
    def assignments_per_token(self) -> float:
        """Expert assignments a token is computed HERE a layer, at an
        even load: its ``experts_per_token`` times the share of the
        experts this process holds (4 x 8 / 64 = 0.5 in the cut the
        benchmark runs; the rest are other chips')."""
        return self.experts_per_token * self.n_held / self.n_experts

    def flops_per_token(self, seq_len: Optional[int] = None) -> int:
        """Training operations a token costs HERE (the 6 N convention
        over the matrices its rows pass through on this process: the
        assignments computed, :attr:`assignments_per_token`, not the
        experts held; causal attention at ``seq_len`` in the attention
        layers alone); recomputation does not count."""
        s = seq_len if seq_len is not None else self.max_seq_len
        d, hd = self.dim, self.head_dim
        total = 2 * d * self.vocab_size
        for i in range(self.n_layers):
            if self.is_attention_layer(i):
                total += 2 * d * (self.n_heads + 2 * self.kv_heads) * hd \
                    + 2 * self.n_heads * hd * d + 2 * s * self.n_heads * hd
            else:
                total += 2 * d * 3 * d + 2 * d * d \
                    + 2 * self.conv_taps * d
            if self.is_dense_layer(i):
                total += 3 * 2 * d * self.dense_hidden
            else:
                total += 2 * d * self.n_experts + int(
                    self.assignments_per_token
                    * 3 * 2 * d * self.expert_hidden
                )
        return 3 * total


LFM2_24B_A2B = ConvMoEConfig(name="lfm2-24b-a2b")


def is_conv_moe(cfg: Any) -> bool:
    return isinstance(cfg, ConvMoEConfig)


def refuse(cfg: Any, who: str, why: str) -> None:
    """One clear error, by name, from every path that has not learned
    this decoder: never a silent run of an attention layer on a
    convolution's weights."""
    if is_conv_moe(cfg):
        raise NotImplementedError(
            f"{who} does not run {cfg.name!r} ({type(cfg).__name__}: "
            f"gated short-convolution mixers, sigmoid-routed experts, "
            f"leading dense layers): {why}. Train it through "
            "train.Trainer with models.conv_moe.make_forward."
        )


# ---------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------


def param_shapes(cfg: ConvMoEConfig) -> Dict:
    """The TRAINED weights' tree as shapes, ``[in, out]`` like
    ``llama2``. ``in_proj``'s columns are ``[B | C | X]``; the
    convolution's kernel is ``[taps, dim]``; routed experts are stacked
    on a leading axis of the experts HELD. The head is the embedding
    table; the router's selection bias is no weight (:func:`state_shapes`)."""
    d, hd, e = cfg.dim, cfg.head_dim, cfg.n_held
    ffn = latent_moe._ffn_shapes
    tree = {}
    for i in range(cfg.n_layers):
        layer = {
            "operator_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)},
        }
        if cfg.is_attention_layer(i):
            layer["attention"] = {
                "wq": {"kernel": (d, cfg.n_heads * hd)},
                "wk": {"kernel": (d, cfg.kv_heads * hd)},
                "wv": {"kernel": (d, cfg.kv_heads * hd)},
                "wo": {"kernel": (cfg.n_heads * hd, d)},
                "q_norm": {"scale": (hd,)},
                "k_norm": {"scale": (hd,)},
            }
        else:
            layer["conv"] = {
                "in_proj": {"kernel": (d, 3 * d)},
                "conv": {"kernel": (cfg.conv_taps, d)},
                "out_proj": {"kernel": (d, d)},
            }
        if cfg.is_dense_layer(i):
            layer["feed_forward"] = ffn(d, cfg.dense_hidden)
        else:
            layer["moe"] = {
                "router": {"kernel": (d, cfg.n_experts)},
                "w1": (e, d, cfg.expert_hidden),
                "w3": (e, d, cfg.expert_hidden),
                "w2": (e, cfg.expert_hidden, d),
            }
        tree[f"layers_{i}"] = layer
    tree["tok_embeddings"] = {"embedding": (cfg.vocab_size, d)}
    tree["norm"] = {"scale": (d,)}
    return tree


def state_shapes(cfg: ConvMoEConfig) -> Dict:
    """What the model carries and does not train: each expert layer's
    selection bias."""
    return {
        f"layers_{i}": {"router_bias": (cfg.n_experts,)}
        for i in range(cfg.n_layers) if not cfg.is_dense_layer(i)
    }


def count_params(cfg: ConvMoEConfig) -> Dict[str, int]:
    """``total`` held here (the selection biases beside it: ``state``),
    ``active`` a token's rows pass through HERE (the assignments
    computed, ``cfg.assignments_per_token`` experts of each expert
    layer, not the experts held; the tied table once), and the kinds
    of layer both are made of."""
    size = latent_moe._size
    shapes = param_shapes(cfg)
    one_expert = 3 * cfg.dim * cfg.expert_hidden
    routed = cfg.n_held * one_expert
    n_expert = sum(
        not cfg.is_dense_layer(i) for i in range(cfg.n_layers)
    )
    total = size(shapes)
    out = {
        "total": total,
        "state": size(state_shapes(cfg)),
        "experts_per_layer": routed,
        "active": total - n_expert * (
            routed - int(cfg.assignments_per_token * one_expert)
        ),
        "embed_and_head": size(shapes["tok_embeddings"]),
    }
    for i in range(cfg.n_layers):
        kind = ("attention" if cfg.is_attention_layer(i) else "conv") \
            + ("_dense" if cfg.is_dense_layer(i) else "_expert") + "_layer"
        out.setdefault(kind, size(shapes[f"layers_{i}"]))
    return out


def init_conv_moe(rng: jax.Array, cfg: ConvMoEConfig) -> Dict:
    """Seeded weights in ``cfg.param_dtype`` (jit this). Normal(0.02)
    matrices, the table among them (it is the head too: a unit-normal
    table would score logits of spread 45); the residual output
    projections (``wo``, ``out_proj``, every ``w2``) scaled by depth as
    ``llama2`` does; the convolution's taps U(+-taps ** -0.5), so that
    a random mixer passes what it is given; unit norm scales."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    keys = jax.random.split(rng, len(leaves))
    dtype = cfg.param_dtype
    out = []
    for key, (path, shape) in zip(keys, leaves):
        names = [getattr(p, "key", None) for p in path]
        if names[-1] == "scale":
            out.append(jnp.ones(shape, dtype))
        elif "conv" in names[-2:]:
            bound = cfg.conv_taps ** -0.5
            out.append(jax.random.uniform(
                key, shape, jnp.float32, -bound, bound
            ).astype(dtype))
        else:
            std = 0.02
            if cfg.depth_init and {"wo", "out_proj", "w2"} & set(names):
                layer = int(names[0].split("_")[1])
                std = 0.02 / (2 * (layer + 1)) ** 0.5
            out.append(
                (std * jax.random.normal(key, shape, jnp.float32))
                .astype(dtype)
            )
    return jax.tree.unflatten(treedef, out)


def init_state(
    rng: jax.Array, cfg: ConvMoEConfig, bias_std: float = 0.0
) -> Dict:
    """The selection biases, float32: zeros, as the published model
    starts them, or Normal(``bias_std``) where a run wants the
    selection rule to decide something while the biases stay frozen
    (a check against a reference: with zeros the rule is never tested)."""
    shapes = state_shapes(cfg)
    keys = jax.random.split(rng, len(shapes))
    return {
        name: {"router_bias": bias_std * jax.random.normal(
            key, shapes[name]["router_bias"], jnp.float32
        )}
        for key, name in zip(keys, sorted(shapes))
    }


# ---------------------------------------------------------------------
# Stages (functional, over the raw dict)
# ---------------------------------------------------------------------

_rmsnorm = latent_moe._rmsnorm


def _dot(x, leaf, cfg, out_dtype=None):
    """Contract the trailing dim with operands in ``cfg.dtype`` and a
    float32 accumulator, rounded to ``out_dtype`` (``cfg.dtype``
    where none is given; float32 where the result joins the stream)."""
    out = jax.lax.dot_general(
        x.astype(cfg.dtype), leaf["kernel"].astype(cfg.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(cfg.dtype if out_dtype is None else out_dtype)


def _kept(x, name, keeps):
    return checkpoint_name(x, name) if keeps else x


def rope_half(x, cos, sin):
    """Rotate ``[b, s, heads, head_dim]`` by position in the rotate-half
    convention (number ``j`` pairs with ``j + head_dim / 2``), float32,
    cast back. ``cos`` / ``sin``: ``[s, head_dim / 2]``."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


def causal_attention(q, k, v):
    """Plain grouped-query causal attention, for where no kernel is
    handed in (a test, a tiny model): ``[b, s, heads, d]`` against
    ``[b, s, kv_heads, d]``, float32 softmax."""
    b, s, h, d = q.shape
    groups = h // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], groups, d)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, h, d)


def short_conv(u, lp, cfg: ConvMoEConfig, keeps: bool = False):
    """The gated short convolution over whole sequences ``u [b, s,
    dim]`` -> float32 ``[b, s, dim]``. The taps are
    ``hybrid_ssm_moe.conv_chunk``'s sum, over ``B * X`` and with no
    bias, activation or rows from before."""
    conv = lp["conv"]
    d, taps = cfg.dim, cfg.conv_taps
    bcx = _kept(_dot(u, conv["in_proj"], cfg), "conv_in", keeps)
    gate_b, gate_c, x = (
        bcx[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(3)
    )
    z = jnp.pad(gate_b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = conv["conv"]["kernel"].astype(jnp.float32)
    n = u.shape[1]
    c = sum(kernel[j] * z[:, j:j + n] for j in range(taps))
    return _dot(gate_c * c, conv["out_proj"], cfg, jnp.float32)


def attention(u, lp, cfg: ConvMoEConfig, cos, sin, attn_fn, keeps=False):
    """The attention mixer over ``u [b, s, dim]`` -> float32 ``[b, s,
    dim]``, under the three stage names the dense decoder gives it."""
    att = lp["attention"]
    b, s, _ = u.shape
    hd = cfg.head_dim
    with jax.named_scope("qkv"):
        q = _dot(u, att["wq"], cfg).reshape(b, s, cfg.n_heads, hd)
        k = _dot(u, att["wk"], cfg).reshape(b, s, cfg.kv_heads, hd)
        v = _dot(u, att["wv"], cfg).reshape(b, s, cfg.kv_heads, hd)
        q = rope_half(_rmsnorm(q, att["q_norm"], cfg.norm_eps), cos, sin)
        k = rope_half(_rmsnorm(k, att["k_norm"], cfg.norm_eps), cos, sin)
        q, k, v = (
            _kept(t, name, keeps)
            for t, name in ((q, "proj_q"), (k, "proj_k"), (v, "proj_v"))
        )
    with jax.named_scope("attention"):
        out = (attn_fn or causal_attention)(q, k, v)
    with jax.named_scope("attn_out"):
        return _dot(
            out.reshape(b, s, cfg.n_heads * hd), att["wo"], cfg,
            jnp.float32,
        )


def dense_ffn(u, lp, cfg: ConvMoEConfig, keeps: bool = False):
    ffn = lp["feed_forward"]
    gate = _kept(_dot(u, ffn["w1"], cfg), "ffn_gate", keeps)
    up = _kept(_dot(u, ffn["w3"], cfg), "ffn_up", keeps)
    return _dot(jax.nn.silu(gate) * up, ffn["w2"], cfg, jnp.float32)


def expert_layer(u, lp, bias, cfg: ConvMoEConfig):
    """``u [b, s, dim]`` (the normed stream, float32) -> the held
    experts' part of the layer's result, float32, the step's counts
    and the experts each token chose ``[b, s, k]``."""
    b, s, d = u.shape
    rows = u.reshape(b * s, d)
    with jax.named_scope("router"):
        router = {"kernel": lp["moe"]["router"]["kernel"], "bias": bias}
        gates, experts = latent_moe.route(rows, {"moe": {"router": router}},
                                          cfg)
    out, counts = sparse_moe.ragged_expert_ffn(
        rows, gates, experts, lp["moe"], cfg
    )
    return out.reshape(b, s, d), counts, experts.reshape(b, s, -1)


def _block(x, lp, bias, cfg: ConvMoEConfig, layer, cos, sin, attn_fn, keeps):
    """One layer over the float32 stream ``x`` -> ``(x', counts,
    chosen)``; the last two ``None`` for a dense layer."""
    if cfg.is_attention_layer(layer):
        with jax.named_scope("qkv"):
            u = _rmsnorm(x, lp["operator_norm"], cfg.norm_eps)
        mixed = attention(u, lp, cfg, cos, sin, attn_fn, keeps)
    else:
        with jax.named_scope("short_conv"):
            u = _rmsnorm(x, lp["operator_norm"], cfg.norm_eps)
            mixed = short_conv(u, lp, cfg, keeps)
    h = _kept(x + mixed, "attn_residual", keeps)
    if cfg.is_dense_layer(layer):
        with jax.named_scope("mlp"):
            u = _rmsnorm(h, lp["ffn_norm"], cfg.norm_eps)
            return h + dense_ffn(u, lp, cfg, keeps), None, None
    with jax.named_scope("router"):
        u = _rmsnorm(h, lp["ffn_norm"], cfg.norm_eps)
    out, counts, chosen = expert_layer(u, lp, bias, cfg)
    return h + out, counts, chosen


def _blocks_keeping(cfg: ConvMoEConfig, n_tokens: int) -> int:
    """``llama2._blocks_keeping`` for this stack: how many leading
    blocks keep the products :data:`remat.CONV_MOE_PRODUCTS` names,
    from the budget of the Trainer lowering this trace (0 with none
    open). The blocks differ, so the budget is asked block by block."""
    budget = remat.open_budget()
    if budget is None:
        return 0
    from tpu_hpc.checks import fit

    tokens = n_tokens // budget.batch_shards
    act = fit.conv_moe_activation_bytes(cfg, tokens)
    # A peak over the step, not a sum: the head is done with before the
    # last block is recomputed.
    return budget.decide_each(
        [fit.conv_moe_kept_block_bytes(cfg, i, tokens)
         for i in range(cfg.n_layers)],
        act["residual_checkpoints"] + max(
            act["block_recompute_live"], act["lm_head_and_loss"]
        ),
    )


def apply(params, state, tokens, cfg: ConvMoEConfig, attn_fn=None):
    """``tokens [b, s]`` -> ``(logits [b, s, vocab] in cfg.dtype,
    counts, chosen)``: the step's expert counts summed over the expert
    layers (the longest group: the largest) under the Trainer's names
    (:data:`COUNTERS`), and the experts every token chose in each
    expert layer, ``{layer name: [b, s, k]}``."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        lookup = llama2._make_embed_lookup(
            cfg.vocab_size, jnp.dtype(cfg.dtype).name
        )
        table = params["tok_embeddings"]["embedding"]
        x = lookup(table.astype(cfg.dtype), tokens).astype(jnp.float32)
    cos, sin = llama2.rope_cos_sin(s, cfg.head_dim, cfg.rope_theta)
    keeping = _blocks_keeping(cfg, tokens.size) if cfg.remat else 0
    totals, chosen = None, {}
    for i in range(cfg.n_layers):
        name = f"layers_{i}"
        keeps = i < keeping

        def block(x, lp, bias, layer=i, keeps=keeps):
            return _block(x, lp, bias, cfg, layer, cos, sin, attn_fn, keeps)

        if cfg.remat:
            block = jax.checkpoint(
                block, policy=remat.keep_products(remat.CONV_MOE_PRODUCTS)
                if keeps else None,
            )
        bias = state.get(name, {}).get("router_bias")
        x, counts, picked = block(x, params[name], bias)
        if counts is None:
            continue
        chosen[name] = picked
        totals = counts if totals is None else {
            key: (jnp.maximum if key == "max_rows_per_expert" else jnp.add)(
                totals[key], value
            ) for key, value in counts.items()
        }
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["norm"], cfg.norm_eps)
        # Rounded to the compute dtype as ``llama2.Llama`` rounds them:
        # the loss upcasts inside its reductions, so no float32
        # ``[b, s, vocab]`` array is held.
        logits = jax.lax.dot_general(
            x.astype(cfg.dtype), table.astype(cfg.dtype),
            (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(cfg.dtype)
    return logits, {
        COUNTERS[key][0]: value for key, value in (totals or {}).items()
    }, chosen


def loss_and_routing(params, state, batch, cfg: ConvMoEConfig, attn_fn=None):
    """Next-token cross-entropy on an ``(inputs, targets)`` batch ->
    ``(loss, (counts, chosen))`` as :func:`apply` gives them
    (``has_aux`` for ``jax.value_and_grad``: a check reads the experts
    chosen by the very run it differentiates)."""
    inputs, targets = batch
    logits, counts, chosen = apply(params, state, inputs, cfg, attn_fn)
    with jax.named_scope("head"):
        return cross_entropy(logits, targets), (counts, chosen)


def make_forward(cfg: ConvMoEConfig, attn_fn=None):
    """The Trainer-contract forward: :func:`loss_and_routing`'s loss,
    the state (the selection biases) handed through untouched, the
    expert counts as the step's metrics (``*_total`` summed and a
    ``_max_`` one's largest taken over a chunk by the Trainer). It
    carries the configuration it was built for (``forward.config``:
    the Trainer checks an expert tree against it) and what its counts
    say (``forward.counters``, for the registry)."""

    def forward(params, model_state, batch, step_rng):
        loss, (counts, _) = loss_and_routing(
            params, model_state, batch, cfg, attn_fn
        )
        return loss, model_state, counts

    forward.config = cfg
    forward.counters = dict(COUNTERS.values())
    return forward


def check_forward(params: Any, forward: Any, who: str) -> None:
    """A parameter tree that holds an expert stack trains only through
    the forward of the configuration it was made for: ``who`` refuses
    any other pairing by name, never runs a dense block on expert
    weights."""
    layers = [
        v for v in params.values() if isinstance(v, dict) and "moe" in v
    ] if isinstance(params, dict) else []
    if not layers:
        return
    cfg = getattr(forward, "config", None)
    if not is_conv_moe(cfg):
        raise NotImplementedError(
            f"{who} was handed a parameter tree with an expert stack "
            "and a forward that names no configuration for it: only "
            "models.conv_moe.make_forward differentiates through an "
            "expert layer"
        )
    want = jax.tree.map(tuple, param_shapes(cfg), is_leaf=_is_shape)
    got = jax.tree.map(lambda leaf: tuple(leaf.shape), params)
    if want != got:
        raise ValueError(
            f"{who}: the parameter tree is not {cfg.name!r}'s "
            f"(conv_moe.param_shapes): the forward it was handed is "
            "another configuration's"
        )
