"""A decoder of state-space and attention layers over an expert
feed-forward.

The language model of ``ibm-granite/granite-4.0-h-small`` (preset
:data:`GRANITE_4_0_H_SMALL`; ``model_type`` ``granitemoehybrid``): the
residual stack of ``models/llama2.py`` with these departures, each a
field here:

* the MIXER of a layer is the layer's own (``layer_types``): a
  **Mamba-2 state-space mixer** (nine layers of ten) or grouped-query
  attention with **no position signal** (``position_embedding``
  "nope": nothing is rotated) whose scores are scaled by
  ``attention_multiplier`` (1 / 128, not 128 ** -0.5);
* the state-space mixer, for the normed input ``h``: ``[z | xBC | dt] =
  h W_in`` (widths ``d_inner | d_inner + 2 ssm_state | ssm_heads``);
  ``xBC = silu(conv(xBC))``, a causal depthwise convolution of
  ``ssm_conv`` taps with a bias; ``xBC -> x [heads, head_dim], B
  [state], C [state]`` (one group: every head shares ``B`` and ``C``);
  ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a head; a
  head's state ``S [head_dim, state]`` goes ``S(t) = exp(dt A) S(t-1) +
  dt x(t) (outer) B(t)`` and gives ``y(t) = S(t) C(t) + D x(t)``;
  ``out = rmsnorm(y * silu(z)) W_out`` (the gate INSIDE the norm, one
  group over all of ``d_inner``). What a token leaves behind is ``S``
  and the last ``ssm_conv - 1`` rows of the pre-convolution ``xBC``: a
  fixed size a SEQUENCE, not a row a token. Two forms of the same
  equations: :func:`scan_step` / :func:`conv_step` for one row a
  sequence (decode) and :func:`scan_chunk` / :func:`conv_chunk` for a
  run of rows of one sequence (a prefill chunk: the products inside
  blocks of ``ssm_chunk`` rows, the state carried between blocks and
  in from the caller), both taking the state and the convolution rows
  in and giving them out;
* every layer ends in the same expert feed-forward: a linear router
  over ``n_experts``, the ``experts_per_token`` largest, gates the
  softmax over those (``sparse_moe.route`` with ``norm_topk_prob``),
  the experts HELD here (``held_experts``) through
  ``sparse_moe.expert_ffn``, and one shared SwiGLU of width
  ``shared_hidden`` that every token passes, added ungated;
* ``x0 = embedding_multiplier * E[token]``, every residual addition is
  scaled by ``residual_multiplier``, and ``logits = rmsnorm(x) E^T /
  logits_scaling``: ONE table for both ends (``tie_word_embeddings``).

The same module serves ``tiiuae/Falcon-H1-34B-Instruct`` (preset
:data:`FALCON_H1_34B`; ``model_type`` ``falcon_h1``), whose every layer
is of a third kind, ``"parallel"``: attention and a Mamba-2 mixer side
by side on ONE normed input, their outputs added in one residual, so
that every layer keeps both K/V pages and a recurrent state. What it
brings, each a field that is 1 (or off) for granite and then not
applied:

* attention rotated at ``rope_theta`` in the rotate-half convention
  (``position_embedding`` "rope_half"), heads of ``attention_head_dim``
  numbers where that is not ``dim / n_heads``, the keys times
  ``key_multiplier`` and the output times ``attention_out_multiplier``;
* a mixer of ``ssm_groups`` groups (head ``j`` reads group ``j //
  (heads / groups)``'s ``B`` and ``C``; the gated norm is taken over
  each group's ``d_inner / groups`` numbers), a width ``ssm_d_ssm``
  given by the source rather than ``ssm_expand * dim``, its input times
  ``ssm_in_multiplier`` and each segment of ``[z | x | B | C | dt]``
  times its own multiplier, its output times ``ssm_out_multiplier``;
* a dense SwiGLU feed-forward of ``mlp_hidden`` (``n_experts`` 0),
  ``down(silu(mlp_gate_multiplier * gate) * up) * mlp_down_multiplier``;
* an untied head (``output``) whose logits are divided by
  ``logits_scaling`` (Falcon-H1's ``lm_head_multiplier`` is its
  inverse).

Only the paged server runs it (``serve/paging.py``: a recurrent state a
slot beside the page pool, snapshots of it in the prefix trie). The slab
engine, the speculative runner (a rejected draft would have to roll a
state back), the host tier, disaggregation, the Pallas read path, int8
pages, the flat live-page read, a tensor axis, the pipeline split and
the trainer refuse it by name (:func:`refuse`). The functions below are
the stages ``serve/decoder.py``'s layer loop and the engine's recurrent
state call; the weights are a plain dict.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_hpc.models import llama2
from tpu_hpc.models.sparse_moe import _is_shape

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
_EXACT = jax.lax.Precision.HIGHEST
_KINDS = ("mamba", "attention", "parallel")


@dataclasses.dataclass(frozen=True)
class HybridSSMMoEConfig(llama2.LlamaConfig):
    """Defaults are granite-4.0-h-small's published sizes
    (config.json)."""

    name: str = "hybrid-ssm-moe-decoder"
    dim: int = 4096
    n_layers: int = 40
    n_heads: int = 32
    n_kv_heads: Optional[int] = 8
    vocab_size: int = 100352
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    # A layer's mixer, "mamba", "attention" or "parallel" (both on one
    # normed input, added): the leading ``n_layers`` entries are the
    # layers run.
    layer_types: Tuple[str, ...] = _PERIOD * 4
    # "nope" (nothing rotated) or "rope_half" (rotate-half at
    # ``rope_theta``).
    position_embedding: str = "nope"
    rope_theta: float = 10000.0
    # Numbers a head; None: ``dim / n_heads``.
    attention_head_dim: Optional[int] = None
    # What the scores are multiplied by; None: ``head_dim ** -0.5``.
    attention_multiplier: Optional[float] = 0.0078125
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    tie_word_embeddings: bool = True
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_expand: int = 2
    # The mixer's width where the source gives it (``mamba_d_ssm``);
    # None: ``ssm_expand * dim``.
    ssm_d_ssm: Optional[int] = None
    ssm_chunk: int = 256
    ssm_in_multiplier: float = 1.0
    # Each segment of the input projection ``[z | x | B | C | dt]``.
    ssm_z_multiplier: float = 1.0
    ssm_x_multiplier: float = 1.0
    ssm_b_multiplier: float = 1.0
    ssm_c_multiplier: float = 1.0
    ssm_dt_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    # What a sequence keeps (``S`` and the convolution's rows): what is
    # added up over a whole context stays float32 whatever the
    # products' dtype.
    ssm_state_dtype: Any = jnp.float32
    # 0: the feed-forward is a dense SwiGLU of ``mlp_hidden``.
    n_experts: int = 72
    experts_per_token: int = 10
    expert_hidden: int = 768
    shared_hidden: int = 1536
    norm_topk_prob: bool = True
    held_experts: Optional[Tuple[int, ...]] = None
    mlp_hidden: int = 0
    mlp_gate_multiplier: float = 1.0
    mlp_down_multiplier: float = 1.0

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
        if self.n_experts and not 0 < self.experts_per_token <= self.n_experts:
            raise ValueError("experts_per_token out of range")
        if not self.n_experts and self.mlp_hidden <= 0:
            raise ValueError(
                "n_experts 0 needs mlp_hidden: the width of the dense "
                "feed-forward in place of the experts"
            )
        kinds = self.layer_types[:self.n_layers]
        if len(kinds) < self.n_layers or set(kinds) - set(_KINDS):
            raise ValueError(
                f"layer_types must name one of {_KINDS} for each of the "
                f"{self.n_layers} layers, got {self.layer_types}"
            )
        if self.position_embedding not in ("nope", "rope_half"):
            raise ValueError(
                "position_embedding must be 'nope' (nothing rotated) or "
                "'rope_half' (the rotate-half convention at rope_theta), "
                f"got {self.position_embedding!r}"
            )
        if self.ssm_groups < 1 or self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"ssm_groups {self.ssm_groups} must divide ssm_heads "
                f"{self.ssm_heads}: each head reads one group's B and C"
            )
        width = self.ssm_d_ssm or self.ssm_expand * self.dim
        if self.d_inner != width:
            raise ValueError(
                f"ssm_heads x ssm_head_dim {self.d_inner} must be the "
                f"mixer's width {width} (ssm_d_ssm, else ssm_expand x dim)"
            )

    @property
    def head_dim(self) -> int:
        return self.attention_head_dim or self.dim // self.n_heads

    @property
    def lm_head_multiplier(self) -> float:
        """What the logits are multiplied by (Falcon-H1's name for the
        inverse of ``logits_scaling``)."""
        return 1.0 / self.logits_scaling

    @property
    def ffn_hidden(self) -> int:
        return self.expert_hidden if self.n_experts else self.mlp_hidden

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held_experts is None \
            else len(self.held_experts)

    @property
    def residual_dtype(self):
        """The residual stream, the router's scores and the logits are
        float32 sums of compute-dtype products, as
        ``LatentMoEConfig.residual_dtype`` has it and for its reason:
        what is added up decides a top-10 of 72 and an arg-max over
        100352 logits (PERF.md, PR 31)."""
        return jnp.float32

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Width of ``xBC``: what the convolution runs over."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    def is_ssm_layer(self, layer: int) -> bool:
        """Does ``layer`` hold a state-space mixer (and a state)?"""
        return self.layer_types[layer] in ("mamba", "parallel")

    def has_attention(self, layer: int) -> bool:
        """Does ``layer`` attend (and keep pages)?"""
        return self.layer_types[layer] in ("attention", "parallel")

    @property
    def n_ssm_layers(self) -> int:
        return sum(self.is_ssm_layer(i) for i in range(self.n_layers))

    @property
    def n_attention_layers(self) -> int:
        return sum(self.has_attention(i) for i in range(self.n_layers))

    def state_layer(self, layer: int, kind: str) -> int:
        """Where ``layer`` keeps what ``kind`` caches: its row of the
        page pool among the layers that attend (``"attention"``), or of
        the recurrent state among those with a state-space mixer
        (``"mamba"``). A parallel layer has a row in both."""
        held = self.has_attention if kind == "attention" \
            else self.is_ssm_layer
        return sum(held(i) for i in range(layer))

    def state_shapes(self, slots: int) -> Tuple[Tuple[int, ...], ...]:
        """The recurrent state of ``slots`` sequences as an engine
        keeps it: ``S`` with a head's rows end to end, ``[ssm layers,
        slots, heads * head_dim, state]``, and the convolution rows end
        to end, ``[ssm layers, slots, (taps - 1) * conv_dim]``. Two
        trailing axes that fill the chip's (8, 128) tiles whichever
        program reads them: with heads and head_dim apart the chunk
        program's products wanted them in the other order than the
        decode program's and copied all slots' state in and out a
        chunk (2.3 GB at the published sizes), and three rows are no
        tile (PERF.md, PR 33; PR 31 has the same of a page pool)."""
        return (
            (self.n_ssm_layers, slots, self.d_inner, self.ssm_state),
            (self.n_ssm_layers, slots, (self.ssm_conv - 1) * self.conv_dim),
        )

    def state_bytes(self, slots: int = 1) -> int:
        return sum(map(math.prod, self.state_shapes(slots))) \
            * jnp.dtype(self.ssm_state_dtype).itemsize


GRANITE_4_0_H_SMALL = HybridSSMMoEConfig(name="granite-4.0-h-small")

# tiiuae/Falcon-H1-34B-Instruct's config.json: 72 parallel layers.
FALCON_H1_34B = HybridSSMMoEConfig(
    name="falcon-h1-34b", dim=5120, n_layers=72, n_heads=20, n_kv_heads=4,
    vocab_size=261120, max_seq_len=262144, layer_types=("parallel",) * 72,
    position_embedding="rope_half", rope_theta=1e11, attention_head_dim=128,
    attention_multiplier=None, key_multiplier=0.011048543456039804,
    attention_out_multiplier=0.0375,
    embedding_multiplier=5.656854249492381, residual_multiplier=1.0,
    logits_scaling=128.0, tie_word_embeddings=False, ssm_heads=32,
    ssm_head_dim=128, ssm_state=256, ssm_groups=2, ssm_d_ssm=4096,
    ssm_chunk=128, ssm_in_multiplier=0.25,
    ssm_z_multiplier=0.3535533905932738, ssm_x_multiplier=0.25,
    ssm_b_multiplier=0.1767766952966369, ssm_c_multiplier=0.5,
    ssm_dt_multiplier=0.3535533905932738,
    ssm_out_multiplier=0.08838834764831845, n_experts=0,
    experts_per_token=0, expert_hidden=0, shared_hidden=0,
    mlp_hidden=21504, mlp_gate_multiplier=0.1767766952966369,
    mlp_down_multiplier=0.011160714285714284,
)

# ``python -m tpu_hpc.serve --model <name>``: the published sizes, and a
# size for the simulated mesh with every kind of layer present.
PRESETS: Dict[str, HybridSSMMoEConfig] = {
    "granite-4.0-h-small": GRANITE_4_0_H_SMALL,
    "hybrid-tiny": HybridSSMMoEConfig(
        name="hybrid-tiny", dim=64, n_layers=4,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        n_heads=4, n_kv_heads=2, vocab_size=512, max_seq_len=512,
        attention_multiplier=1 / 16, ssm_heads=8, ssm_head_dim=16,
        ssm_state=16, ssm_chunk=8, n_experts=8, experts_per_token=3,
        expert_hidden=32, shared_hidden=48,
    ),
    "falcon-h1-34b": FALCON_H1_34B,
    # Every multiplier off 1, heads of 24 numbers where dim / n_heads
    # is 16, a mixer 96 wide where ssm_expand x dim is 128, two groups.
    "falcon-h1-tiny": dataclasses.replace(
        FALCON_H1_34B, name="falcon-h1-tiny", dim=64, n_layers=3,
        layer_types=("parallel",) * 3, n_heads=4, n_kv_heads=2,
        attention_head_dim=24, vocab_size=512, max_seq_len=512,
        rope_theta=500.0, key_multiplier=0.3, attention_out_multiplier=0.7,
        embedding_multiplier=3.0, logits_scaling=4.0, ssm_heads=8,
        ssm_head_dim=12, ssm_state=16, ssm_groups=2, ssm_d_ssm=96,
        ssm_chunk=8, ssm_in_multiplier=0.5, ssm_z_multiplier=0.8,
        ssm_x_multiplier=1.25, ssm_b_multiplier=0.6, ssm_c_multiplier=1.5,
        ssm_dt_multiplier=0.9, ssm_out_multiplier=0.6, mlp_hidden=96,
        mlp_gate_multiplier=0.7, mlp_down_multiplier=1.3,
    ),
}


def is_hybrid_ssm_moe(cfg: Any) -> bool:
    return isinstance(cfg, HybridSSMMoEConfig)


def has_experts(cfg: Any) -> bool:
    """A configuration of this module whose feed-forward is the expert
    layer (every one but a dense ``n_experts`` 0)."""
    return is_hybrid_ssm_moe(cfg) and cfg.n_experts > 0


def refuse(cfg: Any, who: str, why: str) -> None:
    """One clear error, by name, from every path that has not learned
    this decoder: never a silent run of an attention layer on a
    state-space layer's weights."""
    if is_hybrid_ssm_moe(cfg):
        if "parallel" in cfg.layer_types[:cfg.n_layers]:
            what = ("layers that run attention and a state-space mixer "
                    "side by side, keeping K/V pages AND a recurrent "
                    "state a sequence, a dense feed-forward")
        else:
            what = ("state-space layers with a recurrent state a "
                    "sequence, attention without positions, an expert "
                    "feed-forward with a shared expert")
        raise NotImplementedError(
            f"{who} does not run {cfg.name!r} ({type(cfg).__name__}: "
            f"{what}): {why}. Serve it through "
            "serve.paging.PagedEngine (kernel='gather', unquantised "
            "pages, no tensor axis)."
        )


def refuse_weights(params: Any, who: str, why: str) -> None:
    """:func:`refuse` for a path that sees weights and no
    configuration (the trainer): the tree of :func:`param_shapes` is
    told by a layer's ``ssm`` group, a parallel layer's by its
    ``attention`` beside it."""
    layers = [
        layer for layer in params.values()
        if isinstance(layer, dict) and "ssm" in layer
    ] if isinstance(params, dict) else []
    if layers:
        refuse(
            FALCON_H1_34B if any("attention" in l for l in layers)
            else GRANITE_4_0_H_SMALL, who, why,
        )


# ---------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------


def _ffn_shapes(d: int, hidden: int) -> Dict:
    return {
        "w1": {"kernel": (d, hidden)},
        "w3": {"kernel": (d, hidden)},
        "w2": {"kernel": (hidden, d)},
    }


def param_shapes(cfg: HybridSSMMoEConfig) -> Dict:
    """The weights' tree as shapes, ``[in, out]`` like ``llama2``.
    ``in_proj``'s columns are ``[z | x | B | C | dt]`` (``B`` and ``C``
    group after group); the convolution's kernel is ``[tap, channel]``,
    tap ``ssm_conv - 1`` the current row's; routed experts are stacked
    on a leading axis of the experts HELD, and a dense feed-forward
    (``n_experts`` 0) is ``feed_forward``. A parallel layer holds both
    ``attention`` and ``ssm`` beside its one ``attention_norm``. A tied
    configuration has no ``output``: the head reads
    ``tok_embeddings``."""
    d, hd, e = cfg.dim, cfg.head_dim, cfg.n_held
    attention = {
        "wq": {"kernel": (d, cfg.n_heads * hd)},
        "wk": {"kernel": (d, cfg.kv_heads * hd)},
        "wv": {"kernel": (d, cfg.kv_heads * hd)},
        "wo": {"kernel": (cfg.n_heads * hd, d)},
    }
    ssm = {
        "in_proj": {"kernel": (
            d, cfg.d_inner + cfg.conv_dim + cfg.ssm_heads
        )},
        "conv": {
            "kernel": (cfg.ssm_conv, cfg.conv_dim),
            "bias": (cfg.conv_dim,),
        },
        "dt_bias": (cfg.ssm_heads,),
        "A_log": (cfg.ssm_heads,),
        "D": (cfg.ssm_heads,),
        "norm": {"scale": (cfg.d_inner,)},
        "out_proj": {"kernel": (cfg.d_inner, d)},
    }
    moe = {
        "router": {"kernel": (d, cfg.n_experts)},
        "w1": (e, d, cfg.expert_hidden),
        "w3": (e, d, cfg.expert_hidden),
        "w2": (e, cfg.expert_hidden, d),
        "shared": _ffn_shapes(d, cfg.shared_hidden),
    }
    ffn = {"moe": moe} if cfg.n_experts \
        else {"feed_forward": _ffn_shapes(d, cfg.mlp_hidden)}
    tree = {}
    for i in range(cfg.n_layers):
        mixer = {}
        if cfg.has_attention(i):
            mixer["attention"] = attention
        if cfg.is_ssm_layer(i):
            mixer["ssm"] = ssm
        tree[f"layers_{i}"] = {
            "attention_norm": {"scale": (d,)},
            **mixer,
            "ffn_norm": {"scale": (d,)},
            **ffn,
        }
    tree["tok_embeddings"] = {"embedding": (cfg.vocab_size, d)}
    tree["norm"] = {"scale": (d,)}
    if not cfg.tie_word_embeddings:
        tree["output"] = {"kernel": (d, cfg.vocab_size)}
    return tree


def _size(tree) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(tree, is_leaf=_is_shape))


def count_params(cfg: HybridSSMMoEConfig) -> Dict[str, int]:
    """``total`` held here, ``active`` a token passes through (its
    ``experts_per_token`` routed experts of each layer, no embedding
    row but its own; the table counts once, as the head: a tied one IS
    the head, an untied head is as large), and the parts both are made
    of."""
    shapes = param_shapes(cfg)
    one_expert = 3 * cfg.dim * cfg.expert_hidden
    routed = cfg.n_held * one_expert if cfg.n_experts else 0
    stack = [shapes[f"layers_{i}"] for i in range(cfg.n_layers)]
    layers = sum(map(_size, stack))
    table = cfg.vocab_size * cfg.dim
    head = 0 if cfg.tie_word_embeddings else table

    def mixer(kind):
        return next((_size(lp[kind]) for lp in stack if kind in lp), 0)

    return {
        "ssm_per_layer": mixer("ssm"),
        "attention_per_layer": mixer("attention"),
        "experts_per_layer": routed,
        "feed_forward_per_layer": mixer("feed_forward"),
        "embed_and_head": table + head,
        "total": layers + table + head + cfg.dim,
        "active": layers - cfg.n_layers * (
            routed - cfg.experts_per_token * one_expert if routed else 0
        ) + table + cfg.dim,
    }


def init_hybrid_ssm_moe(rng: jax.Array, cfg: HybridSSMMoEConfig) -> Dict:
    """Seeded weights in ``cfg.param_dtype``, made where they are used
    (jit this). Matrices by ``llama2``'s scheme as
    ``sparse_moe.init_sparse_moe`` has it (Normal(0.02), the residual
    output projections ``wo``, ``out_proj`` and every ``w2`` scaled by
    depth, unit norm scales), the state-space layer's own parameters
    by Mamba-2's published initialisation, and three choices that
    condition a random model of THIS parametrisation like a trained
    one (a tied table, a 1 / head_dim score scale):

    * ``A_log = log(U[1, 16])``; ``dt_bias`` the inverse softplus of a
      log-uniform draw in [1e-3, 1e-1] (memories of a few to a thousand
      tokens); ``D = 1``; the convolution's kernel and bias U(+-1/2)
      (``1 / sqrt(taps)``);
    * the table is Normal(0.001). It is BOTH ends of the model: with a
      unit-normal table the stream ``12 E[token]`` outweighs every
      layer's addition, the final state still points along ``E[token]``
      and the tied head scores the input token 60 standard deviations
      above the rest, so a random model echoes its input and no check
      of its logits sees the layers. At 0.001 the embedding is a few
      per cent of the final stream, as in a trained model, and the
      arg-max is the layers' (the norms make every layer's input unit
      size whatever the table's);
    * ``wq`` and ``wk`` are Normal(0.02 * head_dim ** 0.25): under the
      published score scale ``1 / head_dim`` Normal(0.02) projections
      give scores a spread of 0.15 and uniform attention over a 29k
      context, whose output is the mean of 29k values, nothing; this
      gives the spread ``head_dim ** -0.5`` gives the other decoders
      (1.6), so that what attention reads reaches the logits.

    A configuration with an untied head and multipliers throughout
    (Falcon-H1) is conditioned by one rule instead: every matrix is
    drawn as ``llama2.init_llama`` draws its counterpart (the table
    Normal(1), the head and the other matrices Normal(0.02), the output
    projections scaled by depth) and DIVIDED by the multiplier the
    equations put on its product: the table by
    ``embedding_multiplier``, the head by ``lm_head_multiplier``,
    ``wk`` by ``key_multiplier``, ``wo`` by
    ``attention_out_multiplier``, each column of ``in_proj`` by its
    segment's multiplier times ``ssm_in_multiplier``, ``out_proj`` by
    ``ssm_out_multiplier``, ``w1`` and ``w2`` of the feed-forward by
    the gate's and the down projection's. A trained model's weights are
    as large as its multipliers are small; drawn so, every product is
    the size a plain pre-norm decoder's is, the scores spread as
    ``head_dim ** -0.5`` spreads Normal(0.02) projections (1.6 at 4096
    wide, 2.0 at 5120), and each half of a parallel layer moves the
    stream by a share of its own (PERF.md, section 4). With every
    multiplier 1 and a tied table the draw is the one above."""
    untied = not cfg.tie_word_embeddings
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    keys = jax.random.split(rng, len(leaves))
    dtype = cfg.param_dtype
    out = []
    for key, (path, shape) in zip(keys, leaves):
        names = [getattr(p, "key", None) for p in path]
        leaf = names[-1]
        if leaf in ("scale", "D"):
            value = jnp.ones(shape, jnp.float32)
        elif leaf == "A_log":
            value = jnp.log(jax.random.uniform(
                key, shape, jnp.float32, 1.0, 16.0
            ))
        elif leaf == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)
            ))
            value = dt + jnp.log(-jnp.expm1(-dt))
        elif "conv" in names:
            bound = cfg.ssm_conv ** -0.5
            value = jax.random.uniform(
                key, shape, jnp.float32, -bound, bound
            )
        else:
            std = 0.02
            if leaf == "embedding":
                std = 1.0 / cfg.embedding_multiplier if untied else 0.001
            elif "output" in names:
                std = 0.02 / cfg.lm_head_multiplier
            elif "wq" in names or "wk" in names:
                if cfg.attention_multiplier is not None:
                    std = 0.02 * cfg.head_dim ** 0.25
            elif cfg.depth_init and (
                {"wo", "w2", "out_proj"} & set(names)
            ):
                layer = int(names[0].split("_")[1])
                std = 0.02 / (2 * (layer + 1)) ** 0.5
            std = std / _multiplier_on(names, cfg)
            value = std * jax.random.normal(key, shape, jnp.float32)
        out.append(value.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def _multiplier_on(names, cfg):
    """What the equations multiply the product of the matrix at the
    tree path ``names`` by (the table's and the head's are their own
    branch of :func:`init_hybrid_ssm_moe`): 1 for a matrix they leave
    alone, a row over ``in_proj``'s columns where a segment has one."""
    if "in_proj" in names:
        return in_scale(cfg, 1.0)
    if "feed_forward" in names:
        return {"w1": cfg.mlp_gate_multiplier,
                "w2": cfg.mlp_down_multiplier}.get(names[-2], 1.0)
    for leaf, m in (("wk", cfg.key_multiplier),
                    ("wo", cfg.attention_out_multiplier),
                    ("out_proj", cfg.ssm_out_multiplier)):
        if leaf in names:
            return m
    return 1.0


# ---------------------------------------------------------------------
# Stages (functional, over the raw dict, like sparse_moe.py's)
# ---------------------------------------------------------------------


def _dense(x, leaf, dtype):
    """A product of ``dtype`` operands that KEEPS its float32
    accumulator: what goes into the recurrence (``z``, ``xBC``, ``dt``)
    and what the mixer adds to the float32 residual stream are not
    rounded to the compute dtype on the way. With them rounded the
    benchmark's check read a mean regret of up to 9.7e-4 sigma over
    nine seeds against a limit of 1e-3 (PERF.md, PR 33): ``x``, ``B``
    and ``C`` multiply, so their roundings compound, and a perturbed
    stream flips tenth-against-eleventh expert choices downstream."""
    return jax.lax.dot_general(
        x.astype(dtype), leaf["kernel"].astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def in_scale(cfg: HybridSSMMoEConfig, none=None):
    """What :func:`in_proj` multiplies its float32 product's columns
    ``[z | x | B | C | dt]`` by: ``ssm_in_multiplier`` times each
    segment's own multiplier, as one float32 row, or ``none`` where
    every one is 1. The equations put ``ssm_in_multiplier`` on the
    normed input; on the product it gives the same numbers wherever it
    is a power of two (Falcon-H1's is 0.25)."""
    bc = cfg.ssm_groups * cfg.ssm_state
    segments = (
        (cfg.d_inner, cfg.ssm_z_multiplier),
        (cfg.d_inner, cfg.ssm_x_multiplier),
        (bc, cfg.ssm_b_multiplier),
        (bc, cfg.ssm_c_multiplier),
        (cfg.ssm_heads, cfg.ssm_dt_multiplier),
    )
    if cfg.ssm_in_multiplier == 1.0 and all(m == 1.0 for _, m in segments):
        return none
    return jnp.concatenate([
        jnp.full((width,), cfg.ssm_in_multiplier * m, jnp.float32)
        for width, m in segments
    ])


def in_proj(h, lp, cfg: HybridSSMMoEConfig):
    """The normed input ``h [b, s, dim]`` -> the gate ``z [b, s,
    d_inner]``, the convolution's input ``xBC [b, s, conv_dim]`` (what
    a sequence's convolution rows are rows of) and the raw step ``dt
    [b, s, heads]``, float32 (:func:`_dense`), each segment at its
    multiplier (:func:`in_scale`)."""
    zxd = _dense(h, lp["ssm"]["in_proj"], cfg.dtype)
    scale = in_scale(cfg)
    if scale is not None:
        zxd = zxd * scale
    d, c = cfg.d_inner, cfg.conv_dim
    return zxd[..., :d], zxd[..., d:d + c], zxd[..., d + c:]


def out_proj(y, z, lp, cfg: HybridSSMMoEConfig):
    """``y [b, s, heads, head_dim]`` float32 and the gate ``z`` ->
    ``rmsnorm(y * silu(z)) W_out`` times ``ssm_out_multiplier``: the
    gate inside the norm, the norm over each group's ``d_inner /
    groups`` numbers (one group: all of ``d_inner``), in float32."""
    ssm = lp["ssm"]
    g = y.reshape(*y.shape[:2], cfg.d_inner) \
        * jax.nn.silu(z.astype(jnp.float32))
    groups = cfg.ssm_groups
    if groups > 1:
        g = g.reshape(*g.shape[:-1], groups, cfg.d_inner // groups)
    g = g * jax.lax.rsqrt(
        jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps
    )
    if groups > 1:
        g = g.reshape(*g.shape[:-2], cfg.d_inner)
    out = _dense(
        g * ssm["norm"]["scale"].astype(jnp.float32), ssm["out_proj"],
        cfg.dtype,
    )
    m = cfg.ssm_out_multiplier
    return out if m == 1.0 else out * m


def split_xbc(xbc, cfg: HybridSSMMoEConfig):
    """The convolved ``xBC [..., conv_dim]`` -> ``x [..., heads,
    head_dim]``, ``B [..., state]``, ``C [..., state]``; with several
    groups ``B`` and ``C`` are ``[..., groups, state]``."""
    d, n, groups = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups
    x = xbc[..., :d].reshape(*xbc.shape[:-1], cfg.ssm_heads,
                             cfg.ssm_head_dim)
    b, c = xbc[..., d:d + groups * n], xbc[..., d + groups * n:]
    if groups > 1:
        b, c = (v.reshape(*v.shape[:-1], groups, n) for v in (b, c))
    return x, b, c


def _per_head(v, heads):
    """A group's ``B`` or ``C`` ``[..., groups, state]`` -> each head's
    ``[..., heads, state]``: head ``j`` reads group ``j // (heads /
    groups)``."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def discretise(dt, lp):
    """The raw step ``dt [..., heads]`` -> ``softplus(dt + dt_bias)``
    and the head's ``A = -exp(A_log)``, float32."""
    ssm = lp["ssm"]
    step = jax.nn.softplus(
        dt.astype(jnp.float32) + ssm["dt_bias"].astype(jnp.float32)
    )
    return step, -jnp.exp(ssm["A_log"].astype(jnp.float32))


def _taps(lp):
    conv = lp["ssm"]["conv"]
    return conv["kernel"].astype(jnp.float32), \
        conv["bias"].astype(jnp.float32)


def conv_chunk(xbc, rows, lp, true_len, snap_len):
    """The causal depthwise convolution over one sequence's run of rows
    ``xbc [L, conv_dim]`` behind the ``taps - 1`` rows that came before
    it (``rows``) -> ``silu(conv)`` float32 ``[L, conv_dim]``, the rows
    the NEXT run comes behind, which are the last ``taps - 1`` of the
    ``true_len`` real rows (a bucket's padded rows leave nothing), and
    the same after ``snap_len`` rows."""
    kernel, bias = _taps(lp)
    n = xbc.shape[0]
    window = jnp.concatenate([rows.astype(xbc.dtype), xbc], axis=0)
    out = bias + sum(
        kernel[j] * window[j:j + n].astype(jnp.float32)
        for j in range(kernel.shape[0])
    )
    after = [
        jax.lax.dynamic_slice_in_dim(window, at, rows.shape[0], axis=0)
        for at in (true_len, snap_len)
    ]
    return jax.nn.silu(out), *after


def conv_step(xbc, rows, lp):
    """:func:`conv_chunk` for one row a sequence: ``xbc [b,
    conv_dim]`` behind ``rows [b, taps - 1, conv_dim]`` -> ``silu(conv)
    [b, conv_dim]`` and the rows the next step comes behind."""
    kernel, bias = _taps(lp)
    window = jnp.concatenate(
        [rows.astype(xbc.dtype), xbc[:, None]], axis=1
    )
    out = bias + jnp.einsum(
        "jc,bjc->bc", kernel, window.astype(jnp.float32)
    )
    return jax.nn.silu(out), window[:, 1:]


def scan_step(x, dt, a, b, c, state):
    """One step of the recurrence for ``b`` sequences: ``x [b, heads,
    head_dim]``, ``dt [b, heads]``, ``a [heads]``, ``b``, ``c [b,
    state]`` (``[b, groups, state]`` with several groups) float32 and
    ``state [b, heads, head_dim, state]`` -> ``(S C, S)`` with ``S =
    exp(dt a) state + dt x (outer) b``. Elementwise in float32: no
    product is rounded."""
    state = state.astype(jnp.float32)

    def heads(v):           # B or C against [b, heads, head_dim, state]
        if v.ndim == 2:
            return v[:, None, None, :]
        return _per_head(v, x.shape[1])[:, :, None, :]

    new = state * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * heads(b)
    return jnp.sum(new * heads(c), axis=-1), new


def _state_after(xd, la, b, state, n):
    """The state after the leading ``n`` rows of a run: ``xd [L, heads,
    head_dim]`` (``dt x``), ``la [L, heads]`` (``dt a``, the log of a
    row's decay, <= 0), ``b [L, state]`` (each head's: ``[L, heads,
    state]``). Every exponent is <= 0, so the run needs no blocks: a
    row that has decayed to nothing underflows to 0."""
    keep = jnp.arange(xd.shape[0]) < n
    cum = jnp.cumsum(jnp.where(keep[:, None], la, 0.0), axis=0)
    to_end = jnp.where(keep[:, None], jnp.exp(cum[-1] - cum), 0.0)
    bn = "shn" if b.ndim == 3 else "sn"
    return state * jnp.exp(cum[-1])[:, None, None] + jnp.einsum(
        f"sh,shp,{bn}->hpn", to_end, xd, b, precision=_EXACT
    )


def scan_chunk(x, dt, a, b, c, state, block, snap_len):
    """The recurrence over one sequence's run of rows, in the chunked
    form: ``x [L, heads, head_dim]``, ``dt [L, heads]`` (0 on a padded
    row: it decays nothing and leaves nothing), ``a [heads]``, ``b``,
    ``c [L, state]`` (``[L, groups, state]`` with several groups)
    float32, ``state [heads, head_dim, state]`` the run comes behind ->
    ``(y [L, heads, head_dim], the state after the run, the state after
    its leading snap_len rows)``.

    Inside a block of ``block`` rows a row reads the rows before it
    through one masked product, ``y_t = sum_{s <= t} exp(sum_{s < u <=
    t} dt_u a) (c_t . b_s) dt_s x_s``; what came before the block
    reaches it through the state at the block's start, decayed to the
    row. The same numbers as :func:`scan_step` row by row, in another
    order (tests/test_hybrid_ssm_moe.py), with the products in
    float32."""
    n = x.shape[0]
    q = min(block, n)
    if n % q:
        raise ValueError(f"a run of {n} rows is no multiple of {q}")
    state = state.astype(jnp.float32)
    # Several groups: each head's B and C, and every product below a
    # head's own ("h" before the state's axis).
    grouped = b.ndim == 3
    if grouped:
        b, c = (_per_head(v, x.shape[1]) for v in (b, c))
    bn, h = ("hn", "h") if grouped else ("n", "")
    la, xd = dt * a, dt[..., None] * x
    causal = jnp.tril(jnp.ones((q, q), bool))
    ys = []
    for k in range(n // q):
        rows = slice(k * q, (k + 1) * q)
        start = state if k == 0 else _state_after(xd, la, b, state, k * q)
        cum = jnp.cumsum(la[rows], axis=0).T                 # [h, q]
        decay = jnp.exp(jnp.where(
            causal, cum[:, :, None] - cum[:, None, :], -jnp.inf
        ))                                                   # [h, t, s]
        inside = jnp.einsum(
            f"{h}ts,hts,shp->thp",
            jnp.einsum(f"t{bn},s{bn}->{h}ts", c[rows], b[rows],
                       precision=_EXACT),
            decay, xd[rows], precision=_EXACT,
        )
        before = jnp.einsum(
            f"t{bn},hpn->thp", c[rows], start, precision=_EXACT
        ) * jnp.exp(cum).T[..., None]
        ys.append(inside + before)
    return (
        jnp.concatenate(ys, axis=0),
        _state_after(xd, la, b, state, n),
        _state_after(xd, la, b, state, snap_len),
    )
