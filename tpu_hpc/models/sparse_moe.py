"""A sparse-expert decoder with a learned token selector.

The language model of ``Kwai-Keye/Keye-VL-2.0-30B-A3B`` (preset
:data:`KEYE_VL2_30B_A3B`): the Llama-shaped residual stack of
``models/llama2.py`` with four departures, each a field here so that a
``LlamaConfig`` (all of them off) stays the dense model it was:

* ``head_dim`` is its own size (32 heads x 128 over a hidden size of
  2048), the rotary base is ``rope_theta``, and q and k pass a per-head
  RMSNorm before the rotation (``qk_norm``);
* the feed-forward is ``n_experts`` SwiGLU experts of width
  ``expert_hidden``, ``experts_per_token`` a token by the softmax of a
  linear router, gates renormalised over the chosen (``norm_topk_prob``).
  ``held_experts`` names the expert ids whose weights this process holds
  (``None``: all). The router keeps its published width and chooses
  among ALL experts; an absent expert adds nothing, so the layer
  computes its share of an expert-parallel deployment's result and the
  shares add up to the whole (tests/test_sparse_moe.py);
* an **indexer** beside attention (the lightning indexer of DeepSeek
  Sparse Attention): ``indexer_heads`` small query heads and ONE key of
  ``indexer_head_dim`` numbers a token score every cached token,
  ``I[t, s] = sum_a w[t, a] * relu(qI[t, a] . kI[s])``, and attention
  reads only the ``indexer_topk`` best-scoring ``s <= t`` (all of them
  while there are no more than that), ties to the lower ``s``.

Only the paged server runs it (``serve/paging.py``: the indexer's key
is a third per-token array of the page pool). The slab engine, the
speculative runner, the host tier, the pipeline split and the trainer
refuse it by name (:func:`refuse`). The functions below are the
stages the serving programs call; the weights are a plain dict in the
checkpoint layout of ``llama2`` where the two share a matrix.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpu_hpc.kernels import grouped_experts
from tpu_hpc.models import llama2


@dataclasses.dataclass(frozen=True)
class SparseMoEConfig(llama2.LlamaConfig):
    """Defaults are Keye-VL-2.0-30B-A3B's published sizes
    (config.json; ``sa_config`` for the indexer)."""

    name: str = "sparse-moe-decoder"
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: Optional[int] = 4
    head_dim: int = 128
    vocab_size: int = 151936
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    rope_theta: float = 1e7
    qk_norm: bool = True
    n_experts: int = 128
    experts_per_token: int = 8
    expert_hidden: int = 768
    norm_topk_prob: bool = True
    held_experts: Optional[Tuple[int, ...]] = None
    indexer_heads: int = 16
    indexer_head_dim: int = 64
    # Leading numbers of each indexer head that are rotated (DeepSeek
    # Sparse Attention rotates half of its indexer head).
    indexer_rope_dim: int = 32
    indexer_topk: int = 2048

    def __post_init__(self):
        held = self.held_experts
        if held is not None:
            if len(set(held)) != len(held) or not all(
                0 <= e < self.n_experts for e in held
            ):
                raise ValueError(
                    f"held_experts {held} must be distinct ids below "
                    f"{self.n_experts}"
                )
        if not 0 < self.experts_per_token <= self.n_experts:
            raise ValueError("experts_per_token out of range")
        if self.indexer_rope_dim % 2 \
                or self.indexer_rope_dim > self.indexer_head_dim:
            raise ValueError("indexer_rope_dim must be even and fit a head")

    @property
    def ffn_hidden(self) -> int:
        return self.expert_hidden

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held_experts is None \
            else len(self.held_experts)


KEYE_VL2_30B_A3B = SparseMoEConfig(name="keye-vl2-30b-a3b")


def is_sparse_moe(cfg: Any) -> bool:
    return isinstance(cfg, SparseMoEConfig)


def refuse(cfg: Any, who: str, why: str) -> None:
    """One clear error, by name, from every path that has not learned
    this decoder: never a silent run of the dense layer on its
    weights."""
    if is_sparse_moe(cfg):
        raise NotImplementedError(
            f"{who} does not run {cfg.name!r} ({type(cfg).__name__}: "
            f"expert feed-forward, indexer-selected attention): {why}. "
            "Serve it through serve.paging.PagedEngine."
        )


def refuse_weights(params: Any, who: str, why: str) -> None:
    """:func:`refuse` for a path that sees weights and no
    configuration (the trainer): the tree of :func:`param_shapes` is
    told by its ``moe`` and ``indexer`` groups."""
    layer = params.get("layers_0") if isinstance(params, dict) else None
    if isinstance(layer, dict) and "moe" in layer and "indexer" in layer:
        refuse(KEYE_VL2_30B_A3B, who, why)


# ---------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------


def param_shapes(cfg: SparseMoEConfig) -> Dict:
    """The weights' tree as shapes. Matrices shared with ``llama2``
    keep its names and ``[in, out]`` layout; experts are stacked on a
    leading axis of the experts HELD."""
    d, hd = cfg.dim, cfg.head_dim
    hi, di, e = cfg.indexer_heads, cfg.indexer_head_dim, cfg.n_held
    layer = {
        "attention_norm": {"scale": (d,)},
        "attention": {
            "wq": {"kernel": (d, cfg.n_heads * hd)},
            "wk": {"kernel": (d, cfg.kv_heads * hd)},
            "wv": {"kernel": (d, cfg.kv_heads * hd)},
            "wo": {"kernel": (cfg.n_heads * hd, d)},
        },
        "indexer": {
            "wq": {"kernel": (d, hi * di)},
            "wk": {"kernel": (d, di)},
            "k_norm": {"scale": (di,), "bias": (di,)},
            "weights": {"kernel": (d, hi)},
        },
        "ffn_norm": {"scale": (d,)},
        "moe": {
            "router": {"kernel": (d, cfg.n_experts)},
            "w1": (e, d, cfg.expert_hidden),
            "w3": (e, d, cfg.expert_hidden),
            "w2": (e, cfg.expert_hidden, d),
        },
    }
    if cfg.qk_norm:
        layer["attention"]["q_norm"] = {"scale": (hd,)}
        layer["attention"]["k_norm"] = {"scale": (hd,)}
    tree = {f"layers_{i}": layer for i in range(cfg.n_layers)}
    tree["tok_embeddings"] = {"embedding": (cfg.vocab_size, d)}
    tree["norm"] = {"scale": (d,)}
    tree["output"] = {"kernel": (d, cfg.vocab_size)}
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def count_params(cfg: SparseMoEConfig) -> Dict[str, int]:
    """``total`` held here, ``active`` a token passes through (its
    ``experts_per_token`` experts of each layer, no embedding row but
    its own), and the parts both are made of."""
    def size(tree):
        return sum(
            math.prod(s) for s in jax.tree.leaves(tree, is_leaf=_is_shape)
        )

    shapes = param_shapes(cfg)
    layer = shapes["layers_0"]
    one_expert = 3 * cfg.dim * cfg.expert_hidden
    experts = cfg.n_held * one_expert
    per_layer = size(layer)
    edge = size(shapes["tok_embeddings"]) + size(shapes["output"])
    return {
        "per_layer": per_layer,
        "experts_per_layer": experts,
        "indexer_per_layer": size(layer["indexer"]),
        "embed_and_head": edge,
        "total": cfg.n_layers * per_layer + edge + cfg.dim,
        "active": cfg.n_layers * (
            per_layer - experts + cfg.experts_per_token * one_expert
        ) + edge // 2 + cfg.dim,
    }


def init_sparse_moe(rng: jax.Array, cfg: SparseMoEConfig) -> Dict:
    """Seeded weights in ``cfg.param_dtype``, made where they are used
    (jit this: eagerly it would park each float32 draw on device 0).
    ``llama2``'s scheme: a unit-normal embedding (so the residual
    stream outweighs what one layer adds, as in a trained model: with
    a 0.02 embedding one flipped expert choice moved the hidden state
    by 40 % and bf16 could not be told from a fault, PERF.md PR 27),
    Normal(0.02) matrices with depth-scaled output projections, unit
    norm scales, zero norm bias."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    keys = jax.random.split(rng, len(leaves))
    dtype = cfg.param_dtype
    out = []
    for key, (path, shape) in zip(keys, leaves):
        names = [getattr(p, "key", None) for p in path]
        if names[-1] == "scale":
            out.append(jnp.ones(shape, dtype))
        elif names[-1] == "bias":
            out.append(jnp.zeros(shape, dtype))
        else:
            std = 1.0 if names[-1] == "embedding" else 0.02
            if cfg.depth_init and ("wo" in names or "w2" in names):
                layer = int(names[0].split("_")[1])
                std = 0.02 / (2 * (layer + 1)) ** 0.5
            out.append(
                (std * jax.random.normal(key, shape, jnp.float32))
                .astype(dtype)
            )
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------
# Stages (functional, over the raw dict, like serve/engine.py's)
# ---------------------------------------------------------------------


def _dot(x, kernel, dtype):
    """Contract the trailing dim in ``dtype``, accumulate and return
    float32 (the router's and the indexer's small products feed a
    softmax or a ranking: they keep their accumulator's bits)."""
    return jax.lax.dot_general(
        x.astype(dtype), kernel.astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def route(h, lp, cfg: SparseMoEConfig):
    """``h [..., dim]`` -> ``(gates [..., k] float32, experts [..., k]
    int32)``: softmax over ALL experts, the exact top-k (ties to the
    lower id), gates renormalised over the chosen. A configuration
    that keeps its residual stream float32 (``residual_dtype``:
    ``models/hybrid_ssm_moe.py``) has the router's product in float32
    too, as ``latent_moe.route`` does and for its reason."""
    kernel = lp["moe"]["router"]["kernel"]
    if getattr(cfg, "residual_dtype", None) is None:
        logits = _dot(h, kernel, cfg.dtype)
    else:
        logits = jax.lax.dot_general(
            h.astype(jnp.float32), kernel.astype(jnp.float32),
            (((h.ndim - 1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
        )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


def _held_slots(cfg: SparseMoEConfig):
    """Expert id -> its row in the held stack, ``n_held`` for an absent
    one."""
    if cfg.held_experts is None:
        return jnp.arange(cfg.n_experts, dtype=jnp.int32)
    slot = [cfg.n_held] * cfg.n_experts
    for row, e in enumerate(cfg.held_experts):
        slot[e] = row
    return jnp.asarray(slot, jnp.int32)


def grouped_by_shape(n_tokens: int, cfg: SparseMoEConfig) -> bool:
    """Whether :func:`expert_ffn` over ``n_tokens`` rows visits the
    touched experts only: where the rows' assignments are fewer than
    the experts routed among (``n_tokens * experts_per_token <
    n_experts``), so that most experts get no token WHATEVER the
    routing (a share of the experts sees that share of the
    assignments: the same test). A decode step of a dozen slots passes
    it, a prefill chunk does not. Measured on the v5e the grouped
    traversal costs what it touches (12.3 us an expert of 9.44 MB) and
    meets the dense one only where every expert is touched, so the
    rule is conservative: it keeps the dense form where a list saves
    few bytes or none (docs/guide/sparse_moe.md has the numbers). And
    two whole experts have to fit the chip's fast memory, which is how
    the kernel streams them."""
    return n_tokens * cfg.experts_per_token < cfg.n_experts \
        and grouped_experts.fits(
            cfg.dim, cfg.expert_hidden, jnp.dtype(cfg.dtype).itemsize
        )


def visit_list(touched, n_visit: int):
    """``touched [n_held]`` bool -> ``(visit [n_visit] int32,
    n_touched [] int32)``: the touched rows in ascending order, then
    the last of them repeated (row 0 where none is touched).
    ``n_visit`` must be at least the most rows that can be touched."""
    rows = jnp.arange(touched.shape[0], dtype=jnp.int32)
    steps = jnp.arange(n_visit, dtype=jnp.int32)
    n_touched = jnp.sum(touched, dtype=jnp.int32)
    rank = jnp.cumsum(touched, dtype=jnp.int32) - 1
    at = touched[None, :] & (rank[None, :] == steps[:, None])
    visit = jnp.sum(jnp.where(at, rows[None, :], 0), axis=1)
    last = jnp.max(jnp.where(touched, rows, 0))
    return jnp.where(steps < n_touched, visit, last), n_touched


def _on_mesh(kernel, mesh):
    """The kernel where the program runs: interpreted unless that is a
    TPU (the serving ``mesh``'s devices; the default backend's where
    the caller is no serving program), and on a mesh of several chips
    under ``shard_map`` with every operand whole on every chip (the
    expert stacks are replicated, ``serving_pspecs``; XLA cannot
    partition a Mosaic call by itself)."""
    platform = jax.default_backend() if mesh is None \
        else mesh.devices.flat[0].platform
    call = functools.partial(kernel, interpret=platform != "tpu")
    if mesh is None or mesh.size == 1 or platform != "tpu":
        return call
    return jax.shard_map(
        call, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )


def _dense_experts(h, held_gates, moe, cfg: SparseMoEConfig):
    """ONE feed-forward of width ``n_held * expert_hidden`` whose hidden
    activations are scaled by the gates: every held expert's weights
    read once, whatever the routing."""
    x = h.astype(cfg.dtype)
    gate = jnp.einsum("td,edf->tef", x, moe["w1"].astype(cfg.dtype))
    up = jnp.einsum("td,edf->tef", x, moe["w3"].astype(cfg.dtype))
    hidden = jax.nn.silu(gate) * up * held_gates.astype(cfg.dtype)[..., None]
    return jnp.einsum("tef,efd->td", hidden, moe["w2"].astype(cfg.dtype))


def _grouped_experts(h, held_gates, spread, w, moe, cfg, mesh):
    """The held experts that at least one counted row (``w``) chose,
    each read once by ``kernels/grouped_experts.py`` -> ``(out
    [tokens, dim], visited [n_held] bool)``: the float32 sum rounded
    once, and the experts the list really named (what ``dropped`` is
    reckoned against)."""
    n_tok = h.shape[0]
    touched = jnp.einsum("t,tke->e", w.astype(jnp.float32), spread) > 0
    visit, n_touched = visit_list(
        touched, min(cfg.n_held, n_tok * cfg.experts_per_token)
    )
    out = _on_mesh(grouped_experts.grouped_expert_ffn, mesh)(
        h.astype(cfg.dtype), held_gates, visit, n_touched,
        moe["w1"].astype(cfg.dtype), moe["w3"].astype(cfg.dtype),
        moe["w2"].astype(cfg.dtype),
    )
    made = jnp.arange(visit.shape[0]) < n_touched
    visited = jnp.any(
        made[:, None] & (visit[:, None] == jnp.arange(cfg.n_held)), axis=0
    )
    return out.astype(cfg.dtype), visited


def expert_ffn(h, gates, experts, lp, cfg: SparseMoEConfig, weight=None,
               mesh=None):
    """The held experts' part of ``sum_e gate_e * W2_e(silu(W1_e h) *
    W3_e h)`` for tokens ``h [tokens, dim]``, and the step's counts.

    Every assignment to a held expert is computed, at any imbalance:
    the gates are spread over the held experts' axis (zero where a
    token did not choose one) and every row meets every expert the
    product reads. ONE sum, two traversals, chosen by the static shape
    (:func:`grouped_by_shape`; no option selects one):

    * **dense**: one feed-forward over the whole held stack
      (:func:`_dense_experts`). Each held expert's weights are read
      once whatever the routing: right where the rows' assignments
      cover the experts (a prefill chunk; a decode step of sixteen
      slots x ten over eighteen held experts);
    * **grouped**: the experts with at least one counted token, in
      ascending order (:func:`visit_list`), each read once by
      ``kernels/grouped_experts.py`` and multiplied against every row
      (a row that did not choose it has gate zero). It leaves out
      terms that are exactly zero and nothing else: the same operands,
      the sum over experts in float32, rounded once where the dense
      form rounds. A decode step of twelve slots x eight over 128
      experts touches 69 of them.

    ``weight [tokens]`` (0/1) marks the tokens that count (a decode
    step's inactive slots compute garbage nobody reads, and add no
    expert to the grouped form's list). Counts, all int32: assignments
    made, those of them that landed on held experts (the rest are
    other chips'), distinct HELD experts chosen (what the grouped form
    reads), the most tokens one expert got, assignments of counted
    tokens to held experts that the product did not cover (0 by
    construction, in the grouped form against the list it visited; the
    counter is the contract)."""
    n_tok = h.shape[0]
    slots = _held_slots(cfg)[experts]                      # [t, k]
    spread = jax.nn.one_hot(slots, cfg.n_held, dtype=jnp.float32)
    held_gates = jnp.einsum("tk,tke->te", gates, spread)   # [t, held]
    moe = lp["moe"]
    grouped = grouped_by_shape(n_tok, cfg)
    if not grouped:
        # Ahead of the counts, as it always was: trace order is the
        # lowered text's order, and the dense programs' text is pinned.
        out = _dense_experts(h, held_gates, moe, cfg)

    w = jnp.ones((n_tok,), jnp.int32) if weight is None \
        else weight.astype(jnp.int32)
    chosen = jax.nn.one_hot(experts, cfg.n_experts, dtype=jnp.int32)
    per_expert = jnp.einsum("t,tke->e", w, chosen)
    to_held = jnp.sum(w[:, None] * (slots < cfg.n_held))
    if grouped:
        out, visited = _grouped_experts(
            h, held_gates, spread, w, moe, cfg, mesh
        )
        covered = jnp.sum(w[:, None] * ((held_gates > 0) & visited))
    else:
        covered = jnp.sum(w[:, None] * (held_gates > 0))
    def of_held(chosen):
        if cfg.held_experts is None:
            return chosen
        return chosen & (_held_slots(cfg) < cfg.n_held)

    counts = {
        "assignments": jnp.sum(per_expert),
        "experts_touched": jnp.sum(of_held(per_expert > 0)),
        "max_tokens_per_expert": jnp.max(per_expert),
        "dropped": to_held - covered,
        "assignments_held": to_held,
    }
    return out, counts


# ---------------------------------------------------------------------
# The third traversal: many rows, differentiable (training)
# ---------------------------------------------------------------------

# Rows a tile of the TPU's ragged product holds (read off its compiled
# metadata on the v5e: ``jax.lax.ragged_dot`` over 131072 rows lowers
# to a Mosaic call over 256 row tiles, a tile visited once for each
# group it overlaps and the tiles past the last group's rows not at
# all). ``rows_computed`` is RECKONED in these from the groups' sizes,
# not returned by the kernel; tests/test_fit.py compiles the products
# for the v5e and holds this number to the visits their metadata lists.
RAGGED_ROW_TILE = 512


def ragged_rows(n_tokens: int, cfg) -> int:
    """The most rows :func:`ragged_expert_ffn` can be asked to compute
    for ``n_tokens`` tokens: every one of them choosing as many held
    experts as it may. The row buffer's static size."""
    return n_tokens * min(cfg.experts_per_token, cfg.n_held)


@jax.custom_vjp
def _dispatch(x, row_token, at, held):
    """``rows[r] = x[row_token[r]]``. Its transpose is written as the
    gather it is (``dx[t] = sum_j drows[at[t, j]]`` over the held
    assignments of token ``t``): the gather's own transpose would be a
    scatter-add of every row, which a TPU walks index by index."""
    return x[row_token]


def _dispatch_fwd(x, row_token, at, held):
    return x[row_token], (at, held)


def _dispatch_bwd(res, d_rows):
    at, held = res
    picked = jnp.where(held[..., None], d_rows[at], 0)
    return jnp.sum(picked.astype(jnp.float32), axis=1) \
        .astype(d_rows.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, gates, at, held, row_token, row_gate_at, n_rows):
    """``out[t] = sum_j gates[t, j] * rows[at[t, j]]`` over the held
    assignments of token ``t``, in float32. ``row_token [R]`` and
    ``row_gate_at [R]`` (a row's token and its place in the flattened
    gates) and ``n_rows`` (the rows that carry an assignment) are for
    the transpose, a gather too: ``drows[r] = gate of r * dout[token of
    r]``, and ``dgates[t, j] = dout[t] . rows[at[t, j]]``."""
    picked = jnp.where(held[..., None], rows[at], 0).astype(jnp.float32)
    return jnp.einsum("tk,tkd->td", gates, picked)


def _combine_fwd(rows, gates, at, held, row_token, row_gate_at, n_rows):
    out = _combine(rows, gates, at, held, row_token, row_gate_at, n_rows)
    return out, (rows, gates, at, held, row_token, row_gate_at, n_rows)


def _combine_bwd(res, d_out):
    rows, gates, at, held, row_token, row_gate_at, n_rows = res
    picked = jnp.where(held[..., None], rows[at], 0).astype(jnp.float32)
    d_gates = jnp.einsum("td,tkd->tk", d_out, picked)
    live = jnp.arange(rows.shape[0]) < n_rows
    row_gate = jnp.where(live, gates.reshape(-1)[row_gate_at], 0.0)
    d_rows = (row_gate[:, None] * d_out[row_token]).astype(rows.dtype)
    return d_rows, d_gates, None, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def ragged_expert_ffn(h, gates, experts, moe, cfg):
    """The held experts' part of ``sum_e gate_e * W2_e(silu(W1_e h) *
    W3_e h)`` for MANY rows ``h [tokens, dim]``, float32, and the
    step's counts: :func:`expert_ffn`'s sum by a third traversal, whose
    work follows the assignments MADE and which ``jax.grad`` goes
    through.

    The assignments to held experts are sorted by expert (stable: a
    group's rows stay in token order), their tokens' rows gathered into
    a buffer of :func:`ragged_rows` rows (static: the worst case the
    step can produce), and three ragged grouped products
    (``jax.lax.ragged_dot``: group ``e`` is the run of rows expert
    ``e`` got, of any length) make ``W2_e(silu(W1_e x) * W3_e x)`` for
    each row; a token adds up its rows by gate. The product visits the
    row tiles that hold a group's rows and no tile past the last row
    that exists, so EVERY assignment to a held expert is computed at
    any imbalance (no capacity, nothing dropped: ``dropped`` counts the
    held assignments whose row lies outside its expert's group, 0 by
    construction) and an absent expert adds nothing. Operands in ``cfg.dtype``, float32
    accumulation, each product rounded once.

    Counts, int32: ``assignments`` made, ``assignments_held`` of them
    on held experts (= rows that carry an assignment), ``rows_computed``
    (rows of the tiles the products visit: :data:`RAGGED_ROW_TILE` a
    tile, a tile once for each group it overlaps),
    ``max_rows_per_expert`` (the longest group) and ``dropped``."""
    n_tok, k = experts.shape
    n_held, n_rows_max = cfg.n_held, ragged_rows(n_tok, cfg)
    with jax.named_scope("dispatch"):
        slot = _held_slots(cfg)[experts]                    # [t, k]
        held = slot < n_held
        flat = slot.reshape(-1)
        # Sorted by expert, absent ones (slot n_held) last; an
        # assignment's place among the rows is the inverse permutation
        # (a second sort: the scatter that would write it walks its
        # indices one by one).
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        at = jnp.argsort(order).astype(jnp.int32).reshape(n_tok, k)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(n_held, dtype=jnp.int32), axis=0,
            dtype=jnp.int32,
        )
        ends = jnp.cumsum(group_sizes)
        starts = ends - group_sizes
        n_rows = ends[-1]
        # Where a token can choose more experts than are held, the
        # rows past the bound are absent experts' alone.
        order = order[:n_rows_max]
        row_token = order // k

    def product(x, w):
        return jax.lax.ragged_dot(
            x, w.astype(cfg.dtype), group_sizes,
            preferred_element_type=jnp.float32,
        ).astype(cfg.dtype)

    with jax.named_scope("dispatch"):
        at = jnp.minimum(at, n_rows_max - 1)
        rows = _dispatch(h.astype(cfg.dtype), row_token, at, held)
    with jax.named_scope("experts"):
        hidden = jax.nn.silu(product(rows, moe["w1"])) \
            * product(rows, moe["w3"])
        rows = product(hidden, moe["w2"])
    with jax.named_scope("combine"):
        out = _combine(
            rows, gates.astype(jnp.float32), at, held, row_token, order,
            n_rows,
        )
    tiles = jnp.where(
        group_sizes > 0,
        (ends + RAGGED_ROW_TILE - 1) // RAGGED_ROW_TILE
        - starts // RAGGED_ROW_TILE,
        0,
    )
    # An assignment is covered where its row lies inside its expert's
    # group, which is all the products multiply by that expert.
    own = jnp.minimum(slot, n_held - 1)
    covered = held & (at >= starts[own]) & (at < ends[own])
    counts = {
        "assignments": jnp.int32(n_tok * k),
        "assignments_held": n_rows,
        "rows_computed": jnp.sum(tiles) * RAGGED_ROW_TILE,
        "max_rows_per_expert": jnp.max(group_sizes),
        "dropped": jnp.sum(held & ~covered, dtype=jnp.int32),
    }
    return out, counts


def rope_part(x, cos, sin, n):
    """Rotate the leading ``n`` numbers of the last dim (adjacent
    pairs, as ``llama2.apply_rope``), pass the rest."""
    if n == x.shape[-1]:
        return llama2.apply_rope(x, cos, sin)
    return jnp.concatenate(
        [llama2.apply_rope(x[..., :n], cos, sin), x[..., n:]], axis=-1
    )


def indexer_project(h, lp, cfg: SparseMoEConfig, cos, sin):
    """``h [b, s, dim]`` -> the indexer's queries ``[b, s, heads,
    head_dim]``, key ``[b, s, head_dim]`` (LayerNorm, then rotated like
    the queries: it is what the pool keeps) and head weights ``[b, s,
    heads]`` float32, carrying the constant ``heads ** -0.5 *
    head_dim ** -0.5``. ``cos`` / ``sin``: tables over
    ``indexer_rope_dim``."""
    ix = lp["indexer"]
    b, s = h.shape[0], h.shape[1]
    hi, di = cfg.indexer_heads, cfg.indexer_head_dim
    q = _dot(h, ix["wq"]["kernel"], cfg.dtype).reshape(b, s, hi, di)
    k = _dot(h, ix["wk"]["kernel"], cfg.dtype)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(var + cfg.norm_eps) \
        * ix["k_norm"]["scale"].astype(jnp.float32) \
        + ix["k_norm"]["bias"].astype(jnp.float32)
    q = rope_part(q, cos, sin, cfg.indexer_rope_dim)
    k = rope_part(k[:, :, None, :], cos, sin, cfg.indexer_rope_dim)[:, :, 0]
    w = _dot(h, ix["weights"]["kernel"], cfg.dtype) * (hi * di) ** -0.5
    return q.astype(cfg.dtype), k.astype(cfg.dtype), w


def indexer_scores(q, w, keys, cfg: SparseMoEConfig):
    """``I = sum_a w_a * relu(q_a . k)``: ``q [..., heads, head_dim]``,
    ``w [..., heads]`` against ``keys [..., n, head_dim]`` (leading
    dims shared or broadcast) -> float32 ``[..., n]``."""
    dots = jnp.einsum(
        "...ad,...nd->...an", q, keys.astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )
    scores = jnp.einsum("...an,...a->...n", jax.nn.relu(dots), w)
    return scores + 0.0   # -0.0 (every head at rest) ranks as 0.0


def _rank_key(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    flipped = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(flipped, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def select_topk(scores, valid, k: int):
    """The exact top-``k`` of each row of ``scores [..., n]`` among the
    columns ``valid`` allows, as a mask: the ``k`` largest, ties to the
    lower column; every valid column where there are no more than
    ``k``.

    No sort: the ``k``-th largest value is built bit by bit (32 counts
    of ``key >= candidate`` over the row), then the columns above it
    are taken, and of those equal to it the first few that fill the
    count. Exact for any input without NaN."""
    n = scores.shape[-1]
    if k >= n:
        return valid
    key = jnp.where(valid, _rank_key(scores), jnp.uint32(0))

    def grow(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(
        0, 32, grow, jnp.zeros(scores.shape[:-1], jnp.uint32)
    )[..., None]
    above = key > thr
    equal = key == thr
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(equal, axis=-1) <= room
    return (above | (equal & first)) & valid
