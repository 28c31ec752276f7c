"""The decoder layer stack of every serving program.

A functional replay of ``models/llama2.py`` over the raw param dict
(flax params are a plain dict; serving needs the K/V tensors mid-block,
which ``nn.Module`` hides): identical dtype promotion (compute-dtype
matmuls, fp32 RMSNorm / RoPE / softmax), identical einsum contractions,
so greedy decode with a cache is token-exact against the no-cache
forward pass -- the parity oracle tests/test_serve.py enforces.

Every program under ``serve/`` (slab prefill and decode in engine.py,
chunk prefill and paged decode in paging.py, speculative draft and
verify in spec.py) embeds its tokens, runs :func:`decoder_layers`, and
applies its own token rule to :func:`_logits_head`. What differs
between them is where this step's K/V go and what attention reads back,
and that is the **attention state** the program hands the loop: a
callable ``(layer, h, lp, q, k, v) -> attended rows`` that lives next
to the cache it knows (engine.py: slab rows; paging.py:
``PagedAttention``, the page pool with its int8 scales, Pallas kernels
and indexer keys). A layer whose mixer is a state-space one
(``models/hybrid_ssm_moe.py``; told by the weights it holds, as the
feed-forward is) goes through the program's **recurrent state**
instead, ``(layer, lp, xbc, dt) -> y`` (paging.py:
``RecurrentState``, a state a slot beside the pool). A configuration's
new stage is an edit to the loop (a stage every cache sees the same
way, as the feed-forward in :func:`_ffn_stage`) or to one such state
(a stage that reads or writes what is cached), never to a program.

The loop names its stages for the trace
(docs/guide/observability.md, "Stage names"): ``qkv`` and ``attn_out``
here, ``mlp`` or ``router`` / ``experts`` in :func:`_ffn_stage`,
``kv_write``, ``indexer``, ``kv_read`` and ``attention`` in the
attention state, ``ssm_in`` and ``ssm_out`` here and ``ssm_conv`` and
``ssm_scan`` in the recurrent state for a state-space layer, ``embed``
and ``head`` in the program. An operation's stage is the LAST of these
names on its path, so no stage wraps another.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from tpu_hpc.models import hybrid_ssm_moe, latent_moe, llama2, sparse_moe


def _dense(x: jax.Array, kernel: jax.Array, dtype) -> jax.Array:
    """nn.Dense(use_bias=False, dtype=dtype): promote both operands to
    the compute dtype, then contract the trailing dim."""
    return jax.lax.dot_general(
        x.astype(dtype), kernel.astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
    )


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in fp32 with a learned scale (llama2.RMSNorm)."""
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
    )
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def _embed(params: Dict, tokens: jax.Array, cfg: llama2.LlamaConfig):
    """Token embedding lookup in the compute dtype. Identical values to
    both training paths (iota_embed's forward IS a plain gather)."""
    table = params["tok_embeddings"]["embedding"].astype(cfg.dtype)
    x = jnp.take(table, tokens, axis=0)
    # The residual stream starts here: in the compute dtype, unless the
    # configuration keeps what it adds up wider (``residual_dtype``),
    # and at the configuration's multiple of the row, if it names one.
    stream = getattr(cfg, "residual_dtype", None)
    x = x if stream is None else x.astype(stream)
    scale = getattr(cfg, "embedding_multiplier", 1.0)
    return x if scale == 1.0 else x * scale


def _residual(x, y, cfg):
    """``x + y``, ``y`` at the configuration's ``residual_multiplier``
    where it names one."""
    scale = getattr(cfg, "residual_multiplier", 1.0)
    return x + y if scale == 1.0 else x + scale * y.astype(x.dtype)


def _score_scale(cfg):
    """What attention multiplies its scores by: ``head_dim ** -0.5``,
    or the configuration's ``attention_multiplier``."""
    scale = getattr(cfg, "attention_multiplier", None)
    return cfg.head_dim ** -0.5 if scale is None else scale


def _attn_out_proj(h, lp, cfg):
    """``h [b, s, heads, value dim]`` through ``wo``."""
    b, s = h.shape[0], h.shape[1]
    return _dense(
        h.reshape(b, s, -1), lp["attention"]["wo"]["kernel"], cfg.dtype,
    )


def _mlp(x, ff, cfg):
    """The SwiGLU of one ``{w1, w3, w2}`` group of weights."""
    gate = _dense(x, ff["w1"]["kernel"], cfg.dtype)
    up = _dense(x, ff["w3"]["kernel"], cfg.dtype)
    return _dense(jax.nn.silu(gate) * up, ff["w2"]["kernel"], cfg.dtype)


def _qkv(x, lp, cfg):
    b, s = x.shape[0], x.shape[1]
    hd, n_kv = cfg.head_dim, cfg.kv_heads
    q = _dense(x, lp["attention"]["wq"]["kernel"], cfg.dtype)
    k = _dense(x, lp["attention"]["wk"]["kernel"], cfg.dtype)
    v = _dense(x, lp["attention"]["wv"]["kernel"], cfg.dtype)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, n_kv, hd)
    if getattr(cfg, "qk_norm", False):
        # Per-head RMSNorm ahead of the rotation (sparse_moe.py).
        q = _rmsnorm(q, lp["attention"]["q_norm"]["scale"], cfg.norm_eps)
        k = _rmsnorm(k, lp["attention"]["k_norm"]["scale"], cfg.norm_eps)
    return q, k, v.reshape(b, s, n_kv, hd)


def _rope_tables(cfg, n, positions=None):
    """``llama2.rope_cos_sin`` at the configuration's rotary base
    (10000 unless it names one) over the numbers of a head it rotates
    (``rope_dim``; the whole head unless it names fewer): ``[n,
    rope_dim / 2]`` for positions ``0..n-1``, or a row for each of
    ``positions``."""
    return llama2.rope_cos_sin(
        n, getattr(cfg, "rope_dim", cfg.head_dim),
        getattr(cfg, "rope_theta", 10000.0), positions=positions,
    )


def _grouped_attention(q, k, v, mask, cfg, scale=None):
    """The model's einsum attention with an explicit mask: scores in
    the compute dtype, fp32 softmax, GQA via the grouped query view
    (llama2.Attention's no-repeat-KV contraction). ``scale`` is the
    configuration's (:func:`_score_scale`) unless the caller names
    another (a latent configuration's expanded read, whose keys are
    wider than its values: the result has the values' width)."""
    b, s_q = q.shape[0], q.shape[1]
    n_kv = cfg.kv_heads
    groups = cfg.n_heads // n_kv
    qg = q.reshape(b, s_q, n_kv, groups, q.shape[-1])
    if scale is None:
        scale = _score_scale(cfg)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * scale
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, s_q, cfg.n_heads, v.shape[-1])


def _latent_attention(q, q_rope, latents, k_rope, mask, cfg, scale):
    """A latent configuration's ABSORBED read
    (``models/latent_moe.py``): queries already carried into the
    latent space ``q [b, s, heads, rank]`` with their rotary part
    ``q_rope [b, s, heads, rope]``, against the cached rows themselves,
    ``latents [b, n, rank]`` and their rotary keys ``k_rope [b, n,
    rope]``, one row a token under every head: ``score = (q . c +
    q_rope . kR) * scale``, fp32 softmax under ``mask [b, 1, 1, s,
    n]``, and the attended latent ``sum_n p c`` a head, ``[b, s,
    heads, rank]``. The rows are key AND value; no per-head key or
    value is built."""
    scores = (
        jnp.einsum("bqhr,bkr->bhqk", q, latents)
        + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)
    ) * scale
    scores = jnp.where(mask[:, 0], scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bhqk,bkr->bqhr", probs, latents)


def _grouped_attention_paged(q, k_pages, v_pages, mask, cfg):
    """:func:`_grouped_attention` over gathered pages as they lie in
    the pool: ``k_pages`` / ``v_pages`` are ``[b, pages, kv_heads,
    block_size, head_dim]`` (``pool[layer, tables]``), ``mask`` is over
    the ``pages * block_size`` token columns. Same products, same fp32
    softmax; the contraction runs over (page, row) where the other
    runs over tokens, so no view is transposed to token-major first.
    For the decode programs the views ARE the working set (every
    slot's whole capacity) and that transpose read and wrote each of
    them once more, 13-15 GB a step at 7B width."""
    b, s_q = q.shape[0], q.shape[1]
    n_kv = cfg.kv_heads
    groups = cfg.n_heads // n_kv
    n_pages, block_size = k_pages.shape[1], k_pages.shape[3]
    qg = q.reshape(b, s_q, n_kv, groups, cfg.head_dim)
    scale = _score_scale(cfg)
    scores = jnp.einsum("bqhgd,bphkd->bhgqpk", qg, k_pages) * scale
    scores = scores.reshape(b, n_kv, groups, s_q, n_pages * block_size)
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    probs = probs.reshape(b, n_kv, groups, s_q, n_pages, block_size)
    out = jnp.einsum("bhgqpk,bphkd->bqhgd", probs, v_pages)
    return out.reshape(b, s_q, cfg.n_heads, cfg.head_dim)


def _grouped_attention_flat(q, k_pages, v_pages, own, mask, cfg):
    """:func:`_grouped_attention_paged` over a FLAT list of pages, the
    live pages of all slots end to end: ``k_pages`` / ``v_pages`` are
    ``[pages, kv_heads, block_size, head_dim]`` (``pool[layer, ids]``),
    ``own [pages, slots]`` says whose each page is (one slot, or
    nobody's) and ``mask [pages, block_size]`` which of its rows that
    slot's query may read; ``q`` is ``[slots, 1, n_heads, head_dim]``.
    Same products in the compute dtype, same fp32 softmax, another
    summation order: a page is scored against its owner's query, the
    softmax's max and sum run over a slot's pages, and the weighted V
    pages are summed by owner. Every move between slots and pages is a
    product with ``own`` (one term a row, so exact) or a masked
    reduction over ``[pages, slots]``, never a scatter (a TPU scatter
    walks its indices one by one: PERF.md, PR 26). A slot that owns no
    page (inactive) comes out 0, not 0/0."""
    slots = q.shape[0]
    n_kv = cfg.kv_heads
    groups = cfg.n_heads // n_kv
    n_pages = k_pages.shape[0]
    exact = jax.lax.Precision.HIGHEST
    scale = _score_scale(cfg)
    own_f = own.astype(jnp.float32)
    q_of = jnp.einsum(
        "ps,sf->pf", own.astype(q.dtype), q.reshape(slots, -1)
    ).reshape(n_pages, n_kv, groups, cfg.head_dim)
    scores = jnp.einsum("phgd,phkd->phgk", q_of, k_pages) * scale
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    top = jnp.max(jnp.where(
        own[:, :, None, None], jnp.max(scores, axis=-1)[:, None], -jnp.inf
    ), axis=0)                                    # [slots, kv, groups]
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    top_of = jnp.einsum("ps,shg->phg", own_f, top, precision=exact)
    weights = jnp.exp(scores - top_of[..., None])
    total = jnp.einsum(
        "ps,phg->shg", own_f, jnp.sum(weights, axis=-1), precision=exact
    )
    out = jnp.einsum(
        "phgk,phkd->phgd", weights.astype(cfg.dtype), v_pages,
        preferred_element_type=jnp.float32,
    )
    out = jnp.einsum("ps,phgd->shgd", own_f, out, precision=exact)
    out = out / jnp.where(total > 0, total, 1.0)[..., None]
    return out.astype(cfg.dtype).reshape(
        slots, 1, cfg.n_heads, cfg.head_dim
    )


def _logits_head(x, params, cfg):
    """Final norm and vocabulary product. The logits come out in the
    compute dtype unless the configuration names a ``residual_dtype``
    (``models/latent_moe.py``: float32 out of the same product of
    compute-dtype operands, so that the arg-max over 129280 logits is
    not decided by their own rounding). A configuration that ties its
    two ends (``tie_word_embeddings``) has no ``output``: the product
    contracts the embedding table's second axis, the one array read as
    it lies, and its logits are divided by ``logits_scaling``."""
    x = _rmsnorm(x, params["norm"]["scale"], cfg.norm_eps)
    if getattr(cfg, "tie_word_embeddings", False):
        kernel, axis = params["tok_embeddings"]["embedding"], 1
    else:
        kernel, axis = params["output"]["kernel"], 0
        if getattr(cfg, "residual_dtype", None) is None:
            return _dense(x, kernel, cfg.dtype)
    logits = jax.lax.dot_general(
        x.astype(cfg.dtype), kernel.astype(cfg.dtype),
        (((x.ndim - 1,), (axis,)), ((), ())),
        preferred_element_type=getattr(cfg, "residual_dtype", None),
    )
    scale = getattr(cfg, "logits_scaling", 1.0)
    return logits if scale == 1.0 else logits / scale


def _ffn_stage(x, lp, cfg, weight=None, mesh=None):
    """The layer's feed-forward, residual included -> ``(x, counts)``,
    by the weights the layer holds: the dense SwiGLU under ``mlp``
    (``counts`` None), or the configuration's router and its held
    experts under ``router`` / ``experts``
    (``sparse_moe.expert_ffn``'s counts; ``weight`` marks the tokens
    that count; ``mesh``, the program's, is where its kernel runs) and,
    where the layer has one, the shared expert every token passes,
    under ``mlp``."""
    scope = jax.named_scope
    if "moe" not in lp:
        with scope("mlp"):
            h = _rmsnorm(x, lp["ffn_norm"]["scale"], cfg.norm_eps)
            x = _residual(x, _mlp(h, lp["feed_forward"], cfg), cfg)
        return x, None
    route = latent_moe.route if latent_moe.is_latent_moe(cfg) \
        else sparse_moe.route
    b, s, d = x.shape
    with scope("router"):
        h = _rmsnorm(x, lp["ffn_norm"]["scale"], cfg.norm_eps)
        h = h.reshape(b * s, d)
        gates, experts = route(h, lp, cfg)
    shared = lp["moe"].get("shared")
    with scope("experts"):
        y, counts = sparse_moe.expert_ffn(
            h, gates, experts, lp, cfg, weight=weight, mesh=mesh
        )
        if shared is None:
            x = _residual(x, y.reshape(b, s, d).astype(x.dtype), cfg)
    if shared is not None:
        with scope("mlp"):
            # Routed and shared parts meet in float32 and reach the
            # residual stream in ONE rounding of it.
            y = y.astype(jnp.float32) + _mlp(h, shared, cfg)
            x = _residual(x, y.reshape(b, s, d).astype(x.dtype), cfg)
    return x, counts


def _project(h, lp, cfg, cos, sin):
    """The layer's projections of the normed input, rotated by the
    program's ``cos`` / ``sin`` tables: what :func:`decoder_layers`
    hands the attention state as ``q, k, v``. Per-head queries, keys
    and values (:func:`_qkv`; rotated by nothing where the
    configuration's ``position_embedding`` is "nope"), or a latent
    configuration's queries, latent row and rotary key
    (``latent_moe.project``)."""
    if latent_moe.is_latent_moe(cfg):
        return latent_moe.project(h, lp, cfg, cos, sin)
    q, k, v = _qkv(h, lp, cfg)
    if getattr(cfg, "position_embedding", "rope") == "nope":
        return q, k, v
    # [s, D/2] tables rotate every row alike, [b, s, D/2] each to its
    # own position (apply_rope broadcasts either shape).
    q = llama2.apply_rope(q, cos, sin)
    k = llama2.apply_rope(k, cos, sin)
    return q, k, v


def decoder_layers(params, cfg, x, cos, sin, attend, weight=None,
                   recur=None, mesh=None):
    """Every layer of the decoder over ``x [b, s, dim]`` -> ``(x,
    counts)``. A layer's mixer is attention, or, where the layer holds
    a state-space mixer's weights (``ssm``:
    ``models/hybrid_ssm_moe.py``): ``ssm_in`` (the norm and the input
    projection); ``recur(layer, lp, xbc, dt)``, the program's recurrent
    state, which runs the convolution and the recurrence over the
    state it keeps for the step's sequences, leaves both advanced and
    returns ``y [b, s, heads, head_dim]``; ``ssm_out`` (the gated norm,
    the output projection and its residual). An attention layer is:
    ``qkv`` (the norm and :func:`_project`: the
    configuration's projections, rotated by the program's ``cos`` /
    ``sin`` tables); ``attend(layer, h, lp, q, k, v)``, the program's
    attention state, which writes this step's K/V (a latent
    configuration's row) where the program keeps them and returns the
    attended rows ``[b, s, n_heads, value dim]`` (``h`` is the normed
    input, for a stage of its own such as an indexer's projections);
    ``attn_out`` (the output projection and its residual);
    :func:`_ffn_stage`, by the kind of layer.
    ``counts`` are the expert layers' of a sparse-expert configuration
    over the tokens ``weight`` marks, summed over layers (``max*``: the
    largest), and empty for a dense one. ``mesh`` is the serving mesh
    of a program whose expert layers may run a kernel on it."""
    scope = jax.named_scope
    counts = {}
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        if "ssm" in lp:
            with scope("ssm_in"):
                h = _rmsnorm(x, lp["attention_norm"]["scale"], cfg.norm_eps)
                z, xbc, dt = hybrid_ssm_moe.in_proj(h, lp, cfg)
            y = recur(i, lp, xbc, dt)
            with scope("ssm_out"):
                x = _residual(
                    x, hybrid_ssm_moe.out_proj(y, z, lp, cfg), cfg
                )
        else:
            with scope("qkv"):
                h = _rmsnorm(x, lp["attention_norm"]["scale"], cfg.norm_eps)
                q, k, v = _project(h, lp, cfg, cos, sin)
            attn = attend(i, h, lp, q, k, v)
            with scope("attn_out"):
                x = _residual(x, _attn_out_proj(attn, lp, cfg), cfg)
        x, moe = _ffn_stage(x, lp, cfg, weight=weight, mesh=mesh)
        for name, value in (moe or {}).items():
            with scope("experts"):
                counts[name] = jnp.maximum(
                    counts.get(name, 0), value
                ) if name.startswith("max") \
                    else counts.get(name, 0) + value
    return x, counts
