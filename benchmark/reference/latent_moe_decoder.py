"""The plain reference of the decoder with latent attention and a
sigmoid-routed expert layer (JoyAI-LLM-Flash; the DeepSeek-V3 layer),
forward only, in straightforward ``jax.numpy``.

Float32 throughout, every matmul under
``jax.default_matmul_precision("highest")``, the EXPANDED attention
form only (every head's key and value built from the latent), no
kernels, no cache, no batching tricks and no import from ``tpu_hpc``:
it shares nothing with the code it judges but the parameter tree's
layout:

    tok_embeddings.embedding                      [vocab, dim]
    layers_<i>.attention_norm.scale               [dim]
    layers_<i>.attention.wq_a.kernel              [dim, q_lora_rank]
    layers_<i>.attention.q_norm.scale             [q_lora_rank]
    layers_<i>.attention.wq_b.kernel              [q_lora_rank, heads*(nope+rope)]
    layers_<i>.attention.wkv_a.kernel             [dim, kv_lora_rank+rope]
    layers_<i>.attention.kv_norm.scale            [kv_lora_rank]
    layers_<i>.attention.wkv_b.kernel             [kv_lora_rank, heads*(nope+v)]
    layers_<i>.attention.wo.kernel                [heads*v, dim]
    layers_<i>.ffn_norm.scale                     [dim]
    layers_<i>.feed_forward.{w1,w3,w2}.kernel     a dense layer's SwiGLU
    layers_<i>.moe.router.{kernel,bias}           [dim, n_experts], [n_experts]
    layers_<i>.moe.{w1,w3}, .w2                   [held, dim, width], [held, width, dim]
    layers_<i>.moe.shared.{w1,w3,w2}.kernel       the shared expert's SwiGLU
    norm.scale, output.kernel                     [dim], [dim, vocab]

One layer, for token ``t`` with residual ``x_t`` (``arch`` names the
sizes; every norm an RMSNorm):

* ``h = RMSNorm(x)``; ``cq = RMSNorm(h W_DQ)``; ``[qN_i | qR_i] = cq
  W_UQ`` a head ``i``, ``qR_i`` rotated at ``t`` (base ``rope_theta``,
  adjacent pairs); ``[c | kR] = h W_DKV``, ``c = RMSNorm(c)``, ``kR``
  rotated at ``t``, one key under all heads; ``[kN_i | v_i] = c W_UKV``.
* ``o_i = sum_{s <= t} softmax_s((qN_i . kN_s,i + qR_i . kR_s) *
  (nope + rope) ** -0.5) v_s,i``; ``x' = x + concat_i(o_i) W_O``.
* ``h2 = RMSNorm(x')``. A layer ``< first_dense_layers``: ``x'' = x' +
  SwiGLU(h2)``. Every other: ``s = sigmoid(h2 W_R)``; ``T`` = the
  ``experts_per_token`` largest of ``s + b`` (ties to the lower id);
  ``gate_e = s_e / sum_T s * routed_scaling_factor``; ``x'' = x' +
  SwiGLU_shared(h2) + sum_{e in T, e held} gate_e SwiGLU_e(h2)``: an
  expert whose weights are not held adds nothing. The held ids are
  ``arch["held_experts"]`` where given, else the leading
  ``arch["n_held"]`` (the chip's share of the stated deployment), else
  all of them.
* ``logits = RMSNorm(x_L) W_out``.

So that 30k tokens fit beside a serving pool: weights held in bf16 are
upcast where they are used, one matrix at a time; attention runs over
``q_block`` query rows at a time; the held experts run one at a time
over every token, each token's gate for an expert it did not choose
being zero (no capacity to exceed).
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _w(leaf):
    return leaf["kernel"].astype(F32)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, theta):
    """x [S, H, D] rotated by position on adjacent pairs."""
    s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).reshape(x.shape)


def swiglu(h, ff):
    return (jax.nn.silu(h @ _w(ff["w1"])) * (h @ _w(ff["w3"]))) \
        @ _w(ff["w2"])


def held_ids(arch):
    if arch.get("held_experts") is not None:
        return list(arch["held_experts"])
    return list(range(arch.get("n_held") or arch["n_experts"]))


def router(h, moe, arch):
    """-> the gate of every expert ``[S, n_experts]`` (zero where the
    token did not choose it) and the chosen ids ``[S, k]``."""
    scores = jax.nn.sigmoid(h @ _w(moe["router"]))
    _, chosen = jax.lax.top_k(
        scores + moe["router"]["bias"].astype(F32),
        arch["experts_per_token"],
    )
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if arch.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * arch["routed_scaling_factor"]
    gates = jnp.einsum(
        "sk,ske->se", top,
        jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32),
    )
    return gates, chosen


def routed_experts(h, moe, arch):
    """The held experts' part of the routed sum."""
    gates, _ = router(h, moe, arch)
    held_gates = gates[:, jnp.asarray(held_ids(arch))].T     # [held, S]

    def one(out, expert):
        w1, w3, w2, gate = expert
        y = (jax.nn.silu(h @ w1.astype(F32)) * (h @ w3.astype(F32))) \
            @ w2.astype(F32)
        return out + gate[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (moe["w1"], moe["w3"], moe["w2"], held_gates),
    )
    return out


def feed_forward(h, lp, arch):
    """The layer's feed-forward by the weights it holds: the dense
    SwiGLU, or the shared expert plus the held routed experts' part."""
    if "moe" not in lp:
        return swiglu(h, lp["feed_forward"])
    return swiglu(h, lp["moe"]["shared"]) \
        + routed_experts(h, lp["moe"], arch)


def attention(h, att, arch, q_block):
    """Latent attention over the whole sequence ``h [S, dim]``, causal,
    expanded: -> ``[S, heads * v]``."""
    s = h.shape[0]
    heads, eps = arch["n_heads"], arch["norm_eps"]
    nope, rdim = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    rank, vdim = arch["kv_lora_rank"], arch["v_head_dim"]
    cq = rmsnorm(h @ _w(att["wq_a"]), att["q_norm"]["scale"], eps)
    q = (cq @ _w(att["wq_b"])).reshape(s, heads, nope + rdim)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], arch["rope_theta"])
    ckv = h @ _w(att["wkv_a"])
    c = rmsnorm(ckv[:, :rank], att["kv_norm"]["scale"], eps)
    k_rope = rope(ckv[:, None, rank:], arch["rope_theta"])[:, 0]   # [S, rope]
    kv = (c @ _w(att["wkv_b"])).reshape(s, heads, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    col = jnp.arange(s)
    scale = (nope + rdim) ** -0.5

    def block(start):
        rows = start + jnp.arange(q_block)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, q_block
        )
        sc = (
            jnp.einsum("qhd,khd->hqk", cut(q_nope), k_nope)
            + jnp.einsum("qhd,kd->hqk", cut(q_rope), k_rope)
        ) * scale
        sc = jnp.where(col[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)
        return out.reshape(q_block, heads * vdim)

    return jax.lax.map(block, jnp.arange(0, s, q_block)).reshape(
        s, heads * vdim
    )


def forward(params, tokens, arch, probe_rows=None, q_block=256):
    """tokens [S] int -> ``(hidden [S, dim], probes)``: the last
    block's output before the final norm. This model selects no cached
    tokens, so ``probes`` holds an empty ``scores`` where
    ``probe_rows`` is given (``jobs/serve_arch.py``'s contract) and is
    None otherwise."""
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        if s % q_block:
            raise ValueError(f"pad the sequence to a multiple of {q_block}")
        x = params["tok_embeddings"]["embedding"][tokens].astype(F32)
        for i in range(arch["n_layers"]):
            lp = params[f"layers_{i}"]
            h = rmsnorm(x, lp["attention_norm"]["scale"], arch["norm_eps"])
            x = x + attention(h, lp["attention"], arch, q_block) \
                @ _w(lp["attention"]["wo"])
            h = rmsnorm(x, lp["ffn_norm"]["scale"], arch["norm_eps"])
            x = x + feed_forward(h, lp, arch)
    if probe_rows is None:
        return x, None
    return x, {"scores": jnp.zeros((0,), F32)}


def logits(params, hidden, arch):
    """hidden [..., dim] -> float32 logits [..., vocab]."""
    with jax.default_matmul_precision("highest"):
        return rmsnorm(hidden, params["norm"]["scale"], arch["norm_eps"]) \
            @ _w(params["output"])


def regret(params, tokens, positions, emitted, arch, probe_rows=None,
           q_block=256):
    """How far the tokens a server emitted are from the reference's
    own choice (``dense_decoder.regret``'s contract, one request a
    call). ``tokens`` [S] is the prompt followed by what the server
    emitted, padded on the right (causal, so padding never reaches an
    earlier position); ``emitted[j]`` is the token the server produced
    from position ``positions[j]``.

    Returns ``(regret, std, probes)``: ``max(logits) -
    logits[emitted]`` in float32 at each such position, that row's
    logit standard deviation, and :func:`forward`'s probes."""
    hid, probes = forward(params, tokens, arch, probe_rows, q_block)
    lg = logits(params, hid[positions], arch)
    chosen = jnp.take_along_axis(lg, emitted[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - chosen, jnp.std(lg, axis=-1), probes
