"""The plain reference of the sparse-expert decoder with a learned
token selector (Keye-VL-2.0-30B-A3B's language model), forward only,
in straightforward ``jax.numpy``.

Float32 throughout, every matmul under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching tricks and no import from ``tpu_hpc``: it shares nothing with
the code it judges but the parameter tree's layout:

    tok_embeddings.embedding                    [vocab, dim]
    layers_<i>.attention_norm.scale             [dim]
    layers_<i>.attention.{wq,wk,wv,wo}.kernel   [in, out]
    layers_<i>.attention.{q_norm,k_norm}.scale  [head_dim]
    layers_<i>.indexer.{wq,wk,weights}.kernel   [dim, heads*64 | 64 | heads]
    layers_<i>.indexer.k_norm.{scale,bias}      [64]
    layers_<i>.ffn_norm.scale                   [dim]
    layers_<i>.moe.router.kernel                [dim, n_experts]
    layers_<i>.moe.{w1,w3}                      [held, dim, width]
    layers_<i>.moe.w2                           [held, width, dim]
    norm.scale, output.kernel                   [dim], [dim, vocab]

One layer, for token ``t`` with residual ``x_t`` (``arch`` names the
sizes):

* ``h = RMSNorm(x; g1)``; ``q = R_t(RMSNorm(Wq h; gq))`` and ``k =
  R_t(RMSNorm(Wk h; gk))`` a head of ``head_dim``, ``v = Wv h``;
  ``R_t`` the rotary embedding at base ``rope_theta`` on adjacent pairs
  (on text the three m-rope position ids are equal, so the sections
  collapse to this).
* Indexer: ``qI_a = R'_t(WqI_a h)`` (``indexer_heads`` x 64), ``kI =
  R'_t(LayerNorm(WkI h))`` (one head), ``w_a = (Ww h)_a *
  (indexer_heads * 64) ** -0.5``; ``R'`` rotates the leading
  ``indexer_rope_dim`` numbers of a head. ``I[t, s] = sum_a w[t, a] *
  relu(qI[t, a] . kI[s])``; ``S_t`` = the ``indexer_topk`` positions
  ``s <= t`` of largest ``I[t, s]`` (all while ``t + 1 <=
  indexer_topk``), ties to the lower ``s``.
* ``o_i = sum_{s in S_t} softmax_{S_t}(q_i . k_{s,g(i)} /
  sqrt(head_dim)) v_{s,g(i)}``; ``x' = x + Wo concat_i o_i``.
* ``h2 = RMSNorm(x'; g2)``; ``p = softmax(Wr h2)`` over ALL experts;
  ``T`` = its ``experts_per_token`` largest (ties to the lower id);
  ``gate_e = p_e / sum_T p``; ``x'' = x' + sum_{e in T, e held} gate_e
  * W2_e(silu(W1_e h2) * W3_e h2)``: an expert whose weights are not
  held (``held_experts``) adds nothing.
* ``logits = Wout RMSNorm(x_L; gf)``.

So that 30k tokens fit beside a serving pool: weights held in bf16 are
upcast where they are used, one matrix at a time; attention and
selection run over ``q_block`` query rows at a time; the experts run
one at a time over every token, each token's gate for an expert it did
not choose being zero (sixteen times the products of a routed
dispatch, and no capacity to exceed).
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _w(leaf):
    return leaf["kernel"].astype(F32)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(F32) \
        + bias.astype(F32)


def rope(x, theta, n=None):
    """x [S, H, D]: the leading ``n`` numbers of each head rotated by
    position on adjacent pairs (all of them by default)."""
    s, _, d = x.shape
    n = d if n is None else n
    inv_freq = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=F32) / n))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:n:2], x[..., 1:n:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([rot.reshape(s, -1, n), x[..., n:]], axis=-1)


def top_mask(scores, valid, k):
    """The ``k`` largest of each row among ``valid``, ties to the
    lower column, as a mask (every valid column where there are no
    more than ``k``)."""
    if k >= scores.shape[-1]:
        return valid
    masked = jnp.where(valid, scores, -jnp.inf)
    kth = -jnp.sort(-masked, axis=-1)[..., k - 1:k]
    above = masked > kth
    equal = (masked == kth) & valid
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))


def _indexer(h, ix, arch):
    hi, di = arch["indexer_heads"], arch["indexer_head_dim"]
    n = arch["indexer_rope_dim"]
    q = rope((h @ _w(ix["wq"])).reshape(-1, hi, di), arch["rope_theta"], n)
    k = layernorm(
        h @ _w(ix["wk"]), ix["k_norm"]["scale"], ix["k_norm"]["bias"],
        arch["norm_eps"],
    )
    k = rope(k[:, None, :], arch["rope_theta"], n)[:, 0]
    w = (h @ _w(ix["weights"])) * (hi * di) ** -0.5
    return q, k, w


def _experts(h, moe, arch):
    probs = jax.nn.softmax(h @ _w(moe["router"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, arch["experts_per_token"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.einsum(
        "sk,ske->se", top, jax.nn.one_hot(chosen, probs.shape[-1], dtype=F32)
    )                                                  # [S, n_experts]
    held = arch.get("held_experts") or range(arch["n_experts"])
    held_gates = gates[:, jnp.asarray(list(held))].T   # [held, S]

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


def forward(params, tokens, arch, probe_rows=None, q_block=256):
    """tokens [S] int -> ``(hidden [S, dim], probes)``: the last
    block's output before the final norm, and, for ``probe_rows [R]``
    (positions), each layer's indexer scores ``I[t, :]`` and selection
    ``S_t`` as ``{"scores": [L, R, S], "selected": [L, R, S]}``
    (columns ``s > t`` hold -inf and False)."""
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        if s % q_block:
            raise ValueError(f"pad the sequence to a multiple of {q_block}")
        x = params["tok_embeddings"]["embedding"][tokens].astype(F32)
        n_heads, n_kv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
        col = jnp.arange(s)
        probes = {"scores": [], "selected": []}
        for i in range(arch["n_layers"]):
            lp = params[f"layers_{i}"]
            att = lp["attention"]
            h = rmsnorm(x, lp["attention_norm"]["scale"], arch["norm_eps"])
            q = (h @ _w(att["wq"])).reshape(s, n_heads, hd)
            k = (h @ _w(att["wk"])).reshape(s, n_kv, hd)
            v = (h @ _w(att["wv"])).reshape(s, n_kv, hd)
            q = rmsnorm(q, att["q_norm"]["scale"], arch["norm_eps"])
            k = rmsnorm(k, att["k_norm"]["scale"], arch["norm_eps"])
            q, k = rope(q, arch["rope_theta"]), rope(k, arch["rope_theta"])
            qi, ki, wi = _indexer(h, lp["indexer"], arch)

            def select(rows, qi_rows, wi_rows):
                dots = jnp.einsum("rad,nd->ran", qi_rows, ki)
                scores = jnp.einsum("ran,ra->rn", jax.nn.relu(dots), wi_rows)
                valid = col[None, :] <= rows[:, None]
                return (
                    jnp.where(valid, scores, -jnp.inf),
                    top_mask(scores, valid, arch["indexer_topk"]),
                )

            def block(start):
                rows = start + jnp.arange(q_block)
                cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    a, start, q_block
                )
                _, chosen = select(rows, cut(qi), cut(wi))
                qb = cut(q).reshape(q_block, n_kv, n_heads // n_kv, hd)
                sc = jnp.einsum("qhgd,khd->hgqk", qb, k) * hd ** -0.5
                sc = jnp.where(chosen[None, None], sc, -jnp.inf)
                out = jnp.einsum(
                    "hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v
                )
                return out.reshape(q_block, n_heads * hd)

            attn = jax.lax.map(block, jnp.arange(0, s, q_block))
            x = x + attn.reshape(s, n_heads * hd) @ _w(att["wo"])
            if probe_rows is not None:
                scores, chosen = select(
                    probe_rows, qi[probe_rows], wi[probe_rows]
                )
                probes["scores"].append(scores)
                probes["selected"].append(chosen)
            h = rmsnorm(x, lp["ffn_norm"]["scale"], arch["norm_eps"])
            x = x + _experts(h, lp["moe"], arch)
        if probe_rows is None:
            return x, None
        return x, {k: jnp.stack(v) for k, v in probes.items()}


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
