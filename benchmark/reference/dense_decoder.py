"""The plain reference: a dense decoder-only transformer, forward and
next-token loss, in straightforward ``jax.numpy``.

Float32 throughout, every matmul under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes), no kernels, no cache, no batching
tricks, and no import from ``tpu_hpc``: it shares nothing with the code
it judges but the parameter tree's layout, which is the checkpoint
format:

    tok_embeddings.embedding            [vocab, dim]
    layers_<i>.attention_norm.scale     [dim]
    layers_<i>.attention.{wq,wk,wv,wo}.kernel
    layers_<i>.ffn_norm.scale           [dim]
    layers_<i>.feed_forward.{w1,w3,w2}.kernel   (gate, up, down)
    norm.scale                          [dim]
    output.kernel                       [dim, vocab]   (untied head)

Equations (Mistral-7B-v0.1 and DeepSeek-LLM-7B share them; they differ
in sizes only): pre-norm residual blocks; RMSNorm
``x / sqrt(mean(x^2) + eps) * scale``; rotary embedding with base
``rope_theta`` on ADJACENT pairs ``(x[2i], x[2i+1])`` -- the layout the
program's ``llama2.apply_rope`` uses (the published checkpoints store
the half-split layout, which is a fixed permutation of wq/wk columns;
with weights drawn from a seed the two are the same model); causal
softmax attention scaled by ``head_dim ** -0.5`` with grouped KV heads
expanded by plain repetition; SiLU-gated feed-forward
``w2(silu(w1 x) * w3 x)``; final RMSNorm; untied output head.

Weights held in bf16 are upcast where they are used, one matrix at a
time, so the reference fits beside a serving pool.
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
    """x [B, S, H, D], rotated by position on adjacent pairs."""
    _, s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def hidden_states(params, tokens, *, n_layers, n_heads, n_kv_heads,
                  norm_eps, rope_theta=10000.0):
    """tokens [B, S] int -> the last block's output [B, S, dim], before
    the final norm."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        x = params["tok_embeddings"]["embedding"][tokens].astype(F32)
        hd = x.shape[-1] // n_heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(n_layers):
            lp = params[f"layers_{i}"]
            att = lp["attention"]
            h = rmsnorm(x, lp["attention_norm"]["scale"], norm_eps)
            q = (h @ _w(att["wq"])).reshape(b, s, n_heads, hd)
            k = (h @ _w(att["wk"])).reshape(b, s, n_kv_heads, hd)
            v = (h @ _w(att["wv"])).reshape(b, s, n_kv_heads, hd)
            q, k = rope(q, rope_theta), rope(k, rope_theta)
            rep = n_heads // n_kv_heads
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            scores = jnp.where(causal, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            x = x + out.reshape(b, s, n_heads * hd) @ _w(att["wo"])
            ff = lp["feed_forward"]
            h = rmsnorm(x, lp["ffn_norm"]["scale"], norm_eps)
            x = x + (jax.nn.silu(h @ _w(ff["w1"])) * (h @ _w(ff["w3"]))) \
                @ _w(ff["w2"])
        return x


def logits(params, hidden, *, norm_eps):
    """hidden [..., dim] -> float32 logits [..., vocab]."""
    with jax.default_matmul_precision("highest"):
        return rmsnorm(hidden, params["norm"]["scale"], norm_eps) \
            @ _w(params["output"])


def loss(params, inputs, targets, **arch):
    """Mean next-token cross-entropy of ``inputs`` against ``targets``
    (both [B, S])."""
    lg = logits(params, hidden_states(params, inputs, **arch),
                norm_eps=arch["norm_eps"])
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def regret(params, tokens, positions, emitted, **arch):
    """How far the tokens a server emitted are from the reference's
    own choice. ``tokens`` [B, S] is each request's prompt followed by
    what the server emitted (padded on the right; causal, so padding
    never reaches an earlier position); ``emitted[b, j]`` is the token
    the server produced from position ``positions[b, j]``.

    Returns ``(regret, std)``, both [B, N]: ``max(logits) -
    logits[emitted]`` in float32 at each such position, and that row's
    logit standard deviation. A server that picks the reference's
    arg-max has regret 0; one that picks a near-tie has a regret of
    the size of its own rounding error."""
    hid = hidden_states(params, tokens, **arch)
    rows = jnp.take_along_axis(hid, positions[..., None], axis=1)
    lg = logits(params, rows, norm_eps=arch["norm_eps"])
    chosen = jnp.take_along_axis(lg, emitted[..., None], axis=-1)[..., 0]
    return jnp.max(lg, axis=-1) - chosen, jnp.std(lg, axis=-1)
