"""The plain reference of the decoder of state-space and attention
layers over an expert feed-forward (granite-4.0-h-small;
``model_type`` ``granitemoehybrid``), forward only, in straightforward
``jax.numpy``.

Float32 throughout, every matmul under
``jax.default_matmul_precision("highest")``, the recurrence as a plain
``lax.scan`` over tokens (NOT the chunked form the server's prefill
uses, nor its one-step form's code: the forms checking each other is
the point), full causal attention, no kernels, no cache, no batching
tricks and no import from ``tpu_hpc``: it shares nothing with the code
it judges but the parameter tree's layout:

    tok_embeddings.embedding                      [vocab, dim]  (also the head)
    layers_<i>.attention_norm.scale               [dim]  (the norm ahead of the mixer)
    layers_<i>.attention.{wq,wk,wv,wo}.kernel     an attention layer's
    layers_<i>.ssm.in_proj.kernel                 [dim, d_inner + (d_inner + 2 state) + heads]
    layers_<i>.ssm.conv.{kernel,bias}             [taps, d_inner + 2 state], [d_inner + 2 state]
    layers_<i>.ssm.{dt_bias,A_log,D}              [heads]
    layers_<i>.ssm.norm.scale                     [d_inner]
    layers_<i>.ssm.out_proj.kernel                [d_inner, dim]
    layers_<i>.ffn_norm.scale                     [dim]
    layers_<i>.moe.router.kernel                  [dim, n_experts]
    layers_<i>.moe.{w1,w3}, .w2                   [held, dim, width], [held, width, dim]
    layers_<i>.moe.shared.{w1,w3,w2}.kernel       the shared expert's SwiGLU
    norm.scale                                    [dim]

A layer's mixer is told by the weights it holds. With ``x`` the
residual stream, ``r = residual_multiplier`` and every norm an RMSNorm
(``arch`` names the sizes):

* ``x0 = embedding_multiplier * E[token]``.
* ``h = RMSNorm(x)``. An ATTENTION layer: ``q_i = h W_Q,i`` for each of
  ``n_heads`` heads, ``k_j, v_j = h W_K,j, h W_V,j`` for each of
  ``n_kv_heads`` (head ``i`` reads group ``i // (n_heads /
  n_kv_heads)``), NOTHING rotated and no other position signal
  (``position_embedding_type`` "nope"); ``o_i(t) = sum_{s <= t}
  softmax_s(q_i(t) . k(s) * attention_multiplier) v(s)``; ``x' = x + r
  * concat_i(o_i) W_O``.
* A STATE-SPACE layer (Mamba-2, one group): ``[z | xBC | dt] = h
  W_in``; ``xBC(t) = silu(bias + sum_j w_j * xBC(t - (taps - 1) + j))``
  a channel (rows before the sequence are zero); ``xBC -> x [heads,
  head_dim], B [state], C [state]``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` a head, with no clamp of ``dt``
  (``time_step_limit`` (0, inf)); a head's state, zero before the
  sequence, goes ``S(t) = exp(dt(t) A) S(t-1) + dt(t) x(t) (outer)
  B(t)`` and ``y(t) = S(t) C(t) + D x(t)``; ``g = y * silu(z)``; ``x' =
  x + r * (g * rsqrt(mean(g^2) + eps) * w) W_out``: the gate INSIDE the
  norm, one group over all of ``d_inner``.
* ``h2 = RMSNorm(x')``; ``l = h2 W_R``; ``T`` = the
  ``experts_per_token`` largest of ``l`` (ties to the lower id);
  ``gate_e = softmax over T of l``; ``x'' = x' + r *
  (SwiGLU_shared(h2) + sum_{e in T, e held} gate_e SwiGLU_e(h2))``: an
  expert whose weights are not held adds nothing. The held ids are
  ``arch["held_experts"]`` where given, else the leading
  ``arch["n_held"]`` (the chip's share of the stated deployment), else
  all of them.
* ``logits = RMSNorm(x_L) E^T / logits_scaling``: the embedding table
  is the head (``tie_word_embeddings``).

Departures from the published description: none in the mathematics.
The published implementation computes the recurrence in blocks of
``mamba_chunk_size`` rows (a reformulation; this file scans token by
token, which the blocks must equal); it selects ``T`` on the logits
and gates by their softmax, which equals a softmax over all experts,
top-k, renormalised (what the server's router computes). The widths of
an expert (``intermediate_size``) and the absence of a ``dt`` clamp
are read as ``benchmark/configs/granite-4.0-h-small.json`` says under
``assumed``.

So that 30k tokens fit beside a serving pool: weights held in bf16 are
upcast where they are used, one matrix at a time; attention runs over
``q_block`` query rows at a time; the held experts run one at a time
over every token, each token's gate for an expert it did not choose
being zero (no capacity to exceed); the scan carries one sequence's
state and emits ``y`` a token.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _w(leaf):
    return leaf["kernel"].astype(F32)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


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
    logits = h @ _w(moe["router"])
    top, chosen = jax.lax.top_k(logits, arch["experts_per_token"])
    top = jax.nn.softmax(top, axis=-1)
    gates = jnp.einsum(
        "sk,ske->se", top,
        jax.nn.one_hot(chosen, logits.shape[-1], dtype=F32),
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
    return swiglu(h, lp["moe"]["shared"]) \
        + routed_experts(h, lp["moe"], arch)


def attention(h, att, arch, q_block):
    """Grouped-query attention over the whole sequence ``h [S, dim]``,
    causal, nothing rotated: -> ``[S, heads * head_dim]``."""
    s = h.shape[0]
    heads, kv = arch["n_heads"], arch["n_kv_heads"]
    hd = arch["dim"] // heads
    q = (h @ _w(att["wq"])).reshape(s, kv, heads // kv, hd)
    k = (h @ _w(att["wk"])).reshape(s, kv, hd)
    v = (h @ _w(att["wv"])).reshape(s, kv, hd)
    col = jnp.arange(s)

    def block(start):
        rows = start + jnp.arange(q_block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block)
        sc = jnp.einsum("qjgd,kjd->jgqk", qb, k) \
            * arch["attention_multiplier"]
        sc = jnp.where(col[None, None, None, :] <= rows[None, None, :, None],
                       sc, -jnp.inf)
        out = jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(sc, axis=-1), v)
        return out.reshape(q_block, heads * hd)

    return jax.lax.map(block, jnp.arange(0, s, q_block)).reshape(
        s, heads * hd
    )


def ssm_mixer(h, ssm, arch):
    """The Mamba-2 mixer over the whole sequence ``h [S, dim]``, token
    by token: -> ``[S, dim]``."""
    s = h.shape[0]
    heads, hd, n = arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"]
    taps, eps = arch["ssm_conv"], arch["norm_eps"]
    d_inner = heads * hd
    # One product a part (29696 rows of all 16768 columns in float32
    # would be 2 GB beside a serving pool).
    z, xbc, dt = (
        h @ part for part in jnp.split(
            _w(ssm["in_proj"]), [d_inner, 2 * d_inner + 2 * n], axis=-1
        )
    )
    kernel = ssm["conv"]["kernel"].astype(F32)               # [taps, c]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc], axis=0
    )
    xbc = ssm["conv"]["bias"].astype(F32) + sum(
        kernel[j] * padded[j:j + s] for j in range(taps)
    )
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d_inner].reshape(s, heads, hd)
    b, c = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt + ssm["dt_bias"].astype(F32))    # [S, heads]
    a = -jnp.exp(ssm["A_log"].astype(F32))                   # [heads]

    def token(state, row):
        x_t, b_t, c_t, dt_t = row
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(
        token, jnp.zeros((heads, hd, n), F32), (x, b, c, dt)
    )
    y = y + ssm["D"].astype(F32)[None, :, None] * x
    g = y.reshape(s, d_inner) * jax.nn.silu(z)
    return rmsnorm(g, ssm["norm"]["scale"], eps) @ _w(ssm["out_proj"])


def forward(params, tokens, arch, probe_rows=None, q_block=256):
    """tokens [S] int -> ``(hidden [S, dim], probes)``: the last
    block's output before the final norm. This model selects no cached
    tokens, so ``probes`` holds an empty ``scores`` where
    ``probe_rows`` is given (``jobs/serve_arch.py``'s contract) and is
    None otherwise."""
    r = arch["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        if s % q_block:
            raise ValueError(f"pad the sequence to a multiple of {q_block}")
        x = params["tok_embeddings"]["embedding"][tokens].astype(F32) \
            * arch["embedding_multiplier"]
        for i in range(arch["n_layers"]):
            lp = params[f"layers_{i}"]
            h = rmsnorm(x, lp["attention_norm"]["scale"], arch["norm_eps"])
            if "ssm" in lp:
                x = x + r * ssm_mixer(h, lp["ssm"], arch)
            else:
                x = x + r * (
                    attention(h, lp["attention"], arch, q_block)
                    @ _w(lp["attention"]["wo"])
                )
            h = rmsnorm(x, lp["ffn_norm"]["scale"], arch["norm_eps"])
            x = x + r * feed_forward(h, lp, arch)
    if probe_rows is None:
        return x, None
    return x, {"scores": jnp.zeros((0,), F32)}


def logits(params, hidden, arch):
    """hidden [..., dim] -> float32 logits [..., vocab]."""
    with jax.default_matmul_precision("highest"):
        table = params["tok_embeddings"]["embedding"].astype(F32)
        return rmsnorm(hidden, params["norm"]["scale"], arch["norm_eps"]) \
            @ table.T / arch["logits_scaling"]


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
