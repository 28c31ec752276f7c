"""The plain reference of the decoder whose every layer runs attention
and a Mamba-2 mixer side by side on one normed input
(Falcon-H1-34B-Instruct; ``model_type`` ``falcon_h1``), forward only,
in straightforward ``jax.numpy``.

Float32 throughout, every matmul under
``jax.default_matmul_precision("highest")``, the recurrence as a plain
``lax.scan`` over tokens (NOT the chunked form the server's prefill
uses, nor its one-step form's code), full causal attention, no kernels,
no cache, no batching tricks and no import from ``tpu_hpc``: it shares
nothing with the code it judges but the parameter tree's layout:

    tok_embeddings.embedding                      [vocab, dim]
    layers_<i>.attention_norm.scale               [dim]  (the ONE norm both halves read)
    layers_<i>.attention.{wq,wk,wv}.kernel        [dim, heads * head_dim], [dim, kv_heads * head_dim] x 2
    layers_<i>.attention.wo.kernel                [heads * head_dim, dim]
    layers_<i>.ssm.in_proj.kernel                 [dim, d_ssm + (d_ssm + 2 groups state) + heads]
    layers_<i>.ssm.conv.{kernel,bias}             [taps, d_ssm + 2 groups state], [...]
    layers_<i>.ssm.{dt_bias,A_log,D}              [ssm heads]
    layers_<i>.ssm.norm.scale                     [d_ssm]
    layers_<i>.ssm.out_proj.kernel                [d_ssm, dim]
    layers_<i>.ffn_norm.scale                     [dim]
    layers_<i>.feed_forward.{w1,w3,w2}.kernel     gate, up [dim, hidden]; down [hidden, dim]
    norm.scale                                    [dim]
    output.kernel                                 [dim, vocab]  (untied)

With ``x`` the residual stream, every norm an RMSNorm of ``norm_eps``
and every multiplier ``arch``'s (the published ``config.json``'s):

* ``x0 = embedding_multiplier * E[token]``.
* ``h = RMSNorm(x)``, read by both halves.
* Attention: ``q = h W_Q`` (``n_heads`` heads of ``head_dim``), ``k =
  key_multiplier * h W_K``, ``v = h W_V`` (``n_kv_heads`` heads; query
  head ``i`` reads key head ``i // (n_heads / n_kv_heads)``); ``q`` and
  ``k`` rotated at base ``rope_theta`` in the rotate-half convention
  (number ``j`` of a head turns with number ``j + head_dim / 2`` by
  ``pos * rope_theta ** (-2j / head_dim)``); ``o(t) = sum_{s <= t}
  softmax_s(q(t) . k(s) / sqrt(head_dim)) v(s)``; ``attn =
  attention_out_multiplier * o W_O``. (``attention_in_multiplier`` is
  1 in the published configuration and is not applied.)
* Mixer (Mamba-2, ``ssm_groups`` groups): ``p = (ssm_in_multiplier *
  h) W_in``, each segment of ``[z | x | B | C | dt]`` times its own
  multiplier (``ssm_z_multiplier`` .. ``ssm_dt_multiplier``, the
  published ``ssm_multipliers``); ``xBC(t) = silu(bias + sum_j w_j *
  xBC(t - (taps - 1) + j))`` a channel (rows before the sequence are
  zero); ``xBC -> x [heads, head_dim], B [groups, state], C [groups,
  state]``; ``dt = softplus(dt + dt_bias)`` with no clamp, ``A =
  -exp(A_log)`` a head; head ``j``'s state, zero before the sequence,
  goes ``S(t) = exp(dt A) S(t-1) + dt x(t) (outer) B_g(t)`` with ``g =
  j // (heads / groups)`` and gives ``y(t) = S(t) C_g(t) + D x(t)``;
  ``u = y * silu(z)`` normed over each group's ``d_ssm / groups``
  numbers with one ``d_ssm``-wide scale (``mamba_rms_norm`` true,
  ``mamba_norm_before_gate`` false); ``ssm = ssm_out_multiplier * u
  W_out``.
* ``x' = x + attn + ssm``; ``g = RMSNorm(x')``; ``x'' = x' +
  mlp_down_multiplier * (silu(mlp_gate_multiplier * g W_gate) * g
  W_up) W_down``.
* ``logits = lm_head_multiplier * RMSNorm(x_L) W_head``.

Departures from the published description: none in the mathematics.
The published implementation computes the recurrence in blocks of
``mamba_chunk_size`` rows (a reformulation; this file scans token by
token, which the blocks must equal).

So that 30k tokens fit beside a serving pool: weights held in bf16 are
upcast where they are used, one matrix at a time; attention runs over
``q_block`` query rows at a time and the feed-forward over blocks of
``q_block`` rows; the scan carries one sequence's state and emits ``y``
a token.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
SSM_SEGMENTS = ("z", "x", "b", "c", "dt")


def _w(leaf):
    return leaf["kernel"].astype(F32)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rotate_half(x, positions, theta):
    """``x [S, heads, d]`` rotated by position, number ``j`` with ``j +
    d / 2``."""
    d = x.shape[-1]
    # (config.json's 100000000000 is an int: as a float, not int32)
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, att, arch, q_block):
    """Grouped-query causal attention over the whole sequence ``h [S,
    dim]``, rotated: -> ``o W_O`` times the out multiplier, ``[S,
    dim]``."""
    s = h.shape[0]
    heads, kv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    pos = jnp.arange(s)
    q = rotate_half((h @ _w(att["wq"])).reshape(s, heads, hd), pos,
                    arch["rope_theta"]).reshape(s, kv, heads // kv, hd)
    k = rotate_half(
        arch["key_multiplier"] * (h @ _w(att["wk"])).reshape(s, kv, hd),
        pos, arch["rope_theta"],
    )
    v = (h @ _w(att["wv"])).reshape(s, kv, hd)

    def block(start):
        rows = start + jnp.arange(q_block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block)
        sc = jnp.einsum("qjgd,kjd->jgqk", qb, k) * hd ** -0.5
        sc = jnp.where(pos[None, None, None, :] <= rows[None, None, :, None],
                       sc, -jnp.inf)
        out = jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(sc, axis=-1), v)
        return out.reshape(q_block, heads * hd)

    o = jax.lax.map(block, jnp.arange(0, s, q_block)).reshape(s, heads * hd)
    return arch["attention_out_multiplier"] * (o @ _w(att["wo"]))


def ssm_mixer(h, ssm, arch):
    """The Mamba-2 mixer over the whole sequence ``h [S, dim]``, token
    by token: -> ``[S, dim]``, times the out multiplier."""
    s = h.shape[0]
    heads, hd, n = arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"]
    groups, taps, eps = arch["ssm_groups"], arch["ssm_conv"], arch["norm_eps"]
    d_ssm, gn = heads * hd, groups * n
    mult = [arch[f"ssm_{seg}_multiplier"] for seg in SSM_SEGMENTS]
    hs = arch["ssm_in_multiplier"] * h
    # One product a part (29696 rows of all 9248 columns in float32
    # would be 1.1 GB beside a serving pool).
    z, xs, bs, cs, dt = (
        m * (hs @ part) for m, part in zip(mult, jnp.split(
            _w(ssm["in_proj"]),
            [d_ssm, 2 * d_ssm, 2 * d_ssm + gn, 2 * d_ssm + 2 * gn], axis=-1,
        ))
    )
    xbc = jnp.concatenate([xs, bs, cs], axis=-1)
    kernel = ssm["conv"]["kernel"].astype(F32)               # [taps, c]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc], axis=0
    )
    xbc = jax.nn.silu(ssm["conv"]["bias"].astype(F32) + sum(
        kernel[j] * padded[j:j + s] for j in range(taps)
    ))
    x = xbc[:, :d_ssm].reshape(s, heads, hd)
    b = xbc[:, d_ssm:d_ssm + gn].reshape(s, groups, n)
    c = xbc[:, d_ssm + gn:].reshape(s, groups, n)
    group_of = jnp.arange(heads) // (heads // groups)
    dt = jax.nn.softplus(dt + ssm["dt_bias"].astype(F32))    # [S, heads]
    a = -jnp.exp(ssm["A_log"].astype(F32))                   # [heads]

    def token(state, row):
        x_t, b_t, c_t, dt_t = row
        b_t, c_t = b_t[group_of], c_t[group_of]              # [heads, n]
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        token, jnp.zeros((heads, hd, n), F32), (x, b, c, dt)
    )
    y = y + ssm["D"].astype(F32)[None, :, None] * x
    u = (y.reshape(s, d_ssm) * jax.nn.silu(z)).reshape(s, groups, -1)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
    u = u.reshape(s, d_ssm) * ssm["norm"]["scale"].astype(F32)
    return arch["ssm_out_multiplier"] * (u @ _w(ssm["out_proj"]))


def feed_forward(g, ff, arch, block):
    """The dense SwiGLU with its two multipliers over ``block`` rows at
    a time."""
    w1, w3, w2 = (_w(ff[k]) for k in ("w1", "w3", "w2"))

    def rows(gb):
        gate = jax.nn.silu(arch["mlp_gate_multiplier"] * (gb @ w1))
        return arch["mlp_down_multiplier"] * ((gate * (gb @ w3)) @ w2)

    s, d = g.shape
    return jax.lax.map(rows, g.reshape(s // block, block, d)).reshape(s, d)


def forward(params, tokens, arch, probe_rows=None, q_block=256):
    """tokens [S] int -> ``(hidden [S, dim], probes)``: the last
    block's output before the final norm. This model selects no cached
    tokens, so ``probes`` holds an empty ``scores`` where
    ``probe_rows`` is given (``jobs/serve_arch.py``'s contract) and is
    None otherwise."""
    eps = arch["norm_eps"]
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        if s % q_block:
            raise ValueError(f"pad the sequence to a multiple of {q_block}")
        x = params["tok_embeddings"]["embedding"][tokens].astype(F32) \
            * arch["embedding_multiplier"]
        for i in range(arch["n_layers"]):
            lp = params[f"layers_{i}"]
            h = rmsnorm(x, lp["attention_norm"]["scale"], eps)
            x = x + attention(h, lp["attention"], arch, q_block) \
                + ssm_mixer(h, lp["ssm"], arch)
            g = rmsnorm(x, lp["ffn_norm"]["scale"], eps)
            x = x + feed_forward(g, lp["feed_forward"], arch, q_block)
    if probe_rows is None:
        return x, None
    return x, {"scores": jnp.zeros((0,), F32)}


def logits(params, hidden, arch):
    """hidden [..., dim] -> float32 logits [..., vocab]."""
    with jax.default_matmul_precision("highest"):
        return arch["lm_head_multiplier"] * (
            rmsnorm(hidden, params["norm"]["scale"], arch["norm_eps"])
            @ _w(params["output"])
        )


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
