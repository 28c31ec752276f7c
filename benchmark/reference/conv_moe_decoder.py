"""The plain reference for a decoder of gated short convolutions, a few
attention layers and sigmoid-routed experts: LiquidAI/LFM2-24B-A2B's
language model (``lfm2_moe``), written from its ``config.json`` and the
family's public model code, in ``jax.numpy`` and float32, with no
kernel, no sorting, no grouped product and nothing from ``tpu_hpc``. It
reads the program's weight tree (``tpu_hpc/models/conv_moe.py``:
``[in, out]`` matrices, ``in_proj``'s columns ``[B | C | X]``, a
convolution kernel ``[taps, dim]``, experts stacked on a leading axis
of the experts HELD, the embedding table as the head).

With ``u`` the normed input (RMSNorm, eps ``norm_eps``, learned scale):

* block: ``h = x + Mixer(norm_operator(x))``, ``x' = h + FFN(norm_ffn(h))``;
* ``conv`` mixer: ``[B, C, X] = split3(u W_in)``; ``z = B * X``;
  ``c_t = sum_j k_j * z_{t - (taps - 1) + j}`` (zero before the
  sequence, no bias); ``y = (C * c) W_out``;
* ``full_attention`` mixer: 32 query / 8 key-value heads; ``q`` and
  ``k`` RMS-normed over a head's numbers BEFORE the rotation; the
  rotation pairs number ``j`` with ``j + head_dim / 2`` (rotate-half)
  at base ``rope_theta``; causal softmax of ``q k^T / sqrt(head_dim)``;
* FFN of the leading dense layers: ``W2(silu(W1 u) * W3 u)``;
* FFN of the others: ``s = sigmoid(u W_r)``; the ``k`` largest of
  ``s + b`` (ties to the lower id); gates ``s[chosen] /
  (sum(s[chosen]) + 1e-6)`` x ``routed_scaling_factor``; ``sum_e gate_e
  W2_e(silu(W1_e u) * W3_e u)``, the experts ONE AT A TIME over every
  row;
* a final RMSNorm and the head (the embedding table, transposed); the
  loss is the mean token cross-entropy over the vocabulary rows held.

Departures from the published code, each on purpose:

* ``held``: the expert ids whose weights the tree holds, in the order
  of its stack (``None``: the leading ids). An expert that is not held
  adds nothing: the reference computes the same share of an
  expert-parallel deployment's result as the program. The router keeps
  its published width and chooses among ALL experts;
* the vocabulary is the table's rows (a slice of the published one:
  the traffic draws its ids from the slice, logits and loss are over
  the slice);
* the selection bias ``b`` is an argument and takes no gradient (its
  update rule is a training recipe, not in ``config.json``): frozen;
* ``chosen``: the experts each token is SENT to, a layer, may be given
  (``{layer name: [b, s, k]}``) in place of the reference's own top-k.
  Gates are still the reference's own scores at those ids. The
  benchmark's check hands it the program's choices, so that the two
  differentiate the same function and a near-tie decided otherwise by
  the program's rounding is judged where it belongs: by the band round
  the reference's own ``k``-th score (``forward``'s second result).
"""
import jax
import jax.numpy as jnp

PRECISION = "highest"
GATE_EPS = 1e-6


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * scale


def _rotate_half(x, theta):
    """``x [s, heads, d]`` rotated by position, number ``j`` paired with
    ``j + d / 2``."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + turned * sin


def _conv_mixer(u, p):
    d = u.shape[-1]
    bcx = u @ p["in_proj"]["kernel"]
    z = bcx[:, :d] * bcx[:, 2 * d:]
    kernel = p["conv"]["kernel"]
    taps = kernel.shape[0]
    z = jnp.concatenate([jnp.zeros((taps - 1, d), z.dtype), z], axis=0)
    c = sum(kernel[j] * z[j:j + u.shape[0]] for j in range(taps))
    return (bcx[:, d:2 * d] * c) @ p["out_proj"]["kernel"]


def _one_head(q, k, v):
    """Causal softmax attention of ONE head: ``q, k, v [s, d]``."""
    s, d = q.shape
    scores = (q @ k.T) / jnp.sqrt(float(d))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def _attention_mixer(u, p, n_heads, n_kv_heads, eps, theta):
    s = u.shape[0]
    hd = p["wq"]["kernel"].shape[1] // n_heads
    q = (u @ p["wq"]["kernel"]).reshape(s, n_heads, hd)
    k = (u @ p["wk"]["kernel"]).reshape(s, n_kv_heads, hd)
    v = (u @ p["wv"]["kernel"]).reshape(s, n_kv_heads, hd)
    q = _rotate_half(_rmsnorm(q, p["q_norm"]["scale"], eps), theta)
    k = _rotate_half(_rmsnorm(k, p["k_norm"]["scale"], eps), theta)
    groups = n_heads // n_kv_heads
    k, v = (jnp.repeat(t, groups, axis=1) for t in (k, v))
    # A head at a time, its scores recomputed for the gradient: the
    # scores of 32 heads over 8192 tokens would be 8.6 GB.
    out = jax.lax.map(
        lambda qkv: jax.checkpoint(_one_head)(*qkv),
        tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v)),
    )
    return jnp.swapaxes(out, 0, 1).reshape(s, n_heads * hd) \
        @ p["wo"]["kernel"]


@jax.checkpoint
def _swiglu(u, w1, w3, w2):
    # (Its hidden activations are recomputed for the gradient: eight
    # experts over every row of 8192 would hold 1.2 GB a layer.)
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def _experts(u, p, bias, held, k, scaling, chosen):
    """-> (the held experts' part of the layer's result, the selection
    scores ``s + b`` of every expert, the experts used)."""
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])
    select = scores + jax.lax.stop_gradient(bias)
    if chosen is None:
        _, chosen = jax.lax.top_k(select, k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS)
    gates = gates * scaling
    out = jnp.zeros_like(u)
    for row, expert in enumerate(held):
        gate = jnp.sum(jnp.where(chosen == expert, gates, 0.0), axis=-1)
        out = out + gate[:, None] * _swiglu(
            u, p["w1"][row], p["w3"][row], p["w2"][row]
        )
    return out, select, chosen


def forward(params, bias, tokens, *, n_layers, n_heads, n_kv_heads,
            norm_eps, rope_theta, layer_types, first_dense_layers,
            experts_per_token, routed_scaling_factor=1.0, held=None,
            chosen=None):
    """One sequence ``tokens [s]`` -> ``(the final normed stream [s,
    dim], {layer name: (selection scores [s, n_experts], experts used
    [s, k])})``. ``bias``: ``{layer name: {"router_bias": [n_experts]}}``."""
    with jax.default_matmul_precision(PRECISION):
        params = jax.tree.map(lambda w: w.astype(jnp.float32), params)
        x = params["tok_embeddings"]["embedding"][tokens]
        routed = {}
        for i in range(n_layers):
            name = f"layers_{i}"
            p = params[name]
            u = _rmsnorm(x, p["operator_norm"]["scale"], norm_eps)
            if layer_types[i] == "full_attention":
                x = x + _attention_mixer(
                    u, p["attention"], n_heads, n_kv_heads, norm_eps,
                    rope_theta,
                )
            else:
                x = x + _conv_mixer(u, p["conv"])
            u = _rmsnorm(x, p["ffn_norm"]["scale"], norm_eps)
            if i < first_dense_layers:
                ffn = p["feed_forward"]
                x = x + _swiglu(
                    u, ffn["w1"]["kernel"], ffn["w3"]["kernel"],
                    ffn["w2"]["kernel"],
                )
                continue
            ids = held if held is not None \
                else range(p["moe"]["w1"].shape[0])
            out, select, used = _experts(
                u, p["moe"], bias[name]["router_bias"].astype(jnp.float32),
                ids, experts_per_token, routed_scaling_factor,
                None if chosen is None else chosen[name],
            )
            routed[name] = (select, used)
            x = x + out
        return _rmsnorm(x, params["norm"]["scale"], norm_eps), routed


def logits(params, bias, tokens, **kw):
    """``tokens [s]`` -> float32 logits ``[s, vocabulary rows held]``."""
    x, _ = forward(params, bias, tokens, **kw)
    with jax.default_matmul_precision(PRECISION):
        table = params["tok_embeddings"]["embedding"].astype(jnp.float32)
        return x @ table.T


def loss(params, bias, tokens, targets, **kw):
    """Mean token cross-entropy of one sequence, and what ``forward``
    says of its routing: ``-> (loss, routed)`` (``has_aux`` for
    ``jax.value_and_grad``)."""
    x, routed = forward(params, bias, tokens, **kw)
    with jax.default_matmul_precision(PRECISION):
        table = params["tok_embeddings"]["embedding"].astype(jnp.float32)
        z = x @ table.T
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold), routed
