"""Operations and bytes the decoder whose every layer runs attention and
a Mamba-2 mixer side by side needs, from shapes alone
(``flops_bytes_hybrid_ssm_moe.py``'s counterpart for
``benchmark/reference/parallel_hybrid_decoder.py``'s model). Each count
is a floor: what ANY implementation of the step must move, so a share
of the chip's peak made of it cannot pass 100 %.

``arch`` is the job's dict of the sizes as run: dim, n_layers,
n_ssm_layers, n_attention_layers (a parallel layer counts in both),
n_heads, n_kv_heads, head_dim (which is not ``dim / n_heads`` here),
vocab_size, ssm_heads, ssm_head_dim, ssm_state, ssm_groups, ssm_conv,
mlp_hidden (the dense feed-forward's width).
"""


def d_inner(arch):
    return arch["ssm_heads"] * arch["ssm_head_dim"]


def conv_dim(arch):
    """Width of ``xBC``: x, and B and C of every group."""
    return d_inner(arch) + 2 * arch["ssm_groups"] * arch["ssm_state"]


def ssm_params(arch):
    """One state-space mixer: in_proj ``[z | xBC | dt]``, the
    convolution's kernel and bias, dt_bias, A_log and D, the gated
    norm, out_proj."""
    d, inner, heads = arch["dim"], d_inner(arch), arch["ssm_heads"]
    return (
        d * (inner + conv_dim(arch) + heads)
        + (arch["ssm_conv"] + 1) * conv_dim(arch)
        + 3 * heads + inner + inner * d
    )


def attention_params(arch):
    """``W_Q``, ``W_K``, ``W_V``, ``W_O`` at ``head_dim`` a head."""
    d, hd = arch["dim"], arch["head_dim"]
    return 2 * d * arch["n_heads"] * hd + 2 * d * arch["n_kv_heads"] * hd


def ffn_params(arch):
    """The dense SwiGLU: gate, up, down."""
    return 3 * arch["dim"] * arch["mlp_hidden"]


def params_but_table(arch):
    """Every weight a decode step reads: the mixers, the feed-forwards,
    the norms and the head; not the token table, of which a step reads
    one row a sequence."""
    d = arch["dim"]
    return (
        arch["n_attention_layers"] * attention_params(arch)
        + arch["n_ssm_layers"] * ssm_params(arch)
        + arch["n_layers"] * (ffn_params(arch) + 2 * d)
        + d + d * arch["vocab_size"]
    )


def n_params(arch):
    """Everything held: the table and the untied head both."""
    return params_but_table(arch) + arch["dim"] * arch["vocab_size"]


def cache_bytes_per_token(arch, itemsize=2):
    """What a cached token keeps: keys and values of every layer that
    attends."""
    return arch["n_attention_layers"] * 2 * arch["n_kv_heads"] \
        * arch["head_dim"] * itemsize


def state_bytes_per_slot_layer(arch):
    """What a sequence keeps a state-space layer: ``S`` and the
    convolution's ``taps - 1`` rows, float32."""
    return 4 * (
        d_inner(arch) * arch["ssm_state"]
        + (arch["ssm_conv"] - 1) * conv_dim(arch)
    )


def ssm_layer_bytes(arch, slots, itemsize=2):
    """Bytes ONE state-space mixer must move for a decode step that
    advances ``slots`` sequences: its weights once, and each advanced
    sequence's ``S`` and convolution rows read and written."""
    return itemsize * ssm_params(arch) \
        + 2 * slots * state_bytes_per_slot_layer(arch)


def decode_step_bytes(arch, experts_touched, live_tokens, slots,
                      itemsize=2):
    """Bytes one decode step must move: every weight but the token table
    once, the keys and values of the distinct live tokens once a layer
    that attends, and the recurrent state of the advanced sequences
    read and written a state-space mixer. ``experts_touched`` is
    :func:`window_means`' first entry, 0: the feed-forward is dense."""
    return (
        itemsize * params_but_table(arch)
        + live_tokens * cache_bytes_per_token(arch, itemsize)
        + arch["n_ssm_layers"] * 2 * slots * state_bytes_per_slot_layer(arch)
    )


def window_means(stats, n_layers, block_size=16):
    """From the window's counters (``obs["serve"]["stats"]``) -> the
    means a decode step, in the order ``decode_roofline.sparse_moe`` and
    ``ssm_roofline`` take them from every configuration's module:
    (experts touched a layer, 0 here; distinct live
    tokens; sequences advanced), or None where the program counted
    nothing. ``block_size``: tokens a page (``serve_kv_pages_live_total``
    counts pages)."""
    steps = stats.get("decode_steps", 0)
    if not steps or "serve_ssm_slot_steps_total" not in stats \
            or "serve_kv_pages_live_total" not in stats:
        return None
    return (
        0.0,
        stats["serve_kv_pages_live_total"] / steps * block_size,
        stats["serve_ssm_slot_steps_total"] / steps,
    )
