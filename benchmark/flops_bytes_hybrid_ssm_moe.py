"""Operations and bytes the decoder of state-space and attention layers
over an expert feed-forward needs, from shapes alone
(``flops_bytes_latent_moe.py``'s counterpart for
``benchmark/reference/hybrid_ssm_moe_decoder.py``'s model). Each count
is a floor: what ANY implementation of the step must move, so a share
of the chip's peak made of it cannot pass 100 %.

``arch`` is the job's dict of the sizes as run: dim, n_layers,
n_ssm_layers, n_attention_layers, n_heads, n_kv_heads, vocab_size,
ssm_heads, ssm_head_dim, ssm_state, ssm_conv, n_experts (the router's
width), n_held (the routed experts this chip holds of a layer),
experts_per_token, expert_hidden, shared_hidden.
"""


def d_inner(arch):
    return arch["ssm_heads"] * arch["ssm_head_dim"]


def conv_dim(arch):
    """Width of ``xBC``: x, B and C (one group)."""
    return d_inner(arch) + 2 * arch["ssm_state"]


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
    d = arch["dim"]
    hd = d // arch["n_heads"]
    return 2 * d * arch["n_heads"] * hd + 2 * d * arch["n_kv_heads"] * hd


def expert_params(arch):
    """One routed expert: w1, w3, w2."""
    return 3 * arch["dim"] * arch["expert_hidden"]


def router_params(arch):
    return arch["dim"] * arch["n_experts"]


def layer_params_outside_mixer_and_routed(arch):
    """The two norms, the router and the shared expert."""
    return 2 * arch["dim"] + router_params(arch) \
        + 3 * arch["dim"] * arch["shared_hidden"]


def params_outside_routed(arch):
    """Every weight a decode step reads whatever its tokens chose, but
    the table: the mixers, norms, routers and shared experts."""
    return (
        arch["n_ssm_layers"] * ssm_params(arch)
        + arch["n_attention_layers"] * attention_params(arch)
        + arch["n_layers"] * layer_params_outside_mixer_and_routed(arch)
        + arch["dim"]
    )


def n_params(arch):
    """Everything held; the table, which is the head too, once."""
    return params_outside_routed(arch) \
        + arch["n_layers"] * arch["n_held"] * expert_params(arch) \
        + arch["dim"] * arch["vocab_size"]


def cache_bytes_per_token(arch, itemsize=2):
    """What a cached token keeps: keys and values of the ATTENTION
    layers alone."""
    hd = arch["dim"] // arch["n_heads"]
    return arch["n_attention_layers"] * 2 * arch["n_kv_heads"] * hd \
        * itemsize


def state_bytes_per_slot_layer(arch):
    """What a sequence keeps a state-space layer: ``S`` and the
    convolution's ``taps - 1`` rows, float32."""
    return 4 * (
        d_inner(arch) * arch["ssm_state"]
        + (arch["ssm_conv"] - 1) * conv_dim(arch)
    )


def ssm_layer_bytes(arch, slots, itemsize=2):
    """Bytes ONE state-space layer must move for a decode step that
    advances ``slots`` sequences: the mixer's weights once, and each
    advanced sequence's ``S`` and convolution rows read and written."""
    return itemsize * ssm_params(arch) \
        + 2 * slots * state_bytes_per_slot_layer(arch)


def moe_layer_bytes(arch, experts_touched, itemsize=2):
    """Bytes the router and the routed experts must read for one step,
    a layer: the router, and the weights of the HELD experts its
    tokens really chose, once each (``experts_touched``:
    :func:`window_means`' mean a layer; never the number held). The
    shared expert is no part of it: it runs under ``mlp``."""
    return itemsize * (
        router_params(arch) + experts_touched * expert_params(arch)
    )


def decode_step_bytes(arch, experts_touched, live_tokens, slots,
                      itemsize=2):
    """Bytes one decode step must move: every weight outside the routed
    experts once, the table once (it is the head), the touched held
    experts once each, the keys and values of the distinct live tokens
    once an attention layer, and the recurrent state of the advanced
    sequences read and written a state-space layer."""
    weights = itemsize * (
        params_outside_routed(arch)
        + arch["n_layers"] * experts_touched * expert_params(arch)
        + arch["dim"] * arch["vocab_size"]
    )
    return (
        weights
        + live_tokens * cache_bytes_per_token(arch, itemsize)
        + arch["n_ssm_layers"] * 2 * slots * state_bytes_per_slot_layer(arch)
    )


def window_means(stats, n_layers, block_size=16):
    """From the window's counters (``obs["serve"]["stats"]``) -> the
    means a decode step: (held experts touched a layer, distinct live
    tokens, sequences advanced), or None where the program counted
    nothing. ``block_size``: tokens a page (``serve_kv_pages_live_total``
    counts pages; every cell of the benchmark has pages of 16)."""
    steps = stats.get("decode_steps", 0)
    if not steps or "serve_ssm_slot_steps_total" not in stats:
        return None
    return (
        stats["serve_moe_experts_touched_total"] / (steps * n_layers),
        stats["serve_kv_pages_live_total"] / steps * block_size,
        stats["serve_ssm_slot_steps_total"] / steps,
    )
