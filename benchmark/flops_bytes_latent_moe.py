"""Operations and bytes the latent-attention expert decoder needs, from
shapes alone (``flops_bytes_sparse_moe.py``'s counterpart for
``benchmark/reference/latent_moe_decoder.py``'s model). Each count is
a floor: what ANY implementation of the step must move or multiply,
so a share of the chip's peak made of it cannot pass 100 %.

``arch`` is the job's dict of the sizes as run: dim, n_layers, n_heads,
vocab_size, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, dense_hidden, first_dense_layers,
n_experts (the router's width), n_held (the routed experts this chip
holds of a layer), experts_per_token, expert_hidden, n_shared_experts.
"""


def n_dense_layers(arch):
    return min(arch["first_dense_layers"], arch["n_layers"])


def n_expert_layers(arch):
    return arch["n_layers"] - n_dense_layers(arch)


def latent_dim(arch):
    """Numbers a cached token keeps a layer: the latent and the one
    rotary key."""
    return arch["kv_lora_rank"] + arch["qk_rope_head_dim"]


def attention_params(arch):
    d, h = arch["dim"], arch["n_heads"]
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    return (
        d * arch["q_lora_rank"] + arch["q_lora_rank"]        # W_DQ, norm
        + arch["q_lora_rank"] * h * qk                       # W_UQ
        + d * latent_dim(arch) + arch["kv_lora_rank"]        # W_DKV, norm
        + arch["kv_lora_rank"] * h * (
            arch["qk_nope_head_dim"] + arch["v_head_dim"]    # W_UK, W_UV
        )
        + h * arch["v_head_dim"] * d                         # W_O
    )


def expert_params(arch):
    """One routed expert: w1, w3, w2."""
    return 3 * arch["dim"] * arch["expert_hidden"]


def router_params(arch):
    return arch["dim"] * arch["n_experts"] + arch["n_experts"]


def dense_layer_params(arch):
    return attention_params(arch) + 2 * arch["dim"] \
        + 3 * arch["dim"] * arch["dense_hidden"]


def expert_layer_params_outside_routed(arch):
    """Attention, the two norms, the router and the shared expert."""
    return attention_params(arch) + 2 * arch["dim"] + router_params(arch) \
        + arch["n_shared_experts"] * expert_params(arch)


def n_params(arch, embedding=True):
    total = (
        n_dense_layers(arch) * dense_layer_params(arch)
        + n_expert_layers(arch) * (
            expert_layer_params_outside_routed(arch)
            + arch["n_held"] * expert_params(arch)
        )
        + arch["dim"] + arch["dim"] * arch["vocab_size"]
    )
    return total + (arch["dim"] * arch["vocab_size"] if embedding else 0)


def cache_bytes_per_token(arch, itemsize=2):
    return arch["n_layers"] * latent_dim(arch) * itemsize


def moe_layer_bytes(arch, experts_touched, itemsize=2):
    """Bytes the router and the routed experts must read for one step,
    A LAYER OF ``n_layers`` (the readers multiply by ``n_layers``, and
    a dense layer has neither): each expert layer's router, and the
    weights of the HELD experts its tokens really chose, once each
    (``experts_touched``: :func:`window_means`' mean over every layer,
    dense ones counting 0; never the number held). The shared expert
    is no part of it: it runs under ``mlp``."""
    return itemsize * (
        n_expert_layers(arch) / arch["n_layers"] * router_params(arch)
        + experts_touched * expert_params(arch)
    )


def latent_read_bytes(arch, tokens, itemsize=2):
    """Bytes one layer's attention must read of the cache for a step:
    the latent row of each DISTINCT live token once (``tokens``: the
    distinct live pages times the page size; a page two slots share is
    one page)."""
    return tokens * latent_dim(arch) * itemsize


def latent_read_flops(arch, query_tokens):
    """Products one layer's absorbed read needs: a head's score against
    a cached row (``latent_dim`` numbers) and its value out of the
    latent (``kv_lora_rank``), for every head, query and cached token
    it attends (``query_tokens``: each slot's context, summed: sharing
    a page saves its bytes, not its products)."""
    return 2 * arch["n_heads"] * query_tokens * (
        latent_dim(arch) + arch["kv_lora_rank"]
    )


def decode_step_bytes(arch, experts_touched, live_tokens, itemsize=2):
    """Bytes one decode step must move: every weight outside the routed
    experts but the embedding table once (a step gathers only
    ``slots`` of its rows), the touched held experts once each
    (``experts_touched`` a layer of ``n_layers``), and the latent rows
    of the distinct live tokens once a layer."""
    weights = itemsize * (
        n_dense_layers(arch) * dense_layer_params(arch)
        + n_expert_layers(arch) * expert_layer_params_outside_routed(arch)
        + arch["n_layers"] * experts_touched * expert_params(arch)
        + arch["dim"] + arch["dim"] * arch["vocab_size"]
    )
    return weights + arch["n_layers"] * latent_read_bytes(
        arch, live_tokens, itemsize
    )


def window_means(stats, n_layers, block_size=16):
    """From the window's counters (``obs["serve"]["stats"]``) -> the
    means a decode step: (held experts touched a layer of
    ``n_layers``, distinct live tokens), or None where the program
    counted nothing. ``block_size``: tokens a page
    (``serve_latent_pages_live_total`` counts pages; every cell of the
    benchmark has pages of 16)."""
    steps = stats.get("decode_steps", 0)
    if not steps or "serve_latent_pages_live_total" not in stats:
        return None
    return (
        stats["serve_moe_experts_touched_total"] / (steps * n_layers),
        stats["serve_latent_pages_live_total"] / steps * block_size,
    )
