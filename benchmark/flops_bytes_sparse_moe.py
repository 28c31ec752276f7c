"""Operations and bytes the sparse-expert decoder needs, from shapes
alone (``flops_bytes.py``'s counterpart for
``benchmark/reference/sparse_moe_decoder.py``'s model).

``arch`` is the job's dict of the sizes as run: dim, n_layers, n_heads,
n_kv_heads, head_dim, vocab_size, n_experts, experts_per_token,
expert_hidden, indexer_heads, indexer_head_dim.
"""


def expert_params(arch):
    """One expert: w1, w3, w2."""
    return 3 * arch["dim"] * arch["expert_hidden"]


def layer_params_outside_experts(arch):
    d, hd = arch["dim"], arch["head_dim"]
    hi, di = arch["indexer_heads"], arch["indexer_head_dim"]
    return (
        d * (arch["n_heads"] + 2 * arch["n_kv_heads"]) * hd   # wq, wk, wv
        + arch["n_heads"] * hd * d                            # wo
        + 2 * hd                                              # q, k norms
        + d * hi * di + d * di + 2 * di + d * hi              # indexer
        + d * arch["n_experts"]                               # router
        + 2 * d                                               # two norms
    )


def n_params(arch, embedding=True):
    total = arch["n_layers"] * (
        layer_params_outside_experts(arch)
        + arch["n_experts"] * expert_params(arch)
    ) + arch["dim"] + arch["dim"] * arch["vocab_size"]
    return total + (arch["dim"] * arch["vocab_size"] if embedding else 0)


def moe_layer_flops(arch, tokens):
    """Products one layer's router and experts need for ``tokens``
    tokens: each token its ``experts_per_token`` experts."""
    return 2 * tokens * (
        arch["dim"] * arch["n_experts"]
        + arch["experts_per_token"] * expert_params(arch)
    )


def moe_layer_bytes(arch, experts_touched, itemsize=2):
    """Bytes one layer's router and experts must read for one step:
    the router, and the weights of the experts its tokens really
    chose, once each (``experts_touched``: distinct experts with at
    least one token, a count the program reports; never the number
    held)."""
    return itemsize * (
        arch["dim"] * arch["n_experts"]
        + experts_touched * expert_params(arch)
    )


def kv_bytes_per_token_layer(arch, itemsize=2):
    return 2 * arch["n_kv_heads"] * arch["head_dim"] * itemsize


def indexer_bytes_per_token_layer(arch, itemsize=2):
    return arch["indexer_head_dim"] * itemsize


def cache_bytes_per_token(arch, itemsize=2):
    return arch["n_layers"] * (
        kv_bytes_per_token_layer(arch, itemsize)
        + indexer_bytes_per_token_layer(arch, itemsize)
    )


def decode_step_bytes(arch, experts_touched_per_layer, scored_tokens,
                      read_tokens, itemsize=2):
    """Bytes one decode step must move: every weight outside the
    experts but the embedding table once (a step gathers only
    ``slots`` of its rows), each layer's touched experts once, the
    indexer key of every token the indexer scores
    (``scored_tokens``: the active slots' whole contexts, summed) and
    the keys and values of the tokens attention reads after selection
    (``read_tokens``: min(context, topk) a slot, summed)."""
    layers = arch["n_layers"]
    weights = itemsize * (
        layers * (
            layer_params_outside_experts(arch)
            + experts_touched_per_layer * expert_params(arch)
        ) + arch["dim"] + arch["dim"] * arch["vocab_size"]
    )
    return (
        weights
        + layers * scored_tokens * indexer_bytes_per_token_layer(arch, itemsize)
        + layers * read_tokens * kv_bytes_per_token_layer(arch, itemsize)
    )


def window_means(stats, n_layers):
    """From the window's counters (``obs["serve"]["stats"]``) -> the
    means a decode step: (experts touched a layer, tokens scored,
    tokens read), or None where the program counted nothing."""
    steps = stats.get("decode_steps", 0)
    if not steps or "serve_moe_experts_touched_total" not in stats:
        return None
    per_step_layer = steps * n_layers
    return (
        stats["serve_moe_experts_touched_total"] / per_step_layer,
        stats["serve_sparse_candidate_tokens_total"] / per_step_layer,
        stats["serve_sparse_selected_tokens_total"] / per_step_layer,
    )
