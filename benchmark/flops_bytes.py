"""Operations and bytes the algorithm needs, from shapes alone.

``arch`` is a dict of the sizes as the cell runs them: dim, n_layers,
n_heads, n_kv_heads, head_dim, ffn_hidden, vocab_size.
"""


def n_params(arch, embedding=True):
    d, h = arch["dim"], arch["ffn_hidden"]
    per_layer = (
        d * (arch["n_heads"] + 2 * arch["n_kv_heads"]) * arch["head_dim"]
        + arch["n_heads"] * arch["head_dim"] * d  # wo
        + 3 * d * h                               # w1, w3, w2
        + 2 * d                                   # two norm scales
    )
    total = arch["n_layers"] * per_layer + d + d * arch["vocab_size"]
    return total + (d * arch["vocab_size"] if embedding else 0)


def train_flops_per_token(arch, seq_len):
    """Forward + backward matmul operations one trained token requires
    (the 6N convention over the matrices a token passes through, plus
    causal attention at ``seq_len``); recomputation does not count."""
    d, h = arch["dim"], arch["ffn_hidden"]
    per_layer = (
        2 * d * (arch["n_heads"] + 2 * arch["n_kv_heads"]) * arch["head_dim"]
        + 2 * arch["n_heads"] * arch["head_dim"] * d
        + 3 * 2 * d * h
        + 2 * seq_len * arch["n_heads"] * arch["head_dim"]  # QK^T + PV, causal half
    )
    return 3 * (arch["n_layers"] * per_layer + 2 * d * arch["vocab_size"])


# Causal matmuls each flash kernel runs: forward QK^T, PV; the dQ
# kernel recomputes QK^T, then dP = dO V^T and dQ = dS K; the dK/dV
# kernel recomputes QK^T, then dP, dV = P^T dO and dK = dS^T Q.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call(kind, batch, n_heads, n_kv_heads, seq, head_dim, itemsize=2):
    """(operations, bytes) of ONE causal flash-attention kernel call on
    one chip's shard: each matmul is ``2 * S*(S+1)/2 * D`` a head; the
    bytes are the tensors the call must read and write once."""
    one = 2 * batch * n_heads * (seq * (seq + 1) // 2) * head_dim
    q = batch * seq * n_heads * head_dim * itemsize
    kv = batch * seq * n_kv_heads * head_dim * itemsize
    tensors = {
        "fwd": 2 * q + 2 * kv,       # q, k, v in; o out
        "dq": 4 * q + 2 * kv,        # q, k, v, o, do in; dq out
        "dkv": 3 * q + 4 * kv,       # q, k, v, o, do in; dk, dv out
    }[kind]
    return FLASH_MATMULS[kind] * one, tensors


def flash_step(arch, batch, seq, remat, model_shards=1):
    """(operations, bytes) of every flash call one training step makes
    on one chip: per layer one forward (two under remat: the block is
    recomputed in the backward pass, and the kernel runs again), one dQ
    and one dK/dV call, over this chip's share of the heads."""
    heads = arch["n_heads"] // model_shards
    kv_heads = max(arch["n_kv_heads"] // model_shards, 1)
    calls = {"fwd": 2 if remat else 1, "dq": 1, "dkv": 1}
    ops = byts = 0
    for kind, n in calls.items():
        o, b = flash_call(kind, batch, heads, kv_heads, seq, arch["head_dim"])
        ops += n * o
        byts += n * b
    return arch["n_layers"] * ops, arch["n_layers"] * byts, \
        arch["n_layers"] * sum(calls.values())


def kv_bytes_per_token(arch, itemsize=2):
    return 2 * arch["n_layers"] * arch["n_kv_heads"] * arch["head_dim"] * itemsize


def decode_step_bytes(arch, live_tokens, weight_itemsize=2, kv_itemsize=2):
    """Bytes one decode step must move: every weight but the embedding
    table read once (a step gathers only ``slots`` of its rows), and
    the cached keys and values of the live tokens of the active slots
    read once."""
    return (
        n_params(arch, embedding=False) * weight_itemsize
        + live_tokens * kv_bytes_per_token(arch, kv_itemsize)
    )
