"""Operations and bytes the algorithm needs, from shapes and from what
the run COUNTED, for a decoder of short convolutions, attention layers
and routed experts (``configs/lfm2-24b-a2b.json``; reference
``reference/conv_moe_decoder.py``).

``arch`` is the job's dict of sizes as run (``jobs/train_arch.py``):
dim, n_layers, n_heads, n_kv_heads, layer_types, first_dense_layers,
conv_taps, dense_hidden, n_experts, experts_per_token, expert_hidden,
held_experts, vocab_size. Nothing here knows how the program computes
the expert layer: a later implementation reads the same work.
"""

from benchmark import flops_bytes

# Matrix products over a row of an expert: W1, W3, W2.
EXPERT_MATRICES = 3


def expert_layers(arch):
    return arch["n_layers"] - arch["first_dense_layers"]


def n_params(arch):
    """Parameters held and trained (the tied table once)."""
    d = arch["dim"]
    head_dim = d // arch["n_heads"]
    total = arch["vocab_size"] * d + d
    for i in range(arch["n_layers"]):
        total += 2 * d
        if arch["layer_types"][i] == "full_attention":
            total += 2 * d * arch["n_heads"] * head_dim \
                + 2 * d * arch["n_kv_heads"] * head_dim + 2 * head_dim
        else:
            total += 3 * d * d + d * d + arch["conv_taps"] * d
        if i < arch["first_dense_layers"]:
            total += 3 * d * arch["dense_hidden"]
        else:
            total += d * arch["n_experts"] + len(arch["held_experts"]) \
                * EXPERT_MATRICES * d * arch["expert_hidden"]
    return total


def expert_row_flops(arch):
    """Forward operations of ONE assignment: a row through one
    expert's three matrices."""
    return EXPERT_MATRICES * 2 * arch["dim"] * arch["expert_hidden"]


def expert_products_step(arch, held_rows, remat, itemsize=2):
    """(operations, bytes) of the expert products one training step
    must make for ``held_rows`` assignments to held experts (summed
    over the expert layers, as the run counted them): each row passes
    the three matrices forward, and twice more for the two gradients
    (the rows' and the weights'); under recomputation the forward pass
    runs again. Bytes: every pass reads each held expert's three
    matrices once and reads and writes the rows at both widths."""
    passes = 4 if remat else 3
    ops = passes * held_rows * expert_row_flops(arch)
    weights = expert_layers(arch) * len(arch["held_experts"]) \
        * EXPERT_MATRICES * arch["dim"] * arch["expert_hidden"] * itemsize
    rows = held_rows * EXPERT_MATRICES \
        * (arch["dim"] + arch["expert_hidden"]) * itemsize
    return ops, passes * (weights + rows)


def flash_step(arch, batch, seq, remat):
    """(operations, bytes) of the flash calls one training step makes:
    for each ATTENTION layer of the run (``layer_types``; the others
    mix by convolution and call no kernel) one forward, a second under
    recomputation, one dQ and one dK/dV call, each as
    ``flops_bytes.flash_call`` counts it at this model's head width."""
    attending = arch["layer_types"][:arch["n_layers"]].count("full_attention")
    calls = {"fwd": 2 if remat else 1, "dq": 1, "dkv": 1}
    ops = byts = 0
    for kind, n in calls.items():
        o, b = flops_bytes.flash_call(
            kind, batch, arch["n_heads"], arch["n_kv_heads"], seq,
            arch["dim"] // arch["n_heads"],
        )
        ops, byts = ops + n * o, byts + n * b
    return attending * ops, attending * byts


def train_flops_per_token(arch, seq_len, held_rows_per_token):
    """Forward + backward matmul operations one trained token costs on
    this chip (the 6 N convention; causal attention at ``seq_len`` in
    the attention layers; ``held_rows_per_token`` assignments computed
    here a token, summed over the expert layers, as counted);
    recomputation does not count."""
    d = arch["dim"]
    head_dim = d // arch["n_heads"]
    total = 2 * d * arch["vocab_size"]
    for i in range(arch["n_layers"]):
        if arch["layer_types"][i] == "full_attention":
            total += 2 * d * (arch["n_heads"] + 2 * arch["n_kv_heads"]) \
                * head_dim + 2 * arch["n_heads"] * head_dim * d \
                + 2 * seq_len * arch["n_heads"] * head_dim
        else:
            total += 2 * d * 3 * d + 2 * d * d + 2 * arch["conv_taps"] * d
        if i < arch["first_dense_layers"]:
            total += 3 * 2 * d * arch["dense_hidden"]
        else:
            total += 2 * d * arch["n_experts"]
    return 3 * (total + held_rows_per_token * expert_row_flops(arch))
