"""Kernels against the chip: the least time one step's expert products
could take on one chip -- the larger of their operations over the bf16
peak and their bytes over the memory bandwidth, both from the
assignments to held experts the run COUNTED
(``flops_bytes_conv_moe.expert_products_step``: a row through three
matrices, forward, again under recomputation, and twice for the two
gradients), never from the rows a buffer holds -- over the device time
per step under the scope ``experts`` (the ragged products and the
elementwise work between them). At 2048 rows an expert the operations
are the roof."""
from benchmark import harness, program_trace


def read(obs):
    moe = (obs.get("train") or {}).get("moe")
    measured = program_trace.scope_ms_per_step(obs, "experts")
    if not obs.get("flops_bytes") or not moe or not measured:
        return None
    fb = harness.load_module(f"{obs['flops_bytes']}.py")
    train, peaks = obs["train"], obs["peaks"]
    ops, byts = fb.expert_products_step(
        obs["arch"],
        moe["train_moe_assignments_held_total"] / train["steps"],
        train["remat"],
    )
    least = max(
        ops / peaks["bf16_flops_per_s"], byts / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / (1e-3 * measured)
