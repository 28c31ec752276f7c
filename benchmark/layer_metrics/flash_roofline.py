"""Kernels against the chip: the least time one step's flash calls
could take on one chip -- the larger of their causal operations over
the bf16 peak and their bytes over the memory bandwidth
(``flops_bytes.flash_step``: per layer two forward calls under remat,
one dQ, one dK/dV, over this chip's heads) -- over their device time
per step from the trace. At these shapes compute is the roof."""
from benchmark import flops_bytes, harness

per_step = harness.load_module(
    "layer_metrics", "flash_ms_per_step.train.py"
).flash_s_per_step


def read(obs):
    measured = per_step(obs)
    if measured is None:
        return None
    train, peaks = obs["train"], obs["peaks"]
    ops, byts, _ = flops_bytes.flash_step(
        obs["arch"], train["batch_per_chip"], train["seq_len"],
        train["remat"], train["model_shards"],
    )
    least = max(
        ops / peaks["bf16_flops_per_s"], byts / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / measured
