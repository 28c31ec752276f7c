"""Kernels against the chip, where only some layers attend: the least
time one step's flash calls could take on one chip -- the larger of
their causal operations over the bf16 peak and their bytes over the
memory bandwidth, counted over the ATTENTION layers the run has
(``<flops_bytes>.flash_step``: ``arch.layer_types``, this model's head
width), where ``flash_roofline`` counts every layer -- over the device
time per step of the three kernels BY NAME (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``): a step with other Mosaic calls
(ragged expert products) is read right."""
from benchmark import harness, program_trace


def read(obs):
    if not obs.get("trace") or not obs.get("flops_bytes"):
        return None
    fb = harness.load_module(f"{obs['flops_bytes']}.py")
    measured = program_trace.kernel_ms_per_step(
        obs, "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"
    )
    if not measured or not hasattr(fb, "flash_step"):
        return None
    train, peaks = obs["train"], obs["peaks"]
    ops, byts = fb.flash_step(
        obs["arch"], train["batch_per_chip"], train["seq_len"],
        train["remat"],
    )
    least = max(
        ops / peaks["bf16_flops_per_s"], byts / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / (1e-3 * measured)
