"""Model step against the chip, for a latent-attention decoder: the
least time the cache read of a decode step could take -- each layer
reads the latent row of every DISTINCT live token once
(``flops_bytes_latent_moe.latent_read_bytes`` at the window's mean of
``serve_latent_pages_live_total`` a step; a page several slots share
is one page) over the chip's memory bandwidth, or multiplies every
head against it in the absorbed form (``latent_read_flops``) at the
chip's bf16 peak, whichever is longer, times the layers -- over the
device time a decode-program run spends under ``kv_read`` and
``attention``. A program without the counter, or a configuration whose
``flops_bytes`` counts no latent read, reports nothing."""
from benchmark import harness, program_trace


def read(obs):
    if not obs.get("flops_bytes"):
        return None
    fb = harness.load_module(f"{obs['flops_bytes']}.py")
    if not hasattr(fb, "latent_read_bytes"):
        return None
    arch = obs["arch"]
    means = fb.window_means(obs["serve"]["stats"], arch["n_layers"])
    parts = [
        program_trace.scope_ms_per_run(obs, "decode", scope)
        for scope in ("kv_read", "attention")
    ]
    if means is None or None in parts or not sum(parts):
        return None
    tokens = means[1]
    least_s = arch["n_layers"] * max(
        fb.latent_read_bytes(arch, tokens) / obs["peaks"]["hbm_bytes_per_s"],
        fb.latent_read_flops(arch, tokens)
        / obs["peaks"]["bf16_flops_per_s"],
    )
    return 100.0 * least_s / (1e-3 * sum(parts))
