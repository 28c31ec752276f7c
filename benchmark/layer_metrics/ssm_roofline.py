"""Model step against the chip: the least time the state-space layers
of a decode step could take -- each reads its mixer's weights once and
reads and writes the recurrent state of the sequences the step advanced
(``flops_bytes_hybrid_ssm_moe.ssm_layer_bytes`` at the window's mean of
``serve_ssm_slot_steps_total`` a step), times the state-space layers,
over the chip's memory bandwidth -- over the device time a
decode-program run spends under their four scopes (``ssm_ms.serve``).
A few rows against 204 MB of weights and 38 MB of state a sequence: the
bytes are the roof, not the products. A program without the counter, or
a configuration whose ``flops_bytes`` counts no such layer, reports
nothing."""
from benchmark import harness

_ms = harness.load_module("layer_metrics", "ssm_ms.serve.py")


def read(obs):
    if not obs.get("flops_bytes"):
        return None
    fb = harness.load_module(f"{obs['flops_bytes']}.py")
    if not hasattr(fb, "ssm_layer_bytes"):
        return None
    arch = obs["arch"]
    means = fb.window_means(obs["serve"]["stats"], arch["n_layers"])
    ms = _ms.read(obs)
    if means is None or not ms:
        return None
    least_s = arch["n_ssm_layers"] * fb.ssm_layer_bytes(arch, means[2]) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (1e-3 * ms)
