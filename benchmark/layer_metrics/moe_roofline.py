"""Model step against the chip: the least time the expert layers of a
decode step could take -- the router and the weights of the experts
the step's tokens really chose, once each
(``flops_bytes_sparse_moe.moe_layer_bytes`` at the window's mean of
``serve_moe_experts_touched_total`` a step a layer; never counted from
the experts held), over the chip's memory bandwidth -- over the device
time a decode-program run spends under ``router`` and ``experts``.
Twelve tokens a step: the weights read are the roof, not the
products."""
from benchmark import harness, program_trace


def read(obs):
    if not obs.get("flops_bytes"):
        return None
    fb = harness.load_module(f"{obs['flops_bytes']}.py")
    arch = obs["arch"]
    means = fb.window_means(obs["serve"]["stats"], arch["n_layers"])
    parts = [
        program_trace.scope_ms_per_run(obs, "decode", scope)
        for scope in ("router", "experts")
    ]
    if means is None or None in parts or not sum(parts):
        return None
    least_s = arch["n_layers"] * fb.moe_layer_bytes(arch, means[0]) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (1e-3 * sum(parts))
