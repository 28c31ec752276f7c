"""Model step against the chip, for a sparse-expert decoder: the least
time a decode step could take -- every weight outside the experts but
the embedding table once, each layer's touched experts once, the
indexer keys of the live tokens, the keys and values of the tokens
attention reads after selection
(``flops_bytes_sparse_moe.decode_step_bytes`` at the window's means,
from the program's own counters) over the chip's memory bandwidth --
over the device time of the decode program's executions in the trace.
``decode_roofline`` counts the dense decoder and is not reported
where this is."""
from benchmark import harness


def read(obs):
    trace = obs["trace"]
    if not trace or not obs.get("flops_bytes"):
        return None
    fb = harness.load_module(f"{obs['flops_bytes']}.py")
    arch = obs["arch"]
    means = fb.window_means(obs["serve"]["stats"], arch["n_layers"])
    runs = [
        m for dev in trace["devices"].values()
        for name, m in dev["modules"].items() if "decode" in name
    ]
    device_s = sum(m["total_s"] for m in runs)
    n_runs = sum(m["n"] for m in runs)
    if means is None or not n_runs:
        return None
    least_s = fb.decode_step_bytes(arch, *means) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (device_s / n_runs)
