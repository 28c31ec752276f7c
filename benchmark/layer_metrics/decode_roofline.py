"""Model step against the chip: the least time the decode steps of
the traced window could take -- the bytes each must move (every
weight but the embedding table once, plus the live keys and values of
its active slots; ``flops_bytes.decode_step_bytes``) over the chip's
memory bandwidth -- over the device time of the decode program's
executions in the trace. Decode is memory-bound: bandwidth is the
roof."""
from benchmark import flops_bytes


def read(obs):
    trace, serve = obs["trace"], obs["serve"]
    if not trace:
        return None
    runs = [
        m for dev in trace["devices"].values()
        for name, m in dev["modules"].items() if "decode" in name
    ]
    device_s = sum(m["total_s"] for m in runs)
    n_runs = sum(m["n"] for m in runs)
    live = [
        tokens for (t, _), (tokens, _) in zip(
            serve["calls"]["decode"], serve["decode_live"]
        ) if trace["t_open"] <= t <= obs["window_s"]
    ]
    if not n_runs or not live:
        return None
    least_s = sum(
        flops_bytes.decode_step_bytes(obs["arch"], n) for n in live
    ) / len(live) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (device_s / n_runs)
