"""Mesh: time per step in all-gather / reduce-scatter / all-reduce /
collective-permute operations during which no other operation runs on
that device, from the trace, on the device that waited most."""


def read(obs):
    trace = obs["trace"]
    if not trace:
        return None
    return 1e3 * max(
        dev["collective_exposed_s"] for dev in trace["devices"].values()
    ) / trace["steps"]
