"""Kernels: device time of the Mosaic custom calls (the three flash
attention kernels; the step has no other) per training step, from the
trace, on the device that spent most."""


def flash_s_per_step(obs):
    trace = obs["trace"]
    if not trace:
        return None
    worst = max(
        dev["buckets_s"].get("custom_call", 0.0)
        for dev in trace["devices"].values()
    )
    return worst / trace["steps"] if worst else None


def read(obs):
    v = flash_s_per_step(obs)
    return None if v is None else 1e3 * v
