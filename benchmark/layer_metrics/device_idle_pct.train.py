"""Device: share of the traced window in which no operation ran, on
the device that idled most (1 - union of its operations' intervals
over the window)."""


def read(obs):
    trace = obs["trace"]
    if not trace:
        return None
    return 100.0 * max(
        dev["idle_s"] for dev in trace["devices"].values()
    ) / trace["window_s"]
