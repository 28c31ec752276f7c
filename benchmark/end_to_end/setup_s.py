"""Process start to window start: imports, the device, weights,
compilation or cache loads, the correctness check, warm-up."""


def read(obs):
    return obs["setup_s"]
