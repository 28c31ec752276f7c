"""Trainer: time of one step, from the trainer's own chunk timer
(host clock round one fetch a chunk), median over the window's
chunks."""
from benchmark.stats import median


def read(obs):
    return median([
        1e3 * c["seconds"] / c["steps"] for c in obs["train"]["chunks"]
    ])
