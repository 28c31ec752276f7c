"""Scheduler: slot admission (the meter's ``t_admit``) minus due
time, 95th percentile."""
from benchmark.stats import quantile


def read(obs):
    return quantile([
        1e3 * (r["admit"] - r["due"])
        for r in obs["serve"]["requests"] if r["admit"] is not None
    ], 0.95)
