"""Load generator: how late a request was submitted after it was due
(one thread drives both the server and the arrivals, so a long tick
delays the arrivals behind it), 95th percentile."""
from benchmark.stats import quantile


def read(obs):
    return quantile([
        1e3 * (r["submit"] - r["due"]) for r in obs["serve"]["requests"]
    ], 0.95)
