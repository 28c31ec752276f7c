"""First token minus DUE time, 95th percentile over the requests due
in the window (a request with no first token has no reading here and
counts under ``failed``)."""
from benchmark.stats import quantile


def read(obs):
    return quantile([
        1e3 * (r["first"] - r["due"])
        for r in obs["serve"]["requests"] if r["first"] is not None
    ], 0.95)
