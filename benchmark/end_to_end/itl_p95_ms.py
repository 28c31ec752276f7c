"""Gap between a request's successive tokens, 95th percentile over
all gaps of all requests. Under a backlog only gaps that closed
inside the window count (the run stops there)."""
from benchmark.stats import quantile


def gaps_ms(obs):
    end = obs["window_s"] if obs["serve"]["backlog"] else float("inf")
    return [
        1e3 * (b - a)
        for r in obs["serve"]["requests"]
        for a, b in zip(r["token_times"], r["token_times"][1:])
        if b <= end
    ]


def read(obs):
    return quantile(gaps_ms(obs), 0.95)
