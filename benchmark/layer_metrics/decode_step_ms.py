"""Model step: wall time of one ``engine.decode`` (all slots, ends in
the token fetch), median."""
from benchmark.stats import median


def read(obs):
    v = median([w for _, w in obs["serve"]["calls"]["decode"]])
    return None if v is None else 1e3 * v
