"""Model step: wall time of one ``engine.prefill_step`` (one chunk of
one slot, dispatch to token fetch), median."""
from benchmark.stats import median


def read(obs):
    v = median([w for _, w in obs["serve"]["calls"]["prefill"]])
    return None if v is None else 1e3 * v
