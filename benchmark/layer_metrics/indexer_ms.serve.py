"""Model step: device time one run of the decode program spends under
the scope ``indexer`` (the indexer's projections, its key's page
write, the scores over every slot's view, the exact top-k), mean over
the traced window's runs."""
from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_run(obs, "decode", "indexer")
