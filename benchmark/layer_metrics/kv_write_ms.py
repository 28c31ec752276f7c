"""Model step: device time one run of the decode program spends under
the scope ``kv_write`` (the token scatter into the pool, and the
compiler's copies and bitcasts that inherit it), mean over the traced
window's runs."""
from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_run(obs, "decode", "kv_write")
