"""Model step: device time one run of the decode program spends under
the scope ``kv_read`` (each layer's slice of the pool, the gather of
every slot's view, ``pages_to_tokens``), mean over the traced window's
runs."""
from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_run(obs, "decode", "kv_read")
