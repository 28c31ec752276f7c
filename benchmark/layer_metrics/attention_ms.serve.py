"""Model step: device time one run of the decode program spends under
the scope ``attention`` (scores, softmax and values over what
``kv_read`` gathered), mean over the traced window's runs."""
from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_run(obs, "decode", "attention")
