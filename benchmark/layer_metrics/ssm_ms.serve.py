"""Model step: device time one run of the decode program spends under
the four scopes of its state-space layers -- ``ssm_in`` (norm and input
projection), ``ssm_conv`` (the convolution and its kept rows),
``ssm_scan`` (the state's read, the recurrence, ``y`` and the state's
write) and ``ssm_out`` (gated norm and output projection) -- mean over
the traced window's runs. A program without those scopes reports
nothing."""
from benchmark import program_trace

SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")


def read(obs):
    parts = [
        program_trace.scope_ms_per_run(obs, "decode", scope)
        for scope in SCOPES
    ]
    return None if None in parts or not sum(parts) else sum(parts)
