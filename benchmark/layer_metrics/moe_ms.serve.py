"""Model step: device time one run of the decode program spends under
the scopes ``router`` (FFN norm, router product, softmax, top-k) and
``experts`` (dispatch, the expert products, combine), mean over the
traced window's runs."""
from benchmark import program_trace


def read(obs):
    parts = [
        program_trace.scope_ms_per_run(obs, "decode", scope)
        for scope in ("router", "experts")
    ]
    return None if None in parts or not sum(parts) else sum(parts)
