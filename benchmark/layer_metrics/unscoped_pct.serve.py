"""Model step: share of the decode and prefill programs' device time
in the traced window that falls under none of the program's scopes,
own or inherited. It measures the tracing: what is left are the
compiler's operations that no producer or consumer claims."""
from benchmark import program_trace


def read(obs):
    trace = program_trace.load(obs)
    if not trace:
        return None
    worst = None
    for dev in trace["devices"].values():
        scoped = unscoped = 0.0
        for name, prog in dev["programs"].items():
            if "decode" in name or "prefill" in name:
                scoped += sum(prog["scopes_s"].values()) \
                    + sum(prog["inherited_s"].values())
                unscoped += prog["unscoped_s"]
        if scoped > 0:
            share = 100.0 * unscoped / (scoped + unscoped)
            worst = share if worst is None else max(worst, share)
    return worst
