"""Model step: device time per training step under the four scopes of
the expert layers -- ``router`` (FFN norm, router product, sigmoid,
top-k, gates), ``dispatch`` (the sort by expert and the gather of the
rows), ``experts`` (the ragged grouped products) and ``combine`` (a
token's rows added up by gate) -- forward and backward, on the device
that spent most. A program without those scopes reports nothing."""
from benchmark import program_trace

SCOPES = ("router", "dispatch", "experts", "combine")


def read(obs):
    parts = [program_trace.scope_ms_per_step(obs, s) for s in SCOPES]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None
