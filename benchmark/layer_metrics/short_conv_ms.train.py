"""Model step: device time per training step under the scope
``short_conv`` (the gated short convolution's norm, input projection,
gates, taps and output projection, forward and backward), on the
device that spent most. A program without the scope reports
nothing."""
from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_step(obs, "short_conv")
