"""Trainer: device time per training step under the scope ``head``
(final norm, the vocabulary matmul and the loss, forward and
backward), on the device that spent most."""
from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_step(obs, "head")
