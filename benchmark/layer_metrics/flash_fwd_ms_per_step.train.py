"""Kernels: device time of the Mosaic calls named ``flash_fwd`` per
training step (twice a layer under remat), on the device that spent
most."""
from benchmark import program_trace


def read(obs):
    return program_trace.kernel_ms_per_step(obs, "flash_fwd")
