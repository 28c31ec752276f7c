"""Kernels: device time of the Mosaic calls named ``flash_bwd_dq`` and
``flash_bwd_dkv`` per training step, on the device that spent most."""
from benchmark import program_trace


def read(obs):
    return program_trace.kernel_ms_per_step(
        obs, "flash_bwd_dq", "flash_bwd_dkv"
    )
