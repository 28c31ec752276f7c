"""Scheduler: wall time of ``tpu_hpc:tick`` outside its children
``tick.admit``, ``tick.prefill`` and the engine's ``decode``: admission
control, the per-slot token loop with the meter and evictions, and the
tick's own glue, mean over the traced window's ticks. The inside twin
of ``host_ms_per_tick.serve``, which also counts the scheduler's share
of the admit and prefill loops."""
from benchmark import program_trace


def read(obs):
    return program_trace.tick_own_ms(obs)
