"""Model step: wall time of ``tpu_hpc:decode.prep`` (copy-on-write
guard, executable lookup, the host-to-device transfers of tokens,
positions, tables and the active mask), median over the traced
window's decode calls."""
from benchmark import program_trace
from benchmark.stats import median


def read(obs):
    walls = program_trace.span_walls(obs, "decode.prep")
    return 1e3 * median(walls) if walls else None
