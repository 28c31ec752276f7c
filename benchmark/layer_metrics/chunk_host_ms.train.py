"""Trainer: wall time of ``tpu_hpc:chunk.host`` (from the chunk's one
fetch to the next dispatch: stall watermark, registry, heartbeat,
digest, JSONL), median over the traced window's chunks."""
from benchmark import program_trace
from benchmark.stats import median


def read(obs):
    walls = program_trace.span_walls(obs, "chunk.host")
    return 1e3 * median(walls) if walls else None
