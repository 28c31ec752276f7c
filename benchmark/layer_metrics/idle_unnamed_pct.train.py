"""Device: share of the devices' idle time in the traced window that
lies in no leaf ``tpu_hpc:`` span: idle the program cannot yet put a
name to."""
from benchmark import program_trace

read = program_trace.idle_unnamed_pct
