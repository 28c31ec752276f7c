"""Device: the serving cells' twin of ``idle_unnamed_pct.train`` (one
quantity, two metrics: a metric moves one end-to-end metric)."""
from benchmark import program_trace

read = program_trace.idle_unnamed_pct
