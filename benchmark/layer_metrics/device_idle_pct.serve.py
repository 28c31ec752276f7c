"""Device: share of the traced window in which no operation ran, on
the device that idled most; the serving cells' twin of
``device_idle_pct.train`` (one quantity, two metrics, because a metric
moves one end-to-end metric and the cells report different ones)."""
from benchmark import harness

read = harness.load_module("layer_metrics", "device_idle_pct.train.py").read
