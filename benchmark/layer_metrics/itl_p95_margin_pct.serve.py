"""Scheduler: how far the 95 % mark lies from the nearer edge of the
class ``itl_p95_class.serve`` names, in points of % of the window's
token gaps (cumulative counts, classes in order ``c0 .. c3``). 0.4
means a run that files 0.4 % of its gaps otherwise lands the
percentile on another class: under ~1 the flip between classes from
run to run IS the spread of ``itl_p95_ms``; at 5 (everything in one
class, or the class ends at 100 %) nothing near can move it. A program
without the counters reports nothing."""
from benchmark import harness


def read(obs):
    found = harness.load_module(
        "layer_metrics", "itl_p95_class.serve.py"
    ).locate(obs)
    return None if found is None else found[1]
