"""Scheduler: the class in which the 95th percentile of the token gaps
falls (0 .. 3: prefill chunk programs ahead of the emission that closed
the gap, 3 standing for three or more), the classes taken in order
``c0 < c1 < c2 < c3`` by their token counts: the first class at which
the cumulative share of the gaps reaches 95 %. What ``itl_p95_ms``
stands on in this run: a plain decode step (0), a step behind one chunk
(1), behind two (2). A program without the counters reports nothing.

``locate(obs)`` also gives the margin ``itl_p95_margin_pct.serve``
reports: (class, points of % from the 95 % mark to the nearer edge of
that class in the cumulative counts)."""
from benchmark import harness

MARK = 95.0


def locate(obs):
    counts = harness.load_module(
        "layer_metrics", "chunk_gap_pct.serve.py"
    ).tokens(obs)
    if counts is None:
        return None
    total, cum = sum(counts), 0
    for cls, n in enumerate(counts):
        below, cum = cum, cum + n
        if 100 * cum >= MARK * total:
            return cls, min(
                MARK - 100.0 * below / total, 100.0 * cum / total - MARK
            )


def read(obs):
    found = locate(obs)
    return None if found is None else found[0]
