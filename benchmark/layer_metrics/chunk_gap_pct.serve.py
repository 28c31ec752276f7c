"""Scheduler: the share of the window's token gaps that closed behind
at least one prefill chunk -- tokens the batcher filed in classes
``c1`` .. ``c3`` (``serve_gap_tokens_<c>_total``: the emission that
carried them had one, two, three or more chunk programs run ahead of
it, or waited for by the host) over the tokens of all four classes, in
per cent. The variable of the cliff in ``itl_p95_ms``: past 5 the
percentile is a gap behind a chunk. A program without the counters
reports nothing.

``tokens(obs)`` is what the other gap readers start from: the four
classes' token counts, or ``None``."""

CLASSES = ("c0", "c1", "c2", "c3")


def by_class(obs, what):
    """``serve_gap_<what>_<c>_total`` for the four classes, or None
    where the program does not count them."""
    stats = (obs.get("serve") or {}).get("stats") or {}
    names = [f"serve_gap_{what}_{c}_total" for c in CLASSES]
    if any(name not in stats for name in names):
        return None
    return [stats[name] for name in names]


def tokens(obs):
    counts = by_class(obs, "tokens")
    return counts if counts and sum(counts) else None


def read(obs):
    counts = tokens(obs)
    if counts is None:
        return None
    return 100.0 * sum(counts[1:]) / sum(counts)
