"""Model step: how much of every slot's capacity the decode steps
read -- KV pages the dispatched decode programs gathered a layer
(``serve_decode_view_pages_read_total``: a flat rung's size, or slots x
pages a slot on the rectangle) over the pages the rectangle would have
gathered (``serve_decode_view_pages_total``), in per cent. 100 where
every step ran the rectangle (an indexer, a table-walking kernel); how
often and how far the flat read engages elsewhere. The program counts
since the engine was built (``engine.paged_stats``: warm-up and check
steps included, like ``decode_steps``) in the ``serve`` job and over
the window in ``serve_arch``. A program without the counters reports
nothing."""


def read(obs):
    stats = (obs.get("serve") or {}).get("stats") or {}
    total = stats.get("serve_decode_view_pages_total")
    if not total:
        return None
    return 100.0 * stats["serve_decode_view_pages_read_total"] / total
