"""Scheduler: how much of the traffic pays the synchronous first-token
fetch -- tokens kept in emissions that waited for at least one chunk
that completed a prompt (``serve_gap_first_fetch_tokens_total``: the
host takes that chunk's token at once, so the emission waits for the
chunk and the device then idles until the next step is dispatched)
over the tokens of all four classes, in per cent. A program without
the counters reports nothing."""
from benchmark import harness


def read(obs):
    counts = harness.load_module(
        "layer_metrics", "chunk_gap_pct.serve.py"
    ).tokens(obs)
    stats = (obs.get("serve") or {}).get("stats") or {}
    if counts is None \
            or "serve_gap_first_fetch_tokens_total" not in stats:
        return None
    return 100.0 * stats["serve_gap_first_fetch_tokens_total"] \
        / sum(counts)
