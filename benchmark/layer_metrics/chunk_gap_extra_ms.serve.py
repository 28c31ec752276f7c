"""Scheduler: what one prefill chunk ahead costs a decoding request --
the wall of the emissions filed in classes ``c1`` .. ``c3``
(``serve_gap_seconds_<c>_total``) less what as many plain gaps would
have cost (their emissions x ``plain_gap_ms.serve``), over the chunk
programs filed with them (``serve_gap_chunks_total``), in ms a chunk.
The inside answer to ``prefill_chunk_ms``, whose outside wall also
holds the decode step queued before the chunk: this is the chunk
program's own time on the device, plus the idle behind a chunk whose
first token the host fetched. A program without the counters, or a
window with no chunk or no plain gap, reports nothing."""
from benchmark import harness


def read(obs):
    plain = harness.load_module(
        "layer_metrics", "plain_gap_ms.serve.py"
    ).seconds(obs)
    by_class = harness.load_module(
        "layer_metrics", "chunk_gap_pct.serve.py"
    ).by_class
    stats = (obs.get("serve") or {}).get("stats") or {}
    chunks = stats.get("serve_gap_chunks_total")
    seconds, emissions = by_class(obs, "seconds"), by_class(obs, "emissions")
    if plain is None or not chunks or None in (seconds, emissions):
        return None
    return 1e3 * (sum(seconds[1:]) - sum(emissions[1:]) * plain) / chunks
