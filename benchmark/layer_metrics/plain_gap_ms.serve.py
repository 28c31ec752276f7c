"""Scheduler: what a token gap costs with nothing ahead of it -- the
wall between an emission of class ``c0`` (no prefill chunk program
between its decode step and the one before, none waited for) and the
emission before it (``serve_gap_seconds_c0_total``, by the meter's
clock) over the count of such emissions
(``serve_gap_emissions_c0_total``), in ms. The inside twin of
``decode_step_ms``: on a steady tick one period of the device's decode
program. A program without the counters, or a window with no such
emission, reports nothing."""


def seconds(obs):
    """Seconds a plain gap costs, or None."""
    stats = (obs.get("serve") or {}).get("stats") or {}
    emissions = stats.get("serve_gap_emissions_c0_total")
    if not emissions:
        return None
    return stats["serve_gap_seconds_c0_total"] / emissions


def read(obs):
    plain = seconds(obs)
    return None if plain is None else 1e3 * plain
