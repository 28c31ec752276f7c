"""Model step: how many decode steps of an engine WITH a ladder fell
back to the rectangle -- ``serve_decode_rectangle_steps_total`` (steps
whose live pages no flat rung held, so the program gathered every
slot's whole capacity) over ``serve_decode_ladder_steps_total`` (every
decode step such an engine dispatched), in per cent. Both are counted
in the same line of ``PagedEngine.decode`` and share a lifetime
whichever job reads them (since the engine was built in the ``serve``
job: warm-up and check steps included, like the ``view_pages`` pair).
An engine without a ladder holds neither counter and reports
nothing."""


def read(obs):
    stats = (obs.get("serve") or {}).get("stats") or {}
    steps = stats.get("serve_decode_ladder_steps_total")
    if not steps:
        return None
    return 100.0 * stats["serve_decode_rectangle_steps_total"] / steps
