"""Model step: the share of the decode steps' token-to-expert
assignments that landed on experts THIS chip holds
(``serve_moe_assignments_held_total`` over
``serve_moe_assignments_total`` in the window), in per cent. The
configuration's cut states a share of each layer's experts (64 of 256:
25 % if routing is even); this says whether the share carries the load
the cut states. A program without the counters reports nothing."""


def read(obs):
    stats = (obs.get("serve") or {}).get("stats") or {}
    total = stats.get("serve_moe_assignments_total")
    if not total or "serve_moe_assignments_held_total" not in stats:
        return None
    return 100.0 * stats["serve_moe_assignments_held_total"] / total
