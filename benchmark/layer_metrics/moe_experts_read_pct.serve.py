"""Model step: how many of the experts this chip holds the decode
steps' expert products read -- ``serve_moe_experts_read_total`` (the
experts a step's tokens touched where the step's shape has the product
visit those alone; every held expert of every expert layer where it
reads the whole stack) over held experts x expert layers x decode
steps of the window, in per cent. 100 where every step read the whole
stack (sixteen slots x ten over eighteen held experts: nothing to
skip); the mean share of the held experts touched a layer where the
grouped product engages. A program without the counter reports
nothing."""


def read(obs):
    stats = (obs.get("serve") or {}).get("stats") or {}
    arch = obs.get("arch") or {}
    steps = stats.get("decode_steps")
    if not steps or "serve_moe_experts_read_total" not in stats:
        return None
    expert_layers = arch["n_layers"] - min(
        arch.get("first_dense_layers", 0), arch["n_layers"]
    )
    held = arch.get("n_held", arch["n_experts"])
    return 100.0 * stats["serve_moe_experts_read_total"] \
        / (held * expert_layers * steps)
