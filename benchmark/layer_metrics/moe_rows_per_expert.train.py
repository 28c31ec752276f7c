"""Model step: the load of a held expert -- assignments to held
experts the window's steps counted (``train_moe_assignments_held_total``,
fetched with each chunk's loss) over held experts, expert layers and
steps: rows one expert got in one layer of one step, 2048 where the
cut's deployment is met. A run that counted nothing reports
nothing."""


def read(obs):
    moe = (obs.get("train") or {}).get("moe")
    arch = obs.get("arch") or {}
    if not moe or not moe.get("train_moe_assignments_held_total"):
        return None
    layers = arch["n_layers"] - arch["first_dense_layers"]
    return moe["train_moe_assignments_held_total"] / (
        len(arch["held_experts"]) * layers * obs["train"]["steps"]
    )
