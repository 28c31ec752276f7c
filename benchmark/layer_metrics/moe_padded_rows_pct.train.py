"""Model step: rows the expert products computed that carry no
assignment, over rows computed (``train_moe_rows_computed_total`` less
``train_moe_assignments_held_total``, over the former): what the
product's row tiles cost beyond the assignments made. A run that
counted nothing reports nothing."""


def read(obs):
    moe = (obs.get("train") or {}).get("moe")
    if not moe or not moe.get("train_moe_rows_computed_total"):
        return None
    computed = moe["train_moe_rows_computed_total"]
    return 100.0 * (
        computed - moe["train_moe_assignments_held_total"]
    ) / computed
