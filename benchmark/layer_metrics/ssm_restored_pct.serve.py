"""Cache: the share of the window's admitted prompt tokens whose
recurrent state came out of a snapshot in the prefix trie
(``serve_ssm_restored_tokens_total`` over the prompt tokens of the
requests admitted in the window), in per cent. Pages alone serve
nothing on a model with state-space layers: a prompt is prefilled from
the deepest position at which the trie holds the state too, so this is
the hit rate that saves prefill there. A program without the counter
reports nothing."""


def read(obs):
    serve = obs.get("serve") or {}
    stats = serve.get("stats") or {}
    if "serve_ssm_restored_tokens_total" not in stats:
        return None
    admitted = sum(
        r["prompt_len"] for r in serve.get("requests", ())
        if r["admit"] is not None
    )
    if not admitted:
        return None
    return 100.0 * stats["serve_ssm_restored_tokens_total"] / admitted
