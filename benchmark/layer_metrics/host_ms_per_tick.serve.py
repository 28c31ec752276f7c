"""Scheduler and host loop: wall time of ``batcher.step()`` that is
NOT inside an engine call (decode, prefill_step, admit, release, each
wrapped in a span by the benchmark), mean over the ticks."""


def read(obs):
    serve = obs["serve"]
    if not serve["ticks"]:
        return None
    in_calls = sum(w for calls in serve["calls"].values() for _, w in calls)
    return 1e3 * (sum(w for _, w in serve["ticks"]) - in_calls) \
        / len(serve["ticks"])
