"""Traffic kind ``open_loop``: requests sent on a schedule, whether or
not earlier ones have finished.

One general generator; a mix is a data file of its parameters:

    mix_seed              fixes the mix's gaps and lengths once
    arrivals.process      "backlog" (every request due at 0),
                          "poisson" (rate_per_s), or "onoff"
                          (burst_size arrivals at burst_rate_per_s,
                          then off_s of silence)
    n_requests | horizon_s   how many requests to draw (a backlog
                          states n_requests; a rate states the longest
                          window it must cover)
    prompt_len, output_len   lognormal {median, sigma} clipped to
                          [lo, hi] tokens
    shared_prefix_tokens, prefix_groups   leading tokens shared by
                          all requests of a group (0 = unshared)

Every seed gets the SAME multiset of gaps and lengths (drawn from
``mix_seed``), in another order, and its own tokens: the run's
``--seed`` reorders the work, it does not change how much there is.
Under a rate that holds for the window too: the requests of a window of
``seconds`` are the mix's first n, n being how many of its own arrivals
fall inside ``seconds``, so any order of their gaps ends inside it.
The two length generators and the Poisson gaps are copies of
``tpu_hpc/loadgen/scenarios.py`` (``heavy_tail_lengths``,
``poisson_arrivals``, ``onoff_arrivals``): the yardstick may not
change with the program.
"""
import math

import numpy as np


def lognormal_lengths(rng, n, median, sigma, lo, hi):
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length range [{lo}, {hi}]")
    vals = rng.lognormal(mean=np.log(median), sigma=sigma, size=n)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def arrival_gaps(rng, arrivals, n):
    """Inter-arrival gaps in seconds; cumulated AFTER the run's
    reordering, so every seed has the same gaps."""
    process = arrivals["process"]
    if process == "backlog":
        return np.zeros(n)
    if process == "poisson":
        return rng.exponential(1.0 / arrivals["rate_per_s"], size=n)
    if process == "onoff":
        gaps = rng.exponential(1.0 / arrivals["burst_rate_per_s"], size=n)
        gaps[::arrivals["burst_size"]] += arrivals["off_s"]
        gaps[0] -= arrivals["off_s"]
        return gaps
    raise ValueError(f"unknown arrival process {process!r}")


def n_requests(params):
    if "n_requests" in params:
        return int(params["n_requests"])
    arr = params["arrivals"]
    rate = arr.get("rate_per_s") or (
        arr["burst_size"]
        / (arr["burst_size"] / arr["burst_rate_per_s"] + arr["off_s"])
    )
    return math.ceil(rate * params["horizon_s"] * 1.25) + 8


def generate(params, seed, vocab_size, seconds):
    """-> list of {"rid", "due_s", "prompt" (int32 array), "max_new"},
    sorted by due time."""
    n = n_requests(params)
    mix = np.random.default_rng(params["mix_seed"])
    gaps = arrival_gaps(mix, params["arrivals"], n)
    prompt_lens = lognormal_lengths(mix, n, **params["prompt_len"])
    output_lens = lognormal_lengths(mix, n, **params["output_len"])
    if params["arrivals"]["process"] != "backlog":
        n = int(np.searchsorted(np.cumsum(gaps), seconds))
        if n == len(gaps):
            raise ValueError(
                f"the mix's horizon_s {params['horizon_s']} does not "
                f"cover a window of {seconds} s"
            )
        gaps, prompt_lens, output_lens = (
            gaps[:n], prompt_lens[:n], output_lens[:n]
        )
    shared = int(params.get("shared_prefix_tokens", 0))
    groups = int(params.get("prefix_groups", 1))
    prefixes = mix.integers(0, vocab_size, size=(groups, shared), dtype=np.int32)

    run = np.random.default_rng(seed)
    due = np.cumsum(gaps[run.permutation(n)])
    prompt_lens = prompt_lens[run.permutation(n)]
    output_lens = output_lens[run.permutation(n)]
    group_of = run.integers(0, groups, size=n)
    requests = []
    for i in range(n):
        own = max(int(prompt_lens[i]) - shared, 1)
        suffix = run.integers(0, vocab_size, size=own, dtype=np.int32)
        requests.append({
            "rid": f"q{i:05d}",
            "due_s": float(due[i]),
            "prompt": np.concatenate([prefixes[group_of[i]], suffix]),
            "max_new": int(output_lens[i]),
        })
    return requests
