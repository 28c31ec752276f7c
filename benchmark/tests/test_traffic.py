"""Traffic is a function of (mix file, seed): byte-identical for one
seed, another order and other tokens for another, the same multiset of
lengths and gaps for every seed."""
import numpy as np
import pytest

from benchmark import harness

BIG = 2**31 + 11  # the driver's seeds pass 32 signed bits


def _mix(name):
    params = harness.load_json("traffic", f"{name}.json")
    return params, harness.load_module("traffic", f"{params['kind']}.py")


def _flat(reqs):
    return [
        (r["rid"], r["due_s"], r["prompt"].tobytes(), r["max_new"])
        for r in reqs
    ]


@pytest.mark.parametrize("name", ["backlog-decode", "chat-poisson"])
def test_open_loop_is_a_function_of_the_seed(name):
    params, gen = _mix(name)
    a, b = gen.generate(params, BIG, 32000, 30), gen.generate(params, BIG, 32000, 30)
    c = gen.generate(params, BIG + 1, 32000, 30)
    assert _flat(a) == _flat(b)
    assert _flat(a) != _flat(c)
    # every seed: the same work in another order
    for key in ("max_new",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in c)
    assert sorted(len(r["prompt"]) for r in a) \
        == sorted(len(r["prompt"]) for r in c)
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    lo, hi = params["prompt_len"]["lo"], params["prompt_len"]["hi"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)
    assert all(0 <= int(r["prompt"].max()) < 32000 for r in a)


def test_a_window_holds_the_same_requests_for_every_seed():
    params, gen = _mix("chat-poisson")
    runs = [gen.generate(params, s, 100, 30) for s in (1, BIG)]
    gaps = [
        np.sort(np.diff([0.0] + [r["due_s"] for r in reqs])) for reqs in runs
    ]
    assert len(runs[0]) == len(runs[1])
    assert np.allclose(gaps[0], gaps[1])
    # all of them due inside the window, whatever the order
    assert all(r["due_s"] < 30 for reqs in runs for r in reqs)
    rate = len(runs[0]) / 30
    assert 0.8 * params["arrivals"]["rate_per_s"] < rate \
        < 1.25 * params["arrivals"]["rate_per_s"]
    # a shorter window is a prefix of the mix, not another mix
    assert len(gen.generate(params, 1, 100, 10)) < len(runs[0])


def test_onoff_and_shared_prefix_need_no_new_code():
    _, gen = _mix("chat-poisson")
    params = {
        "kind": "open_loop", "mix_seed": 3, "horizon_s": 4,
        "arrivals": {"process": "onoff", "burst_size": 8,
                     "burst_rate_per_s": 100.0, "off_s": 0.5},
        "prompt_len": {"median": 64, "sigma": 0.3, "lo": 40, "hi": 128},
        "output_len": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 16},
        "shared_prefix_tokens": 32, "prefix_groups": 2,
    }
    reqs = gen.generate(params, 7, 1000, 2)
    heads = {r["prompt"][:32].tobytes() for r in reqs}
    assert len(heads) == 2
    assert all(len(r["prompt"]) >= 40 for r in reqs)


def test_token_stream_is_a_function_of_the_seed():
    params, gen = _mix("tokens-2x2048")
    a, b = gen.generate(params, BIG, 32000), gen.generate(params, BIG, 32000)
    c = gen.generate(params, BIG + 1, 32000)
    assert a["start_step"] == b["start_step"] != c["start_step"]
    assert a["check_inputs"].tobytes() == b["check_inputs"].tobytes()
    assert a["check_inputs"].tobytes() != c["check_inputs"].tobytes()
    assert a["check_inputs"].shape == (2, 2048)
    assert (a["check_inputs"][:, 1:] == a["check_targets"][:, :-1]).all()
    assert 0 < a["start_step"] < 2**30
