"""The reduction from trace events to numbers, on a trace small enough
to check by hand, and on one recorded on the chip."""
import json
import os

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")

# One device, a 10 ms window. Operations (ms): a while 1-4 spanning a
# matmul fusion 1-3 and a Mosaic call 3-4; an all-gather 5-7 of which
# 6-7 runs beside a copy; then nothing until a slice fusion at 9-9.5.
#   busy = [1,4] + [5,7] + [9,9.5] = 5.5 ms; idle 4.5 ms
#   gaps: 0-1 (tick), 4-5 (decode), 7-9 (no span), 9.5-10 (no span)
def _op(name, opcode, start, dur, tail=""):
    return [f"%{name} = bf16[8,128]{{1,0:T(8,128)(2,1)}} {opcode}("
            f"bf16[8,128]{{1,0}} %custom-call.77, %all-gather.5){tail}",
            start, dur]


HAND = {
    "devices": {"0": {
        "ops": [
            _op("while.7", "while", 0.001, 0.003, ", body=%b"),
            _op("fusion.1", "fusion", 0.001, 0.002, ", kind=kOutput"),
            _op("attention.2", "custom-call", 0.003, 0.001,
                ', custom_call_target="tpu_custom_call"'),
            _op("custom-call.8", "custom-call", 0.0035, 0.0,
                ', custom_call_target="AllocateBuffer"'),
            _op("all-gather-start.3", "all-gather-start", 0.005, 0.002),
            _op("copy.4", "copy", 0.006, 0.001),
            _op("slice_bitcast_fusion.5", "fusion", 0.009, 0.0005,
                ", kind=kLoop"),
            _op("fusion.9", "fusion", 0.020, 0.001),  # outside the window
        ],
        "modules": [
            ["jit_decode(1)", 0.001, 0.003],
            ["jit_decode(1)", 0.005, 0.002],
            ["jit_other(2)", 0.009, 0.0005],
        ],
    }},
    "spans": [
        ["bench:window", 0.0, 0.010],
        ["bench:tick", 0.0, 0.006],
        ["bench:decode", 0.0035, 0.0025],
    ],
}


def test_by_hand():
    out = trace_reduce.reduce(HAND)
    dev = out["devices"]["0"]
    assert out["window_s"] == pytest.approx(0.010)
    assert dev["busy_s"] == pytest.approx(0.0055)
    assert dev["idle_s"] == pytest.approx(0.0045)
    assert out["busy_s"] == pytest.approx(0.0055)
    # the while is a container: its children are counted, it is not
    assert dev["buckets_s"] == pytest.approx({
        "matmul_fusion": 0.002, "custom_call": 0.001, "collective": 0.002,
        "copy": 0.001, "gather_scatter": 0.0005,
    })
    assert dev["bucket_counts"]["custom_call"] == 1
    # the all-gather's second half runs beside the copy: 1 ms exposed
    assert dev["collective_exposed_s"] == pytest.approx(0.001)
    assert dev["modules"]["jit_decode(1)"] == {
        "n": 2, "total_s": pytest.approx(0.005)
    }
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({
        "tick": 0.001, "decode": 0.001, "between_spans": 0.0025,
    })
    assert out["longest_gap_s"] == pytest.approx(0.002)
    assert out["device_ops"][0][0] == "matmul_fusion:fusion x1"
    assert sum(s for _, s in out["device_ops"]) == pytest.approx(0.0065)


def test_an_operation_is_what_its_own_name_says():
    """Not what its operands' names say: every event's text names other
    operations too."""
    assert trace_reduce.parse_op(_op("fusion.3", "fusion", 0, 0, ", kind=kLoop")[0]) \
        == ("fusion", "fusion", "kLoop")
    b = trace_reduce.bucket_of
    assert b(_op("fusion.3", "fusion", 0, 0, ", kind=kOutput")[0]) == "matmul_fusion"
    assert b(_op("add_fusion.3", "fusion", 0, 0, ", kind=kLoop")[0]) == "other_fusion"
    assert b(_op("all-reduce-done.1", "all-reduce-done", 0, 0)[0]) == "collective"
    assert b(_op("copy-start.2", "copy-start", 0, 0)[0]) == "copy"
    assert b(_op("dynamic-update-slice.4", "dynamic-update-slice", 0, 0)[0]) \
        == "gather_scatter"
    assert b(_op("while.1", "while", 0, 0)[0]) is None
    assert b(_op("convert.1", "convert", 0, 0)[0]) == "other"


def test_no_device_operation_is_nothing_to_read():
    assert trace_reduce.reduce({"devices": {}, "spans": []}) is None


@pytest.mark.parametrize("name", ["t1_events.json", "sd_events.json"])
def test_recorded_on_the_chip(name):
    """A fraction of a second of a real trace (TPU v5 lite, PR 23), as
    ``trace_reduce.py --events`` saved it. Its sums are pinned in the
    file's ``expect``; the invariants hold for any trace."""
    path = os.path.join(DATA, name)
    with open(path) as f:
        events = json.load(f)
    out = trace_reduce.reduce(events)
    for dev in out["devices"].values():
        assert dev["busy_s"] + dev["idle_s"] == pytest.approx(out["window_s"])
        # containers apart, operations on the core's line do not
        # overlap: the buckets sum to the busy time
        assert sum(dev["buckets_s"].values()) == pytest.approx(
            dev["busy_s"], rel=1e-3
        )
        assert dev["n_ops"] > 0
    for key, want in events["expect"].items():
        assert out[key] == pytest.approx(want, rel=1e-6)
    (dev,) = out["devices"].values()
    assert dev["buckets_s"] == pytest.approx(events["expect_buckets"])
