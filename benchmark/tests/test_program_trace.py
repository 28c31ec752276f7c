"""The reduction of a trace by the program's own names: on a trace
small enough to check by hand, on the raw protobuf's wire format, and
on slices of a decode window and a train chunk recorded on the chip
(PERF.md PR 25) with one test a reader."""
import json
import os

import pytest

from benchmark import harness, program_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")


def _op(name, opcode, operands, start, dur, op_name=None, tail=""):
    args = ", ".join(f"bf16[8,128]{{1,0}} %{o}" for o in operands)
    return [
        f"%{name} = bf16[8,128]{{1,0:T(8,128)(2,1)}} {opcode}({args}){tail}",
        start, dur, op_name,
    ]


# One device, a 20 ms window that holds two runs of ``jit_decode`` of
# 5 ms each (1-6, 11-16). Each run (times in ms from its start):
#   copy.1      0.0-1.0  no op_name; its one consumer is the kv_write
#                        fusion                     -> kv_write, inherited
#   fusion.2    1.0-2.0  jit(decode)/kv_write/scatter        -> kv_write
#   fusion.3    2.0-3.5  .../kv_read/gather                   -> kv_read
#   fusion.4    3.5-4.0  .../attention/attn_out/dot_general   -> attn_out
#   fusion.5    4.0-4.5  .../head/dot_general                 -> head
#   copy.6      4.5-4.9  no op_name; producer head, consumer none -> head
#   add.7       4.9-5.0  no op_name, not a move               -> unscoped
# Per run: kv_write 2.0 (1.0 inherited), kv_read 1.5, attn_out 0.5,
# head 0.9 (0.4 inherited), unscoped 0.1 of 5.0 = 2 %.
# Host spans (ms): tick 0.5-10.5 {tick.admit 0.6-0.8, decode 1.0-9.0
# {decode.prep 1.0-1.4, decode.dispatch 1.4-1.6, decode.fetch 1.6-9.0},
# tick.emit 9.0-10.0}; tick 10.6-19.6 with the same children 10 later
# but for tick.admit.
#   own time of a tick: 10 - 0.2 - 8 = 1.8 and 9 - 8 = 1.0 -> 1.4
#   idle gaps: 0-1, 6-11, 16-20 ms (4.9-5.0 of a run is busy); leaf
#   spans cover 0.6-0.8 (tick.admit), 1.0-10.0 and 11.1-20.1 (prep,
#   dispatch, fetch, tick.emit abut), so 0.8 of the first gap, 10-11 of
#   the second and none of the third lie in no leaf: 1.8 of 10 ms of
#   idle is unnamed = 18 %
def _run(t0):
    ms = 1e-3
    return [
        _op("copy.1", "copy", ["ks.1"], t0, 1.0 * ms, "ks:"),
        _op("fusion.2", "fusion", ["copy.1"], t0 + 1.0 * ms, 1.0 * ms,
            "jit(decode)/kv_write/scatter:", ", kind=kLoop"),
        _op("fusion.3", "fusion", ["fusion.2"], t0 + 2.0 * ms, 1.5 * ms,
            "jit(decode)/kv_read/gather:", ", kind=kLoop"),
        _op("fusion.4", "fusion", ["fusion.3"], t0 + 3.5 * ms, 0.5 * ms,
            "jit(decode)/attention/attn_out/dot_general:", ", kind=kOutput"),
        _op("fusion.5", "fusion", ["fusion.4"], t0 + 4.0 * ms, 0.5 * ms,
            "jit(decode)/head/dot_general:", ", kind=kOutput"),
        _op("copy.6", "copy", ["fusion.5"], t0 + 4.5 * ms, 0.4 * ms),
        _op("add.7", "add", ["copy.6"], t0 + 4.9 * ms, 0.1 * ms),
    ]


def _tick(t0, admit=True):
    ms, p = 1e-3, program_trace.PREFIX
    end = 10.0 if admit else 9.0
    spans = [
        [p + "tick", t0, end * ms],
        [p + "decode", t0 + 0.5 * ms, 8.0 * ms],
        [p + "decode.prep", t0 + 0.5 * ms, 0.4 * ms],
        [p + "decode.dispatch", t0 + 0.9 * ms, 0.2 * ms],
        [p + "decode.fetch", t0 + 1.1 * ms, 7.4 * ms],
        [p + "tick.emit", t0 + 8.5 * ms, 1.0 * ms],
    ]
    if admit:
        spans.append([p + "tick.admit", t0 + 0.1 * ms, 0.2 * ms])
    return spans


HAND = {
    "devices": {"0": {
        "ops": _run(0.001) + _run(0.011),
        "modules": [
            ["jit_decode(7)", 0.001, 0.005], ["jit_decode(7)", 0.011, 0.005],
        ],
    }},
    "spans": sorted(
        [["bench:window", 0.0, 0.020]] + _tick(0.0005)
        + _tick(0.0106, admit=False),
        key=lambda s: s[1],
    ),
}


@pytest.fixture
def traced(monkeypatch):
    """Readers see ``events`` as if this run had traced them."""
    def install(events, steps=None):
        monkeypatch.setattr(program_trace, "find_trace", lambda: "hand")
        monkeypatch.setitem(
            program_trace._CACHE, "hand", program_trace.reduce(events)
        )
        return {"trace": {"steps": steps}}
    return install


def _read(metric, obs):
    return harness.load_module("layer_metrics", f"{metric}.py").read(obs)


def test_hand_trace_by_scope(traced):
    obs = traced(HAND)
    prog = program_trace.load(obs)["devices"]["0"]["programs"]["decode"]
    assert prog["n"] == 2 and prog["total_s"] == pytest.approx(0.010)
    assert prog["inherited_s"] == pytest.approx(
        {"kv_write": 0.002, "head": 0.0008}
    )
    assert prog["unscoped_ops"] == pytest.approx({"add": 0.0002})
    assert _read("kv_write_ms", obs) == pytest.approx(2.0)
    assert _read("kv_read_ms", obs) == pytest.approx(1.5)
    assert _read("head_ms.serve", obs) == pytest.approx(0.9)
    assert _read("unscoped_pct.serve", obs) == pytest.approx(2.0)


def test_hand_trace_by_span(traced):
    obs = traced(HAND)
    assert _read("decode_prep_ms", obs) == pytest.approx(0.4)
    assert _read("sched_ms_per_tick", obs) == pytest.approx(1.4)
    assert _read("idle_unnamed_pct.serve", obs) == pytest.approx(18.0)
    leaves = program_trace.load(obs)["leaf_spans"]
    # tick and decode hold children and are no leaves; the nine
    # leaves cover tick.admit (0.2 ms) and, a tick, prep + dispatch +
    # fetch + tick.emit (9 ms), which abut.
    assert sum(b - a for a, b in leaves) == pytest.approx(0.0182)
    assert leaves[0] == pytest.approx([0.0006, 0.0008])
    assert leaves[-1][1] == pytest.approx(0.0201)


def test_a_trace_without_the_programs_names_reads_nothing(traced):
    bare = {
        "devices": {"0": {
            "ops": [[o[0], o[1], o[2], None] for o in HAND["devices"]["0"]["ops"]],
            "modules": HAND["devices"]["0"]["modules"],
        }},
        "spans": [["bench:window", 0.0, 0.020]],
    }
    obs = traced(bare, steps=8)
    for metric in (
        "kv_write_ms", "kv_read_ms", "head_ms.serve", "unscoped_pct.serve",
        "decode_prep_ms", "sched_ms_per_tick", "idle_unnamed_pct.serve",
        "idle_unnamed_pct.train", "flash_fwd_ms_per_step.train",
        "flash_bwd_ms_per_step.train", "head_ms.train",
        "chunk_host_ms.train",
    ):
        assert _read(metric, obs) is None, metric


def test_no_trace_means_no_look_at_the_disk(monkeypatch):
    def boom():
        raise AssertionError("looked for a trace in a --trace 0 run")
    monkeypatch.setattr(program_trace, "find_trace", boom)
    for obs in ({"trace": None}, {"trace": {}}, {}):
        assert program_trace.load(obs) is None
        assert _read("kv_write_ms", obs) is None
        assert _read("chunk_host_ms.train", obs) is None


def test_op_name_paths():
    parts = program_trace.path_of(
        "jit(epoch_fn)/while/body/transpose(jvp(Llama))/layers_3/"
        "attention/qkv/wq/dot_general:"
    )
    assert parts[3:] == [
        "Llama", "layers_3", "attention", "qkv", "wq", "dot_general",
    ]
    assert program_trace.scope_of(parts) == "qkv"
    flash = program_trace.path_of(
        "jit(f)/transpose(attention)/jvp(flash_bwd_dq)/pallas_call"
    )
    assert "flash_bwd_dq" in flash
    assert program_trace.scope_of(flash) == "attention"
    assert program_trace.scope_of(program_trace.path_of("ks:")) is None
    assert program_trace.scope_of(program_trace.path_of(None)) is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    """One length-delimited field (bytes) or one varint field (int)."""
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_event_op_names_from_the_wire(tmp_path):
    """An XSpace written by hand to xplane.proto's field numbers: one
    device plane whose two event metadata carry ``tf_op`` once as a
    string and once as a reference to a stat name, and a host plane
    that is skipped."""
    def stat_meta(sid, name):
        return _field(5, _field(1, sid) + _field(
            2, _field(1, sid) + _field(2, name.encode())
        ))

    def event_meta(eid, name, stats):
        body = _field(1, eid) + _field(2, name.encode()) + b"".join(
            _field(5, st) for st in stats
        )
        return _field(4, _field(1, eid) + _field(2, body))

    device = (
        _field(2, b"/device:TPU:0")
        + stat_meta(1, "tf_op") + stat_meta(2, "flops")
        + stat_meta(3, "jit(decode)/kv_read/gather:")
        + event_meta(10, "%fusion.2 = x fusion()", [
            _field(1, 2) + _field(4, 99),
            _field(1, 1) + _field(5, b"jit(decode)/kv_write/scatter:"),
        ])
        + event_meta(11, "%fusion.3 = x fusion()", [
            _field(1, 1) + _field(7, 3),
        ])
        + event_meta(12, "%copy.1 = x copy()", [])
        # lines (field 3) are skipped whole, whatever they hold
        + _field(3, b"\x0a\x03abc")
    )
    host = _field(2, b"/host:CPU") + event_meta(1, "python", [])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert program_trace.event_op_names(str(path)) == {
        "/device:TPU:0": {
            "%fusion.2 = x fusion()": "jit(decode)/kv_write/scatter:",
            "%fusion.3 = x fusion()": "jit(decode)/kv_read/gather:",
        },
    }


# -- recorded on the chip ------------------------------------------------
def _recorded(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _own_scope_ms(events, program, scope, runs):
    """Independent of the reduction: operations of ``program``'s runs
    whose own op_name path names ``scope`` last."""
    dev = events["devices"]["0"]
    spans = [
        (s, s + d) for n, s, d in dev["modules"] if f"jit_{program}(" in n
    ]
    total = 0.0
    for text, start, dur, op_name in dev["ops"]:
        if " while(" in text or " conditional(" in text:
            continue
        if not any(a <= start < b for a, b in spans):
            continue
        if program_trace.scope_of(program_trace.path_of(op_name)) == scope:
            total += dur
    return 1e3 * total / runs


RECORDED = json.load(open(os.path.join(DATA, "pr25_expected.json"))) \
    if os.path.exists(os.path.join(DATA, "pr25_expected.json")) else {}


@pytest.mark.parametrize("metric", [
    "kv_write_ms", "kv_read_ms", "head_ms.serve", "unscoped_pct.serve",
    "decode_prep_ms", "sched_ms_per_tick", "idle_unnamed_pct.serve",
])
def test_recorded_decode_window(traced, metric):
    """Two ticks of a traced decode window of the decode cell; the
    expected values stand in ``pr25_expected.json`` beside the
    recording, with the runs and spans they were counted over."""
    events = _recorded("pr25_decode_events.json")
    want = RECORDED["decode"]
    obs = traced(events)
    assert _read(metric, obs) == pytest.approx(want[metric], rel=1e-6)
    if metric in ("kv_write_ms", "kv_read_ms", "head_ms.serve"):
        scope = {"head_ms.serve": "head"}.get(metric, metric[:-3])
        own = _own_scope_ms(events, "decode", scope, want["decode_runs"])
        # the reader adds what the compiler's copies inherit
        assert own <= _read(metric, obs) + 1e-9
        assert own == pytest.approx(want["own_ms"][scope], rel=1e-6)


@pytest.mark.parametrize("metric", [
    "flash_fwd_ms_per_step.train", "flash_bwd_ms_per_step.train",
    "head_ms.train", "chunk_host_ms.train", "idle_unnamed_pct.train",
])
def test_recorded_train_chunk(traced, metric):
    """The end of one traced chunk and the start of the next of the
    one-chip train cell (``chunk.fetch`` returning, ``chunk.host``,
    ``chunk.dispatch``); per-step readers divide by the steps the
    slice is counted as in ``pr25_expected.json``."""
    events = _recorded("pr25_train_events.json")
    want = RECORDED["train"]
    obs = traced(events, steps=want["steps"])
    assert _read(metric, obs) == pytest.approx(want[metric], rel=1e-6)


def test_recorded_flash_split_adds_up(traced):
    """Forward + backward by kernel name is all the Mosaic time the
    accepted ``flash_ms_per_step.train`` sums by custom-call target."""
    from benchmark import trace_reduce

    events = _recorded("pr25_train_events.json")
    obs = traced(events, steps=RECORDED["train"]["steps"])
    plain = {
        "devices": {k: {
            "ops": [o[:3] for o in dev["ops"]], "modules": dev["modules"],
        } for k, dev in events["devices"].items()},
        "spans": [s for s in events["spans"] if s[0].startswith("bench:")],
    }
    reduced = trace_reduce.reduce(plain)
    total = 1e3 * reduced["devices"]["0"]["buckets_s"]["custom_call"] \
        / RECORDED["train"]["steps"]
    assert _read("flash_fwd_ms_per_step.train", obs) \
        + _read("flash_bwd_ms_per_step.train", obs) \
        == pytest.approx(total, rel=1e-9)
