"""The seven readers of PR 35 on hand-made ``obs`` dictionaries: the
six over the scheduler's ``serve_gap_*`` counters and the ladder
pair's. Arithmetic and the empty cases only; the counters themselves
are the program's (``tests/test_gap_classes.py``)."""
import pytest

from benchmark import harness

SCHEDULER = (
    "chunk_gap_pct.serve", "itl_p95_class.serve",
    "itl_p95_margin_pct.serve", "plain_gap_ms.serve",
    "chunk_gap_extra_ms.serve", "first_fetch_gap_pct.serve",
)
NEW = SCHEDULER + ("decode_rectangle_pct.serve",)
SERVE_CELLS = [
    "serve-chat-mistral7b", "serve-decode-deepseek7b",
    "serve-docqa-keye30b", "serve-docqa-joyai-flash",
    "serve-docqa-granite4h-small",
]


def _read(name, obs):
    return harness.load_module("layer_metrics", f"{name}.py").read(obs)


def _obs(tokens=(0, 0, 0, 0), emissions=(0, 0, 0, 0),
         seconds=(0.0, 0.0, 0.0, 0.0), chunks=0, first_fetch=0, **more):
    stats = {"admitted": 3, "decode_steps": 7, **more}
    for what, values in (
        ("tokens", tokens), ("emissions", emissions), ("seconds", seconds),
    ):
        for c, v in zip(("c0", "c1", "c2", "c3"), values):
            stats[f"serve_gap_{what}_{c}_total"] = v
    stats["serve_gap_chunks_total"] = chunks
    stats["serve_gap_first_fetch_tokens_total"] = first_fetch
    return {"serve": {"stats": stats}}


# The parent's line: a batcher and an engine that count none of this.
PARENT = {"serve": {"stats": {
    "admitted": 3, "decode_steps": 7, "prefill_chunks": 4,
    "serve_decode_view_pages_total": 70,
    "serve_decode_view_pages_read_total": 30,
}}}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize(
    "obs", [PARENT, {}, {"serve": {}}, {"serve": {"stats": {}}}, _obs()],
    ids=["parent", "no_serve", "no_stats", "empty_stats", "all_zero"],
)
def test_nothing_to_read_is_none(name, obs):
    assert _read(name, obs) is None


@pytest.mark.parametrize("tokens, cls, margin, behind", [
    # Exactly 5 % in c1: the mark lies ON the edge of c0.
    ((95, 5, 0, 0), 0, 0.0, 5.0),
    ((9500, 499, 1, 0), 0, 0.0, 5.0),
    # All in c0: nothing near can move it.
    ((1000, 0, 0, 0), 0, 5.0, 0.0),
    # 7 % behind a chunk (the decode cell's reckoning): c1, 2 points in.
    ((930, 70, 0, 0), 1, 2.0, 7.0),
    ((949, 51, 0, 0), 1, 0.1, 5.1),
    ((951, 49, 0, 0), 0, 0.1, 4.9),
    # The chat cell's reckoning: the mark near the edge of c1 and c2.
    ((800, 152, 46, 2), 1, 0.2, 20.0),
    ((800, 148, 50, 2), 2, 0.2, 20.0),
    ((0, 0, 0, 10), 3, 5.0, 100.0),
    ((900, 0, 0, 100), 3, 5.0, 10.0),
])
def test_class_margin_and_share(tokens, cls, margin, behind):
    obs = _obs(tokens=tokens)
    assert _read("itl_p95_class.serve", obs) == cls
    assert _read("itl_p95_margin_pct.serve", obs) == pytest.approx(margin)
    assert _read("chunk_gap_pct.serve", obs) == pytest.approx(behind)


def test_class_agrees_with_the_percentile_of_the_gaps_themselves():
    """Gaps of 20 ms (c0), 34 (c1), 48 (c2): the class the reader names
    is the class of the gap the benchmark's own quantile lands on."""
    from benchmark.stats import quantile

    for tokens in ((940, 50, 10, 0), (960, 30, 10, 0), (900, 45, 55, 0)):
        gaps = [20.0] * tokens[0] + [34.0] * tokens[1] + [48.0] * tokens[2]
        cls = _read("itl_p95_class.serve", _obs(tokens=tokens))
        lo, hi = (20.0, 34.0, 48.0)[cls], (20.0, 34.0, 48.0)[min(cls + 1, 2)]
        assert lo <= quantile(gaps, 0.95) <= hi


def test_gap_costs():
    """100 plain gaps of 20 ms; 10 emissions behind 12 chunks in all,
    each a plain gap and 14 ms a chunk."""
    obs = _obs(
        tokens=(3000, 200, 40, 0), emissions=(100, 8, 2, 0),
        seconds=(2.0, 8 * 0.034, 2 * 0.048, 0.0), chunks=12,
        first_fetch=120,
    )
    assert _read("plain_gap_ms.serve", obs) == pytest.approx(20.0)
    assert _read("chunk_gap_extra_ms.serve", obs) == pytest.approx(14.0)
    assert _read("first_fetch_gap_pct.serve", obs) \
        == pytest.approx(100 * 120 / 3240)


def test_a_window_with_no_plain_gap_or_no_chunk_has_no_cost():
    no_plain = _obs(tokens=(0, 9, 0, 0), emissions=(0, 9, 0, 0),
                    seconds=(0.0, 0.3, 0.0, 0.0), chunks=9)
    assert _read("plain_gap_ms.serve", no_plain) is None
    assert _read("chunk_gap_extra_ms.serve", no_plain) is None
    no_chunk = _obs(tokens=(9, 0, 0, 0), emissions=(9, 0, 0, 0),
                    seconds=(0.18, 0.0, 0.0, 0.0))
    assert _read("plain_gap_ms.serve", no_chunk) == pytest.approx(20.0)
    assert _read("chunk_gap_extra_ms.serve", no_chunk) is None


def test_rectangle_share():
    obs = _obs(serve_decode_ladder_steps_total=400,
               serve_decode_rectangle_steps_total=30)
    assert _read("decode_rectangle_pct.serve", obs) == pytest.approx(7.5)
    obs = _obs(serve_decode_ladder_steps_total=400,
               serve_decode_rectangle_steps_total=0)
    assert _read("decode_rectangle_pct.serve", obs) == 0.0
    # Counted from construction, nothing dispatched yet.
    obs = _obs(serve_decode_ladder_steps_total=0,
               serve_decode_rectangle_steps_total=0)
    assert _read("decode_rectangle_pct.serve", obs) is None


def test_entries_name_accepted_cells_and_stand_last():
    manifest = harness.load_manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    itl = next(
        m for m in manifest["end_to_end"] if m["name"] == "itl_p95_ms"
    )
    entries = manifest["per_layer"][-len(NEW):]
    assert [m["name"] for m in entries] == list(NEW)
    for m in entries:
        assert m["source"] == "program_counter"
        assert m["moves"] == "itl_p95_ms"
        assert set(m["workloads"]) <= set(cells) & set(itl["workloads"])
    for m in entries[:-1]:
        assert m["layer"] == "scheduler"
        assert sorted(m["workloads"]) == sorted(SERVE_CELLS)
    assert entries[-1]["layer"] == "model step"
    assert sorted(entries[-1]["workloads"]) == [
        "serve-chat-mistral7b", "serve-decode-deepseek7b",
    ]


def test_read_metrics_leaves_the_silent_ones_out():
    manifest = harness.load_manifest()
    metrics = [m for m in manifest["per_layer"] if m["name"] in NEW]
    assert harness.read_metrics(metrics, "layer_metrics", PARENT) == {}
    line = harness.read_metrics(metrics, "layer_metrics", _obs(
        tokens=(95, 5, 0, 0), emissions=(90, 5, 0, 0),
        seconds=(1.8, 0.17, 0.0, 0.0), chunks=5,
    ))
    assert set(line) == set(SCHEDULER)
    assert line["itl_p95_class.serve"] == {"value": 0.0, "unit": "class"}
