"""BENCHMARK.json and every file it names, held to the contract's
limits and to each other."""
import json
import os
import re

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_top_level(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert manifest["paths"] == ["benchmark"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(
        os.path.join(harness.ROOT, "BENCHMARK.json")
    ) <= 64 * 1024


def test_names_units_and_lines(manifest):
    names = []
    for group, keys in (
        ("configs", {"name", "source", "file", "reduced", "why"}),
        ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ):
        for entry in manifest[group]:
            assert set(entry) == keys, entry
            assert NAME.match(entry["name"]), entry["name"]
            assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
        assert len({e["name"] for e in manifest[group]}) == len(manifest[group])
    for group, keys in (
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        for m in manifest[group]:
            assert set(m) - {"workloads"} == keys, m
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            names.append(m["name"])
    assert len(set(names)) == len(names)
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_cells_configs_and_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert w["config"] in configs
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        spec = harness.cell_spec(manifest, w["name"])
        cell = spec["cell"]
        assert cell["job"] in ("train", "serve")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "jobs", f"{cell['job']}.py"
        ))
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "traffic", f"{spec['traffic']['kind']}.py"
        ))
        n_mesh = 1
        for extent in cell["mesh"].values():
            n_mesh *= extent
        assert n_mesh == w["chips"]
    assert used == set(configs)
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"] and len(c["source"]) <= 200
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(
                r"hidden_size|intermediate|_dim$|_rank$|head_dim|latent|state",
                key,
            ), f"{key}: a width may not be reduced"
        # no width differs from the source: the LlamaConfig the program
        # takes reproduces every published size
        pub, kw = body["published"], body["llama_config"]
        assert kw["dim"] == pub["hidden_size"]
        assert kw["n_heads"] == pub["num_attention_heads"]
        assert (kw["n_kv_heads"] or kw["n_heads"]) == pub["num_key_value_heads"]
        assert kw["vocab_size"] == pub["vocab_size"]
        assert kw["norm_eps"] == pub["rms_norm_eps"]
        assert body["asserted"]["ffn_hidden"] == pub["intermediate_size"]


def test_every_cell_reports_what_its_metrics_move(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        mine = harness.metrics_of(manifest, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in mine}
        assert len(mine) >= 2, w["name"]
        layer = harness.metrics_of(manifest, w["name"], "per_layer")
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in e2e and m["moves"] != "setup_s"
            assert m["moves"] in {x["name"] for x in mine}, (w["name"], m)
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_every_metric_has_its_reader(manifest):
    for group, directory in (
        ("end_to_end", "end_to_end"), ("per_layer", "layer_metrics"),
    ):
        for m in manifest[group]:
            module = harness.load_module(directory, f"{m['name']}.py")
            assert callable(module.read), m["name"]
    for m in manifest["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_published_sizes_reach_the_program(manifest):
    for w in manifest["workloads"]:
        spec = harness.cell_spec(manifest, w["name"])
        cell = spec["cell"]
        seq = cell["engine"]["capacity"] if cell["job"] == "serve" \
            else spec["traffic"]["seq_len"]
        cfg, arch = harness.llama_config(spec["config"], cell, seq)
        assert cfg.ffn_hidden == spec["config"]["published"]["intermediate_size"]
        assert cfg.n_layers == cell["n_layers"]
        assert cfg.n_layers <= spec["config"]["published"]["num_hidden_layers"]
