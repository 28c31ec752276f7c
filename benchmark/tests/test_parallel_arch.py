"""The ``falcon-h1-34b-instruct`` configuration and the cell
``serve-docqa-falcon-h1-34b``: the configuration file against the
catalog's row and the program's configuration, the counts of
``flops_bytes_parallel_hybrid.py`` against the program's, the cell's
files and metrics, the plain reference's grouped mixer against one
written out by hand, and a rehearsal of job kind ``serve_hybrid`` on a
recorded toy configuration of the same kind (CPU: counts and control
flow, never a time). ``test_manifest.py`` predates the job kind; this
file holds the same rules for the new files."""
import json
import os

import numpy as np
import pytest

from benchmark import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")
CELL, CONFIG = "serve-docqa-falcon-h1-34b", "falcon-h1-34b-instruct"
# The share of the step's floor this cell reports: the reader the sparse
# and hybrid cells share, fed this configuration's ``flops_bytes``.
ROOF = "decode_roofline.sparse_moe"
# No width may be cut (the contract): these stay as published.
WIDTHS = (
    "hidden_size", "intermediate_size", "head_dim", "mamba_d_head",
    "mamba_d_ssm", "mamba_d_state", "mamba_expand", "mamba_n_heads",
    "mamba_n_groups", "mamba_d_conv", "num_attention_heads",
    "num_key_value_heads",
)


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def fb():
    return harness.load_module("flops_bytes_parallel_hybrid.py")


@pytest.fixture(scope="module")
def built(manifest):
    arch_job = harness.load_module("jobs", "serve_arch.py")
    spec = harness.cell_spec(manifest, CELL)
    cfg, arch = arch_job.build(
        spec["config"], spec["cell"], spec["cell"]["engine"]["capacity"]
    )
    return spec, cfg, arch


def test_the_configuration_file_is_the_catalogs_row(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    body = harness.load_json("configs", f"{CONFIG}.json")
    assert body["source"] == entry["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
        "config.json"
    )
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert body["reduced"] == entry["reduced"] \
        == ["num_hidden_layers", "vocab_size"]
    pub = body["published"]
    assert pub["model_type"] == "falcon_h1" and pub["num_hidden_layers"] == 72
    for key, value in pub.items():      # the contract reads the top level
        if key not in body["reduced"]:
            assert body[key] == value, key
    assert (body["num_hidden_layers"], body["vocab_size"]) == (4, 65280)
    assert not set(WIDTHS) & set(body["reduced"])
    assert set(body["reduced_why"]) == set(body["reduced"])
    said = " ".join(body["assumed"])
    for word in ("rotate-half", "time_step_limit", "A_log", "dt_bias",
                 "U(+-1/2)", "float32", "key_multiplier", "divided by"):
        assert word in said, word
    assert "layers 0-3" in body["deployment"] \
        and "rows 0-65279" in body["deployment"]


def test_the_programs_sizes_are_the_published_ones(built):
    spec, cfg, arch = built
    cell, config = spec["cell"], spec["config"]
    assert cell["job"] == "serve_hybrid"
    assert cfg.n_layers == arch["n_layers"] == 4 and cfg.name == CONFIG
    assert set(cfg.layer_types[:4]) == {"parallel"}
    assert (arch["n_ssm_layers"], arch["n_attention_layers"]) == (4, 4)
    assert (cfg.head_dim, cfg.dim // cfg.n_heads) == (128, 256)
    pub = config["published"]
    segments = [arch[f"ssm_{s}_multiplier"] for s in ("z", "x", "b", "c", "dt")]
    assert segments == pub["ssm_multipliers"]
    assert [arch["mlp_gate_multiplier"], arch["mlp_down_multiplier"]] \
        == pub["mlp_multipliers"]
    assert arch["lm_head_multiplier"] == pub["lm_head_multiplier"]
    assert cfg.vocab_size == arch["vocab_size"] == 65280
    arch_job = harness.load_module("jobs", "serve_arch.py")
    bad = dict(config)
    bad["program"] = dict(bad["program"], config_kwargs=dict(
        bad["program"]["config_kwargs"], ssm_out_multiplier=0.09
    ))
    with pytest.raises(SystemExit, match="ssm_out_multiplier"):
        arch_job.build(bad, cell, cell["engine"]["capacity"])


def test_the_counts_are_the_programs(built, fb):
    """4 x 430,120,032 + 2 x 65,280 x 5,120 + 5,120 = 2,388,952,448 =
    4.45 GiB in bf16."""
    from tpu_hpc.models import hybrid_ssm_moe

    _, cfg, arch = built
    counts = hybrid_ssm_moe.count_params(cfg)
    assert fb.n_params(arch) == counts["total"] == 2_388_952_448
    assert round(2 * fb.n_params(arch) / 2**30, 2) == 4.45
    assert fb.attention_params(arch) == counts["attention_per_layer"] \
        == 31_457_280
    assert fb.ssm_params(arch) == counts["ssm_per_layer"] == 68_351_072
    assert fb.ffn_params(arch) == counts["feed_forward_per_layer"] \
        == 330_301_440


def test_the_cells_bytes_are_the_issues(built, fb):
    """8 KiB a cached token for the stage, 30721 pages = 3.75 GiB; 17.0
    MB of recurrent state a slot; weights twice (the engine's
    construction), pool, states and snapshots fit the chip; the worst
    request fits the capacity."""
    spec, cfg, arch = built
    eng = spec["cell"]["engine"]
    assert (eng["slots"], eng["capacity"], eng["block_size"],
            eng["prefill_chunk"], eng["buckets"], eng["kernel"],
            eng["kv_quant"], eng["prefix_cache"]) \
        == (16, 30720, 16, 512, [128, 256, 512], "gather", "none", True)
    assert fb.cache_bytes_per_token(arch) == 8192
    pages = eng["slots"] * eng["capacity"] // eng["block_size"] + 1
    pool = pages * eng["block_size"] * fb.cache_bytes_per_token(arch)
    assert pages == 30721 and round(pool / 2**30, 2) == 3.75
    a_slot = 4 * fb.state_bytes_per_slot_layer(arch)
    assert a_slot == cfg.state_bytes() == 4 * (4_194_304 + 61_440)
    assert round(a_slot / 1e6, 1) == 17.0
    states = 2 * eng["slots"] * a_slot           # and as many snapshots
    assert (2 * 2 * fb.n_params(arch) + pool + states) / 2**30 < 15.75
    traffic = spec["traffic"]
    assert traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"] \
        <= eng["capacity"]
    assert spec["cell"]["check"] == {
        "new_tokens": 32, "pad_to": 29696, "q_block": 256,
    }


def test_the_step_counts_are_floors(built, fb):
    """Every weight but the table once, the distinct live keys and
    values of four layers, the advanced sequences' state read and
    written four times."""
    _, _, arch = built
    base = fb.decode_step_bytes(arch, 0, 0, 0)
    assert base == 2 * (fb.n_params(arch) - 5120 * 65280)
    assert fb.decode_step_bytes(arch, 0, 1000, 0) - base == 1000 * 8192
    a_layer = fb.state_bytes_per_slot_layer(arch)
    assert fb.decode_step_bytes(arch, 0, 0, 16) - base == 4 * 2 * 16 * a_layer
    assert fb.ssm_layer_bytes(arch, 16) - fb.ssm_layer_bytes(arch, 0) \
        == 2 * 16 * a_layer
    stats = {
        "decode_steps": 10, "serve_kv_pages_live_total": 10 * 15000,
        "serve_ssm_slot_steps_total": 10 * 16,
    }
    assert fb.window_means(stats, 4) == (0.0, 15000 * 16, 16)
    assert fb.window_means({"decode_steps": 3}, 4) is None


def _named(entries, name):
    return next(e for e in entries if e["name"] == name)


def test_the_new_cell_reports_what_the_issue_lists(manifest):
    e2e = {m["name"] for m in harness.metrics_of(manifest, CELL, "end_to_end")}
    assert e2e == {"itl_p95_ms", "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(manifest, CELL, "per_layer")}
    granite = {m["name"] for m in harness.metrics_of(
        manifest, "serve-docqa-granite4h-small", "per_layer"
    )}
    experts = {"moe_ms.serve", "moe_held_assignment_pct.serve",
               "moe_experts_read_pct.serve"}
    assert layer == granite - experts and ROOF in layer
    for name in layer:
        assert callable(harness.load_module(
            "layer_metrics", f"{name}.py"
        ).read)
    roof = _named(manifest["per_layer"], ROOF)
    assert (roof["source"], roof["moves"], roof["workloads"][-1]) \
        == ("device_trace", "itl_p95_ms", CELL)
    entry = _named(manifest["workloads"], CELL)
    assert (entry["name"], entry["config"], entry["traffic"],
            entry["chips"]) == (CELL, CONFIG, "docqa-backlog", 1)
    assert len(entry["why"]) <= 200
    assert _named(manifest["configs"], CONFIG)["file"] \
        == f"benchmark/configs/{CONFIG}.json"


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(
        harness.BENCH_DIR, "reference", "parallel_hybrid_decoder.py"
    )
    source = open(path).read()
    assert "import tpu_hpc" not in source and "from tpu_hpc" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "lax.scan" in source
    # the published base is an int past int32, as config.json has it
    import jax.numpy as jnp

    from benchmark.reference import parallel_hybrid_decoder as reference

    x = jnp.ones((3, 2, 8), jnp.float32)
    out = reference.rotate_half(x, jnp.arange(3), 100000000000)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(out[0], x[0])


def _tiny():
    import jax
    import jax.numpy as jnp

    from tpu_hpc.models import hybrid_ssm_moe

    with open(os.path.join(DATA, "tiny-parallel-config.json")) as f:
        config = json.load(f)
    cfg = hybrid_ssm_moe.HybridSSMMoEConfig(
        **config["program"]["config_kwargs"], n_layers=3,
        max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = jax.jit(lambda k: hybrid_ssm_moe.init_hybrid_ssm_moe(k, cfg))(
        jax.random.key(1)
    )
    arch = {k: config["published"][v] for k, v in config["arch"].items()}
    arch.update(config["assumed_sizes"], n_layers=3)
    return params, arch


def test_the_grouped_mixer_is_the_recurrence_written_out_by_hand():
    """Eight tokens through one mixer of two groups with numpy loops:
    the multipliers segment by segment, the convolution tap by tap
    behind zero rows, head i reading group i // 4's B and C, the gate
    inside a norm taken over each group's half."""
    from benchmark.reference import parallel_hybrid_decoder as reference

    params, arch = _tiny()
    ssm = {k: (np.asarray(v, np.float64) if not isinstance(v, dict)
               else {n: np.asarray(a, np.float64) for n, a in v.items()})
           for k, v in params["layers_0"]["ssm"].items()}
    h = np.random.default_rng(0).standard_normal((8, 64))
    heads, hd, n, groups, taps = 8, 12, 16, 2, 4
    inner, gn = heads * hd, groups * n
    mult = np.concatenate([
        np.full(w, arch["ssm_in_multiplier"] * arch[f"ssm_{s}_multiplier"])
        for w, s in ((inner, "z"), (inner, "x"), (gn, "b"), (gn, "c"),
                     (heads, "dt"))
    ])
    zxd = (h @ ssm["in_proj"]["kernel"]) * mult
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * gn], \
        zxd[:, 2 * inner + 2 * gn:]
    conv = np.zeros_like(xbc)
    for t in range(8):
        acc = ssm["conv"]["bias"].copy()
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                acc += ssm["conv"]["kernel"][j] * xbc[t - (taps - 1) + j]
        conv[t] = acc / (1 + np.exp(-acc))
    dt = np.log1p(np.exp(dt + ssm["dt_bias"]))
    a = -np.exp(ssm["A_log"])
    state = np.zeros((heads, hd, n))
    y = np.zeros((8, heads, hd))
    for t in range(8):
        x = conv[t, :inner].reshape(heads, hd)
        b = conv[t, inner:inner + gn].reshape(groups, n)
        c = conv[t, inner + gn:].reshape(groups, n)
        for i in range(heads):
            g = i // (heads // groups)
            state[i] = np.exp(dt[t, i] * a[i]) * state[i] \
                + dt[t, i] * np.outer(x[i], b[g])
            y[t, i] = state[i] @ c[g] + ssm["D"][i] * x[i]
    u = (y.reshape(8, inner) * (z / (1 + np.exp(-z)))).reshape(8, groups, -1)
    u = u / np.sqrt((u * u).mean(-1, keepdims=True) + arch["norm_eps"])
    want = arch["ssm_out_multiplier"] * (
        (u.reshape(8, inner) * ssm["norm"]["scale"]) @ ssm["out_proj"]["kernel"]
    )
    import jax.numpy as jnp

    got = reference.ssm_mixer(
        jnp.asarray(h, jnp.float32), params["layers_0"]["ssm"], arch
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _spec(**traffic_extra):
    with open(os.path.join(DATA, "tiny-parallel-config.json")) as f:
        config = json.load(f)
    traffic = {
        "kind": "open_loop", "mix_seed": 5,
        "arrivals": {"process": "backlog"}, "n_requests": 48,
        "prompt_len": {"median": 44, "sigma": 0.1, "lo": 36, "hi": 56},
        "output_len": {"median": 6, "sigma": 0.3, "lo": 4, "hi": 8},
    }
    traffic.update(traffic_extra)
    return {
        "name": "tiny-serve-parallel", "chips": 1, "config": config,
        "traffic": traffic,
        "cell": {
            "job": "serve_hybrid", "n_layers": 3, "param_dtype": "float32",
            "compute_dtype": "float32", "mesh": {"data": 1},
            "engine": {"slots": 4, "capacity": 64, "block_size": 8,
                       "prefill_chunk": 16, "buckets": [8, 16]},
            "check": {"new_tokens": 6, "pad_to": 64, "q_block": 16},
            "trace_seconds": 0.2,
        },
    }


@pytest.mark.parametrize("shared", [48, 0])
def test_serve_hybrid_job_on_parallel_layers(tmp_path, shared):
    import jax

    job = harness.load_module("jobs", "serve_hybrid.py")
    extra = {"shared_prefix_tokens": shared, "prefix_groups": 2,
             "prompt_len": {"median": 49, "sigma": 0.01, "lo": 49,
                            "hi": 50}} if shared else {}
    obs = job.run({
        "spec": _spec(**extra), "seed": 2**31 + 13, "seconds": 1.0,
        "trace": False, "devices": jax.devices()[:1],
        "out_dir": str(tmp_path), "counter": harness.CompileCounter(),
        "log": lambda msg: None,
    })
    check = obs["checks"]["reference"]
    assert check["ok"], check
    assert check["regret_mean_sigma"] < 1e-4     # float32 on both sides
    assert obs["checks"]["engine_recompiles"] == 0
    assert obs["checks"]["moe_dropped"] == 0
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    stats = obs["serve"]["stats"]
    assert not any(k.startswith("serve_moe") for k in stats)
    assert 0 < stats["serve_ssm_slot_steps_total"] \
        <= 4 * stats["decode_steps"]
    assert stats["serve_kv_pages_live_total"] > 0
    restored = harness.load_module(
        "layer_metrics", "ssm_restored_pct.serve.py"
    ).read(obs)
    if shared:
        assert obs["checks"]["prefix_hit_rate"] >= 0.95
        assert restored >= 95.0
    else:
        assert restored == 0.0
    obs.update(chips=1, setup_s=1.0, peaks={
        "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
    })
    assert harness.load_module("end_to_end", "itl_p95_ms.py").read(obs) > 0
    # no trace was taken: the trace's readers find nothing and say so
    for name in ("ssm_ms.serve", "ssm_roofline", ROOF):
        assert harness.load_module(
            "layer_metrics", f"{name}.py"
        ).read(obs) is None
    # with a trace of decode runs the share reads this configuration's
    # floor over their mean time
    obs["trace"] = {"devices": {"0": {"modules": {
        "jit_decode": {"total_s": 2.0, "n": 4},
    }}}}
    fb = harness.load_module("flops_bytes_parallel_hybrid.py")
    want = 100.0 * fb.decode_step_bytes(
        obs["arch"], *fb.window_means(stats, 3)
    ) / 819e9 / 0.5
    got = harness.load_module("layer_metrics", f"{ROOF}.py").read(obs)
    assert got == pytest.approx(want)


def test_the_share_says_nothing_without_the_counters():
    """On a program that counts no live pages or advanced sequences (the
    parent's), the shared reader finds no means and returns None."""
    reader = harness.load_module("layer_metrics", f"{ROOF}.py")
    trace = {"devices": {"0": {"modules": {"jit_decode": {
        "total_s": 1.0, "n": 2}}}}}
    for stats in ({"decode_steps": 4},
                  {"decode_steps": 4, "serve_kv_pages_live_total": 40},
                  {"decode_steps": 0, "serve_kv_pages_live_total": 0,
                   "serve_ssm_slot_steps_total": 0}):
        assert reader.read({
            "serve": {"stats": stats}, "trace": trace,
            "flops_bytes": "flops_bytes_parallel_hybrid",
            "arch": {"n_layers": 4},
        }) is None
