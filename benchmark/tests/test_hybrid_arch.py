"""What PR 33 added to the benchmark: the ``granite-4.0-h-small``
configuration file against the catalog's row and the program's
configuration, the counts of ``flops_bytes_hybrid_ssm_moe.py`` against
the program's ``param_shapes``, the new cell's files and metrics, the
plain reference against a recurrence written out by hand, and a
rehearsal of job kind ``serve_hybrid`` on a recorded toy configuration
(CPU: counts and control flow, never a time). ``test_manifest.py``
predates the job kind and may not be edited by the PR that adds a cell
of it; this file holds the same rules for the new files."""
import json
import os

import numpy as np
import pytest

from benchmark import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")
CELL, CONFIG = "serve-docqa-granite4h-small", "granite-4.0-h-small"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

# The model-configs catalog's ``config`` for
# https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
# No width may be cut (the contract): these stay as published.
WIDTHS = (
    "hidden_size", "intermediate_size", "shared_intermediate_size",
    "mamba_d_head", "mamba_d_state", "mamba_expand", "mamba_n_heads",
    "mamba_d_conv", "num_experts_per_tok", "num_attention_heads",
    "num_key_value_heads",
)
SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def job():
    return harness.load_module("jobs", "serve_hybrid.py")


@pytest.fixture(scope="module")
def fb():
    return harness.load_module("flops_bytes_hybrid_ssm_moe.py")


@pytest.fixture(scope="module")
def built(manifest, job):
    arch_job = harness.load_module("jobs", "serve_arch.py")
    spec = harness.cell_spec(manifest, CELL)
    cfg, arch = arch_job.build(
        spec["config"], spec["cell"], spec["cell"]["engine"]["capacity"]
    )
    return spec, cfg, arch


def test_the_configuration_file_is_the_catalogs_row(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    body = harness.load_json("configs", f"{CONFIG}.json")
    assert body["source"] == entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert body["reduced"] == entry["reduced"] \
        == ["num_hidden_layers", "num_local_experts"]
    assert body["published"] == CATALOG
    for key, value in CATALOG.items():   # the contract reads the top level
        if key not in body["reduced"]:
            assert body[key] == value, key
    assert (body["num_hidden_layers"], body["num_local_experts"]) == (10, 18)
    assert not set(WIDTHS) & set(body["reduced"])
    assert set(body["reduced_why"]) == set(body["reduced"])
    for key in ("assumed", "deployment"):
        assert body[key], key
    assert set(body["inactive"]) == {"rope_theta", "rope_scaling"}
    assert "18 each" in body["deployment"] \
        and "ids 0-17" in body["deployment"]
    said = " ".join(body["assumed"])
    for word in ("intermediate_size", "time_step_limit", "A_log", "dt_bias",
                 "U(+-1/2)", "float32", "Normal(0.001)", "wq and wk"):
        assert word in said, word


def test_the_programs_sizes_are_the_published_ones(built):
    spec, cfg, arch = built
    cell, config = spec["cell"], spec["config"]
    assert cell["job"] == "serve_hybrid"
    assert cfg.n_layers == arch["n_layers"] == 10 and cfg.name == CONFIG
    # the layers run are the leading period of the published pattern
    assert list(cfg.layer_types) == CATALOG["layer_types"]
    assert list(cfg.layer_types[:10]) == PERIOD
    assert (arch["n_ssm_layers"], arch["n_attention_layers"]) == (9, 1)
    # every size the program's configuration carries is mapped to its
    # published key (``arch``) or is the chip's share
    sized = set(config["program"]["config_kwargs"]) - {
        "name", "held_experts", "layer_types", "norm_topk_prob",
    }
    assert sized == set(config["arch"])
    # the router stays 72 wide and picks 10; 18 experts are held
    assert (arch["n_experts"], arch["experts_per_token"]) == (72, 10)
    assert cfg.held_experts == tuple(range(18)) and arch["n_held"] == 18
    assert (arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"],
            arch["ssm_conv"], arch["ssm_chunk"]) == (128, 64, 128, 4, 256)
    assert (arch["attention_multiplier"], arch["residual_multiplier"],
            arch["embedding_multiplier"], arch["logits_scaling"]) \
        == (1 / 128, 0.22, 12, 16)
    assert arch["position_embedding"] == "nope" and arch["tie_word_embeddings"]
    arch_job = harness.load_module("jobs", "serve_arch.py")
    bad = dict(config)
    bad["program"] = dict(bad["program"], config_kwargs=dict(
        bad["program"]["config_kwargs"], ssm_state=64
    ))
    with pytest.raises(SystemExit, match="ssm_state"):
        arch_job.build(bad, cell, cell["engine"]["capacity"])


def test_the_counts_are_the_programs_param_shapes(built, fb):
    """Parameters by kind of layer against
    ``hybrid_ssm_moe.param_shapes``: 3,264,039,552 = 6.08 GiB in bf16
    (ISSUE 33)."""
    import jax

    from tpu_hpc.models import hybrid_ssm_moe

    _, cfg, arch = built
    leaves = jax.tree.leaves(
        hybrid_ssm_moe.param_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple),
    )
    counts = hybrid_ssm_moe.count_params(cfg)
    assert fb.n_params(arch) == sum(int(np.prod(s)) for s in leaves) \
        == counts["total"] == 3_264_039_552
    assert round(2 * fb.n_params(arch) / 2**30, 2) == 6.08
    assert fb.ssm_params(arch) == counts["ssm_per_layer"] == 102_286_976
    assert fb.attention_params(arch) == counts["attention_per_layer"] \
        == 41_943_040
    assert 18 * fb.expert_params(arch) == counts["experts_per_layer"]


def test_the_cells_bytes_are_the_issues(built, fb):
    """4 KiB a cached token for the whole stage, 30721 pages = 1.88
    GiB; 38.7 MB of recurrent state a slot; weights twice (the engine's
    construction), pool and states fit the chip; the worst request fits
    the capacity."""
    spec, cfg, arch = built
    eng = spec["cell"]["engine"]
    assert (eng["slots"], eng["capacity"], eng["block_size"]) \
        == (16, 30720, 16)
    assert (eng["prefill_chunk"], eng["buckets"], eng["kernel"],
            eng["kv_quant"], eng["prefix_cache"]) \
        == (512, [128, 256, 512], "gather", "none", True)
    assert fb.cache_bytes_per_token(arch) == 4096
    pages = eng["slots"] * eng["capacity"] // eng["block_size"] + 1
    assert pages == 30721
    pool = pages * eng["block_size"] * fb.cache_bytes_per_token(arch)
    assert round(pool / 2**30, 2) == 1.88
    a_slot = 9 * fb.state_bytes_per_slot_layer(arch)
    assert a_slot == cfg.state_bytes() == 9 * (4_194_304 + 101_376)
    assert round(a_slot / 1e6, 1) == 38.7
    states = eng["slots"] * a_slot
    assert (2 * 2 * fb.n_params(arch) + pool + states) / 2**30 < 15.75
    traffic = spec["traffic"]
    assert traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"] \
        <= eng["capacity"]
    assert traffic["shared_prefix_tokens"] % eng["block_size"] == 0
    assert spec["cell"]["check"] == {
        "new_tokens": 32, "pad_to": 29696, "q_block": 256,
    }


def test_the_step_counts_are_floors(built, fb):
    """What a decode step must move: weights outside the routed experts
    and the table once, the touched held experts, the distinct live
    keys and values of ONE layer, the advanced sequences' state read
    and written nine times."""
    _, _, arch = built
    one = fb.expert_params(arch)
    assert one == 9_437_184
    assert fb.moe_layer_bytes(arch, 0) == 2 * 4096 * 72
    assert fb.moe_layer_bytes(arch, 11) - fb.moe_layer_bytes(arch, 10) \
        == 2 * one
    base = fb.decode_step_bytes(arch, 0, 0, 0)
    assert base == 2 * (fb.n_params(arch) - 10 * 18 * one)
    assert fb.decode_step_bytes(arch, 3, 0, 0) - base == 2 * 10 * 3 * one
    assert fb.decode_step_bytes(arch, 0, 1000, 0) - base == 1000 * 4096
    a_layer = fb.state_bytes_per_slot_layer(arch)
    assert fb.decode_step_bytes(arch, 0, 0, 16) - base == 9 * 2 * 16 * a_layer
    assert fb.ssm_layer_bytes(arch, 0) == 2 * fb.ssm_params(arch)
    assert fb.ssm_layer_bytes(arch, 16) - fb.ssm_layer_bytes(arch, 0) \
        == 2 * 16 * a_layer
    stats = {
        "decode_steps": 10, "serve_moe_experts_touched_total": 10 * 10 * 15,
        "serve_kv_pages_live_total": 10 * 15000,
        "serve_ssm_slot_steps_total": 10 * 16,
    }
    assert fb.window_means(stats, 10) == (15, 15000 * 16, 16)
    assert fb.window_means({"decode_steps": 3}, 10) is None


def test_the_new_cell_reports_what_the_issue_lists(manifest):
    e2e = {m["name"] for m in harness.metrics_of(manifest, CELL, "end_to_end")}
    assert e2e == {"itl_p95_ms", "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(manifest, CELL, "per_layer")}
    joyai = {m["name"] for m in harness.metrics_of(
        manifest, "serve-docqa-joyai-flash", "per_layer"
    )}
    new = {"ssm_ms.serve", "ssm_roofline", "ssm_restored_pct.serve"}
    # not ``moe_roofline``: on this cell it read 112 % (PERF.md, PR 33:
    # the compiler prefetches a third of each layer's expert weights
    # under the mixer before it, in copies that carry no scope, so the
    # time under ``router`` + ``experts`` leaves that read out)
    assert layer == (joyai - {"latent_attention_roofline", "moe_roofline"}) \
        | new
    for name in layer:
        module = harness.load_module("layer_metrics", f"{name}.py")
        assert callable(module.read)
    added = [m for m in manifest["per_layer"] if m["name"] in new]
    assert manifest["per_layer"][-3:] == added
    assert [m["workloads"] for m in added] == [[CELL]] * 3
    assert {(m["layer"], m["moves"]) for m in added} \
        == {("model step", "itl_p95_ms")}
    assert {m["name"]: (m["unit"], m["source"]) for m in added} == {
        "ssm_ms.serve": ("ms", "device_trace"),
        "ssm_roofline": ("%", "device_trace"),
        "ssm_restored_pct.serve": ("%", "program_counter"),
    }
    entry = manifest["workloads"][-1]
    assert (entry["name"], entry["config"], entry["traffic"],
            entry["chips"]) == (CELL, CONFIG, "docqa-backlog", 1)
    assert len(entry["why"]) <= 200
    assert manifest["configs"][-1]["name"] == CONFIG


def test_the_job_names_the_state_space_stages(job):
    from benchmark import program_trace

    assert set(SCOPES) <= set(program_trace.SCOPES)
    assert {"indexer", "router", "experts"} <= set(program_trace.SCOPES)
    assert program_trace.scope_of(
        program_trace.path_of("jit(decode)/ssm_scan/mul:")
    ) == "ssm_scan"
    assert callable(job.run)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(
        harness.BENCH_DIR, "reference", "hybrid_ssm_moe_decoder.py"
    )
    source = open(path).read()
    assert "import tpu_hpc" not in source and "from tpu_hpc" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "lax.scan" in source


def _tiny(n_layers=4):
    import jax
    import jax.numpy as jnp

    from tpu_hpc.models import hybrid_ssm_moe

    with open(os.path.join(DATA, "tiny-hybrid-config.json")) as f:
        config = json.load(f)
    cfg = hybrid_ssm_moe.HybridSSMMoEConfig(
        **config["program"]["config_kwargs"], n_layers=n_layers,
        max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = jax.jit(lambda k: hybrid_ssm_moe.init_hybrid_ssm_moe(k, cfg))(
        jax.random.key(1)
    )
    arch = {k: config["published"][v] for k, v in config["arch"].items()}
    arch.update(config["assumed_sizes"], n_layers=n_layers)
    return params, arch


def test_the_reference_is_the_recurrence_written_out_by_hand():
    """Eight tokens through one state-space mixer with numpy loops:
    the convolution tap by tap behind zero rows, the state head by
    head and token by token, the gate inside the norm."""
    from benchmark.reference import hybrid_ssm_moe_decoder as reference

    params, arch = _tiny()
    ssm = {k: (np.asarray(v, np.float64) if not isinstance(v, dict)
               else {n: np.asarray(a, np.float64) for n, a in v.items()})
           for k, v in params["layers_0"]["ssm"].items()}
    rng = np.random.default_rng(0)
    h = rng.standard_normal((8, 64))
    heads, hd, n, taps = 8, 16, 16, 4
    inner = heads * hd
    zxd = h @ ssm["in_proj"]["kernel"]
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * n], \
        zxd[:, 2 * inner + 2 * n:]
    conv = np.zeros_like(xbc)
    for t in range(8):
        acc = ssm["conv"]["bias"].copy()
        for j in range(taps):
            src = t - (taps - 1) + j
            if src >= 0:
                acc += ssm["conv"]["kernel"][j] * xbc[src]
        conv[t] = acc / (1 + np.exp(-acc))
    dt = np.log1p(np.exp(dt + ssm["dt_bias"]))
    a = -np.exp(ssm["A_log"])
    state = np.zeros((heads, hd, n))
    y = np.zeros((8, heads, hd))
    for t in range(8):
        x = conv[t, :inner].reshape(heads, hd)
        b, c = conv[t, inner:inner + n], conv[t, inner + n:]
        for i in range(heads):
            state[i] = np.exp(dt[t, i] * a[i]) * state[i] \
                + dt[t, i] * np.outer(x[i], b)
            y[t, i] = state[i] @ c + ssm["D"][i] * x[i]
    g = y.reshape(8, inner) * (z / (1 + np.exp(-z)))
    g = g / np.sqrt((g * g).mean(-1, keepdims=True) + arch["norm_eps"]) \
        * ssm["norm"]["scale"]
    want = g @ ssm["out_proj"]["kernel"]
    import jax.numpy as jnp

    got = reference.ssm_mixer(
        jnp.asarray(h, jnp.float32), params["layers_0"]["ssm"], arch
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("q_block", [8, 16, 64])
def test_the_reference_agrees_with_itself_in_blocks(q_block):
    import jax.numpy as jnp

    from benchmark.reference import hybrid_ssm_moe_decoder as reference

    params, arch = _tiny()
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, 64))
    whole, probes = reference.forward(params, tokens, arch, q_block=64)
    blocks, _ = reference.forward(params, tokens, arch, q_block=q_block)
    assert probes is None and float(jnp.abs(whole).max()) > 0
    np.testing.assert_allclose(blocks, whole, atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="multiple"):
        reference.forward(params, tokens[:60], arch, q_block=16)
    assert reference.held_ids(arch) == [0, 1, 2, 3, 4]


def _spec(**traffic_extra):
    with open(os.path.join(DATA, "tiny-hybrid-config.json")) as f:
        config = json.load(f)
    traffic = {
        "kind": "open_loop", "mix_seed": 5,
        "arrivals": {"process": "backlog"}, "n_requests": 48,
        "prompt_len": {"median": 44, "sigma": 0.1, "lo": 36, "hi": 56},
        "output_len": {"median": 6, "sigma": 0.3, "lo": 4, "hi": 8},
    }
    traffic.update(traffic_extra)
    return {
        "name": "tiny-serve-hybrid", "chips": 1, "config": config,
        "traffic": traffic,
        "cell": {
            "job": "serve_hybrid", "n_layers": 4, "param_dtype": "float32",
            "compute_dtype": "float32", "mesh": {"data": 1},
            "engine": {"slots": 4, "capacity": 64, "block_size": 8,
                       "prefill_chunk": 16, "buckets": [8, 16]},
            "check": {"new_tokens": 6, "pad_to": 64, "q_block": 16},
            "trace_seconds": 0.2,
        },
    }


@pytest.mark.parametrize("shared", [48, 0])
def test_serve_hybrid_job(tmp_path, job, shared):
    import jax

    # 48 of 49-50 prompt tokens shared: a hit rate over the job's 0.95
    extra = {"shared_prefix_tokens": shared, "prefix_groups": 2,
             "prompt_len": {"median": 49, "sigma": 0.01, "lo": 49,
                            "hi": 50}} if shared else {}
    obs = job.run({
        "spec": _spec(**extra), "seed": 2**31 + 11, "seconds": 1.0,
        "trace": False, "devices": jax.devices()[:1],
        "out_dir": str(tmp_path), "counter": harness.CompileCounter(),
        "log": lambda msg: None,
    })
    check = obs["checks"]["reference"]
    assert check["ok"], check
    assert check["regret_max_sigma"] == 0.0     # float32: every arg-max
    assert obs["checks"]["engine_recompiles"] == 0
    assert obs["checks"]["moe_dropped"] == 0
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    stats = obs["serve"]["stats"]
    assert 0 < stats["serve_ssm_slot_steps_total"] \
        <= 4 * stats["decode_steps"]
    assert stats["serve_kv_pages_live_total"] > 0
    assert 0 < stats["serve_moe_assignments_held_total"] \
        < stats["serve_moe_assignments_total"]
    restored = harness.load_module(
        "layer_metrics", "ssm_restored_pct.serve.py"
    ).read(obs)
    if shared:
        assert check["prefix_hit_blocks"] == shared // 8
        assert obs["checks"]["prefix_hit_rate"] >= 0.95
        assert stats["serve_ssm_restores_total"] > 0
        assert restored >= 95.0
    else:
        assert restored == 0.0
    obs.update(chips=1, setup_s=1.0, peaks={
        "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
    })
    assert harness.load_module("end_to_end", "itl_p95_ms.py").read(obs) > 0
    # no trace was taken: the trace's readers find nothing and say so
    for name in ("ssm_ms.serve", "ssm_roofline", "moe_roofline",
                 "decode_roofline.sparse_moe"):
        assert harness.load_module(
            "layer_metrics", f"{name}.py"
        ).read(obs) is None


def test_the_new_readers_say_nothing_of_a_program_without_the_counters():
    """On the parent's program (no state-space scopes or counters,
    another configuration's ``flops_bytes``) the readers return None
    and do not raise."""
    readers = [
        harness.load_module("layer_metrics", f"{name}.py")
        for name in ("ssm_ms.serve", "ssm_roofline", "ssm_restored_pct.serve")
    ]
    for obs in (
        {"serve": {"stats": {"decode_steps": 4}, "requests": []},
         "trace": None},
        {"serve": {"stats": {"serve_moe_assignments_total": 9}},
         "flops_bytes": "flops_bytes_latent_moe", "trace": None,
         "arch": {"n_layers": 4}},
        {},
    ):
        for reader in readers:
            assert reader.read(obs) is None
