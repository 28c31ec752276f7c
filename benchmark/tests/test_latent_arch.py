"""What PR 31 added to the benchmark: the ``joyai-llm-flash``
configuration file against the catalog's row and the program's
configuration, the counts of ``flops_bytes_latent_moe.py`` against the
program's ``param_shapes``, the new cell's files and metrics, the plain
reference against itself in blocks, and a rehearsal of job kind
``serve_arch`` on a recorded toy latent configuration (CPU: counts and
control flow, never a time). ``test_manifest.py`` predates the job
kind and may not be edited by the PR that adds a cell of it; this file
holds the same rules for the new files."""
import json
import os

import numpy as np
import pytest

from benchmark import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")
CELL, CONFIG = "serve-docqa-joyai-flash", "joyai-llm-flash"

# The model-configs catalog's ``config`` for
# https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
# No width may be cut (the contract): these stay as published.
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "kv_lora_rank", "q_lora_rank", "qk_head_dim", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "head_dim", "num_experts_per_tok",
)


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def job():
    return harness.load_module("jobs", "serve_arch.py")


@pytest.fixture(scope="module")
def fb():
    return harness.load_module("flops_bytes_latent_moe.py")


@pytest.fixture(scope="module")
def built(manifest, job):
    spec = harness.cell_spec(manifest, CELL)
    cfg, arch = job.build(
        spec["config"], spec["cell"], spec["cell"]["engine"]["capacity"]
    )
    return spec, cfg, arch


def test_the_configuration_file_is_the_catalogs_row(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    body = harness.load_json("configs", f"{CONFIG}.json")
    assert body["source"] == entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert body["reduced"] == entry["reduced"] \
        == ["num_hidden_layers", "n_routed_experts"]
    assert body["published"] == CATALOG
    for key, value in CATALOG.items():   # the contract reads the top level
        if key not in body["reduced"]:
            assert body[key] == value, key
    assert (body["num_hidden_layers"], body["n_routed_experts"]) == (7, 64)
    assert not set(WIDTHS) & set(body["reduced"])
    assert set(body["reduced_why"]) == set(body["reduced"])
    for key in ("assumed", "deployment"):
        assert body[key], key
    assert set(body["inactive"]) == {
        "num_nextn_predict_layers", "head_dim", "num_key_value_heads",
    }
    assert "64 each" in body["deployment"] and "ids 0-63" in body["deployment"]


def test_the_programs_sizes_are_the_published_ones(built, job):
    spec, cfg, arch = built
    cell, config = spec["cell"], spec["config"]
    assert cell["job"] == "serve_arch" and cfg.n_layers == arch["n_layers"] == 7
    assert cfg.name == CONFIG
    # every size the program's configuration class carries is mapped to
    # its published key (``arch``) or is the chip's share (``n_held``)
    sized = {
        "dim", "n_heads", "vocab_size", "norm_eps", "rope_theta",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "dense_hidden",
        "first_dense_layers", "n_experts", "experts_per_token",
        "expert_hidden", "n_shared_experts", "norm_topk_prob",
        "routed_scaling_factor",
    }
    assert sized <= set(config["arch"])
    assert sized | {"name", "held_experts"} \
        == set(config["program"]["config_kwargs"])
    # the router stays 256 wide and picks 8; 64 experts are held
    assert (arch["n_experts"], arch["experts_per_token"]) == (256, 8)
    assert cfg.held_experts == tuple(range(64)) and arch["n_held"] == 64
    assert config["program"]["config_kwargs"]["held_experts"] \
        == list(range(arch["n_held"]))      # what the reference assumes
    assert (arch["q_lora_rank"], arch["kv_lora_rank"], arch["qk_head_dim"],
            arch["v_head_dim"]) == (1536, 512, 192, 128)
    bad = dict(config)
    bad["program"] = dict(bad["program"], config_kwargs=dict(
        bad["program"]["config_kwargs"], kv_lora_rank=256
    ))
    with pytest.raises(SystemExit, match="kv_lora_rank"):
        job.build(bad, cell, cell["engine"]["capacity"])


def test_the_counts_are_the_programs_param_shapes(built, fb):
    """Parameters by kind of layer against ``latent_moe.param_shapes``:
    2.601B = 4.85 GiB in bf16 (ISSUE 31)."""
    import jax

    from tpu_hpc.models import latent_moe

    _, cfg, arch = built
    leaves = jax.tree.leaves(
        latent_moe.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple)
    )
    assert fb.n_params(arch) == sum(int(np.prod(s)) for s in leaves) \
        == latent_moe.count_params(cfg)["total"]
    assert round(fb.n_params(arch) / 1e9, 3) == 2.601
    assert round(2 * fb.n_params(arch) / 2**30, 2) == 4.85
    assert fb.attention_params(arch) == 26_347_520
    assert fb.dense_layer_params(arch) \
        == latent_moe.count_params(cfg)["dense_layer"]
    assert fb.expert_layer_params_outside_routed(arch) \
        + 64 * fb.expert_params(arch) \
        == latent_moe.count_params(cfg)["expert_layer"]
    assert (fb.n_dense_layers(arch), fb.n_expert_layers(arch)) == (1, 6)


def test_the_cells_bytes_are_the_issues(built, fb):
    """1152 B a cached token a layer, 8064 B a token, 30721 pages =
    3.69 GiB; weights twice (the engine's construction) and the pool
    fit the chip; the worst request fits the capacity."""
    spec, cfg, arch = built
    eng = spec["cell"]["engine"]
    assert (eng["slots"], eng["capacity"], eng["block_size"]) \
        == (16, 30720, 16)
    assert fb.latent_dim(arch) == cfg.latent_dim == 576
    assert fb.cache_bytes_per_token(arch) == 7 * 1152 == 8064
    pages = eng["slots"] * eng["capacity"] // eng["block_size"] + 1
    assert pages == 30721
    pool = pages * eng["block_size"] * fb.cache_bytes_per_token(arch)
    assert round(pool / 2**30, 2) == 3.69
    assert (2 * 2 * fb.n_params(arch) + pool) / 2**30 < 15.75
    traffic = spec["traffic"]
    assert traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"] \
        <= eng["capacity"]
    assert traffic["shared_prefix_tokens"] % eng["block_size"] == 0
    assert spec["cell"]["check"] == {
        "new_tokens": 32, "pad_to": 29696, "q_block": 256,
    }


def test_the_step_counts_are_floors(built, fb):
    """What a decode step must move: weights outside the routed experts
    but the embedding table, the touched held experts, the distinct
    live latent rows, once each."""
    _, _, arch = built
    one = fb.expert_params(arch)
    assert one == 4_718_592
    # readers multiply by n_layers: six routers, no seventh
    assert 7 * fb.moe_layer_bytes(arch, 0) \
        == pytest.approx(2 * 6 * (2048 * 256 + 256))
    assert fb.moe_layer_bytes(arch, 21) - fb.moe_layer_bytes(arch, 20) \
        == 2 * one
    base = fb.decode_step_bytes(arch, 0, 0)
    assert base == 2 * (
        fb.dense_layer_params(arch)
        + 6 * fb.expert_layer_params_outside_routed(arch)
        + 2048 + 2048 * 129280
    )
    assert fb.decode_step_bytes(arch, 3, 0) - base == 2 * 7 * 3 * one
    assert fb.decode_step_bytes(arch, 0, 1000) - base == 7 * 1000 * 1152
    assert fb.latent_read_bytes(arch, 1000) == 1000 * 1152
    assert fb.latent_read_flops(arch, 1000) \
        == 1000 * 32 * (576 + 512) * 2
    stats = {
        "decode_steps": 10, "serve_moe_experts_touched_total": 10 * 6 * 25,
        "serve_latent_pages_live_total": 10 * 15000,
    }
    touched, tokens = fb.window_means(stats, 7)
    assert touched == pytest.approx(6 * 25 / 7) and tokens == 15000 * 16
    assert fb.window_means({"decode_steps": 3}, 7) is None
    # a floor below the memory roof: bytes bind the absorbed read
    assert fb.latent_read_bytes(arch, 1) / 819e9 \
        > fb.latent_read_flops(arch, 1) / 197e12


def test_the_new_cell_reports_what_the_issue_lists(manifest):
    e2e = {m["name"] for m in harness.metrics_of(manifest, CELL, "end_to_end")}
    # not ``decode_tokens_per_s``: in Keye's cell of the same mix it
    # spread by 1.2 % where a new cell needs under 1 % (PERF.md PR 27)
    assert e2e == {"itl_p95_ms", "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(manifest, CELL, "per_layer")}
    assert layer == {
        "host_ms_per_tick.serve", "prefill_chunk_ms", "decode_step_ms",
        "device_idle_pct.serve", "kv_write_ms", "kv_read_ms",
        "head_ms.serve", "unscoped_pct.serve", "decode_prep_ms",
        "sched_ms_per_tick", "idle_unnamed_pct.serve", "moe_ms.serve",
        "attention_ms.serve", "moe_roofline", "decode_roofline.sparse_moe",
        "view_pages_read_pct.serve", "latent_attention_roofline",
        "moe_held_assignment_pct.serve",
    }
    for name in layer:
        module = harness.load_module("layer_metrics", f"{name}.py")
        assert callable(module.read)
    new = [m for m in manifest["per_layer"] if m["name"] in (
        "latent_attention_roofline", "moe_held_assignment_pct.serve"
    )]
    assert [m["workloads"] for m in new] == [[CELL], [CELL]]
    assert {(m["layer"], m["moves"]) for m in new} \
        == {("model step", "itl_p95_ms")}
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "docqa-backlog", 1)
    assert len(entry["why"]) <= 200


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(
        harness.BENCH_DIR, "reference", "latent_moe_decoder.py"
    )
    source = open(path).read()
    assert "import tpu_hpc" not in source and "from tpu_hpc" not in source
    assert 'default_matmul_precision("highest")' in source


def _tiny():
    import jax
    import jax.numpy as jnp

    from tpu_hpc.models import latent_moe

    with open(os.path.join(DATA, "tiny-latent-config.json")) as f:
        config = json.load(f)
    cfg = latent_moe.LatentMoEConfig(
        **config["program"]["config_kwargs"], n_layers=3, max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = jax.jit(lambda k: latent_moe.init_latent_moe(k, cfg))(
        jax.random.key(1)
    )
    arch = {k: config["published"][v] for k, v in config["arch"].items()}
    arch.update(config["assumed_sizes"], n_layers=3)
    return params, arch


@pytest.mark.parametrize("q_block", [8, 16, 64])
def test_the_reference_agrees_with_itself_in_blocks(q_block):
    """Attention over ``q_block`` query rows at a time is the one pass
    over all of them (64 = the whole sequence), to float32 rounding."""
    import jax.numpy as jnp

    from benchmark.reference import latent_moe_decoder as reference

    params, arch = _tiny()
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, 64))
    whole, probes = reference.forward(params, tokens, arch, q_block=64)
    blocks, _ = reference.forward(params, tokens, arch, q_block=q_block)
    assert probes is None and float(jnp.abs(whole).max()) > 0
    np.testing.assert_allclose(blocks, whole, atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="multiple"):
        reference.forward(params, tokens[:60], arch, q_block=16)


def test_the_reference_takes_the_leading_held_ids():
    """``n_held`` 6 without ids is experts 0-5, as the cell's
    configuration states its share; other ids give another result."""
    import jax.numpy as jnp

    from benchmark.reference import latent_moe_decoder as reference

    params, arch = _tiny()
    assert reference.held_ids(arch) == [0, 1, 2, 3, 4, 5]
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, 16))
    leading, _ = reference.forward(params, tokens, arch, q_block=16)
    named, _ = reference.forward(
        params, tokens, dict(arch, held_experts=(0, 1, 2, 3, 4, 5)),
        q_block=16,
    )
    other, _ = reference.forward(
        params, tokens, dict(arch, held_experts=(0, 1, 2, 3, 4, 9)),
        q_block=16,
    )
    np.testing.assert_array_equal(leading, named)
    assert float(jnp.abs(other - leading).max()) > 1e-4


def _spec(**traffic_extra):
    with open(os.path.join(DATA, "tiny-latent-config.json")) as f:
        config = json.load(f)
    traffic = {
        "kind": "open_loop", "mix_seed": 5,
        "arrivals": {"process": "backlog"}, "n_requests": 48,
        "prompt_len": {"median": 44, "sigma": 0.1, "lo": 36, "hi": 56},
        "output_len": {"median": 6, "sigma": 0.3, "lo": 4, "hi": 8},
    }
    traffic.update(traffic_extra)
    return {
        "name": "tiny-serve-latent", "chips": 1, "config": config,
        "traffic": traffic,
        "cell": {
            "job": "serve_arch", "n_layers": 3, "param_dtype": "float32",
            "compute_dtype": "float32", "mesh": {"data": 1},
            "engine": {"slots": 4, "capacity": 64, "block_size": 8,
                       "prefill_chunk": 16, "buckets": [8, 16]},
            "check": {"new_tokens": 6, "pad_to": 64, "q_block": 16},
            "trace_seconds": 0.2,
        },
    }


@pytest.mark.parametrize("shared", [48, 0])
def test_serve_arch_job(tmp_path, job, shared):
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
    assert check["selection_rows"] == 0         # no indexer to probe
    assert obs["checks"]["engine_recompiles"] == 0
    assert obs["checks"]["moe_dropped"] == 0
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    if shared:
        assert check["prefix_hit_blocks"] == shared // 8
        assert obs["checks"]["prefix_hit_rate"] >= 0.95
    stats = obs["serve"]["stats"]
    steps = stats["decode_steps"]
    assert 0 < steps <= len(obs["serve"]["calls"]["decode"])
    assert 0 < stats["serve_moe_assignments_held_total"] \
        < stats["serve_moe_assignments_total"]
    assert stats["serve_latent_pages_live_total"] > 0
    assert obs["serve"]["pool_bytes"] == (4 * 64 // 8 + 1) * 8 * 3 * 40 * 4
    obs.update(chips=1, setup_s=1.0, peaks={
        "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
    })
    assert harness.load_module("end_to_end", "itl_p95_ms.py").read(obs) > 0
    held = harness.load_module(
        "layer_metrics", "moe_held_assignment_pct.serve.py"
    ).read(obs)
    assert 0 < held < 100
    assert harness.load_module(
        "layer_metrics", "view_pages_read_pct.serve.py"
    ).read(obs) <= 100
    # no trace was taken: the trace's readers find nothing and say so
    for name in ("latent_attention_roofline", "moe_ms.serve",
                 "attention_ms.serve", "moe_roofline",
                 "decode_roofline.sparse_moe"):
        assert harness.load_module(
            "layer_metrics", f"{name}.py"
        ).read(obs) is None


def test_the_new_readers_say_nothing_of_a_program_without_the_counters():
    """On the parent's program (no latent counters, another
    configuration's ``flops_bytes``) the readers return None and do
    not raise."""
    roof = harness.load_module("layer_metrics", "latent_attention_roofline.py")
    held = harness.load_module(
        "layer_metrics", "moe_held_assignment_pct.serve.py"
    )
    for obs in (
        {"serve": {"stats": {"decode_steps": 4}}, "trace": None},
        {"serve": {"stats": {"serve_moe_assignments_total": 9}},
         "flops_bytes": "flops_bytes_sparse_moe", "trace": None,
         "arch": {"n_layers": 4}},
        {},
    ):
        assert roof.read(obs) is None and held.read(obs) is None
