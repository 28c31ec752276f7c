"""What PR 37 added to the benchmark: the ``lfm2-24b-a2b`` configuration
file against the catalog's row and the program's configuration, the
counts of ``flops_bytes_conv_moe.py`` against the program's own, the
new cell's files and metrics, and a rehearsal of job kind
``train_arch`` at a toy size (CPU: counts and control flow, never a
time). ``test_manifest.py`` predates the job kind and may not be edited
by the PR that adds a cell of it; this file holds the same rules for
the new files."""
import json

import pytest

from benchmark import harness

CELL, CONFIG = "train-lfm2-24b-a2b-1chip", "lfm2-24b-a2b"
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
    "conv_L_cache",
)
NEW_METRICS = (
    "moe_ms.train", "short_conv_ms.train", "moe_rows_per_expert.train",
    "moe_padded_rows_pct.train", "expert_product_roofline",
    "flash_attn_layers_roofline",
)


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def job():
    return harness.load_module("jobs", "train_arch.py")


@pytest.fixture(scope="module")
def built(manifest, job):
    spec = harness.cell_spec(manifest, CELL)
    cfg, arch = job.build(
        spec["config"], spec["cell"], spec["traffic"]["seq_len"]
    )
    return spec, cfg, arch


def test_the_configuration_file_is_the_catalogs_row(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    body = harness.load_json("configs", f"{CONFIG}.json")
    assert body["source"] == entry["source"]
    assert set(entry["reduced"]) == set(body["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
    }
    for key, value in body["published"].items():
        if key in body["reduced"]:
            assert body[key] == body["counts"]["held"][key] != value
        else:
            assert body[key] == value, key
    assert not set(WIDTHS) & set(body["reduced"])


def test_the_programs_sizes_are_the_published_ones(built):
    spec, cfg, arch = built
    pub = spec["config"]["published"]
    assert (cfg.dim, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], 64,
    )
    assert (cfg.dense_hidden, cfg.expert_hidden, cfg.n_experts) == (
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_experts"],
    )
    assert cfg.experts_per_token == pub["num_experts_per_tok"]
    assert cfg.conv_taps == pub["conv_L_cache"]
    # the run: published layer 0 and one whole period, layers 2-5
    assert list(cfg.layer_types) == [
        pub["layer_types"][i] for i in (0, 2, 3, 4, 5)
    ]
    assert cfg.n_held == 8 and cfg.vocab_size == pub["vocab_size"] // 8


def test_the_counts_are_the_programs(built):
    from tpu_hpc.models import conv_moe

    spec, cfg, arch = built
    fb = harness.load_module("flops_bytes_conv_moe.py")
    counts = conv_moe.count_params(cfg)
    assert fb.n_params(arch) == counts["total"] == \
        spec["config"]["counts"]["parameters_trained"] == 469284992
    assert counts["state"] == 256
    assert 16 * (counts["total"] + counts["state"]) == \
        spec["config"]["counts"]["bytes_at_16_a_parameter"]
    seq = spec["traffic"]["seq_len"]
    per_token = cfg.assignments_per_token * fb.expert_layers(arch)
    assert fb.train_flops_per_token(arch, seq, per_token) == \
        cfg.flops_per_token(seq)
    # a step at the deployment's load, under recomputation
    ops, byts = fb.expert_products_step(arch, 4 * 8 * 2048, True)
    assert ops == 4 * 65536 * 3 * 2 * 2048 * 1536
    assert ops / 197e12 > byts / 819e9      # the operations are the roof


def test_the_new_cell_reports_what_the_issue_lists(manifest):
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "tokens-4x8192", 1
    )
    assert manifest["workloads"][-1] is entry
    names = {
        m["name"] for m in harness.metrics_of(manifest, CELL, "per_layer")
    }
    assert names == set(NEW_METRICS) | {
        "step_ms.train", "head_ms.train", "chunk_host_ms.train",
        "device_idle_pct.train", "idle_unnamed_pct.train",
        "flash_fwd_ms_per_step.train", "flash_bwd_ms_per_step.train",
    }
    assert [m["name"] for m in manifest["per_layer"][-6:]] == \
        list(NEW_METRICS)
    assert {
        m["name"] for m in harness.metrics_of(manifest, CELL, "end_to_end")
    } == {"train_tokens_per_s_chip", "setup_s"}
    cell = harness.load_json("workloads", f"{CELL}.json")
    assert cell["job"] == "train_arch" and cell["mesh"] == {"data": 1}
    for limit in ("loss_abs_tol", "grad_rel_tol", "grad_leaf_rel_tol",
                  "selection_differ_share_max", "selection_eps_sigma",
                  "update_rel_tol", "update_leaf_rel_tol"):
        assert cell["check"][limit] > 0
        assert cell["check"][f"{limit}_why"]


def test_the_job_names_the_new_stages(job):
    from benchmark import program_trace

    for scope in job.ARCH_SCOPES:
        assert scope in program_trace.SCOPES


def _spec():
    layers = ["conv", "full_attention", "conv", "conv"]
    kwargs = {
        "name": "toy", "dim": 64, "n_heads": 4, "n_kv_heads": 2,
        "vocab_size": 96, "norm_eps": 1e-5, "rope_theta": 1e6,
        "layer_types": layers, "conv_taps": 3, "dense_hidden": 160,
        "first_dense_layers": 1, "n_experts": 16, "experts_per_token": 4,
        "expert_hidden": 48, "norm_topk_prob": True,
        "routed_scaling_factor": 1.0, "held_experts": [0, 1, 2, 3],
    }
    real = harness.load_json("configs", f"{CONFIG}.json")
    published = {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1e6}, "conv_L_cache": 3,
        "intermediate_size": 160, "num_experts": 16,
        "num_experts_per_tok": 4, "moe_intermediate_size": 48,
        "norm_topk_prob": True, "routed_scaling_factor": 1.0,
        "max_position_embeddings": 64,
    }
    return {
        "name": "toy-cell", "chips": 1,
        "config": {
            "name": "toy", "published": published,
            "program": {**real["program"], "config_kwargs": kwargs},
            "arch": real["arch"],
            "assumed_sizes": {
                "vocab_size": 96, "first_dense_layers": 1,
                "layer_types": layers, "held_experts": [0, 1, 2, 3],
            },
        },
        "traffic": {"kind": "token_stream", "batch_per_data_shard": 2,
                    "seq_len": 32, "stream_seed": 0},
        "cell": {
            "job": "train_arch", "n_layers": 4, "param_dtype": "float32",
            "compute_dtype": "float32", "remat": True, "mesh": {"data": 1},
            "flash": {"impl": "xla", "block_q": 16, "block_k": 16},
            "optimizer": {"learning_rate": 3e-4, "weight_decay": 0.1,
                          "warmup_steps": 20},
            "steps_per_chunk": 2, "warm_chunks": 1, "trace_chunks": 1,
            "check": {
                "loss_abs_tol": 1e-4, "grad_rel_tol": 1e-3,
                "grad_leaf_rel_tol": 1e-2,
                "selection_differ_share_max": 0.01,
                "selection_eps_sigma": 1e-3,
                "update_rel_tol": 1e-2, "update_leaf_rel_tol": 5e-2,
            },
        },
    }


def test_train_arch_job(tmp_path, job):
    import jax

    obs = job.run({
        "spec": _spec(), "seed": 2**31 + 11, "seconds": 0.2,
        "trace": False, "devices": jax.devices()[:1],
        "out_dir": str(tmp_path), "counter": harness.CompileCounter(),
        "log": lambda msg: None,
    })
    check = obs["checks"]["reference"]
    assert check["ok"], check
    assert check["selections_differ_share"] == 0.0  # float32: every top-k
    assert check["selections"] == 3 * 2 * 32
    stepped = obs["checks"]["trainer_step"]
    assert stepped["ok"] and 0 < stepped["update_rel_err"] < 1e-3, stepped
    moe = obs["train"]["moe"]
    assert moe["train_moe_dropped_total"] == 0
    assert 0 < moe["train_moe_assignments_held_total"] \
        < moe["train_moe_assignments_total"] \
        == 3 * 4 * 2 * 32 * obs["train"]["steps"]
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    obs.update(chips=1, setup_s=1.0, peaks={
        "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
    })
    read = lambda name: harness.load_module(  # noqa: E731
        "layer_metrics", f"{name}.py"
    ).read(obs)
    assert harness.load_module(
        "end_to_end", "train_tokens_per_s_chip.py"
    ).read(obs) > 0
    # 64 tokens x 4 of 16 experts over 4 held: 16 rows an expert if even
    assert 8 < read("moe_rows_per_expert.train") < 32
    assert 0 < read("moe_padded_rows_pct.train") < 100
    # no trace was taken: the trace's readers find nothing and say so
    for name in ("moe_ms.train", "short_conv_ms.train",
                 "expert_product_roofline", "flash_attn_layers_roofline"):
        assert read(name) is None
    json.dumps(obs["checks"])


@pytest.fixture(scope="module")
def toy(job, tmp_path_factory):
    """The toy cell as ``run`` sets it up: what a planted fault is
    read on."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_hpc.parallel import tp
    from tpu_hpc.runtime import MeshSpec, build_mesh

    spec = _spec()
    cfg, arch = job.build(spec["config"], spec["cell"], 32)
    stream = harness.load_module("traffic", "token_stream.py").generate(
        spec["traffic"], 2**31 + 17, cfg.vocab_size
    )
    mesh = build_mesh(MeshSpec(axes={"data": 1}), jax.devices()[:1])
    attn_fn = tp.make_tp_flash_attn_fn(
        mesh, "data", None, impl="xla", block_q=16, block_k=16
    )
    params, state = job.init(
        spec["config"], cfg, 2**31 + 17, NamedSharding(mesh, P())
    )
    return {
        "spec": spec, "cfg": cfg, "arch": arch, "attn_fn": attn_fn,
        "params": params, "state": state, "stream": stream, "mesh": mesh,
        "out_dir": str(tmp_path_factory.mktemp("faults")),
    }


FAULTS = harness.load_module("tests", "train_arch_faults.py")


@pytest.mark.parametrize("fault", ("none",) + FAULTS.PROGRAM_FAULTS)
def test_the_check_reads_a_fault_of_the_program(job, toy, fault):
    t = toy
    out = FAULTS.program_readings(
        job, fault, t["spec"], t["cfg"], t["arch"], t["attn_fn"],
        t["params"], t["state"], t["stream"], t["mesh"],
    )
    assert out["ok"] == (fault == "none"), out


@pytest.mark.parametrize("fault", ("none",) + FAULTS.TRAINER_FAULTS)
def test_the_first_chunk_reads_a_fault_of_the_trainers_step(job, toy, fault):
    t = toy
    out = FAULTS.trainer_readings(
        job, fault, t["spec"], t["cfg"], t["attn_fn"], t["params"],
        t["state"], t["stream"], t["mesh"], t["out_dir"],
    )
    assert out["ok"] == (fault == "none"), out
    if fault == "state_unchanged":
        assert out["update_rel_err"] == pytest.approx(1.0, abs=1e-6)


def test_the_new_readers_say_nothing_of_a_program_without_the_counters():
    """On the parent's program (the dense train job: no expert scopes
    or counters, no ``flops_bytes``) the readers return None and do not
    raise."""
    readers = [
        harness.load_module("layer_metrics", f"{name}.py")
        for name in NEW_METRICS
    ]
    for obs in (
        {"train": {"steps": 8, "remat": True}, "trace": None,
         "arch": {"n_layers": 2}},
        {"train": {"steps": 8, "moe": {}}, "trace": None},
        {},
    ):
        for reader in readers:
            assert reader.read(obs) is None
