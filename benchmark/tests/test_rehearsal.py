"""The jobs end to end at a toy size on the CPU (Pallas interpreted):
wrong paths, arguments and control flow show here, before the chip.
Nothing timed here is a result."""
import json
import os

import pytest

from benchmark import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")


def _ctx(spec, tmp_path, seconds=0.5, trace=False, chips=1, seed=2**31 + 7):
    import jax

    return {
        "spec": spec, "seed": seed, "seconds": seconds, "trace": trace,
        "devices": jax.devices()[:chips], "out_dir": str(tmp_path),
        "counter": harness.CompileCounter(), "log": lambda msg: None,
    }


def _config():
    with open(os.path.join(DATA, "tiny-config.json")) as f:
        return json.load(f)


def _train_spec(mesh):
    return {
        "name": "tiny-train", "chips": 1, "config": _config(),
        "traffic": {"kind": "token_stream", "batch_per_data_shard": 2,
                    "seq_len": 128, "stream_seed": 0},
        "cell": {
            "job": "train", "n_layers": 2, "param_dtype": "float32",
            "compute_dtype": "bfloat16", "remat": True, "mesh": mesh,
            "flash": {"impl": "pallas_interpret", "block_q": 64, "block_k": 64},
            "optimizer": {"learning_rate": 3e-4, "weight_decay": 0.1},
            "steps_per_chunk": 2, "warm_chunks": 2, "trace_chunks": 1,
            "check_grads": True,
        },
    }


def _serve_spec(arrivals, **extra):
    traffic = {
        "kind": "open_loop", "mix_seed": 5, "arrivals": arrivals,
        "prompt_len": {"median": 24, "sigma": 0.5, "lo": 8, "hi": 48},
        "output_len": {"median": 6, "sigma": 0.3, "lo": 4, "hi": 8},
    }
    traffic.update(extra)
    return {
        "name": "tiny-serve", "chips": 1, "config": _config(),
        "traffic": traffic,
        "cell": {
            "job": "serve", "n_layers": 2, "param_dtype": "bfloat16",
            "compute_dtype": "bfloat16", "mesh": {"data": 1},
            "engine": {"slots": 4, "capacity": 64, "block_size": 8,
                       "prefill_chunk": 16, "buckets": [8, 16]},
            "check": {"requests": 2, "new_tokens": 4, "pad_to": 64},
            "trace_seconds": 0.2,
        },
    }


@pytest.mark.parametrize("mesh,chips", [
    ({"data": 1}, 1), ({"data": 1, "model": 2}, 2),
])
def test_train_job(tmp_path, mesh, chips):
    job = harness.load_module("jobs", "train.py")
    obs = job.run(_ctx(_train_spec(mesh), tmp_path, chips=chips))
    assert obs["checks"]["reference"]["ok"], obs["checks"]
    assert obs["checks"]["compiles_in_window"] == 0
    assert obs["correct"] and obs["failed"] == 0
    assert obs["attempted"] == sum(c["steps"] for c in obs["train"]["chunks"])
    obs.update(chips=chips, setup_s=1.0)
    for name in ("train_tokens_per_s_chip", "setup_s"):
        assert harness.load_module("end_to_end", f"{name}.py").read(obs) > 0
    assert harness.load_module(
        "layer_metrics", "step_ms.train.py"
    ).read(obs) > 0
    # no trace was taken: the trace's readers find nothing and say so
    assert harness.load_module(
        "layer_metrics", "flash_roofline.py"
    ).read(obs) is None


@pytest.mark.parametrize("arrivals", [
    {"process": "backlog"},
    {"process": "poisson", "rate_per_s": 40.0},
])
def test_serve_job(tmp_path, arrivals):
    extra = {"n_requests": 64} if arrivals["process"] == "backlog" \
        else {"horizon_s": 2}
    job = harness.load_module("jobs", "serve.py")
    obs = job.run(_ctx(_serve_spec(arrivals, **extra), tmp_path, seconds=1.0))
    assert obs["checks"]["reference"]["ok"], obs["checks"]
    assert obs["checks"]["engine_recompiles"] == 0
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    obs.update(chips=1, setup_s=1.0)
    for name in ("itl_p95_ms", "ttft_p95_ms", "decode_tokens_per_s"):
        assert harness.load_module("end_to_end", f"{name}.py").read(obs) > 0
    for name in ("gen_late_p95_ms", "queue_wait_p95_ms", "decode_step_ms",
                 "prefill_chunk_ms", "host_ms_per_tick.serve"):
        value = harness.load_module("layer_metrics", f"{name}.py").read(obs)
        assert value is not None and value >= 0
    # every finished request has its whole answer
    assert all(
        len(r["token_times"]) == r["max_new"]
        for r in obs["serve"]["requests"] if r["done"] is not None
    )
