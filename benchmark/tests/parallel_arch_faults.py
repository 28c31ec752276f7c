"""``serve-docqa-falcon-h1-34b``'s check with a fault put in the
program on purpose, on the chip:

    python benchmark/tests/parallel_arch_faults.py <seed> <fault>

``<fault>`` is one of

    none        the program as it is
    float8      every projection's operands rounded through
                ``float8_e4m3fn`` (the nearest precision below the
                cell's bf16)
    bf16_state  the recurrent state kept in bfloat16, not float32

Runs job ``serve_hybrid`` as ``benchmark/run.py`` would, with a window
of two seconds (the check is made before the window), and prints the
check's readings, its verdict ``check_ok`` and the prefix hit rate as
one JSON line last. A fault the check is there to catch must read
``check_ok`` false. (``correct`` is left out: it also asks for a request
finished in the window, which two seconds never hold.)
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CELL = "serve-docqa-falcon-h1-34b"


def _float8(module, key=None):
    """Round both operands of ``module._dense`` through float8."""
    import jax.numpy as jnp

    dense = module._dense
    f8 = jnp.float8_e4m3fn

    def rounded(x, w, dtype, *args, **kwargs):
        x = x.astype(f8).astype(dtype)
        if key is None:
            w = w.astype(f8).astype(dtype)
        else:
            w = {key: w[key].astype(f8).astype(dtype)}
        return dense(x, w, dtype, *args, **kwargs)

    module._dense = rounded


def main(seed, fault):
    import jax

    from tpu_hpc.models import hybrid_ssm_moe
    from tpu_hpc.serve import decoder

    spec = harness.cell_spec(harness.load_manifest(), CELL)
    if fault == "float8":
        _float8(decoder)
        _float8(hybrid_ssm_moe, key="kernel")
    elif fault == "bf16_state":
        kwargs = spec["config"]["program"]["config_kwargs"]
        kwargs["ssm_state_dtype"] = "bfloat16"
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")
    job = harness.load_module("jobs", f"{spec['cell']['job']}.py")
    out_dir = os.path.join(harness.BENCH_DIR, "out", f"{CELL}-{fault}")
    os.makedirs(out_dir, exist_ok=True)
    obs = job.run({
        "spec": spec, "seed": seed, "seconds": 2.0, "trace": False,
        "devices": jax.devices()[:1], "out_dir": out_dir,
        "counter": harness.CompileCounter(), "log": harness.log,
    })
    check = obs["checks"]["reference"]
    print(json.dumps({
        "fault": fault, "seed": seed, "check_ok": check["ok"],
        "regret_max_sigma": check["regret_max_sigma"],
        "regret_mean_sigma": check["regret_mean_sigma"],
        "argmax_agree": check["argmax_agree"],
        "prefix_hit_rate": obs["checks"]["prefix_hit_rate"],
        "phases": obs["phases"],
    }), flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), sys.argv[2])
