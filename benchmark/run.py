"""One cell, one run, one process:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to start without a TPU (``runtime.require_accelerator``; no CPU
fallback, and ``TPU_HPC_SIM_DEVICES`` is refused by name), keeps the
compile cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else in the
checkout's ``.jax_cache/``, builds the cell from the files
``BENCHMARK.json`` names (``harness.py``), lets the cell's job kind
warm up, check correctness against the plain reference and measure for
``--seconds``, then prints one JSON object as the LAST line of stdout:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
with ``--trace 1``, ``breakdown``. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces a few seconds of the same
window with the profiler and reports its per-layer metrics. Everything
else goes on earlier lines and to ``benchmark/out/<cell>/``.
"""
import time

T_START, T_START_PERF = time.time(), time.perf_counter()

import argparse  # noqa: E402
import importlib.metadata  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    log = harness.log

    if os.environ.get("TPU_HPC_SIM_DEVICES"):
        raise SystemExit(
            "benchmark: refusing to start with TPU_HPC_SIM_DEVICES set "
            "(it forces the CPU platform): the benchmark runs on the chip"
        )
    manifest = harness.load_manifest()
    spec = harness.cell_spec(manifest, args.workload)

    import jax

    from tpu_hpc.runtime import compile_cache_dir, require_accelerator

    dev = require_accelerator()  # exits non-zero unless this is a TPU
    if jax.device_count() < spec["chips"]:
        raise SystemExit(
            f"benchmark: {args.workload} needs {spec['chips']} chips, "
            f"JAX found {jax.device_count()}"
        )
    devices = jax.devices()[:spec["chips"]]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
    }
    counter = harness.CompileCounter()
    versions = {
        pkg: importlib.metadata.version(pkg)
        for pkg in ("jax", "jaxlib", "libtpu")
    }
    out_dir = os.path.join(harness.BENCH_DIR, "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    log(f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace} | {device} | {versions} | compile cache "
        f"{compile_cache_dir()}")

    job = harness.load_module("jobs", f"{spec['cell']['job']}.py")
    obs = job.run({
        "spec": spec, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "devices": devices, "out_dir": out_dir,
        "counter": counter, "log": log,
    })
    obs.update(
        chips=spec["chips"], peaks=harness.peaks_for(dev.device_kind),
        setup_s=obs["t_window"] - T_START_PERF,
    )
    memory = harness.memory_by_device(devices)
    device["memory_peak_bytes"] = max(memory["peak_bytes_in_use"])

    # Every reader runs once; the line carries the group this run is
    # for. In a trace run the end-to-end readings are information only
    # (the profiler is on): they go to the details, never to the result.
    all_metrics = {
        group: harness.read_metrics(
            harness.metrics_of(manifest, args.workload, group), directory, obs
        )
        for group, directory in (
            ("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")
        )
    }
    result = {
        "correct": obs["correct"],
        "attempted": obs["attempted"],
        "failed": obs["failed"],
        "metrics": all_metrics["per_layer" if args.trace else "end_to_end"],
    }
    trace = obs.get("trace")
    if args.trace:
        if not trace or trace["busy_s"] <= 0:
            raise SystemExit(
                "benchmark: the traced window holds no device operation"
            )
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        }
    result["device"] = device

    details = {
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace,
        "started": T_START, "versions": versions,
        "compile": counter.summary(), "memory": memory,
        "mesh": obs["mesh"], "phases": obs["phases"],
        "setup_s": obs["setup_s"], "window_s": obs["window_s"],
        "checks": obs["checks"],
        "trace": trace and {
            k: v for k, v in trace.items() if k != "device_ops"
        },
        "all_metrics": all_metrics,
        "result": result,
    }
    log("details " + json.dumps(details))
    with open(os.path.join(out_dir, "last_run.json"), "w") as f:
        json.dump(details, f, indent=1)
    with open(os.path.join(out_dir, "runs.jsonl"), "a") as f:
        f.write(json.dumps(details) + "\n")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
