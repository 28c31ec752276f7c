"""What every job shares: the manifest and the files it names, the
model configuration as the program takes it, weights made on the device
from the seed, compile counting, the device's memory, and the loading
of metric readers by name.

Everything that belongs to one cell, configuration, traffic mix or
metric is a file of its own, found by the name ``BENCHMARK.json`` gives
it, so a later PR adds files and entries and edits nothing here:

    configs/<config>.json        the published sizes and their mapping
    workloads/<cell>.json        job kind, depth, dtype, mesh, engine
    traffic/<traffic>.json       a mix's parameters; its ``kind`` names
    traffic/<kind>.py            the one general generator that reads it
    jobs/<job>.py                how a job kind drives the program
    end_to_end/<metric>.py       one reader per end-to-end metric
    layer_metrics/<metric>.py    one reader per per-layer metric
"""
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def log(msg):
    print(f"benchmark | {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(*parts):
    """Import ``benchmark/<parts>`` by path (a metric's file is named
    for the metric, dots included, so it is no importable name)."""
    path = os.path.join(BENCH_DIR, *parts)
    name = "benchmark_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def cell_spec(manifest, workload):
    """Everything one cell is made of, gathered by name."""
    entry = next(
        (w for w in manifest["workloads"] if w["name"] == workload), None
    )
    if entry is None:
        raise SystemExit(
            f"benchmark: no workload {workload!r} in BENCHMARK.json "
            f"(have {[w['name'] for w in manifest['workloads']]})"
        )
    cell = load_json("workloads", f"{workload}.json")
    traffic = load_json("traffic", f"{entry['traffic']}.json")
    return {
        "name": workload,
        "chips": entry["chips"],
        "cell": cell,
        "config": load_json("configs", f"{entry['config']}.json"),
        "traffic": traffic,
    }


def metrics_of(manifest, workload, group):
    """The manifest's metrics of ``group`` that this cell reports."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or workload in m["workloads"]
    ]


def read_metrics(metrics, directory, obs):
    """Run each metric's reader; one that finds nothing to read
    returns None and is left out."""
    out = {}
    for m in metrics:
        value = load_module(directory, f"{m['name']}.py").read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def arch_of(config, n_layers):
    """The sizes as run, under the names ``flops_bytes`` and the
    reference use: the published ones, with the cell's depth."""
    pub = config["published"]
    return {
        "dim": pub["hidden_size"],
        "n_layers": n_layers,
        "n_heads": pub["num_attention_heads"],
        "n_kv_heads": pub["num_key_value_heads"],
        "head_dim": pub["hidden_size"] // pub["num_attention_heads"],
        "ffn_hidden": pub["intermediate_size"],
        "vocab_size": pub["vocab_size"],
        "norm_eps": pub["rms_norm_eps"],
        "rope_theta": pub["rope_theta"],
    }


def reference_kwargs(arch):
    return {
        k: arch[k] for k in
        ("n_layers", "n_heads", "n_kv_heads", "norm_eps", "rope_theta")
    }


def llama_config(config, cell, max_seq_len):
    """The program's ``LlamaConfig`` for this cell, held to the
    published sizes: no width may differ from the source."""
    import jax.numpy as jnp

    from tpu_hpc.models import llama2

    cfg = llama2.LlamaConfig(
        **config["llama_config"],
        n_layers=cell["n_layers"],
        max_seq_len=max_seq_len,
        dtype=jnp.dtype(cell["compute_dtype"]),
        param_dtype=jnp.dtype(cell["param_dtype"]),
        remat=bool(cell.get("remat", False)),
    )
    arch = arch_of(config, cell["n_layers"])
    got = {
        "dim": cfg.dim, "n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
        "head_dim": cfg.head_dim, "ffn_hidden": cfg.ffn_hidden,
        "vocab_size": cfg.vocab_size, "norm_eps": cfg.norm_eps,
    }
    bad = {k: (v, arch[k]) for k, v in got.items() if v != arch[k]}
    if bad:
        raise SystemExit(
            f"benchmark: {config['name']}: LlamaConfig differs from the "
            f"published sizes (got, published): {bad}"
        )
    # The program's rotary base is fixed at 10000 and it has no
    # attention window: a configuration that needs another base, or a
    # context past its window, cannot run through it unchanged.
    pub = config["published"]
    if pub["rope_theta"] != 10000.0:
        raise SystemExit("benchmark: the program's rope_theta is 10000")
    limit = min(
        pub.get("sliding_window") or max_seq_len,
        pub["max_position_embeddings"],
    )
    if max_seq_len > limit:
        raise SystemExit(
            f"benchmark: context {max_seq_len} exceeds what "
            f"{config['name']} allows without a window ({limit})"
        )
    return cfg, arch


def init_params(cfg, seed, shardings):
    """Weights on the device, from the seed, in ONE jitted call, in
    the dtype and layout they are used in (``llama2.init_llama`` run
    eagerly parks the whole float32 tree on device 0 first). The seed
    enters as data, so every seed shares one compiled program."""
    import jax
    import jax.numpy as jnp

    from tpu_hpc.models import llama2

    def init(lo, hi):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(0), lo), hi
        )
        return llama2.init_llama(key, cfg)

    return jax.jit(init, out_shardings=shardings)(
        jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF)
    )


class CompileCounter:
    """Counts backend compilations and persistent-cache traffic through
    ``jax.monitoring``; ``mark()`` at window start, ``since_mark()`` at
    its end must be 0."""

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = self.cache_misses = 0
        self._mark = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        self._mark = self.compiles

    def since_mark(self):
        return self.compiles - self._mark

    def summary(self):
        return {
            "backend_compiles": self.compiles,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


def memory_by_device(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "bytes_limit": [s.get("bytes_limit") for s in stats],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    }


def start_trace(trace_dir):
    """The profiler with Python call tracing off (it would slow the
    host loop it is there to watch); TraceAnnotations still land."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)  # one trace at a time
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def peaks_for(device_kind):
    table = load_json("peaks.json")
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: no peaks for device kind {device_kind!r} in "
            "benchmark/peaks.json (an unknown device is an error, not a "
            "default)"
        )
    return table[device_kind]
