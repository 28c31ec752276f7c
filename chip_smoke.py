"""chip_smoke.py -- the quickest proof that the system still starts on
the chip.

One process (the only one that touches JAX) drives the two main paths
through the functions their CLIs call, at the full width of Llama-2 7B
with only the depth cut -- dim 4096, 32 heads x 128, FFN 11008, vocab
32000, ``2 x n_devices`` layers, random weights from a seed:

  kernels  every Pallas kernel on the path, compiled by Mosaic (not
           interpreted) and checked against its XLA reference on the
           chip: flash forward + gradients vs ``attention_reference``,
           both paged kernels vs the gather-then-dense oracle, bf16
           and int8 pools;
  serve    ``serve.server.run_replay`` through ``PagedEngine`` with
           ``kernel="pallas"`` and chunked prefill: prompts of ~100 to
           ~900 tokens, 32 new tokens, 4 slots; every request finishes
           and nothing recompiles. The same replay through
           ``kernel="gather"`` is reported next to it (random weights
           make near-ties flip on rounding, so token agreement is
           printed, not asserted);
  train    the ``bench_llama`` assembly (``auto_mesh_axes`` mesh, Pallas
           flash attention forward and backward, ``Trainer.fit`` on
           ``TokenStream``): one compile step and a few timed ones,
           loss finite at every step, step counter advanced; on several
           chips the state must be spread over all of them.

Any failed check ends the run non-zero; nothing turns a failure into a
printed line. It exits non-zero before any of this unless JAX came up
on a TPU (``runtime.require_accelerator``), and refuses to start where
``TPU_HPC_SIM_DEVICES`` would force the CPU. The last line of stdout is
one JSON object, ``{"ok": true, "device": {"platform", "kind",
"count"}}`` as JAX reports the device, and nothing else; the run's
details (versions, cache hits, walls, per-phase numbers) are the
``summary`` line before it and ``chiprun_out/chip_smoke/summary.json``.
Rates printed on the way are information, not claims.

    python chip_smoke.py        # one chip or one four-chip host
"""
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import time

# Serve and train shapes. Depth is the only cut (2 layers a chip, so
# sharded state is what fills a four-chip host).
LAYERS_PER_DEVICE = 2
TRAIN_BATCH_PER_DP, TRAIN_SEQ, TRAIN_STEPS = 2, 2048, 3
SERVE_SLOTS, SERVE_MAX_NEW, SERVE_REQUESTS = 4, 32, 6
SERVE_PROMPT_LENS = (100, 350, 600, 900)
SERVE_BUCKETS, SERVE_CHUNK, SERVE_SEQ = (128, 256), 256, 1024

# Tolerances, as max |kernel - reference| over max(1, max |reference|).
# bf16 carries 8 significand bits (2^-8 ~ 0.4% a rounding). The flash
# kernel and its reference both round the probability tile to bf16
# before the PV matmul and differ in summation order only; the paged
# oracle keeps f32 probabilities, so its kernels sit one more bf16
# rounding away. int8 pools are compared against the DEQUANTIZED pool
# (same page bytes, same scales): the bound covers the kernel's bf16 q
# against f32 page math, not the quantization error.
TOL_FLASH_BF16 = 2e-2
TOL_PAGED_BF16 = 2e-2
TOL_PAGED_INT8 = 3e-2

OUT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "chiprun_out", "chip_smoke"
)


def verdict_line(device: dict) -> str:
    """The last line of stdout: these keys and no other."""
    return json.dumps({
        "ok": True,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    })


def log(msg: str) -> None:
    print(f"chip_smoke | {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def rel_err(got, want) -> float:
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    check(bool(jnp.isfinite(got).all()), "non-finite kernel output")
    return float(
        jnp.max(jnp.abs(got - want))
        / jnp.maximum(1.0, jnp.max(jnp.abs(want)))
    )


def mosaic(fn, *args):
    """Compile ``fn`` and require a Mosaic call in what XLA built: the
    kernel ran as a TPU custom call, not under the Pallas interpreter
    and not as an XLA fallback."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    check(
        "tpu_custom_call" in compiled.as_text(),
        f"{getattr(fn, '__name__', fn)} compiled without a Mosaic call",
    )
    return compiled(*args)


def memory(label: str) -> dict:
    """Per-device memory after a phase. ``peak_bytes_in_use`` is
    cumulative over the process, so the phases run smallest first."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    out = {
        "bytes_limit": stats[0]["bytes_limit"],
        "bytes_in_use": [s["bytes_in_use"] for s in stats],
        "peak_bytes_in_use": [s["peak_bytes_in_use"] for s in stats],
    }
    log(
        f"{label} memory | peak "
        + ", ".join(f"{p / 2**30:.2f}" for p in out["peak_bytes_in_use"])
        + f" GiB of {out['bytes_limit'] / 2**30:.2f} GiB a chip"
    )
    return out


# ---------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------


def check_flash() -> dict:
    """Flash forward and gradients vs attention_reference at the 7B
    head shape (B2, S2048, H32, D128), bf16."""
    import jax
    import jax.numpy as jnp

    from tpu_hpc.kernels.attention import (
        attention_reference,
        blockwise_attention,
    )

    kq, kk, kv, kg = jax.random.split(jax.random.key(1), 4)
    shape = (2, 2048, 32, 128)
    q, k, v, g = (
        jax.random.normal(key, shape, jnp.bfloat16)
        for key in (kq, kk, kv, kg)
    )

    def flash(q, k, v):
        return blockwise_attention(
            q, k, v, causal=True, impl="pallas", block_q=512,
            block_k=1024,
        )[0]

    def reference(q, k, v):
        return attention_reference(q, k, v, causal=True)[0]

    def grads(fn):
        def run(q, k, v, g):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(g))

        return run

    got = mosaic(grads(flash), q, k, v, g)
    # Heads are independent: the reference runs eight at a time, so
    # its [S, S] score tensors stay small beside the later phases.
    ref = jax.jit(grads(reference))
    want = [
        jnp.concatenate(parts, axis=2)
        for parts in zip(*(
            ref(*(x[:, :, h:h + 8] for x in (q, k, v, g)))
            for h in range(0, shape[2], 8)
        ))
    ]
    errs = {
        name: rel_err(a, b)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want)
    }
    log(f"flash fwd+bwd vs attention_reference | rel err {errs}")
    for name, err in errs.items():
        check(err < TOL_FLASH_BF16, f"flash {name} err {err}")
    return errs


def check_paged() -> dict:
    """Both paged kernels vs the gather-then-dense oracle at the serve
    phase's shapes (32 KV heads x 128, 4 slots, 1024-token views), on a
    bf16 pool (16-row pages) and an int8 pool (32-row pages). Dead
    table entries point at a NaN-poisoned page: a kernel that failed to
    redirect them to scratch would poison its output."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_hpc.kernels import paged_attention as pa

    hkv, g, d, slots, bucket = 32, 1, 128, SERVE_SLOTS, SERVE_CHUNK
    errs = {}
    for quant, bs, tol in (
        (False, 16, TOL_PAGED_BF16), (True, 32, TOL_PAGED_INT8),
    ):
        rng = np.random.default_rng(7 + quant)
        max_blocks = SERVE_SEQ // bs
        nb = slots * max_blocks + 2
        poison = nb - 1
        k_pages, v_pages = (
            jax.random.normal(key, (nb, hkv, bs, d), jnp.bfloat16)
            .at[pa.SCRATCH_PAGE].set(0.0)
            for key in jax.random.split(jax.random.key(2 + quant))
        )
        scales = {}
        if quant:
            k_pages, ksc = pa.quantize_pages_int8(k_pages)
            v_pages, vsc = pa.quantize_pages_int8(v_pages)
            scales = dict(k_scale=ksc, v_scale=vsc)
        else:
            k_pages = k_pages.at[poison].set(jnp.nan)
            v_pages = v_pages.at[poison].set(jnp.nan)
        kq, kp = jax.random.split(jax.random.key(4 + quant))

        # decode: ragged positions, one inactive slot, disjoint tables
        pos = np.array([SERVE_SEQ - 1, 517, 99, 3], np.int32)[:slots]
        active = np.array([1, 1, 1, 0], np.int32)[:slots]
        tables = np.full((slots, max_blocks + 4), poison, np.int32)
        for s in range(slots):
            live = pos[s] // bs + 1 if active[s] else 0
            tables[s, :live] = 1 + s * max_blocks + rng.permutation(
                max_blocks
            )[:live]
        q = jax.random.normal(kq, (slots, hkv, g, d), jnp.bfloat16)
        args = (q, k_pages, v_pages, jnp.asarray(tables),
                jnp.asarray(pos), jnp.asarray(active))

        got = mosaic(
            lambda *a: pa.paged_decode_attention(
                *a, block_size=bs, max_blocks=max_blocks, **scales
            ),
            *args,
        )
        want = pa.paged_decode_reference(
            *args, max_blocks=max_blocks, **scales
        )
        tag = "int8" if quant else "bf16"
        errs[f"decode_{tag}"] = rel_err(got, want)
        check(
            not bool(jnp.any(got[active == 0])),
            "inactive decode slot is not zeros",
        )

        # prefill: a continuation chunk that attends over earlier pages
        start = 512
        live = (start + bucket) // bs
        table = np.full((max_blocks + 4,), poison, np.int32)
        table[:live] = 1 + rng.permutation(max_blocks)[:live]
        q = jax.random.normal(kp, (hkv, bucket, g, d), jnp.bfloat16)
        args = (q, k_pages, v_pages, jnp.asarray(table),
                jnp.asarray(start, jnp.int32))

        got = mosaic(
            lambda *a: pa.paged_prefill_attention(
                *a, block_size=bs, max_blocks=max_blocks, **scales
            ),
            *args,
        )
        want = pa.paged_prefill_reference(
            *args, max_blocks=max_blocks, **scales
        )
        errs[f"prefill_{tag}"] = rel_err(got, want)
        for name in (f"decode_{tag}", f"prefill_{tag}"):
            check(errs[name] < tol, f"paged {name} err {errs[name]}")
    log(f"paged kernels vs gather-then-dense oracle | rel err {errs}")
    return errs


# ---------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------


def serve_phase(cfg) -> dict:
    from tpu_hpc.serve.engine import ServeConfig
    from tpu_hpc.serve.paging import derive_paged_config
    from tpu_hpc.serve.server import run_replay

    def replay(kernel):
        paged, max_seq = derive_paged_config(
            SERVE_SLOTS, SERVE_SEQ, SERVE_BUCKETS, block_size=16,
            prefill_chunk=SERVE_CHUNK, kernel=kernel,
        )
        t0 = time.perf_counter()
        summary = run_replay(
            cfg,
            ServeConfig(
                slots=SERVE_SLOTS, max_seq_len=max_seq,
                prefill_buckets=SERVE_BUCKETS,
            ),
            SERVE_REQUESTS, SERVE_PROMPT_LENS, SERVE_MAX_NEW,
            paged=paged,
        )
        summary["wall_s_with_warmup"] = time.perf_counter() - t0
        gc.collect()  # the engine's params and pool leave the chip
        return summary

    pallas = replay("pallas")
    outputs = pallas["outputs"]
    check(pallas["kv_kernel"] == "pallas", "engine did not run pallas")
    check(
        pallas["requests"] == SERVE_REQUESTS
        and len(outputs) == SERVE_REQUESTS
        and all(len(t) == SERVE_MAX_NEW for t in outputs.values()),
        f"not every request finished: {pallas['requests']} of "
        f"{SERVE_REQUESTS}",
    )
    check(
        pallas["recompiles"] == 0,
        f"{pallas['recompiles']} serving recompile(s)",
    )
    log(
        f"serve pallas | mesh {pallas['mesh']} | "
        f"{pallas['compiled_programs']} programs, 0 recompiles | "
        f"{pallas['tokens']} tokens, {pallas['prefill_chunks']} prefill "
        f"chunks | TTFT p50 {pallas['ttft_ms_p50']:.0f} ms, ITL p50 "
        f"{pallas['itl_ms_p50']:.1f} ms (information)"
    )
    mem = memory("serve")

    gather = replay("gather")
    same = [
        next(
            (i for i, (a, b) in enumerate(zip(t, gather["outputs"][r]))
             if a != b),
            len(t),
        )
        for r, t in sorted(outputs.items())
    ]
    agree = sum(same) / (SERVE_REQUESTS * SERVE_MAX_NEW)
    log(
        f"serve gather | tokens equal to pallas up to the first "
        f"difference, per request: {same} of {SERVE_MAX_NEW} "
        f"({agree:.0%}); ITL p50 {gather['itl_ms_p50']:.1f} ms "
        "(information)"
    )
    return {
        "mesh": pallas["mesh"],
        "requests": pallas["requests"],
        "tokens": pallas["tokens"],
        "compiled_programs": pallas["compiled_programs"],
        "recompiles": pallas["recompiles"],
        "ttft_ms_p50": round(pallas["ttft_ms_p50"], 1),
        "itl_ms_p50": round(pallas["itl_ms_p50"], 2),
        "itl_ms_p50_gather": round(gather["itl_ms_p50"], 2),
        "wall_s_with_warmup": round(pallas["wall_s_with_warmup"], 1),
        "gather_token_agreement": round(agree, 3),
        "memory": mem,
    }


# ---------------------------------------------------------------------
# train
# ---------------------------------------------------------------------


def train_phase(cfg) -> dict:
    """Assembled as bench.py's ``bench_llama`` does it."""
    import jax
    from jax.sharding import PartitionSpec as P

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import datasets, llama2
    from tpu_hpc.parallel import fsdp, hybrid, tp
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.train import Trainer

    n_dev = jax.device_count()
    axes = tp.auto_mesh_axes(n_dev, cfg.n_heads, cfg.kv_heads, cap=4)
    dp_size, tp_size = axes["data"], axes.get("model", 1)
    mesh = build_mesh(MeshSpec(axes=axes))
    attn_fn = tp.make_tp_flash_attn_fn(
        mesh, "data", "model" if tp_size > 1 else None,
        impl="pallas", block_q=512, block_k=1024,
    )
    params = llama2.init_llama(jax.random.key(0), cfg)
    constrain = lambda x: x  # noqa: E731
    specs = None
    if tp_size > 1:
        specs = hybrid.hybrid_pspecs(
            params, tp.llama_rules(), data_size=dp_size
        )
        constrain = tp.sp_constrain(mesh, dp_axis="data", sp_axis="model")
    elif dp_size > 1:
        specs = fsdp.param_pspecs(params, axis="data", axis_size=dp_size)

    metrics_path = os.path.join(OUT_DIR, "train.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)
    # One step an epoch: every step's loss reaches the run log, and
    # epoch 0 is the compile step.
    tcfg = TrainingConfig(
        epochs=1 + TRAIN_STEPS, steps_per_epoch=1,
        global_batch_size=TRAIN_BATCH_PER_DP * dp_size,
        learning_rate=3e-4, weight_decay=0.1,
        metrics_path=metrics_path,
    )
    trainer = Trainer(
        tcfg, mesh,
        llama2.make_forward(cfg, constrain, attn_fn),
        params, param_pspecs=specs, batch_pspec=P("data"),
    )
    del params  # the trainer holds its own sharded copy
    result = trainer.fit(datasets.TokenStream(
        vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ
    ))

    with open(metrics_path) as f:
        records = [json.loads(line) for line in f]
    losses = [r["loss"] for r in records if r["event"] == "epoch"]
    check(
        len(losses) == tcfg.epochs
        and all(x is not None for x in losses),  # null = non-finite
        f"loss not finite at every step: {losses}",
    )
    step = int(jax.device_get(trainer.state.step))
    check(step == tcfg.epochs, f"step counter at {step}")
    tokens_per_s = [
        e["items_per_s"] * TRAIN_SEQ for e in result["epochs"][1:]
    ]
    log(
        f"train | mesh {axes} | {cfg.n_layers} layers, batch "
        f"{tcfg.global_batch_size} x {TRAIN_SEQ} | losses "
        f"{[round(x, 4) for x in losses]} | "
        f"{[round(t) for t in tokens_per_s]} tokens/s after the "
        "compile step (information)"
    )
    mem = memory("train")
    if n_dev > 1:
        # Spread, not parked on device 0: the largest parameter is
        # sharded, and every chip holds a like share of the state.
        leaves = jax.tree.leaves(trainer.state.params)
        biggest = max(leaves, key=lambda a: a.size)
        check(
            not biggest.sharding.is_fully_replicated
            and len(biggest.sharding.device_set) == n_dev,
            f"largest parameter is not sharded: {biggest.sharding}",
        )
        in_use = mem["bytes_in_use"]
        check(
            min(in_use) > 0.5 * max(in_use),
            f"state is not spread over the chips: {in_use}",
        )
        log(f"train | largest parameter sharding {biggest.sharding.spec}")
    return {
        "mesh": axes,
        "n_layers": cfg.n_layers,
        "steps": step,
        "losses": [round(x, 4) for x in losses],
        "tokens_per_s": [round(t) for t in tokens_per_s],
        "memory": mem,
    }


# ---------------------------------------------------------------------


def main() -> int:
    t_start = time.perf_counter()
    if os.environ.get("TPU_HPC_SIM_DEVICES"):
        raise SystemExit(
            "chip_smoke: refusing to start with TPU_HPC_SIM_DEVICES set "
            "(it forces the CPU platform when tpu_hpc is imported); "
            "this script runs on the chip only"
        )
    import jax

    from tpu_hpc.models import llama2
    from tpu_hpc.runtime import compile_cache_dir, require_accelerator

    dev = require_accelerator()  # exits non-zero unless this is a TPU
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    cache = {"dir": compile_cache_dir(), "hits": 0, "writes": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["writes"] += 1

    jax.monitoring.register_event_listener(on_event)
    versions = {
        pkg: importlib.metadata.version(pkg)
        for pkg in ("jax", "jaxlib", "libtpu")
    }
    log(f"device {device} | {versions} | compile cache {cache['dir']}")
    os.makedirs(OUT_DIR, exist_ok=True)

    cfg = dataclasses.replace(
        llama2.PRESETS["7b"],
        n_layers=LAYERS_PER_DEVICE * device["count"],
    )
    log(
        f"model | llama2 7b width, depth cut: dim {cfg.dim}, "
        f"{cfg.n_heads} x {cfg.head_dim} heads, ffn {cfg.ffn_hidden}, "
        f"vocab {cfg.vocab_size}, {cfg.n_layers} of 32 layers"
    )
    phases, walls = {}, {}
    for name, run in (
        ("kernels", lambda: {
            "flash": check_flash(), **check_paged(),
            "memory": memory("kernels"),
        }),
        ("serve", lambda: serve_phase(cfg)),
        ("train", lambda: train_phase(cfg)),
    ):
        t0 = time.perf_counter()
        phases[name] = run()
        walls[name] = round(time.perf_counter() - t0, 1)
        log(f"{name} ok in {walls[name]} s")
    # The run's details go on the line before last and to a file; the
    # LAST line is the verdict alone, exactly {"ok", "device"}.
    summary = json.dumps({
        "versions": versions,
        "compile_cache": cache,
        "wall_s": {
            **walls, "total": round(time.perf_counter() - t_start, 1)
        },
        "phases": phases,
        "claim": None,
    })
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        f.write(summary + "\n")
    log(f"summary {summary}")
    print(verdict_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
