"""`python -m tpu_hpc.serve` -- local request-replay serving run.

Brings up the engine on the TPU chips of this host -- or, asked for by
name (``--sim-devices N`` / ``TPU_HPC_SIM_DEVICES=N``), on a simulated
CPU mesh; any other backend is refused at start-up
(runtime.require_accelerator) -- replays a deterministic synthetic request mix through the continuous
batcher, and emits the serving metrics record -- TTFT/ITL quantiles,
tokens/s/chip, serving MFU -- as one JSON line on stdout plus optional
JSONL traces. The serving analogue of bench.py's training contract.
``--loadgen SCENARIO`` swaps the plain replay for a tpu_hpc.loadgen
scenario (bursty/heavy-tail/multi-tenant/colocation mixes on the
deterministic virtual clock) -- the producer side of the
``python -m tpu_hpc.obs.regress`` gate.

Resilience: ``--supervise N`` re-execs under
tpu_hpc.resilience.supervisor with N bounded restarts (same contract
bench.py --supervise uses), and the batcher ticks the supervisor's
heartbeat file at decode-step granularity, so a wedged decode step is
detected and the run restarted instead of hanging the allocation.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Optional, Sequence

from tpu_hpc.checks.fit import param_counts
from tpu_hpc.checks.roofline import peak_flops_for_device
from tpu_hpc.models import hybrid_ssm_moe, llama2


def tiny_config(vocab_size: int = 512) -> llama2.LlamaConfig:
    """The 8-device-sim-sized model the replay server defaults to."""
    import jax.numpy as jnp

    return llama2.LlamaConfig(
        dim=128, n_layers=2, n_heads=8, n_kv_heads=4,
        vocab_size=vocab_size, multiple_of=32, max_seq_len=512,
        dtype=jnp.bfloat16,
    )


def build_serving_mesh(n_devices: int, cfg: llama2.LlamaConfig):
    """Serving mesh: TP capped at 4 over ``model`` (head divisibility
    validated), remaining chips over ``data`` for batch slots -- the
    same auto split bench.py's training headline uses
    (tp.auto_mesh_axes is the single policy both call). A decoder with
    state-space layers (``models/hybrid_ssm_moe.py``) has no
    tensor-parallel plan: every chip over ``data``."""
    from tpu_hpc.parallel import tp
    from tpu_hpc.runtime import MeshSpec, build_mesh

    if hybrid_ssm_moe.is_hybrid_ssm_moe(cfg):
        return build_mesh(MeshSpec(axes={"data": n_devices}))
    return build_mesh(MeshSpec(axes=tp.auto_mesh_axes(
        n_devices, cfg.n_heads, cfg.kv_heads, cap=4
    )))


def seeded_weights(cfg: llama2.LlamaConfig, seed: int):
    """Dev-mode weights from ``seed``, by the configuration's own
    init."""
    import jax

    key = jax.random.key(seed)
    if hybrid_ssm_moe.is_hybrid_ssm_moe(cfg):
        return jax.jit(
            lambda k: hybrid_ssm_moe.init_hybrid_ssm_moe(k, cfg)
        )(key)
    return llama2.init_llama(key, cfg)


def build_spec(
    engine,
    cfg: llama2.LlamaConfig,
    spec_cfg,
    mesh,
    draft_ckpt: Optional[str] = None,
    draft_cfg: Optional[llama2.LlamaConfig] = None,
    seed: int = 0,
):
    """Attach speculative decoding (serve/spec.py) to a paged engine:
    restore (or dev-mode random-init) the draft model for
    ``mode="draft"``, nothing extra for prompt-lookup. One helper for
    server.py and bench.py -- the draft-restore path and the default
    draft architecture must not fork."""
    import jax

    from tpu_hpc.serve.spec import attach_spec, default_draft_config
    from tpu_hpc.serve.weights import load_serving_params

    draft_params = None
    dcfg = None
    if spec_cfg.mode == "draft":
        dcfg = draft_cfg or default_draft_config(cfg)
        if draft_ckpt:
            draft_params = load_serving_params(draft_ckpt, dcfg, mesh)
        else:
            # Development mode: a random draft proves the wiring (and
            # the greedy oracle) but accepts ~1/vocab of its guesses.
            draft_params = llama2.init_llama(
                jax.random.key(seed + 1), dcfg
            )
    return attach_spec(
        engine, spec_cfg, draft_params=draft_params, draft_cfg=dcfg
    )


def run_replay(
    cfg: llama2.LlamaConfig,
    serve_cfg,
    n_requests: int,
    prompt_lens: Sequence[int],
    max_new_tokens: int,
    checkpoint_dir: Optional[str] = None,
    metrics_path: Optional[str] = None,
    seed: int = 0,
    disagg: bool = False,
    disagg_max_inflight_mb: "Optional[int | str]" = None,
    paged=None,
    spec=None,
    spec_draft_ckpt: Optional[str] = None,
    spec_draft_cfg: Optional[llama2.LlamaConfig] = None,
    temperature: float = 0.0,
    top_p: float = 1.0,
) -> dict:
    """Engine bring-up + warmup + replay; returns the summary dict.
    ``disagg=True`` splits the chips into disaggregated prefill/decode
    tiers (serve/disagg.py), KV blocks crossing via bounded reshard
    plans (``disagg_max_inflight_mb``). ``paged`` (a
    paging.PagedConfig) swaps the slab KV cache for the block-table
    pool with prefix reuse and chunked prefill -- composable with
    ``disagg`` (the hop then ships block tables + referenced pages).
    ``spec`` (a spec.SpecConfig, paged only) turns on speculative
    decoding; ``temperature``/``top_p`` sample the replay mix under
    per-request seeds instead of greedy."""
    import jax

    from tpu_hpc.serve.engine import Engine
    from tpu_hpc.serve.metrics import ServeMeter
    from tpu_hpc.serve.paging import PagedEngine
    from tpu_hpc.serve.scheduler import ContinuousBatcher, replay_requests
    from tpu_hpc.serve.weights import load_serving_params
    from tpu_hpc.resilience.heartbeat import Heartbeat

    from tpu_hpc import obs

    if disagg:
        from tpu_hpc.serve.disagg import (
            DisaggEngine,
            split_serving_meshes,
        )

        prefill_mesh, decode_mesh = split_serving_meshes(
            jax.device_count(), cfg
        )
        mesh = decode_mesh  # the resident tier: restore targets it
    else:
        mesh = build_serving_mesh(jax.device_count(), cfg)
    # Bring-up phases as spans: restore-vs-compile time is the first
    # question about any slow serving start, and these records (to
    # ``metrics_path`` + the flight ring) answer it without a profiler
    # attach.
    with obs.span("restore", sink=metrics_path,
                  hist="serve_restore_s"):
        if checkpoint_dir:
            params = load_serving_params(checkpoint_dir, cfg, mesh)
        else:
            params = seeded_weights(cfg, seed)
    if disagg:
        engine = DisaggEngine(
            params, cfg, serve_cfg, prefill_mesh, decode_mesh,
            max_inflight_bytes=(
                "auto" if disagg_max_inflight_mb == "auto"
                else disagg_max_inflight_mb * (1 << 20)
                if disagg_max_inflight_mb else None
            ),
            paged=paged,
        )
    elif paged is not None:
        engine = PagedEngine(params, cfg, serve_cfg, mesh, paged)
    else:
        engine = Engine(params, cfg, serve_cfg, mesh)
    if spec is not None:
        build_spec(
            engine, cfg, spec, mesh, draft_ckpt=spec_draft_ckpt,
            draft_cfg=spec_draft_cfg, seed=seed,
        )
    with obs.span("warmup", sink=metrics_path, hist="serve_warmup_s"):
        n_programs = engine.warmup()

    meter = ServeMeter(metrics_path=metrics_path)
    batcher = ContinuousBatcher(engine, meter=meter)
    requests = replay_requests(
        n_requests, cfg.vocab_size, prompt_lens, max_new_tokens,
        seed=seed, temperature=temperature, top_p=top_p,
    )
    heartbeat = Heartbeat.from_env()
    tick = None
    if heartbeat is not None:
        # Throttle to ~1 write per 2s of progress: decode steps on
        # real chips run at millisecond cadence, and a per-step
        # atomic-rename file write would turn the liveness signal
        # into measurable I/O on the serving hot loop.
        import time as _time

        last = [0.0]

        def tick(step):
            now = _time.monotonic()
            if now - last[0] >= 2.0:
                last[0] = now
                heartbeat.tick(step)

    outputs = batcher.run(requests, tick=tick)

    peak = peak_flops_for_device(jax.devices()[0])
    summary = meter.summary(
        n_devices=jax.device_count(),
        n_params=param_counts(cfg)["active"],
        peak_flops_per_device=peak,
    )
    summary.update(
        mesh={k: int(v) for k, v in mesh.shape.items()},
        slots=serve_cfg.slots,
        prefill_buckets=list(serve_cfg.prefill_buckets),
        cache_bytes=engine.cache_bytes,
        compiled_programs=n_programs,
        recompiles=getattr(
            engine, "compile_count_total", engine.compile_count
        ) - n_programs,
        batcher=dict(batcher.stats),
    )
    # The cache layout is part of every serving record's identity:
    # the regress gate must never diff a paged run against a slab one
    # without seeing the difference.
    if paged is not None:
        summary.update(engine.paged_summary())
    else:
        summary["kv_layout"] = "slab"
    # So is the speculative mode: acceptance rate + draft cost ride
    # the summary, and spec_mode/spec_k label the rows.
    if getattr(engine, "spec", None) is not None:
        summary.update(engine.spec.spec_summary())
    if disagg:
        # Per-tier attribution: tier meshes, the cross-tier KV load,
        # and THIS run's hop-latency quantiles (the engine's own
        # samples -- the process-wide registry histogram would blend
        # runs) -- TTFT decomposes into prefill-tier + hop on this
        # record.
        summary["disagg"] = engine.describe()
    meter.write_summary(summary)
    # Close the replay's JSONL with the registry snapshot, mirroring
    # the Trainer's run_end discipline -- one schema, two producers.
    obs.get_registry().emit_snapshot(sink=metrics_path)
    # The generated streams ride the RETURNED summary only (callers
    # compare engines token for token); main() prints without them.
    summary["outputs"] = outputs
    return summary


def run_loadgen(
    cfg: llama2.LlamaConfig,
    serve_cfg,
    scenario_name: str,
    n_requests: int,
    max_new_tokens: int,
    checkpoint_dir: Optional[str] = None,
    metrics_path: Optional[str] = None,
    seed: int = 0,
    paged=None,
    spec=None,
    spec_draft_ckpt: Optional[str] = None,
    spec_draft_cfg: Optional[llama2.LlamaConfig] = None,
    capture_dir: Optional[str] = None,
) -> dict:
    """Engine bring-up + a tpu_hpc.loadgen scenario run; returns the
    harness summary (per-tenant quantiles, shed/queued counts,
    occupancy). The scenario's lengths are aligned to THIS engine's
    buckets/capacity, so any catalog entry runs against any serve
    shape. ``paged`` (a paging.PagedConfig) runs the scenario against
    the block-table cache -- the shared_prefix scenario's hit rate and
    the admission block stalls come from exactly this path. ``spec``
    (a spec.SpecConfig; needs ``paged``) drives the scenario through
    speculative decoding -- the virtual clock charges one target
    forward per verify step plus the modeled draft cost, so the
    banked ITL rows carry the acceptance-driven win
    deterministically."""
    import jax

    from tpu_hpc.loadgen import LoadHarness, build_scenario
    from tpu_hpc.serve.engine import Engine
    from tpu_hpc.serve.paging import PagedEngine
    from tpu_hpc.serve.weights import load_serving_params
    from tpu_hpc.resilience.heartbeat import Heartbeat

    from tpu_hpc import obs

    # Scenario FIRST: it is cheap and validates the derived sizing
    # (build_scenario rejects max_prompt/max_new < 2), so a bad CLI
    # combination fails in milliseconds, not after restore + warmup.
    max_prompt = max(serve_cfg.prefill_buckets)
    max_new = min(
        max_new_tokens, serve_cfg.max_seq_len - max_prompt
    )
    scenario = build_scenario(
        scenario_name, seed=seed, n_requests=n_requests,
        vocab_size=cfg.vocab_size, max_prompt=max_prompt,
        max_new=max_new,
    )

    mesh = build_serving_mesh(jax.device_count(), cfg)
    with obs.span("restore", sink=metrics_path,
                  hist="serve_restore_s"):
        if checkpoint_dir:
            params = load_serving_params(checkpoint_dir, cfg, mesh)
        else:
            params = seeded_weights(cfg, seed)
    if paged is not None:
        engine = PagedEngine(params, cfg, serve_cfg, mesh, paged)
    else:
        engine = Engine(params, cfg, serve_cfg, mesh)
    if spec is not None:
        build_spec(
            engine, cfg, spec, mesh, draft_ckpt=spec_draft_ckpt,
            draft_cfg=spec_draft_cfg, seed=seed,
        )
    with obs.span("warmup", sink=metrics_path, hist="serve_warmup_s"):
        n_programs = engine.warmup()
    capture = None
    if capture_dir:
        # Anomaly-triggered capture (obs/trace.py): a stall-watermark
        # trip or SLO breach files one bounded profiler trace +
        # flight dump under capture_dir, keyed by the triggering
        # trace id.
        capture = obs.AnomalyCapture(capture_dir, n_steps=8)
    harness = LoadHarness(
        engine, scenario, metrics_path=metrics_path,
        capture=capture,
    )
    heartbeat = Heartbeat.from_env()
    tick_cb = None
    if heartbeat is not None:
        import time as _time

        last = [0.0]

        def tick_cb(tick):
            now = _time.monotonic()
            if now - last[0] >= 2.0:
                last[0] = now
                heartbeat.tick(tick)

    harness.drive(tick_cb=tick_cb)
    peak = peak_flops_for_device(jax.devices()[0])
    # kv_layout/hit-rate evidence rides in from harness.summarize()
    # itself (the harness owns the engine's identity either way).
    extra = dict(
        mesh={k: int(v) for k, v in mesh.shape.items()},
        slots=serve_cfg.slots,
        prefill_buckets=list(serve_cfg.prefill_buckets),
        compiled_programs=n_programs,
        # Evaluated AFTER the drive: recompiles must count the run
        # (the total includes the spec draft engine's builds).
        recompiles=getattr(
            engine, "compile_count_total", engine.compile_count
        ) - n_programs,
        batcher=dict(harness.batcher.stats),
    )
    # (capture count rides in from harness.summarize() itself, AFTER
    # its SLO-breach trigger -- counting here would miss it.)
    return harness.summarize(
        n_devices=jax.device_count(),
        n_params=param_counts(cfg)["active"],
        peak_flops_per_device=peak,
        extra=extra,
    )


def run_fleet_loadgen(
    cfg: llama2.LlamaConfig,
    serve_cfg,
    scenario_name: str,
    n_requests: int,
    max_new_tokens: int,
    paged,
    n_replicas: int,
    min_replicas: int = 1,
    initial_replicas: Optional[int] = None,
    router: str = "affinity",
    swap_at: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    metrics_path: Optional[str] = None,
    seed: int = 0,
) -> dict:
    """Fleet bring-up + a tpu_hpc.loadgen scenario over N paged
    replicas on disjoint mesh slices (serve/fleet.py): router by
    tenant class + prefix affinity, heartbeat-driven failure
    handling, autoscale between ``min_replicas`` and ``n_replicas``,
    and -- with ``swap_at`` -- a mid-run live weight update
    (dev mode publishes a fresh random init at seed+1: a genuinely
    different model version; production publishes a trained
    checkpoint through the same content-checksum gate).
    ``TPU_HPC_LOADGEN_FAULTS`` fleet keys (replica_kill_at,
    swap_corrupt, slow_replica) inject the chaos matrix."""
    import jax

    from tpu_hpc.loadgen import build_scenario, parse_faults
    from tpu_hpc.serve.fleet import (
        FleetConfig,
        FleetHarness,
        build_fleet_engines,
    )
    from tpu_hpc.serve.weights import load_serving_params

    from tpu_hpc import obs

    max_prompt = max(serve_cfg.prefill_buckets)
    max_new = min(
        max_new_tokens, serve_cfg.max_seq_len - max_prompt
    )
    scenario = build_scenario(
        scenario_name, seed=seed, n_requests=n_requests,
        vocab_size=cfg.vocab_size, max_prompt=max_prompt,
        max_new=max_new,
    )
    with obs.span("restore", sink=metrics_path,
                  hist="serve_restore_s"):
        if checkpoint_dir:
            # One host-side restore; each engine reshards it onto its
            # own slice (the train->serve path, N times).
            mesh = build_serving_mesh(jax.device_count(), cfg)
            params = load_serving_params(checkpoint_dir, cfg, mesh)
            params = jax.device_get(params)
        else:
            params = llama2.init_llama(jax.random.key(seed), cfg)
    swap_weights = None
    if swap_at is not None:
        swap_weights = llama2.init_llama(jax.random.key(seed + 1), cfg)
    with obs.span("warmup", sink=metrics_path, hist="serve_warmup_s"):
        engines = build_fleet_engines(
            params, cfg, serve_cfg, paged, n_replicas
        )
    harness = FleetHarness(
        engines, scenario,
        FleetConfig(
            initial_replicas=(
                initial_replicas
                if initial_replicas is not None
                else max(min_replicas, (n_replicas + 1) // 2)
            ),
            min_replicas=min_replicas,
            max_replicas=n_replicas,
            router=router,
        ),
        metrics_path=metrics_path,
        faults=parse_faults(),
        swap_at=swap_at,
        swap_weights=swap_weights,
    )
    n_programs = harness.fleet.compile_count_total()
    harness.drive()
    return harness.summarize(
        n_devices=jax.device_count(),
        extra=dict(
            mesh={"replicas": n_replicas},
            slots=serve_cfg.slots,
            prefill_buckets=list(serve_cfg.prefill_buckets),
            compiled_programs=n_programs,
            recompiles=(
                harness.fleet.compile_count_total() - n_programs
            ),
        ),
    )


def _last_json_line(log_dir: str) -> Optional[str]:
    """The newest attempt log's final JSON line (the child's summary
    record), or None when no attempt log holds one."""
    import glob

    logs = sorted(
        glob.glob(os.path.join(log_dir, "run.attempt*.log")),
        key=os.path.getmtime,
    )
    for path in reversed(logs):
        try:
            lines = open(path).read().splitlines()
        except OSError:
            continue
        for line in reversed(lines):
            line = line.strip()
            if line.startswith("{"):
                try:
                    json.loads(line)
                except ValueError:
                    continue
                return line
    return None


def _inflight_mb(v: str):
    """--disagg-max-inflight-mb value: an int MB count or 'auto' (the
    collective planner sizes the hop). Range/type errors surface at
    parse, before any model init -- the misplaced-flag discipline."""
    if v == "auto":
        return "auto"
    try:
        return int(v)
    except ValueError:
        import argparse as _argparse

        raise _argparse.ArgumentTypeError(
            f"expected an integer MB count or 'auto', got {v!r}"
        ) from None


def main(argv: Optional[Sequence[str]] = None) -> int:
    # allow_abbrev=False: --supervise is stripped by exact name before
    # re-exec (same recursion guard as bench.py).
    ap = argparse.ArgumentParser(
        prog="tpu_hpc.serve",
        description=__doc__.split("\n")[0],
        allow_abbrev=False,
    )
    ap.add_argument(
        "--model", type=str, default="tiny",
        choices=(
            "tiny", *sorted(llama2.PRESETS), *sorted(hybrid_ssm_moe.PRESETS)
        ),
        help="model architecture (tiny = the 8-device-sim config; "
        "granite-4.0-h-small, falcon-h1-34b and their sim-sized "
        "hybrid-tiny, falcon-h1-tiny need --paged)",
    )
    ap.add_argument("--vocab", type=int, default=512,
                    help="vocab size for --model tiny")
    ap.add_argument("--slots", type=int, default=8,
                    help="fixed decode batch width")
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="KV-cache capacity per slot "
                    "(default: largest bucket + max-new)")
    ap.add_argument(
        "--buckets", type=str, default="16,32",
        help="comma-separated padded prefill lengths",
    )
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument(
        "--prompt-lens", type=str, default="9,14,27",
        help="comma-separated prompt lengths the replay mix cycles",
    )
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--capture-dir", type=str, default=None, metavar="DIR",
        help="arm anomaly-triggered capture for the --loadgen run: a "
        "stall-watermark trip or SLO breach files one bounded "
        "profiler trace + flight dump under DIR, keyed by the "
        "triggering trace id (obs/trace.py)",
    )
    ap.add_argument(
        "--loadgen", type=str, default=None, metavar="SCENARIO",
        help="run a tpu_hpc.loadgen scenario instead of the plain "
        "replay mix (catalog: steady, bursty, heavy_tail, "
        "multi_tenant, saturating_burst, colocate, shared_prefix, "
        "decode_heavy, diurnal, long_idle_sessions); --requests/"
        "--max-new/--seed size it, latencies run on the virtual "
        "clock (deterministic -- the regress gate's input)",
    )
    ap.add_argument(
        "--disagg", action="store_true",
        help="disaggregated serving: prefill on one mesh tier, decode "
        "on another (disjoint halves of the visible chips), KV blocks "
        "crossing via bounded tpu_hpc.reshard plans; consumed by the "
        "replay workload only",
    )
    ap.add_argument(
        "--disagg-max-inflight-mb", type=_inflight_mb, default=None,
        metavar="MB|auto",
        help="peak per-device transient allowed to a cross-tier KV "
        "move (reshard max_inflight_bytes); 'auto' asks the "
        "collective planner (tpu_hpc.comm.planner) for the chunk "
        "that amortizes the cross-tier launch latency on this "
        "topology's cost model; default: unbounded",
    )
    ap.add_argument(
        "--paged", action="store_true",
        help="paged KV cache (serve/paging.py): HBM carved into "
        "fixed-size pages with a block-table per slot, prefix reuse "
        "over shared prompts, chunked prefill; composable with "
        "--disagg (the KV hop then ships block tables + referenced "
        "pages only)",
    )
    ap.add_argument(
        "--kv-block-size", type=int, default=None, metavar="TOKENS",
        help="tokens per KV page (default 16; must divide every "
        "bucket and the cache capacity); requires --paged",
    )
    ap.add_argument(
        "--kv-blocks", type=int, default=None, metavar="N",
        help="physical pages in the pool incl. the scratch page "
        "(default: slab-equivalent capacity, slots x max-seq-len / "
        "block-size + 1); requires --paged",
    )
    ap.add_argument(
        "--kv-host-blocks", type=int, default=None, metavar="N",
        help="host-DRAM page tier (serve/tier.py): N host page slots "
        "incl. the scratch slot behind the HBM pool -- parked trie "
        "pages spill there under pool pressure and refill on a "
        "returning prompt (prefetch-before-seat); size it with "
        "python -m tpu_hpc.checks.fit --kv-host-tier N; requires "
        "--paged",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=None, metavar="TOKENS",
        help="chunked prefill stride: long prompts prefill in "
        "block-aligned chunks interleaved with decode steps (0 = "
        "whole-prompt prefill; with chunking, prompts LONGER than "
        "the largest bucket are servable); requires --paged",
    )
    ap.add_argument(
        "--kv-kernel", choices=("gather", "pallas"), default=None,
        help="paged attention read path "
        "(tpu_hpc.kernels.paged_attention): 'gather' materializes "
        "each slot's pages with a take() before a dense flash call "
        "(the oracle path), 'pallas' walks the block table inside "
        "the kernel -- one HBM read per page, no gathered copy "
        "(interpreted on CPU); token-exact vs gather under greedy; "
        "requires --paged",
    )
    ap.add_argument(
        "--kv-quant", choices=("none", "int8"), default=None,
        help="KV page storage dtype: 'int8' stores pages quantized "
        "per page with a float32 scale side array -- half the bytes "
        "per token, ~2x resident context at equal HBM (size it with "
        "python -m tpu_hpc.checks.fit --kv-quant int8); logits "
        "drift within the pinned tolerance (tests/"
        "test_paged_kernels.py); requires --paged",
    )
    ap.add_argument(
        "--spec", choices=("off", "draft", "ngram"), default="off",
        help="speculative decoding (serve/spec.py; requires --paged): "
        "'draft' drafts k tokens with a small draft model "
        "(--spec-draft-ckpt, or a dev-mode random init), 'ngram' "
        "self-speculates via prompt lookup over each request's own "
        "history -- no extra model; greedy streams stay byte-exact, "
        "only latency changes",
    )
    ap.add_argument(
        "--spec-k", type=int, default=None, metavar="K",
        help="drafted tokens per verify step (default 4); requires "
        "--spec",
    )
    ap.add_argument(
        "--spec-draft-ckpt", type=str, default=None, metavar="DIR",
        help="restore the draft model from the newest trainer "
        "checkpoint here (requires --spec draft; without it the "
        "draft is a random init -- wiring proof, ~zero acceptance)",
    )
    ap.add_argument(
        "--spec-draft-model", type=str, default=None,
        choices=("half", *sorted(llama2.PRESETS)),
        help="draft architecture for --spec draft (default 'half': "
        "the target config at half depth; presets restore real "
        "draft checkpoints)",
    )
    ap.add_argument(
        "--temperature", type=float, default=None,
        help="sample the replay mix at this temperature under "
        "per-request seeds (default: greedy; requires --spec -- "
        "sampling rides the verify program)",
    )
    ap.add_argument(
        "--top-p", type=float, default=None,
        help="nucleus filter for --temperature sampling (default 1.0)",
    )
    ap.add_argument(
        "--fleet", type=int, default=None, metavar="N",
        help="serve the --loadgen scenario from a fleet of N paged "
        "replicas on disjoint mesh slices (serve/fleet.py): tenant-"
        "class + prefix-affinity routing, heartbeat failure handling "
        "with request redispatch, autoscale, live weight swap; "
        "requires --loadgen and --paged with --prefill-chunk",
    )
    ap.add_argument(
        "--fleet-min", type=int, default=None, metavar="N",
        help="autoscaler's minimum live replicas (default 1); "
        "requires --fleet",
    )
    ap.add_argument(
        "--fleet-router", choices=("affinity", "round_robin"),
        default=None,
        help="request placement policy (default affinity; "
        "round_robin is the documented degraded control -- it "
        "divides every shared prefix across N cold tries); requires "
        "--fleet",
    )
    ap.add_argument(
        "--fleet-swap-at", type=int, default=None, metavar="TICK",
        help="publish a live weight update at this fleet tick "
        "(dev mode: a fresh random init at seed+1), rolled out "
        "drain-and-swap one replica at a time behind the content-"
        "checksum gate; requires --fleet",
    )
    ap.add_argument(
        "--checkpoint-dir", type=str, default=None,
        help="restore params from the newest trainer checkpoint here "
        "(serve/weights.py resharding); default: random init",
    )
    ap.add_argument(
        "--metrics", type=str, default=None,
        help="append per-request + summary JSONL records here",
    )
    ap.add_argument(
        "--sim-devices", type=int, default=0,
        help="force an N-device simulated CPU mesh (development mode)",
    )
    ap.add_argument(
        "--supervise", type=int, default=0, metavar="N",
        help="re-launch under the resilience supervisor with N "
        "bounded restarts (heartbeat ticked at decode-step "
        "granularity; a stale heartbeat kills and restarts a wedged "
        "child)",
    )
    ap.add_argument(
        "--heartbeat-timeout", type=float, default=600.0,
        help="seconds of heartbeat staleness before the supervisor "
        "restarts the child (0 = off); must cover backend bring-up "
        "+ checkpoint restore + engine warmup",
    )
    args = ap.parse_args(argv)

    if args.supervise:
        from tpu_hpc.resilience.supervisor import (
            run_supervised,
            strip_flag,
        )

        child_args = strip_flag(
            list(sys.argv[1:] if argv is None else argv), "--supervise"
        )
        log_dir = os.environ.get(
            "TPU_HPC_SUPERVISE_LOGS", "serve_logs"
        )
        rc = run_supervised(
            [sys.executable, "-m", "tpu_hpc.serve", *child_args],
            max_restarts=args.supervise,
            log_dir=log_dir,
            heartbeat=os.path.join(log_dir, "heartbeat.json"),
            heartbeat_timeout=args.heartbeat_timeout,
        )
        if rc == 0:
            # The supervisor redirected the child's stdout into its
            # attempt log; re-emit the summary so the one-JSON-line-
            # on-stdout contract (this module's docstring) survives
            # supervision -- a pipeline `... --supervise 2 | jq`
            # must not read empty output.
            record = _last_json_line(log_dir)
            if record is not None:
                print(record)
        return rc

    # Misplaced-flag discipline (the --comm-mode / --loadgen-scenario
    # guard): a disagg flag on a workload that cannot consume it is a
    # CLI error, not a silent single-tier run. The loadgen harness
    # charges modeled prefill/decode costs on its virtual clock around
    # ONE engine's programs; it has no notion of a cross-tier hop, so
    # "--loadgen --disagg" would measure a single tier while the flag
    # claims two.
    if args.disagg and args.loadgen:
        ap.error(
            "--disagg is only consumed by the replay workload; the "
            "--loadgen harness charges single-tier virtual-clock "
            "costs and would silently ignore the tier split"
        )
    if args.disagg_max_inflight_mb is not None and not args.disagg:
        ap.error(
            "--disagg-max-inflight-mb is only consumed together with "
            "--disagg"
        )
    if args.disagg_max_inflight_mb is not None \
            and args.disagg_max_inflight_mb != "auto" \
            and args.disagg_max_inflight_mb < 1:
        ap.error(
            f"--disagg-max-inflight-mb {args.disagg_max_inflight_mb} "
            "must be >= 1 (or 'auto')"
        )
    # Paged sizing flags only mean something with --paged: a sizing
    # flag on a slab run silently doing nothing is exactly the
    # misplaced-flag failure mode this CLI bans.
    if not args.paged:
        for flag, val in (
            ("--kv-block-size", args.kv_block_size),
            ("--kv-blocks", args.kv_blocks),
            ("--kv-host-blocks", args.kv_host_blocks),
            ("--prefill-chunk", args.prefill_chunk),
            ("--kv-kernel", args.kv_kernel),
            ("--kv-quant", args.kv_quant),
        ):
            if val is not None:
                ap.error(
                    f"{flag} is only consumed together with --paged"
                )
    if args.kv_host_blocks is not None and args.kv_host_blocks < 2:
        ap.error(
            f"--kv-host-blocks {args.kv_host_blocks} must be >= 2 "
            "(one scratch slot plus at least one page)"
        )
    # Speculative decoding rides the paged engine only; a spec flag
    # that cannot take effect is a parse error, not a silent greedy
    # run wearing a speculative label.
    if args.spec != "off" and not args.paged:
        ap.error(
            "--spec rides the paged engine (serve/paging.py); add "
            "--paged"
        )
    if args.spec != "off" and args.disagg:
        ap.error(
            "--spec is not consumed by --disagg (the verify program "
            "is a single-mesh paged program; the decode tier would "
            "silently run greedy)"
        )
    if args.spec != "off" and args.kv_quant == "int8":
        ap.error(
            "--spec is not consumed with --kv-quant int8 (verify "
            "replays drafted positions against pages the draft loop "
            "already requantized -- the accept/reject decision would "
            "drift from the greedy oracle)"
        )
    if args.spec == "off":
        for flag, val in (
            ("--spec-k", args.spec_k),
            ("--spec-draft-ckpt", args.spec_draft_ckpt),
            ("--spec-draft-model", args.spec_draft_model),
            ("--temperature", args.temperature),
            ("--top-p", args.top_p),
        ):
            if val is not None:
                ap.error(
                    f"{flag} is only consumed together with --spec"
                )
    if args.spec != "draft":
        for flag, val in (
            ("--spec-draft-ckpt", args.spec_draft_ckpt),
            ("--spec-draft-model", args.spec_draft_model),
        ):
            if val is not None:
                ap.error(
                    f"{flag} is only consumed together with "
                    "--spec draft"
                )
    if args.temperature is not None and args.loadgen:
        ap.error(
            "--temperature is only consumed by the replay workload; "
            "--loadgen scenarios replay their own greedy mixes"
        )
    if args.capture_dir and not args.loadgen:
        ap.error(
            "--capture-dir is only consumed together with --loadgen "
            "(training runs arm capture via "
            "TrainingConfig.capture_on_anomaly)"
        )
    # Fleet flag discipline: the fleet serves loadgen scenarios over
    # paged replicas with chunked prefill (redispatch replays prompt
    # + committed tokens, which can exceed any bucket); every other
    # combination would silently not be a fleet run.
    if args.fleet is not None:
        if args.fleet < 1:
            ap.error(f"--fleet {args.fleet} must be >= 1")
        if not args.loadgen:
            ap.error("--fleet is only consumed together with "
                     "--loadgen (the fleet serves scenarios)")
        if not args.paged or not args.prefill_chunk:
            ap.error(
                "--fleet needs --paged --prefill-chunk N: replicas "
                "are paged engines (prefix affinity is trie state) "
                "and redispatch replays prompt + committed tokens, "
                "which can exceed any single prefill bucket"
            )
        if args.disagg:
            ap.error("--fleet and --disagg are mutually exclusive")
        if args.spec != "off":
            ap.error(
                "--fleet does not consume --spec (reset_pool cannot "
                "flush a mirrored draft pool)"
            )
        if args.capture_dir:
            ap.error(
                "--capture-dir is only consumed by the single-engine "
                "--loadgen harness"
            )
        if args.fleet_min is not None and not \
                1 <= args.fleet_min <= args.fleet:
            ap.error(
                f"--fleet-min {args.fleet_min} must be in "
                f"[1, --fleet {args.fleet}]"
            )
        if args.fleet_swap_at is not None and args.fleet_swap_at < 0:
            ap.error(
                f"--fleet-swap-at {args.fleet_swap_at} must be >= 0"
            )
    else:
        for flag, val in (
            ("--fleet-min", args.fleet_min),
            ("--fleet-router", args.fleet_router),
            ("--fleet-swap-at", args.fleet_swap_at),
        ):
            if val is not None:
                ap.error(
                    f"{flag} is only consumed together with --fleet"
                )
    if args.top_p is not None and args.temperature is None:
        ap.error(
            "--top-p is only consumed together with --temperature"
        )
    # Range-check at parse like every sibling spec flag: an
    # out-of-range value must not burn a full bring-up+warmup before
    # Request.__post_init__ rejects it with a traceback.
    if args.temperature is not None and args.temperature < 0:
        ap.error(
            f"--temperature {args.temperature} must be >= 0"
        )
    if args.top_p is not None and not 0.0 < args.top_p <= 1.0:
        ap.error(f"--top-p {args.top_p} must be in (0, 1]")
    if args.spec_k is not None and args.spec_k < 1:
        ap.error(f"--spec-k {args.spec_k} must be >= 1")

    if args.sim_devices:
        from tpu_hpc.runtime import sim

        sim.force_sim_devices(args.sim_devices)
    from tpu_hpc.runtime import require_accelerator

    require_accelerator()

    if args.model == "tiny":
        cfg = tiny_config(args.vocab)
    elif args.model in hybrid_ssm_moe.PRESETS:
        cfg = hybrid_ssm_moe.PRESETS[args.model]
    else:
        cfg = llama2.PRESETS[args.model]
    buckets = tuple(int(b) for b in args.buckets.split(","))
    prompt_lens = tuple(int(p) for p in args.prompt_lens.split(","))
    too_long = [p for p in prompt_lens if p > max(buckets)]
    # --loadgen sizes its own prompt distribution to the buckets; the
    # replay mix's --prompt-lens is unused there and must not block.
    # With chunked prefill, prompts longer than the largest bucket
    # chunk through it and are perfectly servable.
    chunked = bool(args.paged and args.prefill_chunk)
    if too_long and not args.loadgen and not chunked:
        ap.error(
            f"prompt lens {too_long} exceed the largest bucket "
            f"{max(buckets)} (chunked prefill -- --paged "
            "--prefill-chunk N -- lifts this limit)"
        )
    # `is not None`, not truthiness: an explicit --max-seq-len 0 must
    # fail capacity validation loudly, not silently take the default.
    max_seq = (
        args.max_seq_len if args.max_seq_len is not None
        else max(buckets) + args.max_new
    )
    paged = None
    if args.paged:
        from tpu_hpc.serve.paging import derive_paged_config

        try:
            # The derived default capacity rounds up to a whole
            # number of pages; an explicit --max-seq-len must align
            # itself (loud). One shared derivation with bench.py --
            # the rows and the CLI must agree on every default.
            paged, max_seq = derive_paged_config(
                args.slots, max_seq, buckets,
                block_size=args.kv_block_size,
                num_blocks=args.kv_blocks,
                prefill_chunk=args.prefill_chunk,
                align_capacity=args.max_seq_len is None,
                host_blocks=args.kv_host_blocks,
                kernel=args.kv_kernel,
                kv_quant=args.kv_quant,
            )
        except ValueError as e:
            ap.error(str(e))
    if max_seq > cfg.max_seq_len:
        ap.error(
            f"cache capacity {max_seq} exceeds the model's "
            f"max_seq_len {cfg.max_seq_len}"
        )
    from tpu_hpc.serve.engine import ServeConfig

    serve_cfg = ServeConfig(
        slots=args.slots, max_seq_len=max_seq, prefill_buckets=buckets
    )
    spec_cfg = None
    spec_draft_cfg = None
    if args.spec != "off":
        from tpu_hpc.serve.spec import SpecConfig

        try:
            spec_cfg = SpecConfig(mode=args.spec, k=args.spec_k or 4)
        except ValueError as e:
            ap.error(str(e))
        if args.spec_draft_model and args.spec_draft_model != "half":
            spec_draft_cfg = llama2.PRESETS[args.spec_draft_model]
    if args.loadgen:
        from tpu_hpc.loadgen import SCENARIOS

        if args.loadgen not in SCENARIOS:
            ap.error(
                f"--loadgen {args.loadgen!r}: unknown scenario "
                f"(catalog: {', '.join(SCENARIOS)})"
            )
        # The scenario's output-length budget is what the cache has
        # left after the largest bucket; a combination that leaves
        # < 2 tokens is a CLI error, not a post-bring-up traceback.
        lg_max_new = min(args.max_new, max_seq - max(buckets))
        if lg_max_new < 2:
            ap.error(
                f"--loadgen: cache capacity {max_seq} minus the "
                f"largest bucket {max(buckets)} leaves "
                f"{max_seq - max(buckets)} generate tokens (< 2); "
                "raise --max-seq-len or --max-new"
            )
        if args.fleet is not None:
            import jax

            if jax.device_count() < args.fleet:
                ap.error(
                    f"--fleet {args.fleet} needs >= {args.fleet} "
                    f"devices (one slice each); only "
                    f"{jax.device_count()} visible -- use "
                    "--sim-devices N for development"
                )
            summary = run_fleet_loadgen(
                cfg, serve_cfg, args.loadgen, args.requests,
                args.max_new, paged,
                n_replicas=args.fleet,
                min_replicas=args.fleet_min or 1,
                router=args.fleet_router or "affinity",
                swap_at=args.fleet_swap_at,
                checkpoint_dir=args.checkpoint_dir,
                metrics_path=args.metrics, seed=args.seed,
            )
        else:
            summary = run_loadgen(
                cfg, serve_cfg, args.loadgen, args.requests,
                args.max_new,
                checkpoint_dir=args.checkpoint_dir,
                metrics_path=args.metrics, seed=args.seed,
                paged=paged,
                spec=spec_cfg,
                spec_draft_ckpt=args.spec_draft_ckpt,
                spec_draft_cfg=spec_draft_cfg,
                capture_dir=args.capture_dir,
            )
    else:
        if args.disagg:
            import jax

            if jax.device_count() < 2:
                ap.error(
                    "--disagg needs >= 2 devices (one per tier); "
                    f"only {jax.device_count()} visible -- use "
                    "--sim-devices N for development"
                )
        summary = run_replay(
            cfg, serve_cfg, args.requests, prompt_lens, args.max_new,
            checkpoint_dir=args.checkpoint_dir,
            metrics_path=args.metrics, seed=args.seed,
            disagg=args.disagg,
            disagg_max_inflight_mb=args.disagg_max_inflight_mb,
            paged=paged,
            spec=spec_cfg,
            spec_draft_ckpt=args.spec_draft_ckpt,
            spec_draft_cfg=spec_draft_cfg,
            temperature=args.temperature or 0.0,
            top_p=args.top_p if args.top_p is not None else 1.0,
        )
        del summary["outputs"]
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
