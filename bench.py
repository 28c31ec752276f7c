"""Headline benchmark: prints ONE JSON line for the driver.

Flagship metric: Llama-2 training throughput in tokens/sec/chip with
MFU accounting -- the BASELINE.md north-star metric (Llama-2 hybrid
FSDPxTP at >=40% MFU; the reference itself publishes no measured
throughput, so ``vs_baseline`` reports achieved-MFU / 0.40 against
that stated target). Runs whatever chips are visible: 1 chip = pure
compute path (TP/FSDP add nothing on one device), N chips = hybrid
recipe via the same code path as examples/06.

The model is sized to the single-chip HBM (v5e ~16 GiB): a ~170M-param
Llama with head_dim 128 (MXU-native), seq 2048, bf16 compute, per-block
remat, and the Pallas flash-attention kernel.

Secondary workload: ``--workload unet`` keeps the reference's own
instrumented DP U-Net throughput (multinode_ddp_unet.py:348-397).
"""
import argparse
import json
import os
import sys

def mfu_fields(tokens_per_s, flops_per_token, n_dev):
    """``(vs_baseline, text)`` for a training row: achieved MFU over
    the 40% north-star target, against the chip's peak from the single
    spec table in checks/roofline.py -- or None on the simulated run
    (no peak: a CPU rate is never turned into a utilisation)."""
    import jax

    from tpu_hpc.checks.roofline import peak_flops_for_device

    peak = peak_flops_for_device(jax.devices()[0])
    if peak is None:
        return None, "MFU n/a (simulated run)"
    mfu = tokens_per_s * flops_per_token / (peak * n_dev)
    return round(mfu / 0.40, 3), f"MFU {mfu:.1%}"


def resolve_batch_accum(batch, accum, microbatch: int):
    """One policy for every llama-family workload's batch/accum CLI
    defaults: with no --batch, run the family's measured-best
    microbatch accumulated 8x (batch = microbatch x accum, so an
    explicit --grad-accum-steps alone sweeps the accum lever at
    CONSTANT microbatch -- the lever-table protocol in
    docs/guide/xla_performance_notes.md, ceiling-budget subsection of
    the measured case study); with an explicit
    --batch and no --grad-accum-steps, run it unaccumulated (--batch 4
    reproduces the round-2 headline unchanged). ``0`` is passed
    through to the Trainer's own validation rather than silently
    replaced."""
    if batch is None:
        accum = 8 if accum is None else accum
        return microbatch * max(accum, 1), accum
    return batch, 1 if accum is None else accum


def flash_blocks_record(attn, block_q, block_k, block_q_bwd, block_k_bwd):
    """The effective flash-attention tiling as artifact fields, bwd
    defaults resolved -- so a JSON row always says which kernel shape
    produced it (the CLI and function defaults drifted once, ADVICE
    r5; now every artifact is self-describing)."""
    if attn != "flash":
        return {}
    return {
        "flash_blocks": {
            "q": block_q,
            "k": block_k,
            "q_bwd": block_q_bwd if block_q_bwd is not None else block_q,
            "k_bwd": block_k_bwd if block_k_bwd is not None else block_k,
        }
    }


def comm_mode_mesh(comm_mode: str, n_dev: int, n_slices: int = 1):
    """Mesh spec for a manual comm-mode run: ``(mesh_spec, batch_axes,
    dp_extent)``.

    Manual gradient-sync modes are DDP-family (replicated params), so
    the whole mesh is data parallelism. ``hierarchical`` needs the two
    fabric tiers as separate axes -- the shared construction policy
    (dcn resolution, validity, slice-aligned ``dcn_axes`` routing on
    real multi-slice hardware) lives in ``runtime.mesh.two_tier_spec``;
    the rejection here just names the CLI lever, because a record
    claiming "hierarchical" while silently measuring something else
    would poison the sweep."""
    from tpu_hpc.runtime import MeshSpec, two_tier_spec

    if comm_mode == "hierarchical":
        try:
            spec = two_tier_spec(n_dev, n_slices, inner_axis="data")
        except ValueError as e:
            raise ValueError(
                f"--comm-mode hierarchical: {e} -- use "
                "bucketed_overlap or flat on this topology"
            ) from None
        return spec, ("dcn", "data"), n_dev
    return MeshSpec(axes={"data": n_dev}), ("data",), n_dev


def bench_model_cfg(seq_len: int = 2048, remat: bool = False):
    """THE bench architecture: the ~170M-param Llama every llama-family
    workload runs, sized to single-chip v5e HBM. One factory so the
    DP headline, the SP rows, and the flagship pp row can never drift
    onto different architectures while claiming comparability."""
    from tpu_hpc.models import llama2

    return llama2.LlamaConfig(
        dim=1024, n_layers=8, n_heads=8, vocab_size=32000,
        multiple_of=256, max_seq_len=seq_len, remat=remat,
    )


def resolve_comm_auto(
    model_cfg,
    comm_table: "str | None" = None,
    bucket_cap_bytes: "int | None" = None,
):
    """Resolve --comm-mode auto for a llama-family workload: the
    collective planner's grad-sync decision (comm.planner) for the
    EXACT gradient payload of ``model_cfg`` on the visible topology.
    Runs before any array exists (eval_shape), because the resolved
    mode decides which mesh family the bench builds.
    ``bucket_cap_bytes`` defaults to the config's comm_bucket_mb --
    the same ladder cap the Trainer's own resolution would apply."""
    import math

    import jax
    import numpy as np

    from tpu_hpc.comm import planner as comm_planner
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import llama2
    from tpu_hpc.runtime.mesh import slice_groups, two_tier_spec

    if bucket_cap_bytes is None:
        bucket_cap_bytes = TrainingConfig().comm_bucket_mb * 2 ** 20

    abstract = jax.eval_shape(
        lambda k: llama2.init_llama(k, model_cfg),
        jax.random.key(0),
    )
    payload = sum(
        int(math.prod(l.shape)) * np.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(abstract)
    )
    n_dev = jax.device_count()
    n_slices = len(slice_groups(jax.devices()))
    try:
        two_tier_spec(n_dev, n_slices)
        two_tier_ok = True
    except ValueError:
        two_tier_ok = False
    table = (
        comm_planner.load_table(comm_table) if comm_table else None
    )
    return comm_planner.Planner.for_devices(
        table=table
    ).plan_grad_sync(
        payload, two_tier=two_tier_ok,
        bucket_cap_bytes=bucket_cap_bytes,
    )


def bench_llama(
    steps: int = 20, remat: bool = False, batch_per_dp: int = 4,
    attn: str = "flash", block_q: int = 512, block_k: int = 1024,
    seq_len: int = 2048, grad_accum_steps: int = 1,
    moments_dtype: str = "float32",
    block_q_bwd: "int | None" = None, block_k_bwd: "int | None" = None,
    comm_mode: str = "flat",
    guard_mode: str = "off",
    comm_table: "str | None" = None,
) -> dict:
    """The single-chip training configuration the CLI runs by default
    (the *function* defaults are the unaccumulated config; main()
    resolves the CLI policy via resolve_batch_accum): no remat (the
    model fits HBM), Pallas flash attention with 512/1024 q/k blocks
    (the function default matches the CLI so both entry points measure
    the same tiling; every record carries the effective blocks),
    microbatch 4, grad-accum 8 over a batch of 32 (amortizing the fp32
    AdamW state traffic across 8x the tokens), fp32 moments,
    gather-forward/matmul-backward embedding, contiguous-pair RoPE.
    These levers were tuned in earlier rounds; the artifacts of those
    runs are gone and none has been re-measured on the chip in this
    round (PERF.md carries what has been)."""
    import jax

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import datasets, llama2
    from tpu_hpc.parallel import fsdp, hybrid, tp
    from tpu_hpc.runtime import MeshSpec, build_mesh, init_distributed
    from tpu_hpc.train import Trainer

    init_distributed(verbose=False)
    n_dev = jax.device_count()
    model_cfg = bench_model_cfg(seq_len, remat)

    # comm_mode="auto": resolve the gradient-sync strategy through the
    # collective planner BEFORE the mesh is built -- the resolved mode
    # decides the mesh family (manual modes are pure-DP; hierarchical
    # needs the two-tier axes), so the resolution cannot live inside
    # the Trainer here. Payload is the exact gradient byte count from
    # an eval_shape (no arrays materialize); the record carries the
    # "auto" label, the resolved mode, and the full decision so a
    # sweep can attribute the row to the planner's reasoning.
    comm_mode_requested = comm_mode
    comm_decision = None
    if comm_mode == "auto":
        comm_decision = resolve_comm_auto(model_cfg, comm_table)
        comm_mode = comm_decision.mode
        print(
            f"llama bench | comm_mode auto -> {comm_mode} "
            f"[{comm_decision.source}] "
            f"pred {comm_decision.predicted_cost_s * 1e3:.3f} ms/sync",
            file=sys.stderr,
        )

    def make_attn_fn(mesh, tp_size):
        if attn == "xla":
            return None  # the model's einsum path (XLA-fused)
        # Pallas flash (GQA in-kernel, no repeated KV); multi-chip
        # runs it under shard_map with heads on the TP axis. Manual
        # comm modes run the WHOLE forward per-shard inside one
        # shard_map (comm.overlap), so they take the bare batch-local
        # closure (wrap=False): nesting a second shard_map over the
        # same mesh would fail to trace (the same batch-local idiom
        # bench_llama_pp's stages use), and the shared factory keeps
        # comm-mode rows on the identical kernel config as flat rows.
        return tp.make_tp_flash_attn_fn(
            mesh, "data", "model" if tp_size > 1 else None,
            block_q=block_q, block_k=block_k,
            block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
            wrap=(comm_mode == "flat"),
        )

    from jax.sharding import PartitionSpec as P

    batch_pspec = P("data")
    if comm_mode != "flat":
        # Manual gradient-sync modes (tpu_hpc.comm.overlap) are
        # DDP-family: replicated params, batch over the whole data
        # axis (both tiers of it in hierarchical mode). FSDP/TP
        # layouts keep GSPMD's fused collectives
        # (fsdp.validate_grad_sync_mode rejects them loudly), so the
        # comm-mode rows measure pure-DP sync strategy, attributable
        # via the record's comm_mode field.
        from tpu_hpc.runtime.mesh import slice_groups

        mesh_spec, batch_axes, dp_size = comm_mode_mesh(
            comm_mode, n_dev, len(slice_groups(jax.devices()))
        )
        batch_pspec = P(batch_axes)
        axes = mesh_spec.resolved_sizes(n_dev)
    else:
        axes = tp.auto_mesh_axes(
            n_dev, model_cfg.n_heads, model_cfg.kv_heads, cap=4
        )
        dp_size = axes["data"]
        mesh_spec = MeshSpec(axes=axes)
    tp_size = axes.get("model", 1)
    mesh = build_mesh(mesh_spec)

    params = llama2.init_llama(jax.random.key(0), model_cfg)
    if tp_size > 1:
        specs = hybrid.hybrid_pspecs(
            params, tp.llama_rules(), data_size=dp_size
        )
        constrain = tp.sp_constrain(mesh, dp_axis="data", sp_axis="model")
    elif dp_size > 1 and comm_mode == "flat":
        specs = fsdp.param_pspecs(params, axis="data", axis_size=dp_size)
        constrain = lambda x: x  # noqa: E731
    else:
        specs = None
        constrain = lambda x: x  # noqa: E731

    cfg = TrainingConfig(
        epochs=2,  # epoch 0 absorbs compilation; epoch 1 is measured
        steps_per_epoch=steps,
        global_batch_size=batch_per_dp * dp_size,
        learning_rate=3e-4,
        weight_decay=0.1,
        grad_accum_steps=grad_accum_steps,
        adam_moments_dtype=moments_dtype,
        # The REQUESTED mode: under "auto" the trainer consumes the
        # pre-resolved decision below (bench had to resolve it first
        # -- the mode picks the mesh family), so the planner's exact
        # bucket choice is honored, not re-derived.
        comm_mode=comm_mode_requested,
        guard_mode=guard_mode,
    )
    ds = datasets.TokenStream(
        vocab_size=model_cfg.vocab_size, seq_len=model_cfg.max_seq_len
    )
    trainer = Trainer(
        cfg, mesh,
        llama2.make_forward(
            model_cfg, constrain, make_attn_fn(mesh, tp_size)
        ),
        params, param_pspecs=specs, batch_pspec=batch_pspec,
        comm_plan=comm_decision,
    )
    result = trainer.fit(ds)
    summary = result["epochs"][-1]
    tokens_per_s = summary["items_per_s"] * model_cfg.max_seq_len
    flops_per_token = model_cfg.flops_per_token(model_cfg.max_seq_len)
    vs_baseline, mfu_text = mfu_fields(
        tokens_per_s, flops_per_token, n_dev
    )
    print(
        f"llama bench | mesh {axes} | {tokens_per_s:.0f} tokens/s | "
        f"{tokens_per_s / n_dev:.0f} tokens/s/chip | {mfu_text} "
        f"({flops_per_token / 1e6:.0f} MFLOP/token)",
        file=sys.stderr,
    )
    return {
        "metric": "llama2_train_tokens_per_s_per_chip",
        "value": round(tokens_per_s / n_dev, 1),
        "unit": "tokens/s/chip",
        # Reference publishes no measured numbers (BASELINE.md);
        # compare against its stated >=40%-MFU target instead.
        "vs_baseline": vs_baseline,
        # Effective attention config: rows from the CLI and from
        # programmatic callers must be distinguishable (ADVICE r5).
        "attn": attn,
        # Gradient-sync strategy: BENCH JSONLs must be able to
        # attribute a step-time delta to the comm layer, not guess it.
        # Under "auto" the row carries the label AND the resolution:
        # a sweep must be able to tell "the planner picked flat" from
        # "the operator picked flat".
        "comm_mode": comm_mode_requested,
        **(
            {
                "comm_mode_resolved": comm_mode,
                "comm_plan": comm_decision.summary(),
            }
            if comm_decision is not None else {}
        ),
        # Numeric-health guard: the health vector rides the jitted
        # step, so a guarded row quantifies exactly what the guard
        # costs (the zero-recompile claim's measured counterpart).
        "guard_mode": guard_mode,
        **flash_blocks_record(
            attn, block_q, block_k, block_q_bwd, block_k_bwd
        ),
    }


def bench_llama_sp(
    steps: int = 20, batch_per_dp: int = 4, sp_mode: str = "zigzag",
    grad_accum_steps: int = 1, moments_dtype: str = "float32",
) -> dict:
    """Sequence-parallel Llama throughput: the ring / zigzag / Ulysses
    code paths under the real training loop (VERDICT r1: these paths
    had no recorded BENCH artifact). Context axis = all visible chips
    (1 chip: degenerate ring, still the kernel-under-shard_map path
    that otherwise only runs in tests). Takes the same grad-accum
    amortization as the headline (the AdamW-traffic lever is
    layout-independent)."""
    import jax

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import datasets, llama2
    from tpu_hpc.parallel import ring_attention as ra
    from tpu_hpc.parallel import sp_ulysses
    from tpu_hpc.runtime import MeshSpec, build_mesh, init_distributed
    from tpu_hpc.train import Trainer

    init_distributed(verbose=False)
    n_dev = jax.device_count()
    model_cfg = bench_model_cfg()
    mesh = build_mesh(MeshSpec(axes={"data": 1, "context": n_dev}))
    zigzag_ring = None
    if sp_mode == "zigzag":
        # Production layout: loader emits zigzag order once per batch,
        # the balanced ring runs with zero per-layer permutes, RoPE
        # reads the slots' global positions.
        zigzag_ring = n_dev
        attn_fn = ra.make_zigzag_ring_attn_fn(
            mesh, "data", "context", data_layout="zigzag"
        )
    elif sp_mode == "ring":
        attn_fn = ra.make_ring_attn_fn(mesh, "data", "context")
    elif sp_mode == "ulysses":
        attn_fn = sp_ulysses.make_ulysses_attn_fn(
            mesh, "data", "context"
        )
    else:
        raise ValueError(
            f"unknown sp_mode {sp_mode!r} (ring|zigzag|ulysses)"
        )
    constrain = ra.cp_constrain(mesh, "data", "context")

    cfg = TrainingConfig(
        epochs=2,  # epoch 0 absorbs compilation; epoch 1 is measured
        steps_per_epoch=steps,
        global_batch_size=batch_per_dp,
        learning_rate=3e-4,
        weight_decay=0.1,
        grad_accum_steps=grad_accum_steps,
        adam_moments_dtype=moments_dtype,
    )
    ds = datasets.TokenStream(
        vocab_size=model_cfg.vocab_size, seq_len=model_cfg.max_seq_len,
        zigzag_ring=zigzag_ring,
    )
    params = llama2.init_llama(jax.random.key(0), model_cfg)
    trainer = Trainer(
        cfg, mesh,
        llama2.make_forward(
            model_cfg, constrain, attn_fn, ds.positions()
        ),
        params,
    )
    result = trainer.fit(ds)
    summary = result["epochs"][-1]
    tokens_per_s = summary["items_per_s"] * model_cfg.max_seq_len
    flops_per_token = model_cfg.flops_per_token(model_cfg.max_seq_len)
    vs_baseline, mfu_text = mfu_fields(
        tokens_per_s, flops_per_token, n_dev
    )
    print(
        f"llama-sp[{sp_mode}] | context={n_dev} | "
        f"{tokens_per_s:.0f} tokens/s | {mfu_text}",
        file=sys.stderr,
    )
    return {
        "metric": f"llama2_sp_{sp_mode}_tokens_per_s_per_chip",
        "value": round(tokens_per_s / n_dev, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": vs_baseline,
    }


def bench_llama_long(
    steps: int = 20, seq_len: int = 8192, batch: int = 1,
    remat: bool = False, grad_accum_steps: int = 1,
    moments_dtype: str = "float32",
    block_q: int = 512, block_k: int = 1024,
    block_q_bwd: "int | None" = None, block_k_bwd: "int | None" = None,
    comm_mode: str = "flat",
    guard_mode: str = "off",
    comm_table: "str | None" = None,
) -> dict:
    """Long-context Llama: seq 8192 (4x the headline bench) -- the
    long-sequence regime the SP family exists for. Same harness as
    bench_llama (so multi-chip sharding, flash/xla selection and
    block tuning stay in one place), at microbatch 1/chip (the CLI
    default resolves to batch 8 x accum 8; the function defaults are
    the unaccumulated batch-1 config). The bench model
    still fits HBM unrematerialized at batch 1, and remat costs ~24%
    here (45.3% vs 34.4% MFU measured on v5e), so remat stays opt-in
    (--remat); at 7B scale the fit analysis (checks/fit.py) shows
    where it becomes mandatory."""
    rec = bench_llama(
        steps, remat, batch, "flash", block_q, block_k,
        seq_len=seq_len, grad_accum_steps=grad_accum_steps,
        moments_dtype=moments_dtype,
        block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
        comm_mode=comm_mode, guard_mode=guard_mode,
        comm_table=comm_table,
    )
    rec["metric"] = f"llama2_seq{seq_len}_tokens_per_s_per_chip"
    return rec


def bench_llama_pp(
    steps: int = 20, schedule: str = "1f1b", microbatches: int = 8,
    microbatch_size: int = 4, attn: str = "flash",
    block_q: int = 512, block_k: int = 1024,
    block_q_bwd: "int | None" = None, block_k_bwd: "int | None" = None,
    grad_accum_steps: int = 1, backward: str = "remat",
    remat_stage: "bool | None" = None,
    model: str = "stack",
) -> dict:
    """Pipeline-parallel throughput (VERDICT r1: the PP path had no
    BENCH artifact). Stages fill the visible chips (1 chip: one stage
    through the same pipelined program -- degenerate ring, real code
    path); reports tokens/s, MFU, plus the analytic bubble fraction.

    ``model="llama"`` pipelines the FLAGSHIP model itself
    (models/llama_pp.py stage-splits the same 8-layer dim-1024 Llama
    the DP headline trains -- bench_model_cfg, one factory -- so the
    row is directly comparable to the 121k tok/s/chip headline). All
    four schedules: the interleaved ones stack the stages in the
    Megatron round-robin layout via split_params_interleaved (v=2
    when the depth divides).

    Round-4 parity with the headline bench (VERDICT r3 weak #2: PP
    ran at 42% of the DP path): bf16 compute (PipeConfig's fp32
    default forfeited the MXU bf16 rate), microbatch SIZE 4 (was 1 --
    batch-1 matmuls underfill), the Pallas flash kernel in the stage
    (called batch-locally inside pp's shard_map), and grad-accum.
    What remains vs DP is the schedule itself: the 1f1b schedules'
    custom-vjp backward costs extra stage forwards (remat 5/3 of
    ideal FLOPs, --pp-backward stash 4/3), and
    bubbles at S>1 -- both reported, neither counted into MFU's
    denominator."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.kernels.attention import blockwise_attention
    from tpu_hpc.models import datasets, losses
    from tpu_hpc.models import pipeline_transformer as ptx
    from tpu_hpc.parallel import pp
    from tpu_hpc.runtime import MeshSpec, build_mesh, init_distributed
    from tpu_hpc.train import Trainer

    if grad_accum_steps > 1 and microbatch_size % grad_accum_steps:
        # Each accum microstep carries batch/accum rows, which must
        # still split into `microbatches` pipeline microbatches --
        # otherwise pp.microbatch raises deep inside tracing.
        raise ValueError(
            f"--grad-accum-steps {grad_accum_steps} must divide the "
            f"pipeline microbatch size {microbatch_size} (PP already "
            "amortizes the optimizer over its microbatches; accum on "
            "top only makes sense when it divides evenly)"
        )
    if model not in ("stack", "llama"):
        raise ValueError(f"unknown pp model {model!r} (stack|llama)")
    init_distributed(verbose=False)
    n_dev = jax.device_count()
    n_stages = n_dev
    mesh = build_mesh(MeshSpec(axes={"pipe": n_stages}))
    # v=2 only while the total depth (8 layers) still divides over
    # v*S stages -- otherwise the interleaved model would have MORE
    # layers than the gpipe/1f1b baselines and tokens/s would compare
    # apples to oranges.
    v = (
        2
        if schedule in ("interleaved", "interleaved-1f1b")
        and 1 < n_stages and 8 % (2 * n_stages) == 0
        else 1
    )
    model_cfg = ptx.PipeConfig(
        vocab_size=32000, dim=1024, n_heads=8, n_stages=n_stages * v,
        layers_per_stage=max(8 // (n_stages * v), 1), max_seq_len=2048,
        dtype=jnp.bfloat16,
    )
    attn_fn = None
    if attn == "flash":
        # Batch-local call (each stage owns its microbatch inside pp's
        # shard_map) -- no nested shard_map; auto falls back to the
        # XLA path on CPU-simulated meshes.
        def attn_fn(q, k, v_):
            out, _ = blockwise_attention(
                q, k, v_, causal=True,
                block_q=block_q, block_k=block_k,
                block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
            )
            return out
    # No coercion: --pp-backward stash with a non-1f1b schedule gets
    # pp.pipelined's clear ValueError instead of silently benchmarking
    # a different backward than the artifact claims.
    if remat_stage is None:
        # The autodiff schedules' backward saves EVERY tick
        # intermediate without this -- measured 51.9G (3.3x HBM) at
        # the re-levered mb 8x4 bf16 config on v5e. remat_stage puts
        # gpipe/interleaved at the same save-stage-inputs memory point
        # the 1f1b custom backward has by construction, which is the
        # comparable configuration.
        remat_stage = schedule in ("gpipe", "interleaved")
    if model == "llama":
        # The flagship itself, stage-split: SAME architecture as the
        # DP headline bench (bench_model_cfg), so this row is
        # directly comparable to it.
        from tpu_hpc.models import llama2, llama_pp

        lcfg = bench_model_cfg()
        if lcfg.n_layers % (n_stages * v):
            raise ValueError(
                f"llama pp needs n_layers {lcfg.n_layers} divisible "
                f"by stages {n_stages} x chunks {v}"
            )
        full = llama2.init_llama(jax.random.key(0), lcfg)
        params = (
            llama_pp.split_params_interleaved(full, lcfg, n_stages, v)
            if v > 1 else
            llama_pp.split_params(full, lcfg, n_stages)
        )
        specs = llama_pp.pp_pspecs(params)
        forward = llama_pp.make_forward(
            lcfg, mesh, n_microbatches=microbatches,
            schedule=schedule, backward=backward, batch_spec=P(),
            attn_fn=attn_fn, remat_stage=remat_stage, n_chunks=v,
        )
        model_cfg = lcfg  # flops_per_token/max_seq_len/vocab source
    else:
        params = ptx.init_pipeline_transformer(
            jax.random.key(0), model_cfg
        )
        if v > 1:
            params = dict(
                params,
                stages=pp.interleave_stacked(params["stages"], n_stages),
            )
        specs = {
            "embed": jax.tree.map(lambda _: P(), params["embed"]),
            "stages": pp.stage_pspecs(params["stages"], axis="pipe"),
            "head": jax.tree.map(lambda _: P(), params["head"]),
        }
        pipe = pp.pipelined(
            ptx.make_stage_fn(model_cfg, attn_fn), mesh, axis="pipe",
            schedule=schedule, batch_spec=P(), n_chunks=v,
            backward=backward, remat_stage=remat_stage,
        )

        def forward(params, model_state, batch, step_rng):
            inputs, targets = batch
            xs = ptx.embed(
                params, pp.microbatch(inputs, microbatches), model_cfg
            )
            ys = pipe(params["stages"], xs)
            logits = ptx.head(params, ys, model_cfg)
            loss = losses.cross_entropy(
                logits, pp.microbatch(targets, microbatches)
            )
            return loss, model_state, {}

    cfg = TrainingConfig(
        epochs=2, steps_per_epoch=steps,
        global_batch_size=microbatches * microbatch_size,
        learning_rate=3e-4, weight_decay=0.1,
        grad_accum_steps=grad_accum_steps,
    )
    ds = datasets.TokenStream(
        vocab_size=model_cfg.vocab_size, seq_len=model_cfg.max_seq_len
    )
    trainer = Trainer(
        cfg, mesh, forward, params, param_pspecs=specs, batch_pspec=P(),
    )
    result = trainer.fit(ds)
    summary = result["epochs"][-1]
    tokens_per_s = summary["items_per_s"] * model_cfg.max_seq_len
    bubble = pp.bubble_fraction(n_stages, microbatches, n_chunks=v)
    flops_per_token = model_cfg.flops_per_token()
    vs_baseline, mfu_text = mfu_fields(
        tokens_per_s, flops_per_token, n_dev
    )
    tag = (
        f"-{backward}"
        if schedule in ("1f1b", "interleaved-1f1b")
        and backward != "remat" else ""
    ) + ("-llama" if model == "llama" else "")
    print(
        f"llama-pp[{schedule}{tag}] | stages={n_stages} "
        f"mb={microbatches}x{microbatch_size} bubble {bubble:.1%} | "
        f"{tokens_per_s:.0f} tokens/s | {mfu_text}",
        file=sys.stderr,
    )
    return {
        "metric": f"pp_{schedule}{tag}_tokens_per_s_per_chip",
        "value": round(tokens_per_s / n_dev, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": vs_baseline,
        # Self-describing: the interleaved schedules degenerate to
        # v=1 when the 8-layer bench model cannot split into 2*S
        # chunks (e.g. 8 stages) -- a record without this field would
        # present a duplicate of the 1f1b row as interleaved evidence.
        "n_chunks": v,
        "bubble_fraction": round(bubble, 4),
        "attn": attn,
        **flash_blocks_record(
            attn, block_q, block_k, block_q_bwd, block_k_bwd
        ),
    }


def bench_llama_pp_mpmd(
    steps: int, microbatches: int, microbatch_size: int = 4,
    attn: str = "flash",
    block_q: int = 512, block_k: int = 1024,
    block_q_bwd: "int | None" = None, block_k_bwd: "int | None" = None,
    model: str = "stack",
) -> dict:
    """The MPMD pipeline runtime row (``--pp-runtime mpmd``):
    per-stage AOT programs dispatched per stage worker
    (tpu_hpc.parallel.mpmd) instead of one SPMD shard_map tick loop.
    One stage per visible device (disjoint fault domains); reports
    tokens/s plus the runtime's MEASURED bubble fraction and -- when
    ``TPU_HPC_FAULTS`` arms a stage fault -- the recovery MTTR and
    per-stage restart/rollback counts, so the banked ``pp_mpmd_*``
    family carries the robustness evidence next to the throughput
    headline. Zero steady-state recompiles is part of the record
    (``recompiles``), pinned like every serving row's."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_hpc.kernels.attention import blockwise_attention
    from tpu_hpc.models import datasets
    from tpu_hpc.models import pipeline_transformer as ptx
    from tpu_hpc.parallel import mpmd
    from tpu_hpc.runtime import init_distributed

    if model not in ("stack", "llama"):
        raise ValueError(f"unknown pp model {model!r} (stack|llama)")
    init_distributed(verbose=False)
    n_dev = jax.device_count()
    n_stages = n_dev
    attn_fn = None
    if attn == "flash":
        def attn_fn(q, k, v_):
            out, _ = blockwise_attention(
                q, k, v_, causal=True,
                block_q=block_q, block_k=block_k,
                block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
            )
            return out
    if model == "llama":
        from tpu_hpc.models import llama2, llama_pp

        lcfg = bench_model_cfg()
        if lcfg.n_layers % n_stages:
            raise ValueError(
                f"llama mpmd needs n_layers {lcfg.n_layers} "
                f"divisible by {n_stages} stages"
            )
        full = llama2.init_llama(jax.random.key(0), lcfg)
        split = llama_pp.split_params(full, lcfg, n_stages)
        bundle = llama_pp.mpmd_bundle(split, lcfg, attn_fn=attn_fn)
        model_cfg = lcfg
    else:
        model_cfg = ptx.PipeConfig(
            vocab_size=32000, dim=1024, n_heads=8,
            n_stages=n_stages,
            layers_per_stage=max(8 // n_stages, 1),
            max_seq_len=2048, dtype=jnp.bfloat16,
        )
        params = ptx.init_pipeline_transformer(
            jax.random.key(0), model_cfg
        )
        bundle = ptx.mpmd_bundle(params, model_cfg, attn_fn=attn_fn)
    cfg = mpmd.MpmdConfig(
        n_microbatches=microbatches, learning_rate=3e-4,
    )
    ds = datasets.TokenStream(
        vocab_size=model_cfg.vocab_size, seq_len=model_cfg.max_seq_len
    )
    batch = microbatches * microbatch_size
    batches = [
        tuple(np.asarray(a) for a in ds.batch_at(i, batch))
        for i in range(steps + 1)
    ]
    pipe = mpmd.MpmdPipeline(bundle, cfg).build(batches[0][0])
    warm_counts = list(pipe.compile_counts)
    pipe.run_step(0, *batches[0])  # warm dispatch outside the timing
    t0 = _time.perf_counter()
    for step, (tokens, targets) in enumerate(batches[1:], start=1):
        pipe.run_step(step, tokens, targets)
    wall = _time.perf_counter() - t0
    res = {
        "bubble_fraction": (
            float(np.mean(pipe.bubble_fractions))
            if pipe.bubble_fractions else 0.0
        ),
        "recovery_mttr_s": (
            float(np.mean([r["mttr_s"] for r in pipe.recoveries]))
            if pipe.recoveries else 0.0
        ),
    }
    recompiles = sum(pipe.compile_counts) - sum(warm_counts)
    tokens_per_s = steps * batch * model_cfg.max_seq_len / wall
    flops_per_token = model_cfg.flops_per_token()
    vs_baseline, mfu_text = mfu_fields(
        tokens_per_s, flops_per_token, n_dev
    )
    tag = "-llama" if model == "llama" else ""
    # A chaos-armed run banks under its OWN pp_mpmd*-chaos family:
    # its recovery MTTR / redispatch counts are that family's judged
    # baseline (robustness drift at the same chaos schedule fails
    # --bank), and they must never pollute the clean family's
    # mttr==0 high-water mark.
    armed = (
        pipe.fault_plan.stage_fault_keys()
        if pipe.fault_plan is not None else []
    )
    if armed:
        tag += "-chaos"
    print(
        f"llama-pp[mpmd{tag}] | stages={n_stages} "
        f"mb={microbatches}x{microbatch_size} "
        f"bubble {res['bubble_fraction']:.1%} | "
        f"{tokens_per_s:.0f} tokens/s | {mfu_text} | "
        f"restarts {dict(pipe.supervisor.restarts)} "
        f"rollbacks {dict(pipe.supervisor.rollbacks)} "
        f"mttr {res['recovery_mttr_s']:.2f}s",
        file=sys.stderr,
    )
    return {
        "metric": f"pp_mpmd{tag}_tokens_per_s_per_chip",
        "value": round(tokens_per_s / n_dev, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": vs_baseline,
        "pp_runtime": "mpmd",
        **({"faults": ",".join(armed)} if armed else {}),
        "bubble_fraction": round(res["bubble_fraction"], 4),
        "recovery_mttr_s": round(res["recovery_mttr_s"], 3),
        "stage_restarts": sum(pipe.supervisor.restarts.values()),
        "stage_rollbacks": sum(pipe.supervisor.rollbacks.values()),
        "redispatched": pipe.redispatched,
        "recompiles": recompiles,
        "wire_mb": round(pipe.wire_bytes / 2**20, 2),
        "attn": attn,
        **flash_blocks_record(
            attn, block_q, block_k, block_q_bwd, block_k_bwd
        ),
    }


def bench_elastic(
    steps: int, shrink_at: int = 2, grow_at: int = 4,
) -> dict:
    """The preemption-storm acceptance row (tpu_hpc.elastic): one
    training run driven through shrink -> train -> grow -> train by
    the topology coordinator, ZERO process restarts, judged against a
    fixed-topology reference on the final layout. The banked
    ``elastic_morph_*`` family carries the transition costs -- mean
    stall seconds per morph as the headline, morph count and wire
    bytes as side keys (all lower-is-better) -- so a coordinator
    change that starts moving more bytes or stalling longer at the
    same chaos schedule fails ``--bank``. ``loss_parity`` records
    whether the morphing run's loss stream stayed bit-identical to
    the fixed run (the data-extent-preserving layout policy's whole
    point)."""
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.elastic import TopologyCoordinator, choose_layout
    from tpu_hpc.runtime import MeshSpec, build_mesh, init_distributed
    from tpu_hpc.train.trainer import Trainer

    init_distributed(verbose=False)
    n_dev = jax.device_count()
    # The storm must actually change the topology: shrink keeps half
    # the pool, so the data axis is pinned to the extent both halves
    # can carry.
    extent = max(n_dev // 2, 1)
    batch = extent * 4

    def init_params():
        k1, k2 = jax.random.split(jax.random.key(7))
        return {
            "w1": jax.random.normal(k1, (64, 128), jnp.float32) * 0.1,
            "w2": jax.random.normal(k2, (128, 16), jnp.float32) * 0.1,
        }

    def forward(params, model_state, b, rng):
        pred = jnp.tanh(b["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - b["y"]) ** 2), model_state, {}

    class _DS:
        def batch_at(self, step, gbs):
            k = jax.random.key(1000 + int(step))
            kx, ky = jax.random.split(k)
            return {
                "x": jax.random.normal(kx, (gbs, 64), jnp.float32),
                "y": jax.random.normal(ky, (gbs, 16), jnp.float32),
            }

    def cfg_for(path):
        return TrainingConfig(
            epochs=steps, steps_per_epoch=1, global_batch_size=batch,
            learning_rate=1e-2, weight_decay=0.01, metrics_path=path,
        )

    def factory_for(cfg):
        def factory(mesh):
            params = init_params()
            return Trainer(
                cfg, mesh, forward, params,
                param_pspecs=jax.tree.map(lambda _: P(), params),
                batch_pspec=P("data"),
            )
        return factory

    def losses_from(path):
        out = []
        with open(path) as f:
            for line in f:
                r = json.loads(line)
                if r.get("event") == "epoch":
                    out.append((r["step"], r["loss"]))
        return out

    tmp = tempfile.mkdtemp(prefix="bench_elastic_")
    # Fixed-topology reference on the FINAL layout (the full pool,
    # same layout policy) -- built before the chaos schedule is
    # armed, or the un-managed Trainer would rightly refuse it.
    fixed_path = os.path.join(tmp, "fixed.jsonl")
    decision = choose_layout(
        jax.devices(), global_batch=batch, current_data_extent=extent
    )
    fixed_mesh = build_mesh(
        MeshSpec(axes=dict(decision.axes)), devices=jax.devices()
    )
    fixed_tr = factory_for(cfg_for(fixed_path))(fixed_mesh)
    fixed_tr.fit(_DS())

    morph_path = os.path.join(tmp, "morph.jsonl")
    prev = os.environ.get("TPU_HPC_FAULTS")
    os.environ["TPU_HPC_FAULTS"] = (
        f"slice_down_at_step={shrink_at},slice_up_at_step={grow_at}"
    )
    t0 = _time.perf_counter()
    try:
        coord = TopologyCoordinator(
            factory_for(cfg_for(morph_path)),
            global_batch=batch, data_extent=extent,
        )
        summary = coord.run(_DS())
    finally:
        if prev is None:
            os.environ.pop("TPU_HPC_FAULTS", None)
        else:
            os.environ["TPU_HPC_FAULTS"] = prev
    wall = _time.perf_counter() - t0
    parity = losses_from(fixed_path) == losses_from(morph_path)
    morphs = summary["morph_count"]
    print(
        f"elastic | {n_dev} devices, shrink@{shrink_at} "
        f"grow@{grow_at} | {morphs} morphs, "
        f"{summary['wire_bytes']} wire bytes, "
        f"{summary['stall_s']:.3f}s stall | restarts "
        f"{summary['restarts']} | loss parity {parity} | "
        f"{wall:.1f}s wall",
        file=sys.stderr,
    )
    return {
        "metric": "elastic_morph_stall_s",
        "value": round(summary["stall_s"] / max(morphs, 1), 6),
        "unit": "s",
        "vs_baseline": None,
        "faults": (
            f"slice_down_at_step={shrink_at},"
            f"slice_up_at_step={grow_at}"
        ),
        "morphs": morphs,
        "morph_wire_bytes": summary["wire_bytes"],
        "stall_s": summary["stall_s"],
        "restarts": summary["restarts"],
        "segments": len(summary["segments"]),
        "loss_parity": parity,
        "n_devices": n_dev,
    }


def _kv_metric_tag(summary: dict) -> str:
    """Metric-family suffix for the paged read path
    (tpu_hpc.kernels.paged_attention): '' for the default gather/fp
    pool -- pre-existing banked histories continue untouched --
    '_pallas', '_q8', or '_pallas_q8' otherwise, so each read-path
    trajectory banks against its own high-water marks."""
    tag = ""
    if summary.get("kv_kernel", "gather") == "pallas":
        tag += "_pallas"
    if summary.get("kv_quant", "none") == "int8":
        tag += "_q8"
    return tag


def serve_record(summary: dict, disagg: bool = False) -> dict:
    """Serving summary -> the training-bench record schema
    (metric/value/unit/vs_baseline), with the serving-native latency
    quantiles riding along. vs_baseline = serving MFU (forward-only
    2N accounting, train.metrics.mfu mode="inference") against the
    same 40% north-star target the training rows use; None on
    backends with no published peak (CPU sim). The KV-cache layout
    (slab|paged, block size, prefix-hit rate) is part of the record's
    identity -- a paged row must never be diffed against a slab one
    unlabeled."""
    mfu = summary.get("serve_mfu")
    rec_serve = {
        "requests": summary["requests"],
        "slots": summary["slots"],
        "prefill_buckets": summary["prefill_buckets"],
        "recompiles": summary["recompiles"],
        "kv_layout": summary.get("kv_layout", "slab"),
    }
    kv_tag = ""
    if summary.get("kv_layout") == "paged":
        rec_serve.update(
            kv_block_size=summary.get("kv_block_size"),
            kv_blocks=summary.get("kv_blocks"),
            kv_kernel=summary.get("kv_kernel", "gather"),
            kv_quant=summary.get("kv_quant", "none"),
            prefix_hit_rate=round(
                summary.get("prefix_hit_rate", 0.0), 4
            ),
            prefix_hit_blocks=summary.get("prefix_hit_blocks", 0),
            block_stalls=summary.get("batcher", {}).get(
                "block_stalls", 0
            ),
        )
        # Read path + storage dtype are part of the metric FAMILY
        # (the kv_layout discipline): a pallas or int8 row banked
        # under the gather/fp family would set high-water marks the
        # next default row gets judged against. Default gather/none
        # contributes no tag, so pre-ISSUE-20 histories continue.
        kv_tag = _kv_metric_tag(summary)
    spec_mode = summary.get("spec_mode")
    acceptance = round(summary.get("acceptance_rate", 0.0), 4)
    if spec_mode:
        # Speculative identity + the two judged signals: a
        # speculative row must never be diffed against a greedy one
        # unlabeled (the kv_layout discipline).
        rec_serve.update(
            spec_mode=spec_mode,
            spec_k=summary.get("spec_k"),
            acceptance_rate=acceptance,
            verify_steps=summary.get("verify_steps"),
            draft_ms=summary.get("draft_ms"),
        )
    if disagg:
        d = summary.get("disagg", {})
        rec_serve["disagg"] = {
            "prefill_mesh": d.get("prefill_mesh"),
            "decode_mesh": d.get("decode_mesh"),
            "kv_transfers": d.get("kv_transfers"),
            "kv_transfer_bytes": d.get("kv_transfer_bytes"),
            "kv_transfer_ms_p95": d.get("kv_transfer_ms_p95"),
        }
    if spec_mode:
        # The speculative mode is part of the METRIC family, not just
        # a sub-dict label: the --bank reduction reads only the
        # top-level value + side keys, so a spec row banked under the
        # greedy family would set itl/ttft high-water marks the next
        # greedy row gets judged against (and draft-vs-ngram
        # trajectories would cross the same way) -- the
        # loadgen_record separation, applied here too.
        metric = f"serve_spec_{spec_mode}{kv_tag}_tokens_per_s_per_chip"
    elif disagg:
        metric = f"serve_disagg{kv_tag}_tokens_per_s_per_chip"
    else:
        metric = f"serve{kv_tag}_tokens_per_s_per_chip"
    rec = {
        "metric": metric,
        "value": round(summary["tokens_per_s_per_chip"], 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 3) if mfu is not None else None,
        "ttft_ms_p50": round(summary["ttft_ms_p50"], 2),
        "ttft_ms_p95": round(summary["ttft_ms_p95"], 2),
        "itl_ms_p50": round(summary["itl_ms_p50"], 2),
        "itl_ms_p95": round(summary["itl_ms_p95"], 2),
        "serve": rec_serve,
    }
    if spec_mode:
        # Top level, where the bank reduction can see it: the
        # mechanism metric rides every spec row (higher-is-better in
        # the gate -- a stale draft fails --bank even when the
        # latency outcome still rides within tolerance).
        rec["acceptance_rate"] = acceptance
    return rec


def _bench_paged_cfg(
    paged: bool, slots: int, max_seq: int, buckets,
    block_size=None, kv_blocks=None, prefill_chunk=None,
    host_blocks=None, kernel=None, kv_quant=None,
):
    """(PagedConfig | None, page-aligned max_seq) for the serve/
    loadgen rows. ONE derivation shared with server.py's CLI
    (paging.derive_paged_config), so the bench rows and the serving
    CLI can never silently diverge on a default; invalid sizing is a
    clean CLI error, not a ValueError traceback after model init."""
    if not paged:
        return None, max_seq
    from tpu_hpc.serve.paging import derive_paged_config

    try:
        return derive_paged_config(
            slots, max_seq, buckets,
            block_size=block_size, num_blocks=kv_blocks,
            prefill_chunk=prefill_chunk, align_capacity=True,
            host_blocks=host_blocks or 0,
            kernel=kernel, kv_quant=kv_quant,
        )
    except ValueError as e:
        raise SystemExit(f"bench.py: {e}")


def _bench_spec_cfg(spec: str, spec_k):
    """(SpecConfig | None) from the CLI spec flags -- invalid
    combinations fail as clean CLI errors like _bench_paged_cfg."""
    if spec == "off":
        return None
    from tpu_hpc.serve.spec import SpecConfig

    try:
        return SpecConfig(mode=spec, k=spec_k if spec_k is not None
                          else 4)
    except ValueError as e:
        raise SystemExit(f"bench.py: {e}")


def bench_serve(
    requests: int = 32, slots: int = 8, max_new: int = 64,
    prompt_lens=(96, 192, 384), buckets=(128, 256, 512),
    model_cfg=None, disagg: bool = False, paged: bool = False,
    block_size=None, kv_blocks=None, prefill_chunk=None,
    host_blocks=None, kernel=None, kv_quant=None,
    spec: str = "off", spec_k=None, draft_ckpt=None,
) -> dict:
    """Batched-inference throughput: the SAME ~170M bench architecture
    as the training headline (bench_model_cfg -- one factory, so
    train and serve rows describe one model), run through the serving
    engine's continuous batcher. Emits TTFT/ITL quantiles and
    tokens/s/chip in the training-record schema; ``recompiles`` in the
    record must read 0 -- the engine warms up every program shape
    before the replay clock starts."""
    import jax

    from tpu_hpc.runtime import init_distributed
    from tpu_hpc.serve.engine import ServeConfig
    from tpu_hpc.serve.server import run_replay

    init_distributed(verbose=False)
    if disagg and jax.device_count() < 2:
        # The server.py guard's twin: a tier split needs a chip per
        # tier -- fail as a CLI error, not a mid-bring-up traceback.
        raise SystemExit(
            "bench.py: --serve-disagg needs >= 2 devices (one per "
            f"tier); only {jax.device_count()} visible"
        )
    model_cfg = model_cfg or bench_model_cfg()
    paged_cfg, max_seq = _bench_paged_cfg(
        paged, slots, max(buckets) + max_new, buckets,
        block_size, kv_blocks, prefill_chunk, host_blocks,
        kernel, kv_quant,
    )
    spec_cfg = _bench_spec_cfg(spec, spec_k)
    serve_cfg = ServeConfig(
        slots=slots,
        max_seq_len=max_seq,
        prefill_buckets=tuple(buckets),
    )
    summary = run_replay(
        model_cfg, serve_cfg, requests, prompt_lens, max_new,
        disagg=disagg, paged=paged_cfg,
        spec=spec_cfg, spec_draft_ckpt=draft_ckpt,
    )
    rec = serve_record(summary, disagg=disagg)
    _attach_logit_rmse(rec, model_cfg, paged_cfg)
    print(
        f"serve{'-disagg' if disagg else ''}"
        f"{'-paged' if paged else ''}"
        f"{f'-{kernel}' if kernel == 'pallas' else ''}"
        f"{f'-{kv_quant}' if kv_quant == 'int8' else ''}"
        f"{f'-spec:{spec}' if spec != 'off' else ''} | "
        f"{summary['mesh']} slots={slots} | "
        f"{summary['tokens_per_s']:.0f} tokens/s | "
        f"TTFT p50 {summary['ttft_ms_p50']:.0f} ms | "
        f"ITL p50 {summary['itl_ms_p50']:.1f} ms",
        file=sys.stderr,
    )
    return rec


def _attach_logit_rmse(rec: dict, model_cfg, paged_cfg) -> None:
    """Pin the quantization error onto every int8 row, top level
    where the --bank reduction judges it (obs/regress
    _BANKED_SIDE_KEYS, lower-is-better via the rmse token): the
    deterministic pre-softmax score RMSE of per-page int8 K against
    fp at THIS model's head geometry and page size. A quantizer
    regression fails the gate even while the latency headline still
    rides within tolerance."""
    if paged_cfg is None or paged_cfg.kv_quant != "int8":
        return
    from tpu_hpc.kernels.paged_attention import int8_logit_rmse

    rec["logit_rmse"] = round(
        int8_logit_rmse(
            head_dim=model_cfg.dim // model_cfg.n_heads,
            kv_heads=model_cfg.n_kv_heads or model_cfg.n_heads,
            n_heads=model_cfg.n_heads,
            block_size=paged_cfg.block_size,
        ),
        6,
    )


def loadgen_record(summary: dict) -> dict:
    """Load-harness summary -> the bench record schema. The headline
    value is the interactive-visible p95 TTFT in VIRTUAL ms (the
    harness's deterministic clock -- scheduling behavior, not machine
    noise; wall-clock throughput remains the serve row's job), with
    the per-tenant shed/queued breakdown riding along so the regress
    gate can hold admission control to its history."""
    tenants = summary.get("tenants", {})
    lg = {
        "scenario": summary["scenario"],
        "seed": summary["seed"],
        "shed": summary["shed"],
        "queued": summary["queued"],
        "occupancy_mean": round(summary["occupancy_mean"], 4),
        "stall_events": summary["stall_events"],
        "slo_violations": summary["slo_violations"],
        "recompiles": summary["recompiles"],
        "kv_layout": summary.get("kv_layout", "slab"),
        "tenants": {
            name: {
                "shed": t["shed"], "queued": t["queued"],
                "ttft_ms_p95": round(t["ttft_ms_p95"], 3),
            }
            for name, t in tenants.items()
        },
    }
    metric = f"loadgen_{summary['scenario']}_ttft_ms_p95"
    kv_tag = ""
    if summary.get("kv_layout") == "paged":
        lg.update(
            kv_block_size=summary.get("kv_block_size"),
            kv_blocks=summary.get("kv_blocks"),
            kv_kernel=summary.get("kv_kernel", "gather"),
            kv_quant=summary.get("kv_quant", "none"),
            prefix_hit_rate=round(
                summary.get("prefix_hit_rate", 0.0), 4
            ),
            block_stalls=summary.get("batcher", {}).get(
                "block_stalls", 0
            ),
        )
        # The cache layout is part of the metric's identity: the
        # --bank gate must track paged and slab trajectories
        # separately (at equal traffic they are different systems).
        # So are the read path and the page storage dtype (the cost
        # model charges them differently); gather/fp contributes no
        # tag so pre-ISSUE-20 histories continue.
        kv_tag = _kv_metric_tag(summary)
        metric = f"loadgen_{summary['scenario']}_paged{kv_tag}_ttft_ms_p95"
    tiered = bool(summary.get("kv_host_blocks"))
    if tiered:
        # A host page tier changes what the same traffic measures
        # (returns prefetch instead of re-prefilling, spill/refill
        # hops ride the cost model), so tiered rows bank under their
        # own family -- an HBM-only trajectory and a tiered one must
        # never cross in the --bank history.
        lg.update(
            kv_host_blocks=summary.get("kv_host_blocks"),
            kv_host_used=summary.get("kv_host_used"),
            kv_host_drops=summary.get("kv_host_drops", 0),
            kv_spill_pages=summary.get("kv_spill_pages", 0),
            kv_refill_pages=summary.get("kv_refill_pages", 0),
        )
        metric = (
            f"loadgen_{summary['scenario']}_paged{kv_tag}"
            "_tiered_ttft_ms_p95"
        )
    spec_mode = summary.get("spec_mode")
    acceptance = round(summary.get("acceptance_rate", 0.0), 4)
    if spec_mode:
        # Speculative rows bank under their own per-MODE metric
        # family (draft and ngram trajectories must not cross) for
        # the same reason, and carry acceptance + modeled draft cost.
        lg.update(
            spec_mode=spec_mode,
            spec_k=summary.get("spec_k"),
            acceptance_rate=acceptance,
            verify_steps=summary.get("verify_steps"),
            draft_ms=summary.get("draft_ms"),
        )
        metric = (
            f"loadgen_{summary['scenario']}_paged{kv_tag}_spec_"
            f"{spec_mode}_ttft_ms_p95"
        )
    fleet = summary.get("fleet")
    if fleet:
        # Fleet rows bank under their own metric family: a
        # multi-replica quantile at the same traffic is a different
        # system from a single-engine one (failure handling, routing
        # and autoscale all in the loop), and the robustness counters
        # ride along so the --bank gate fails on redispatch/
        # replica-loss/swap-rollback drift (regress direction
        # tokens).
        lg.update(
            fleet={
                k: fleet[k]
                for k in (
                    "replicas", "live_min", "live_max", "router",
                    "weights_version", "redispatched",
                    "replica_down", "restarts", "swapped_replicas",
                    "swap_rollbacks", "scale_ups", "scale_downs",
                )
            },
            prefix_affinity_hit_rate=round(
                fleet["prefix_affinity_hit_rate"], 4
            ),
            lost_requests=summary.get("lost_requests", 0),
            block_stalls=summary.get("block_stalls", 0),
        )
        metric = (
            f"loadgen_{summary['scenario']}_fleet{kv_tag}_ttft_ms_p95"
        )
    rec = {
        "metric": metric,
        "value": round(summary["ttft_ms_p95"], 3),
        "unit": "virtual_ms",
        "vs_baseline": None,
        "ttft_ms_p50": round(summary["ttft_ms_p50"], 3),
        "ttft_ms_p99": round(summary["ttft_ms_p99"], 3),
        "itl_ms_p50": round(summary["itl_ms_p50"], 3),
        "itl_ms_p95": round(summary["itl_ms_p95"], 3),
        "loadgen": lg,
    }
    if fleet:
        # Top level so the --bank reduction judges the MECHANISMS
        # (obs/regress._BANKED_SIDE_KEYS -- the reduction reads only
        # the record's top level, sub-dicts are never walked): the
        # router's affinity outcome (higher-is-better by token
        # absence) and the robustness counters (lower via the
        # redispatch/replica_down/swap/lost_requests direction
        # tokens) fail the gate on drift even while the latency
        # headline still rides within tolerance.
        rec["prefix_affinity_hit_rate"] = round(
            fleet["prefix_affinity_hit_rate"], 4
        )
        rec["redispatched"] = fleet["redispatched"]
        rec["replica_down"] = fleet["replica_down"]
        rec["swap_rollbacks"] = fleet["swap_rollbacks"]
        rec["lost_requests"] = summary.get("lost_requests", 0)
    if spec_mode:
        # Top level so the --bank reduction judges the MECHANISM, not
        # just the latency outcome: acceptance_rate is one of the
        # banked side keys (obs/regress._BANKED_SIDE_KEYS,
        # higher-is-better) -- a draft source going stale fails the
        # gate even while ttft/itl still ride within tolerance.
        rec["acceptance_rate"] = acceptance
    ret = tenants.get("return")
    if ret is not None:
        # Top level for the same reason: the return-visit experience
        # is the tier's whole thesis, so the banked side keys judge
        # it directly -- TTFT-on-return quantiles (lower via the
        # ttft token), returns shed at the door (lower via shed),
        # and resident sessions = returns whose KV prefix was still
        # seated or refilled (prefix hits; higher-is-better by token
        # absence). An HBM-only row banks the same keys, so the
        # contrast is in the history, not just this run's stderr.
        rec["ttft_on_return_ms_p50"] = round(ret["ttft_ms_p50"], 3)
        rec["ttft_on_return_ms_p95"] = round(ret["ttft_ms_p95"], 3)
        rec["shed_on_return"] = ret["shed"]
        rec["resident_sessions"] = summary.get("prefix_hits", 0)
    if tiered:
        # Wire volume over the host hop, top level so the --bank
        # reduction catches a spill/refill storm (regress direction
        # tokens: spill/refill + wire_bytes, lower-is-better) even
        # while the latency headline rides within tolerance.
        rec["kv_spill_wire_bytes"] = summary.get(
            "kv_spill_wire_bytes", 0
        )
        rec["kv_refill_wire_bytes"] = summary.get(
            "kv_refill_wire_bytes", 0
        )
    return rec


def bench_loadgen(
    scenario: str = "multi_tenant", requests: int = 64,
    slots: int = 8, max_new: int = 32, seed: int = 0,
    paged: bool = False, block_size=None, kv_blocks=None,
    prefill_chunk=None, host_blocks=None, kernel=None,
    kv_quant=None, model: str = "bench",
    spec: str = "off", spec_k=None, draft_ckpt=None,
    fleet: int = 0, fleet_min: int = 1, fleet_swap_at=None,
    fleet_router: str = "affinity",
) -> dict:
    """Scenario-diverse load row: the SAME ~170M bench architecture as
    the serve row, driven by the tpu_hpc.loadgen harness. ``recompiles``
    must read 0 like the serve row -- a scenario mix that recompiled
    would be measuring the compiler.

    ``model="tiny"`` swaps in the 8-device-sim dev model
    (serve/server.tiny_config). This is legal for THIS workload only:
    loadgen latencies run on the virtual clock, a pure function of
    (scenario, seed, serve shape, cost model) -- the model provides
    the real programs but contributes zero virtual time, so the
    banked quantiles are identical across models. The record still
    carries ``model`` so no row masquerades as a bench-architecture
    measurement. Caveat: ``spec`` weakens model-independence to
    model-DETERMINISM -- acceptance depends on the actual token
    streams, so speculative quantiles are a pure function of
    (scenario, seed, serve shape, cost model, MODEL); the ``model``
    label in the record is part of a speculative row's identity."""
    import dataclasses as _dc

    from tpu_hpc.runtime import init_distributed
    from tpu_hpc.serve.engine import ServeConfig
    from tpu_hpc.serve.server import (
        run_fleet_loadgen,
        run_loadgen,
        tiny_config,
    )

    init_distributed(verbose=False)
    if model == "tiny":
        # The dev model's capacity must still hold bucket + max_new.
        model_cfg = _dc.replace(tiny_config(), max_seq_len=1024)
    else:
        model_cfg = bench_model_cfg()
    buckets = (128, 256, 512)
    paged_cfg, max_seq = _bench_paged_cfg(
        paged, slots, max(buckets) + max_new, buckets,
        block_size, kv_blocks, prefill_chunk, host_blocks,
        kernel, kv_quant,
    )
    spec_cfg = _bench_spec_cfg(spec, spec_k)
    serve_cfg = ServeConfig(
        slots=slots,
        max_seq_len=max_seq,
        prefill_buckets=buckets,
    )
    if fleet:
        summary = run_fleet_loadgen(
            model_cfg, serve_cfg, scenario, requests, max_new,
            paged_cfg, n_replicas=fleet, min_replicas=fleet_min,
            router=fleet_router, swap_at=fleet_swap_at, seed=seed,
        )
    else:
        summary = run_loadgen(
            model_cfg, serve_cfg, scenario, requests, max_new,
            seed=seed, paged=paged_cfg,
            spec=spec_cfg, spec_draft_ckpt=draft_ckpt,
        )
    rec = loadgen_record(summary)
    rec["loadgen"]["model"] = model
    _attach_logit_rmse(rec, model_cfg, paged_cfg)
    print(
        f"loadgen {scenario}{' paged' if paged else ''}"
        f"{f' {kernel}' if kernel == 'pallas' else ''}"
        f"{f' {kv_quant}' if kv_quant == 'int8' else ''}"
        f"{' tiered' if host_blocks else ''}"
        f"{f' fleet:{fleet}' if fleet else ''}"
        f"{f' spec:{spec}' if spec != 'off' else ''} | "
        f"shed {summary['shed']} "
        f"queued {summary['queued']} | TTFT p95 "
        f"{summary['ttft_ms_p95']:.1f} virtual-ms | ITL p50 "
        f"{summary['itl_ms_p50']:.1f} | occupancy "
        f"{summary['occupancy_mean']:.0%}"
        + (
            f" | affinity "
            f"{summary.get('prefix_affinity_hit_rate', 0):.0%} "
            f"redisp {summary['fleet']['redispatched']} "
            f"lost {summary.get('lost_requests', 0)}"
            if fleet else ""
        )
        + (
            f" | acceptance {summary.get('acceptance_rate', 0):.0%}"
            if spec != "off" else ""
        ),
        file=sys.stderr,
    )
    return rec


def bench_unet(steps: int = 20) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import datasets, losses
    from tpu_hpc.models.unet import UNetConfig, apply_unet, init_unet
    from tpu_hpc.parallel import dp
    from tpu_hpc.runtime import MeshSpec, build_mesh, init_distributed
    from tpu_hpc.train import Trainer

    init_distributed(verbose=False)
    cfg = TrainingConfig(
        epochs=2,
        steps_per_epoch=steps,
        global_batch_size=8 * jax.device_count(),
        learning_rate=1e-3,
    )
    mesh = build_mesh(MeshSpec(axes={"data": -1}))
    ds = datasets.ERA5Synthetic()
    model_cfg = UNetConfig(
        in_channels=ds.channels, out_channels=ds.channels,
        dtype=jnp.bfloat16,
    )
    params, model_state = init_unet(
        jax.random.key(0), model_cfg, ds.sample_shape
    )

    def forward(p, ms, batch, step_rng):
        x, y = batch
        pred, new_ms = apply_unet(p, ms, x, model_cfg, train=True)
        return losses.lat_weighted_mse(pred, y), new_ms, {}

    trainer = Trainer(
        cfg, mesh, forward, params, model_state,
        param_pspecs=dp.param_pspecs(params),
    )
    result = trainer.fit(ds)
    summary = result["epochs"][-1]
    return {
        "metric": "unet_dp_train_throughput",
        "value": round(summary["items_per_s"], 2),
        "unit": "samples/s",
        "vs_baseline": 1.0,
    }


def run_all(out_path: str, steps: int) -> int:
    """Record every workload family into one artifact (markdown table
    + raw JSONL next to it): each parallelism family gets a measured
    number on the chips of this host. Each workload runs in a fresh
    subprocess so one family's failure (or HBM state) cannot poison
    the next; this parent never touches JAX -- a chip belongs to one
    process at a time, and every child makes its own device check."""
    import subprocess

    jobs = [
        ("llama (hybrid/dp)", ["--workload", "llama"]),
        ("llama-sp zigzag ring", ["--workload", "llama-sp", "--sp-mode", "zigzag"]),
        ("llama-sp ulysses", ["--workload", "llama-sp", "--sp-mode", "ulysses"]),
        ("llama-pp 1f1b", ["--workload", "llama-pp", "--pp-schedule", "1f1b"]),
        ("llama-pp 1f1b flagship",
         ["--workload", "llama-pp", "--pp-schedule", "1f1b",
          "--pp-model", "llama"]),
        ("llama-pp 1f1b-stash",
         ["--workload", "llama-pp", "--pp-schedule", "1f1b",
          "--pp-backward", "stash"]),
        ("llama-pp gpipe",
         ["--workload", "llama-pp", "--pp-schedule", "gpipe"]),
        ("llama-pp interleaved-1f1b",
         ["--workload", "llama-pp", "--pp-schedule", "interleaved-1f1b"]),
        ("llama dp bucketed-overlap sync",
         ["--workload", "llama", "--comm-mode", "bucketed_overlap"]),
        ("llama-long seq 8192", ["--workload", "llama-long"]),
        ("serve (continuous batching)", ["--workload", "serve"]),
        ("loadgen multi-tenant mix", ["--workload", "loadgen"]),
        ("unet ddp", ["--workload", "unet"]),
    ]
    rows, raw = [], []
    for name, argv in jobs:
        print(f"--- {name} ---", file=sys.stderr)
        try:
            proc = subprocess.run(
                [sys.executable, __file__, *argv, "--steps", str(steps)],
                capture_output=True, text=True, timeout=1800,
            )
            sys.stderr.write(proc.stderr[-500:])
            out, err = proc.stdout.strip(), proc.stderr
        except subprocess.TimeoutExpired as e:
            # One hung family must not poison the sweep: record it
            # failed and keep going.
            out = ""
            err = f"timed out after {e.timeout}s"
        line = out.splitlines()[-1] if out else ""
        try:
            rec = json.loads(line)
            # A child whose last stdout line is valid JSON but not a
            # bench record (or lacks value/unit) must not abort the
            # sweep and lose every already-collected row.
            if not isinstance(rec, dict) or "value" not in rec \
                    or "unit" not in rec:
                raise ValueError(f"not a bench record: {line[:120]!r}")
        except (ValueError, IndexError):
            from tpu_hpc.obs import stamp

            # Failure rows keep the bench schema too: the sweep JSONL
            # must validate end to end even when a family died.
            rec = stamp({
                "event": "bench", "metric": name, "value": None,
                "unit": "FAILED", "vs_baseline": None,
                "error": err[-300:],
            })
        rec["workload"] = name
        raw.append(rec)
        rows.append(
            f"| {name} | {rec['value']} | {rec['unit']} | "
            f"{rec.get('vs_baseline')} |"
        )
    md = "\n".join([
        "# Recorded benchmark sweep",
        "",
        "One row per parallelism family (`python bench.py --all`). "
        "vs_baseline for llama "
        "workloads = achieved MFU / the 40% north-star target "
        "(BASELINE.md; the reference publishes no measured numbers).",
        "",
        "| workload | value | unit | vs_baseline |",
        "|---|---|---|---|",
        *rows,
        "",
    ])
    with open(out_path, "w") as f:
        f.write(md)
    with open(os.path.splitext(out_path)[0] + ".jsonl", "w") as f:
        f.write("\n".join(json.dumps(r) for r in raw) + "\n")
    print(md)
    return 0 if all(r.get("value") is not None for r in raw) else 1


def main(argv=None) -> int:
    # allow_abbrev=False: --supervise is stripped from argv by exact
    # name before re-exec; an accepted abbreviation ("--superv 2")
    # would survive the strip and recurse supervisors forever.
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument(
        "--workload",
        choices=(
            "llama", "llama-sp", "llama-pp", "pp", "llama-long",
            "unet", "serve", "loadgen", "elastic",
        ),
        default=None,  # resolved after --serve alias handling
        help="'pp' is an alias for 'llama-pp' (the pipeline workload "
        "family; --pp-runtime selects the SPMD tick loop or the MPMD "
        "per-stage runtime)",
    )
    ap.add_argument(
        "--serve", action="store_true",
        help="alias for --workload serve: batched-inference "
        "throughput (TTFT/ITL/tokens-per-s) on the bench model via "
        "tpu_hpc.serve",
    )
    ap.add_argument("--serve-requests", type=int, default=32)
    ap.add_argument("--serve-slots", type=int, default=8)
    ap.add_argument("--serve-max-new", type=int, default=64)
    ap.add_argument(
        "--serve-disagg", action="store_true",
        help="disaggregated serving row: prefill/decode on disjoint "
        "mesh tiers, KV blocks moved by tpu_hpc.reshard plans; the "
        "record carries the per-tier meshes and kv-transfer load "
        "(--workload serve only)",
    )
    ap.add_argument(
        "--loadgen-scenario", type=str, default=None,
        help="tpu_hpc.loadgen catalog scenario for --workload loadgen "
        "(default multi_tenant; sized by --serve-requests/"
        "--serve-slots; virtual-clock latencies, the regress gate's "
        "input)",
    )
    ap.add_argument(
        "--serve-fleet", type=int, default=None, metavar="N",
        help="run the loadgen scenario over a fleet of N paged "
        "replicas on disjoint mesh slices (serve/fleet.py): "
        "affinity routing, heartbeat failure handling, autoscale; "
        "the record banks under its own loadgen_<scenario>_fleet_* "
        "family with the robustness counters riding along "
        "(--workload loadgen with --serve-paged "
        "--serve-prefill-chunk only)",
    )
    ap.add_argument(
        "--fleet-swap-at", type=int, default=None, metavar="TICK",
        help="publish a live weight update mid-run at this fleet "
        "tick (dev mode: a fresh random init at seed+1) rolled out "
        "drain-and-swap behind the content-checksum gate; requires "
        "--serve-fleet",
    )
    ap.add_argument(
        "--fleet-router", choices=("affinity", "round_robin"),
        default=None,
        help="fleet request placement (default affinity; round_robin "
        "is the documented degraded control); requires --serve-fleet",
    )
    ap.add_argument(
        "--fleet-min", type=int, default=None, metavar="N",
        help="autoscaler's minimum live replicas (default 1; initial "
        "live set = max(min, ceil(N/2))); requires --serve-fleet",
    )
    ap.add_argument(
        "--serve-paged", action="store_true",
        help="paged KV cache (tpu_hpc/serve/paging.py): block-table "
        "pool with prefix reuse + chunked prefill; the record carries "
        "kv_layout/kv_block_size/prefix-hit rate (--workload serve "
        "or loadgen)",
    )
    ap.add_argument(
        "--serve-block-size", type=int, default=None, metavar="TOK",
        help="tokens per KV page for --serve-paged (default 16)",
    )
    ap.add_argument(
        "--serve-kv-blocks", type=int, default=None, metavar="N",
        help="physical pages in the paged pool incl. scratch "
        "(default: slab-equivalent capacity) for --serve-paged",
    )
    ap.add_argument(
        "--serve-host-blocks", type=int, default=None, metavar="N",
        help="host-DRAM KV page tier (serve/tier.py) slots incl. "
        "scratch for --serve-paged: parked prefixes spill to host "
        "under pool pressure and prefetch back before the return "
        "visit seats; tiered rows bank under their own "
        "_paged_tiered_ metric family; size with "
        "tpu_hpc.checks.fit --kv-host-tier",
    )
    ap.add_argument(
        "--serve-prefill-chunk", type=int, default=None, metavar="TOK",
        help="chunked-prefill stride for --serve-paged (0/omitted = "
        "whole-prompt prefill)",
    )
    ap.add_argument(
        "--serve-kernel", choices=("gather", "pallas"), default=None,
        help="paged attention read path for --serve-paged "
        "(tpu_hpc.kernels.paged_attention): 'gather' materializes "
        "pages before a dense flash call (the oracle), 'pallas' "
        "walks the block table in-kernel -- one HBM read per page; "
        "pallas rows bank under their own _pallas metric family",
    )
    ap.add_argument(
        "--serve-kv-quant", choices=("none", "int8"), default=None,
        help="KV page storage for --serve-paged: 'int8' quantizes "
        "pages per page with fp32 scales -- half the bytes per "
        "token, ~2x resident context at equal HBM; int8 rows bank "
        "under their own _q8 family and carry logit_rmse",
    )
    ap.add_argument(
        "--serve-spec", choices=("off", "draft", "ngram"),
        default="off",
        help="speculative decoding (tpu_hpc/serve/spec.py; requires "
        "--serve-paged): 'draft' = small-model drafting "
        "(--serve-draft-ckpt, else a dev random init), 'ngram' = "
        "prompt-lookup self-speculation; records carry "
        "spec_mode/acceptance_rate (--workload serve or loadgen)",
    )
    ap.add_argument(
        "--serve-draft-ckpt", type=str, default=None, metavar="DIR",
        help="draft-model checkpoint dir for --serve-spec draft",
    )
    ap.add_argument(
        "--spec-k", type=int, default=None, metavar="K",
        help="drafted tokens per verify step for --serve-spec "
        "(default 4)",
    )
    ap.add_argument(
        "--serve-model", choices=("bench", "tiny"), default="bench",
        help="model for --workload loadgen ONLY: 'tiny' runs the "
        "8-device-sim dev model -- legal because loadgen quantiles "
        "are virtual-clock (model-independent); the record carries "
        "the model label",
    )
    ap.add_argument(
        "--all", action="store_true",
        help="run every workload family, write BENCH_EXTRA.md/.jsonl",
    )
    ap.add_argument("--out", type=str, default="BENCH_EXTRA.md")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--remat", action="store_true")
    # Per-dp-shard batch. Default: the family's measured-best
    # microbatch (4; 1 for llama-long at seq 8192) x accum 8 — see
    # resolve_batch_accum. Explicit --batch runs unaccumulated unless
    # --grad-accum-steps is also given.
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--attn", choices=("flash", "xla"), default="flash")
    # 512/1024 q/k tiling: the autotuner's pick (AUTOTUNE_v5e.md).
    # The bench_* function defaults MATCH these (reconciled, ADVICE
    # r5), and every record carries its effective flash_blocks.
    ap.add_argument("--block-q", type=int, default=512)
    ap.add_argument("--block-k", type=int, default=1024)
    ap.add_argument("--block-q-bwd", type=int, default=None,
                    help="backward-kernel q tiling (default: --block-q)")
    ap.add_argument("--block-k-bwd", type=int, default=None,
                    help="backward-kernel k tiling (default: --block-k)")
    ap.add_argument(
        "--sp-mode", choices=("ring", "zigzag", "ulysses"),
        default="zigzag",
    )
    ap.add_argument(
        "--pp-schedule",
        choices=("gpipe", "1f1b", "interleaved", "interleaved-1f1b"),
        default="1f1b"
    )
    ap.add_argument("--pp-microbatches", type=int, default=8)
    ap.add_argument(
        "--pp-microbatch-size", type=int, default=4,
        help="examples per microbatch (the DP headline's measured-best "
        "microbatch; total batch = microbatches x this)",
    )
    ap.add_argument(
        "--pp-model", choices=("stack", "llama"), default="stack",
        help="stack: the homogeneous PipelineTransformer; llama: the "
        "flagship model itself stage-split via models/llama_pp.py "
        "(same architecture as the DP headline -- directly "
        "comparable; all four schedules)",
    )
    ap.add_argument(
        "--pp-runtime", choices=("spmd", "mpmd"), default="spmd",
        help="pipeline runtime: spmd = the single shard_map tick "
        "loop (parallel/pp.py, all four schedules); mpmd = per-stage "
        "AOT programs on disjoint devices with per-stage fault "
        "domains (parallel/mpmd.py) -- the record carries the "
        "measured bubble fraction + recovery MTTR and banks under "
        "the pp_mpmd_* family; stage faults (TPU_HPC_FAULTS "
        "stage_kill_at/stage_nan_at/stage_straggler) are consumed "
        "ONLY here",
    )
    ap.add_argument(
        "--pp-backward", choices=("remat", "stash"), default="remat",
        help="1f1b backward: remat saves only stage inputs and "
        "recomputes the forward (5/3 of ideal FLOPs); stash saves the "
        "vjp residuals (4/3, Megatron-style, O(S) microbatches of "
        "residual HBM)",
    )
    ap.add_argument("--seq-len", type=int, default=None,
                help="sequence length (default: 2048 for llama, 8192 for llama-long)")
    ap.add_argument(
        "--grad-accum-steps", type=int, default=None,
        help="microbatch the per-step batch this many times inside the "
        "jitted step (amortizes optimizer/AdamW-state HBM traffic over "
        "more tokens per optimizer step). llama-family default: 8, "
        "with batch scaled to hold the measured-best microbatch when "
        "--batch is omitted; explicit --batch without this flag runs "
        "unaccumulated",
    )
    ap.add_argument(
        "--comm-mode",
        choices=("flat", "hierarchical", "bucketed_overlap", "auto"),
        default="flat",
        help="gradient-sync strategy (config.comm_mode): flat = "
        "GSPMD's fused collectives; bucketed_overlap = explicit "
        "DDP-style size-capped bucket reductions inside shard_map; "
        "hierarchical = bucketed + two-phase ICI/DCN decomposition; "
        "auto = the collective planner (tpu_hpc.comm.planner) picks "
        "mode and bucket from this topology's cost table (alpha-beta "
        "fallback without one). "
        "Manual modes run the pure-DP replicated-params recipe; the "
        "record carries comm_mode so BENCH JSONLs can attribute "
        "step-time deltas (llama/llama-long workloads)",
    )
    ap.add_argument(
        "--comm-table", type=str, default=None, metavar="PATH",
        help="explicit planner cost-table file for --comm-mode auto "
        "(default: the cache-dir entry for the live topology, "
        "$TPU_HPC_COMM_TABLES); requires --comm-mode auto",
    )
    ap.add_argument(
        "--guard-mode", choices=("off", "skip"), default="off",
        help="numeric-health guard (config.guard_mode): 'skip' arms "
        "the in-step health vector + on-device nonfinite-update skip "
        "so the row measures the guard's steady-state cost "
        "('rollback' needs a checkpoint manager the bench does not "
        "run; llama/llama-long workloads)",
    )
    ap.add_argument(
        "--moments-dtype", choices=("float32", "bfloat16"),
        default="float32",
        help="AdamW moment storage dtype (bfloat16 halves optimizer-"
        "state HBM bytes read+written per step)",
    )
    ap.add_argument(
        "--elastic-shrink-at", type=int, default=None, metavar="N",
        help="topology coordinator chaos: lose half the device pool "
        "at step N (live shrink, no restart; --workload elastic "
        "only; default 2)",
    )
    ap.add_argument(
        "--elastic-grow-at", type=int, default=None, metavar="N",
        help="topology coordinator chaos: the lost slice returns at "
        "step N (live grow back to the full pool; --workload "
        "elastic only; default 4)",
    )
    ap.add_argument(
        "--supervise", type=int, default=0, metavar="N",
        help="re-launch this bench under the resilience supervisor "
        "with N bounded restarts (attempt-unique logs in "
        "bench_logs/; a preempted/crashed run restarts instead of "
        "losing the allocation -- the shell-watchdog replacement)",
    )
    args = ap.parse_args(argv)
    if args.serve:
        if args.workload not in (None, "serve"):
            # The alias must never silently replace an explicit
            # conflicting request -- the record's metric name would
            # not be the one the caller's pipeline expects.
            ap.error(
                f"--serve conflicts with --workload {args.workload}"
            )
        args.workload = "serve"
    elif args.workload is None:
        args.workload = "llama"
    if args.workload == "pp":
        args.workload = "llama-pp"  # documented alias
    if args.pp_runtime == "mpmd":
        # The misplaced-flag discipline: the MPMD runtime only exists
        # on the pipeline workload, runs its own gpipe-ordered
        # dispatch (the schedule flags parameterize the SPMD tick
        # programs), and has its own backward (per-stage vjp).
        if args.workload != "llama-pp":
            ap.error(
                "--pp-runtime mpmd is only consumed by --workload "
                f"llama-pp/pp; --workload {args.workload} would "
                "silently run without it"
            )
        if args.pp_schedule != "gpipe":
            ap.error(
                f"--pp-runtime mpmd dispatches its own gpipe-ordered "
                "schedule; pass --pp-schedule gpipe explicitly "
                f"(got {args.pp_schedule!r} -- a 1f1b/interleaved "
                "row label would misdescribe what ran)"
            )
        if args.pp_backward != "remat":
            ap.error(
                "--pp-runtime mpmd does not consume --pp-backward "
                "(its per-stage backward is an explicit vjp program)"
            )
    if args.loadgen_scenario is not None and args.workload != "loadgen":
        # Same discipline as the --comm-mode guard below: a scenario
        # flag the selected workload never consumes must be a CLI
        # error, not a silently-plain run recorded as the scenario.
        ap.error(
            f"--loadgen-scenario {args.loadgen_scenario} is only "
            f"consumed by --workload loadgen; --workload "
            f"{args.workload} would silently ignore it"
        )
    if args.serve_disagg and args.workload != "serve":
        # The --comm-mode guard discipline: a tier-split flag on a
        # workload that never consumes it must be a CLI error, not a
        # silently single-tier row labeled disaggregated.
        ap.error(
            "--serve-disagg is only consumed by --workload serve; "
            f"--workload {args.workload} would silently run "
            "single-tier"
        )
    if args.serve_paged and args.workload not in ("serve", "loadgen"):
        # Same discipline: a cache-layout flag the workload never
        # consumes must be a CLI error, not a slab row labeled paged.
        ap.error(
            "--serve-paged is only consumed by --workload "
            f"serve/loadgen; --workload {args.workload} would "
            "silently run the slab cache"
        )
    if not args.serve_paged:
        for flag, val in (
            ("--serve-block-size", args.serve_block_size),
            ("--serve-kv-blocks", args.serve_kv_blocks),
            ("--serve-host-blocks", args.serve_host_blocks),
            ("--serve-prefill-chunk", args.serve_prefill_chunk),
            ("--serve-kernel", args.serve_kernel),
            ("--serve-kv-quant", args.serve_kv_quant),
        ):
            if val is not None:
                ap.error(
                    f"{flag} is only consumed together with "
                    "--serve-paged"
                )
    if args.serve_host_blocks is not None and args.serve_host_blocks < 2:
        # server.py's guard, mirrored: the tier reserves host slot 0
        # as scratch, so 1 slot would be a tier that can never hold a
        # page -- a parse error, not a row labeled tiered that never
        # spilled.
        ap.error(
            f"--serve-host-blocks {args.serve_host_blocks} must be "
            ">= 2 (one scratch slot plus at least one page)"
        )
    if args.serve_fleet is not None:
        # The misplaced-flag discipline, fleet edition: a fleet flag
        # on a workload/layout that cannot consume it must be a CLI
        # error, not a single-engine row banked under a fleet label.
        if args.serve_fleet < 1:
            ap.error(f"--serve-fleet {args.serve_fleet} must be >= 1")
        if args.workload != "loadgen":
            ap.error(
                "--serve-fleet is only consumed by --workload "
                f"loadgen; --workload {args.workload} would silently "
                "run a single engine"
            )
        if not args.serve_paged or not args.serve_prefill_chunk:
            ap.error(
                "--serve-fleet needs --serve-paged "
                "--serve-prefill-chunk N (replicas are paged "
                "engines; redispatch replays prompt + committed "
                "tokens, which can exceed any single bucket)"
            )
        if args.serve_spec != "off":
            ap.error(
                "--serve-fleet does not consume --serve-spec"
            )
        if args.fleet_min is not None and not \
                1 <= args.fleet_min <= args.serve_fleet:
            ap.error(
                f"--fleet-min {args.fleet_min} must be in "
                f"[1, --serve-fleet {args.serve_fleet}]"
            )
    else:
        for flag, val in (
            ("--fleet-swap-at", args.fleet_swap_at),
            ("--fleet-router", args.fleet_router),
            ("--fleet-min", args.fleet_min),
        ):
            if val is not None:
                ap.error(
                    f"{flag} is only consumed together with "
                    "--serve-fleet"
                )
    if args.serve_spec != "off":
        # The misplaced-flag discipline, speculative edition: a spec
        # flag on a workload (or cache layout) that cannot consume it
        # is a parse error, not a greedy row wearing a spec label.
        if args.workload not in ("serve", "loadgen"):
            ap.error(
                "--serve-spec is only consumed by --workload "
                f"serve/loadgen; --workload {args.workload} would "
                "silently run greedy"
            )
        if not args.serve_paged:
            ap.error(
                "--serve-spec rides the paged engine; add "
                "--serve-paged"
            )
        if args.serve_disagg:
            ap.error(
                "--serve-spec is not consumed by --serve-disagg "
                "(the verify program is a single-mesh paged program)"
            )
        if args.serve_kv_quant == "int8":
            # server.py's guard, mirrored: verify would replay
            # drafted positions against requantized pages and drift
            # from the greedy oracle.
            ap.error(
                "--serve-spec is not consumed with --serve-kv-quant "
                "int8 (verify replays positions the draft loop "
                "already requantized)"
            )
        if args.spec_k is not None and args.spec_k < 1:
            # server.py's guard, mirrored: `or`-defaulting would
            # silently coerce 0 to 4 and bank a row labeled spec_k=4.
            ap.error(f"--spec-k {args.spec_k} must be >= 1")
    else:
        for flag, val in (
            ("--spec-k", args.spec_k),
            ("--serve-draft-ckpt", args.serve_draft_ckpt),
        ):
            if val is not None:
                ap.error(
                    f"{flag} is only consumed together with "
                    "--serve-spec"
                )
    if args.serve_draft_ckpt is not None \
            and args.serve_spec != "draft":
        ap.error(
            "--serve-draft-ckpt is only consumed together with "
            "--serve-spec draft"
        )
    if args.serve_model != "bench" and args.workload != "loadgen":
        # The dev model is ONLY legal where the virtual clock makes
        # the row model-independent; a tiny-model wall-clock serve row
        # would be an incomparable number wearing the bench label.
        ap.error(
            "--serve-model tiny is only consumed by --workload "
            f"loadgen (virtual-clock rows); --workload "
            f"{args.workload} measures wall clock on the bench model"
        )
    if args.guard_mode != "off" and (
        args.all or args.workload not in ("llama", "llama-long")
    ):
        # The --comm-mode guard discipline: a guard flag on a workload
        # that never consumes it must be a CLI error, not a row
        # labeled guarded that silently ran unguarded.
        ap.error(
            f"--guard-mode {args.guard_mode} is only consumed by the "
            "llama/llama-long workloads; "
            + ("--all runs fixed rows"
               if args.all else
               f"--workload {args.workload} would silently run "
               "unguarded")
        )
    if args.comm_mode != "flat" and (
        args.all or args.workload not in ("llama", "llama-long")
    ):
        # Only the llama/llama-long workloads consume the gradient-sync
        # knob; running any other with it silently flat would emit rows
        # a comm-mode sweep cannot tell apart from the real thing.
        # (comm_mode="auto" without a gradient-sync-consuming workload
        # is the same lie one indirection later: there is no sync for
        # the planner to plan.)
        ap.error(
            f"--comm-mode {args.comm_mode} is only consumed by the "
            "llama/llama-long workloads; "
            + ("--all runs its own fixed comm-mode row"
               if args.all else
               f"--workload {args.workload} would silently run flat")
        )
    if args.workload != "elastic":
        # The misplaced-flag discipline, elastic edition: a morph
        # schedule on a workload that never morphs must be a CLI
        # error, not a fixed-topology row wearing a storm label.
        for flag, val in (
            ("--elastic-shrink-at", args.elastic_shrink_at),
            ("--elastic-grow-at", args.elastic_grow_at),
        ):
            if val is not None:
                ap.error(
                    f"{flag} is only consumed by --workload elastic; "
                    f"--workload {args.workload} would silently run "
                    "fixed-topology"
                )
    else:
        shrink = (
            args.elastic_shrink_at
            if args.elastic_shrink_at is not None else 2
        )
        grow = (
            args.elastic_grow_at
            if args.elastic_grow_at is not None else 4
        )
        if not 0 < shrink < grow:
            ap.error(
                f"--elastic-shrink-at {shrink} must be > 0 and < "
                f"--elastic-grow-at {grow} (the storm is shrink -> "
                "train -> grow -> train)"
            )
        if grow >= args.steps:
            ap.error(
                f"--elastic-grow-at {grow} needs --steps > {grow}: "
                "the grow morph would never fire and the chaos "
                "schedule would fail its vacuous-pass guard"
            )
        args.elastic_shrink_at, args.elastic_grow_at = shrink, grow
    if args.comm_table is not None and args.comm_mode != "auto":
        # Planner flags on non-auto modes: the --comm-mode guard
        # discipline. A table nothing consults must be a CLI error,
        # not a row that silently ignored the measurements it names.
        ap.error(
            f"--comm-table {args.comm_table} is only consumed by "
            f"--comm-mode auto; --comm-mode {args.comm_mode} never "
            "consults the planner"
        )
    if args.supervise:
        from tpu_hpc.resilience.supervisor import (
            run_supervised,
            strip_flag,
        )

        # Strip the flag (both "--supervise N" and "--supervise=N"):
        # the supervised child must run the bench itself.
        child_args = strip_flag(
            list(sys.argv[1:] if argv is None else argv), "--supervise"
        )
        return run_supervised(
            [sys.executable, os.path.abspath(__file__), *child_args],
            max_restarts=args.supervise,
            log_dir=os.environ.get("TPU_HPC_SUPERVISE_LOGS", "bench_logs"),
        )
    if args.all:
        return run_all(args.out, args.steps)
    from tpu_hpc.runtime import require_accelerator

    require_accelerator()
    if args.workload == "llama":
        batch, accum = resolve_batch_accum(
            args.batch, args.grad_accum_steps, microbatch=4
        )
        rec = bench_llama(
            args.steps, args.remat, batch, args.attn,
            args.block_q, args.block_k, seq_len=args.seq_len or 2048,
            grad_accum_steps=accum,
            moments_dtype=args.moments_dtype,
            block_q_bwd=args.block_q_bwd, block_k_bwd=args.block_k_bwd,
            comm_mode=args.comm_mode,
            guard_mode=args.guard_mode,
            comm_table=args.comm_table,
        )
    elif args.workload == "llama-sp":
        batch, accum = resolve_batch_accum(
            args.batch, args.grad_accum_steps, microbatch=4
        )
        rec = bench_llama_sp(
            args.steps, batch, args.sp_mode,
            grad_accum_steps=accum, moments_dtype=args.moments_dtype,
        )
    elif args.workload == "llama-pp" and args.pp_runtime == "mpmd":
        rec = bench_llama_pp_mpmd(
            args.steps, args.pp_microbatches,
            microbatch_size=args.pp_microbatch_size, attn=args.attn,
            block_q=args.block_q, block_k=args.block_k,
            block_q_bwd=args.block_q_bwd, block_k_bwd=args.block_k_bwd,
            model=args.pp_model,
        )
    elif args.workload == "llama-pp":
        rec = bench_llama_pp(
            args.steps, args.pp_schedule, args.pp_microbatches,
            microbatch_size=args.pp_microbatch_size, attn=args.attn,
            block_q=args.block_q, block_k=args.block_k,
            block_q_bwd=args.block_q_bwd, block_k_bwd=args.block_k_bwd,
            grad_accum_steps=args.grad_accum_steps or 1,
            backward=args.pp_backward,
            model=args.pp_model,
        )
    elif args.workload == "llama-long":
        batch, accum = resolve_batch_accum(
            args.batch, args.grad_accum_steps, microbatch=1
        )
        rec = bench_llama_long(
            args.steps, seq_len=args.seq_len or 8192,
            batch=batch, remat=args.remat,
            grad_accum_steps=accum,
            moments_dtype=args.moments_dtype,
            block_q=args.block_q, block_k=args.block_k,
            block_q_bwd=args.block_q_bwd, block_k_bwd=args.block_k_bwd,
            comm_mode=args.comm_mode,
            guard_mode=args.guard_mode,
            comm_table=args.comm_table,
        )
    elif args.workload == "serve":
        rec = bench_serve(
            requests=args.serve_requests, slots=args.serve_slots,
            max_new=args.serve_max_new, disagg=args.serve_disagg,
            paged=args.serve_paged,
            block_size=args.serve_block_size,
            kv_blocks=args.serve_kv_blocks,
            prefill_chunk=args.serve_prefill_chunk,
            host_blocks=args.serve_host_blocks,
            kernel=args.serve_kernel,
            kv_quant=args.serve_kv_quant,
            spec=args.serve_spec, spec_k=args.spec_k,
            draft_ckpt=args.serve_draft_ckpt,
        )
    elif args.workload == "loadgen":
        rec = bench_loadgen(
            scenario=args.loadgen_scenario or "multi_tenant",
            requests=args.serve_requests * 2,
            slots=args.serve_slots,
            max_new=args.serve_max_new,
            paged=args.serve_paged,
            block_size=args.serve_block_size,
            kv_blocks=args.serve_kv_blocks,
            prefill_chunk=args.serve_prefill_chunk,
            host_blocks=args.serve_host_blocks,
            kernel=args.serve_kernel,
            kv_quant=args.serve_kv_quant,
            model=args.serve_model,
            spec=args.serve_spec, spec_k=args.spec_k,
            draft_ckpt=args.serve_draft_ckpt,
            fleet=args.serve_fleet or 0,
            fleet_min=args.fleet_min or 1,
            fleet_swap_at=args.fleet_swap_at,
            fleet_router=args.fleet_router or "affinity",
        )
    elif args.workload == "elastic":
        rec = bench_elastic(
            args.steps, shrink_at=args.elastic_shrink_at,
            grow_at=args.elastic_grow_at,
        )
    else:
        rec = bench_unet(args.steps)
    # Every bench line is a schema-stamped ``bench`` event -- the same
    # record discipline the train/serve JSONL sinks follow, so one
    # validator (tpu_hpc.obs.schema) and one report cover all three.
    from tpu_hpc.obs import get_bus

    print(json.dumps(get_bus().emit_record({"event": "bench", **rec})))
    return 0


if __name__ == "__main__":
    sys.exit(main())
