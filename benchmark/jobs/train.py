"""Job kind ``train``: the trainer's step, assembled as ``bench.py``'s
``bench_llama`` and ``chip_smoke.train_phase`` assemble it (mesh, Pallas
flash attention under ``shard_map``, hybrid FSDPxTP specs and the
sequence-parallel constraint when ``model > 1``, ``Trainer`` over
``datasets.TokenStream``, AdamW, float32 parameters and moments, bf16
compute, remat), with two differences that are the benchmark's own
files' to make: the weights are made under ``jit`` straight into their
shardings, and the seed enters as data (``traffic/token_stream.py``).

One ``fit`` of ``warm_chunks`` chunks compiles and warms; a second
``fit`` of as many chunks as fill ``--seconds`` is the window. A chunk
is ``steps_per_chunk`` steps in one program with one host fetch at its
end, so the device is not stalled per step and every host-clock reading
spans about a second.
"""
import json
import math
import os
import time

from benchmark import harness, trace_reduce
from benchmark.reference import dense_decoder

# Tolerances of the correctness check, program against reference on one
# seeded batch at the cell's own shapes.
#
# The program computes in bf16 with float32 accumulation and float32
# softmax; the reference in float32 throughout. Measured on the v5e
# (PERF.md PR 23, seven seeds, one chip): the loss, a mean over 4096
# positions near ln(vocab), differs by 0.6e-4 to 2.7e-4; the gradients'
# global relative error is 0.00964 to 0.00966, the same for every seed,
# which is what ~10 bf16 matmuls (8 significand bits, 2^-9 a rounding)
# each way leave. The bounds are 3.6x and 1.55x the largest seen: an
# 8-bit float format (3 significand bits, 6 % a rounding), bf16
# accumulation, or a dropped term in a kernel's backward pass would miss
# them by an order of magnitude.
LOSS_ABS_TOL = 1e-3
GRAD_REL_TOL = 1.5e-2


def _check(forward, params, stream, arch, mesh, check_grads, log):
    """Loss and gradients of the program's own forward (the cell's
    flash kernels, forward and both backward) against the reference's
    ``value_and_grad``. The reference takes one sequence at a time and
    its gradients are averaged, so its float32 activations fit beside
    two gradient trees."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    ref_kw = harness.reference_kwargs(arch)
    batch_sh = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    inputs = jax.device_put(stream["check_inputs"], batch_sh)
    targets = jax.device_put(stream["check_targets"], batch_sh)
    n_seq = inputs.shape[0]

    def program_loss(p, x, y):
        return forward(p, {}, (x, y), jax.random.key(0))[0]

    def reference_loss(p, x, y):
        return dense_decoder.loss(p, x, y, **ref_kw)

    def sq(tree):
        return sum(
            jnp.sum(jnp.square(leaf.astype(jnp.float32)))
            for leaf in jax.tree.leaves(tree)
        )

    out = {}
    if not check_grads:
        got = jax.jit(program_loss)(params, inputs, targets)
        want = sum(
            jax.jit(reference_loss)(
                params,
                jax.device_put(stream["check_inputs"][i:i + 1], rep),
                jax.device_put(stream["check_targets"][i:i + 1], rep),
            )
            for i in range(n_seq)
        ) / n_seq
        out["grad_rel_err"] = None
    else:
        ref_step = jax.jit(jax.value_and_grad(reference_loss))
        acc = jax.jit(
            lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,)
        )
        want, ref_grads = 0.0, None
        for i in range(n_seq):
            value, grads = ref_step(
                params,
                jax.device_put(stream["check_inputs"][i:i + 1], rep),
                jax.device_put(stream["check_targets"][i:i + 1], rep),
            )
            want = want + value / n_seq
            ref_grads = grads if ref_grads is None else acc(ref_grads, grads)
        got, grads = jax.jit(jax.value_and_grad(program_loss))(
            params, inputs, targets
        )

        @jax.jit
        def rel_err(g, r):
            r = jax.tree.map(lambda x: x / n_seq, r)
            diff = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b, g, r
            )
            return jnp.sqrt(sq(diff) / sq(r))

        out["grad_rel_err"] = float(rel_err(grads, ref_grads))
        del grads, ref_grads
    out["loss_program"], out["loss_reference"] = float(got), float(want)
    out["loss_abs_err"] = abs(out["loss_program"] - out["loss_reference"])
    out["ok"] = bool(
        math.isfinite(out["loss_program"])
        and out["loss_abs_err"] < LOSS_ABS_TOL
        and (not check_grads or out["grad_rel_err"] < GRAD_REL_TOL)
    )
    log(f"check | {out} (tolerances: loss {LOSS_ABS_TOL}, grads "
        f"{GRAD_REL_TOL}{'' if check_grads else ', gradients not compared'})")
    return out


def run(ctx):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import datasets, llama2
    from tpu_hpc.parallel import fsdp, hybrid, tp
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.train import Trainer

    spec, log = ctx["spec"], ctx["log"]
    cell, config = spec["cell"], spec["config"]
    gen = harness.load_module("traffic", f"{spec['traffic']['kind']}.py")
    cfg, arch = harness.llama_config(
        config, cell, max_seq_len=spec["traffic"]["seq_len"]
    )
    stream = gen.generate(
        spec["traffic"], ctx["seed"], cfg.vocab_size, ctx["seconds"]
    )
    seq_len = stream["seq_len"]

    axes = dict(cell["mesh"])
    dp_size, tp_size = axes["data"], axes.get("model", 1)
    devices = ctx["devices"]
    mesh = build_mesh(
        MeshSpec(axes=axes),
        devices if len(devices) != jax.device_count() else None,
    )
    flash = cell["flash"]
    attn_fn = tp.make_tp_flash_attn_fn(
        mesh, "data", "model" if tp_size > 1 else None,
        impl=flash["impl"], block_q=flash["block_q"],
        block_k=flash["block_k"],
    )
    abstract = jax.eval_shape(
        lambda: llama2.init_llama(jax.random.key(0), cfg)
    )
    constrain = lambda x: x  # noqa: E731
    specs = None
    if tp_size > 1:
        specs = hybrid.hybrid_pspecs(
            abstract, tp.llama_rules(), data_size=dp_size
        )
        constrain = tp.sp_constrain(mesh, dp_axis="data", sp_axis="model")
    elif dp_size > 1:
        specs = fsdp.param_pspecs(abstract, axis="data", axis_size=dp_size)
    rep = NamedSharding(mesh, P())
    shardings = rep if specs is None else jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    phases = {}
    t = time.perf_counter()
    params = harness.init_params(cfg, ctx["seed"], shardings)
    jax.block_until_ready(params)
    phases["init_s"] = time.perf_counter() - t
    forward = llama2.make_forward(cfg, constrain, attn_fn)

    t = time.perf_counter()
    check = _check(
        forward, params, stream, arch, mesh,
        bool(cell.get("check_grads", True)), log,
    )
    phases["reference_s"] = time.perf_counter() - t

    steps = cell["steps_per_chunk"]
    batch = stream["batch_per_data_shard"] * dp_size
    opt = cell["optimizer"]
    tcfg = TrainingConfig(
        epochs=cell["warm_chunks"], steps_per_epoch=steps,
        global_batch_size=batch,
        learning_rate=opt["learning_rate"],
        weight_decay=opt["weight_decay"],
        metrics_path=os.path.join(ctx["out_dir"], "train.jsonl"),
    )
    if os.path.exists(tcfg.metrics_path):
        os.remove(tcfg.metrics_path)
    t = time.perf_counter()
    trainer = Trainer(
        tcfg, mesh, forward, params, param_pspecs=specs,
        batch_pspec=P("data"),
    )
    del params  # the trainer holds its own placed copy
    # The seed picks where in the token stream the run starts; the
    # step counter is the stream's index and enters the chunk as data.
    trainer.state = trainer.state.replace(
        step=jax.device_put(
            jax.numpy.int32(stream["start_step"]), rep
        )
    )
    ds = datasets.TokenStream(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        seed=stream["stream_seed"],
    )
    warm = trainer.fit(ds, epochs=cell["warm_chunks"])
    phases["warmup_s"] = time.perf_counter() - t
    chunk_s = warm["epochs"][-1]["total_s"]
    n_chunks = max(1, math.ceil(ctx["seconds"] / chunk_s))
    n_trace = min(cell.get("trace_chunks", 3), n_chunks) if ctx["trace"] else 0
    log(f"warm | {phases} | chunk of {steps} steps {chunk_s:.3f} s -> "
        f"window of {n_chunks} chunks ({n_trace} traced)")

    # ---- the window ------------------------------------------------
    counter = ctx["counter"]
    counter.mark()
    t_window = time.perf_counter()
    summaries = []
    if n_chunks > n_trace:
        summaries += trainer.fit(ds, epochs=n_chunks - n_trace)["epochs"]
    trace = None
    if n_trace:
        trace_dir = os.path.join(ctx["out_dir"], "trace")
        harness.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            traced = trainer.fit(ds, epochs=n_trace)["epochs"]
        window_s = time.perf_counter() - t_window
        jax.profiler.stop_trace()
        summaries += traced
        trace = trace_reduce.reduce(
            trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        )
        trace["steps"] = n_trace * steps
    else:
        window_s = time.perf_counter() - t_window
    compiles_in_window = counter.since_mark()

    with open(tcfg.metrics_path) as f:
        records = [json.loads(line) for line in f]
    losses = [r["loss"] for r in records if r["event"] == "epoch"]
    window_losses = losses[cell["warm_chunks"]:]
    # A chunk's record carries its last step's loss (null where it was
    # not finite); a step that went non-finite poisons the parameters
    # and so every later loss, so the chunk's last loss speaks for all
    # of its steps.
    failed = steps * sum(1 for x in window_losses if x is None)
    end_step = int(jax.device_get(trainer.state.step))
    steps_done = end_step - stream["start_step"] - cell["warm_chunks"] * steps
    correct = bool(
        check["ok"] and compiles_in_window == 0 and failed == 0
        and steps_done == n_chunks * steps
        and len(window_losses) == n_chunks
    )
    return {
        "correct": correct,
        "attempted": n_chunks * steps,
        "failed": failed,
        "window_s": window_s,
        "t_window": t_window,
        "checks": {
            "reference": check,
            "compiles_in_window": compiles_in_window,
            "steps_done": steps_done,
            "losses": losses,
        },
        "phases": phases,
        "arch": arch,
        "mesh": axes,
        "train": {
            "chunks": [
                {"steps": steps, "seconds": s["total_s"]} for s in summaries
            ],
            "tokens": n_chunks * steps * batch * seq_len,
            "tokens_per_step": batch * seq_len,
            "batch_per_chip": batch // dp_size,
            "seq_len": seq_len,
            "remat": cfg.remat,
            "model_shards": tp_size,
            "data_shards": dp_size,
        },
        "trace": trace,
    }
