"""Job kind ``train_arch``: what ``train`` does (the trainer's step
through ``Trainer.fit`` over ``datasets.TokenStream``: mesh, Pallas
flash attention, AdamW, float32 parameters and moments, bf16 products,
recomputation; one ``fit`` of ``warm_chunks`` chunks warms, a second
fills ``--seconds``), for a decoder ``LlamaConfig`` cannot express.

The configuration file names what the program needs, as ``serve_arch``
has it, so the next trained architecture adds a file and no job:

    program.config, .config_kwargs   the program's configuration class
                                     and the sizes it is built with
    program.init, .init_state        seeded weights and the state the
                                     model carries untrained, jitted here
    program.init_state_kwargs        what the benchmark asks of that
                                     state beyond the program's default
    program.forward                  makes the Trainer's forward
    program.loss                     ``(params, state, batch, cfg,
                                     attn_fn) -> (loss, (counts,
                                     chosen))``, what that forward
                                     differentiates, for the check
    program.reference                the plain reference under
                                     ``benchmark/reference/``
    program.flops_bytes              the functions that count its work
    arch, assumed_sizes              the reference's sizes, by the
                                     PUBLISHED keys they are read from

and every size the program's configuration carries is held to the
published one (``arch``) before anything runs. The tolerances of the
check are the CELL's (``check`` in ``workloads/<cell>.json``), each
with its reason there.

Four things of its own:

* the stage names an expert layer and a short convolution add
  (docs/guide/observability.md) join ``program_trace.SCOPES`` for this
  process, as ``serve_arch.py`` adds its three and for its reason;
* the expert counters the Trainer fetches with each chunk's loss
  (``train_moe_*``, in the run's ``train.jsonl``) are summed over the
  window's chunks into ``obs["train"]["moe"]``, and ``correct`` needs
  ``train_moe_dropped_total`` 0 there;
* the check, on one seeded batch at the timed shapes, outside the
  window: the program's loss and gradients (``jax.value_and_grad`` of
  ``program.loss``: the flash kernels, the ragged products, forward
  and backward) against the reference's, one sequence at a time, with
  the reference SENT the experts the program chose, so that both
  differentiate one function; every gradient leaf is compared by
  itself (a missing backward term of one small leaf, the router's say,
  is lost in a norm over the tree) and all together; and every
  (token, layer) selection of the program is held to a band round the
  reference's own ``k``-th score on the same history;
* and the Trainer's own step, which that comparison never enters: the
  FIRST chunk of the warm-up (the compiled chunk the window times:
  the scan over the stream's batches, the recomputation the budget
  chose, the folded counters, AdamW under the schedule) is held to a
  plain AdamW written out here (:func:`plain_adamw`), fed the
  gradients of the function the check has just held to the reference,
  on the same batches of the stream. What is compared is the
  parameters' CHANGE, leaf by leaf and over the tree: a state left
  unchanged reads 1, whatever the learning rate.
"""
import functools
import importlib
import json
import math
import os
import time

from benchmark import harness, program_trace, trace_reduce

ARCH_SCOPES = ("short_conv", "router", "dispatch", "experts", "combine")
program_trace.SCOPES = tuple(
    dict.fromkeys(program_trace.SCOPES + ARCH_SCOPES)
)

def _resolve(path):
    module, _, name = path.partition(":")
    return getattr(importlib.import_module(module), name)


def build(config, cell, max_seq_len):
    """-> (the program's configuration, the reference's sizes), every
    size of the first held to the published one or to the cut the file
    states (``assumed_sizes``)."""
    import jax.numpy as jnp

    program, pub = config["program"], config["published"]
    cfg = _resolve(program["config"])(
        **program["config_kwargs"],
        n_layers=cell["n_layers"], max_seq_len=max_seq_len,
        dtype=jnp.dtype(cell["compute_dtype"]),
        param_dtype=jnp.dtype(cell["param_dtype"]),
        remat=bool(cell.get("remat", False)),
    )

    def published(key):
        value = pub
        for part in key.split("."):
            value = value[part]
        return value

    arch = {k: published(v) for k, v in config["arch"].items()}
    arch.update(config.get("assumed_sizes", {}))
    got = {
        k: getattr(cfg, "kv_heads" if k == "n_kv_heads" else k) for k in arch
    }
    bad = {
        k: (got[k], v) for k, v in arch.items()
        if (list(got[k]) if isinstance(got[k], tuple) else got[k]) != v
    }
    if bad:
        raise SystemExit(
            f"benchmark: {config['name']}: the program's configuration "
            f"differs from the published sizes (got, published): {bad}"
        )
    if max_seq_len > pub["max_position_embeddings"]:
        raise SystemExit(
            f"benchmark: context {max_seq_len} exceeds what "
            f"{config['name']} declares"
        )
    arch["n_layers"] = cell["n_layers"]
    return cfg, arch


def reference_kwargs(arch):
    kw = {
        k: arch[k] for k in (
            "n_layers", "n_heads", "n_kv_heads", "norm_eps", "rope_theta",
            "first_dense_layers", "experts_per_token",
            "routed_scaling_factor",
        )
    }
    kw["layer_types"] = tuple(arch["layer_types"])
    kw["held"] = tuple(arch["held_experts"])
    return kw


def init(config, cfg, seed, sharding):
    """Weights and state on the device, from the seed, in ONE jitted
    call (the seed enters as data: every seed shares one program)."""
    import jax
    import jax.numpy as jnp

    make_params = _resolve(config["program"]["init"])
    make_state = _resolve(config["program"]["init_state"])
    state_kwargs = config["program"].get("init_state_kwargs", {})

    def make(lo, hi):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(0), lo), hi
        )
        k_params, k_state = jax.random.split(key)
        return make_params(k_params, cfg), \
            make_state(k_state, cfg, **state_kwargs)

    return jax.jit(make, out_shardings=sharding)(
        jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF)
    )


def selection_band(select, chosen, k):
    """One sequence's layer: the reference's selection scores ``[s,
    n_experts]`` (on the history the program's choices made) against
    the experts the program chose ``[s, k]`` -> (tokens whose choice
    is not the reference's top-k, the furthest any chosen expert lies
    BELOW the reference's k-th score, in standard deviations of that
    token's scores)."""
    import jax
    import jax.numpy as jnp

    top, own = jax.lax.top_k(select, k)
    differ = jnp.any(
        jnp.sort(own, axis=-1) != jnp.sort(chosen, axis=-1), axis=-1
    )
    picked = jnp.take_along_axis(select, chosen, axis=-1)
    below = jnp.maximum(top[:, -1] - jnp.min(picked, axis=-1), 0.0)
    return jnp.sum(differ), jnp.max(below / jnp.std(select, axis=-1))


def check(grad_fn, reference, ref_kw, params, state, stream, mesh, tol, log):
    """The program (``grad_fn``: the jitted ``jax.value_and_grad`` of
    its loss) against the reference on the seeded check batch -> the
    readings, each beside its limit, and ``ok``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    inputs = jax.device_put(stream["check_inputs"], NamedSharding(mesh, P("data")))
    targets = jax.device_put(stream["check_targets"], NamedSharding(mesh, P("data")))
    n_seq = inputs.shape[0]
    k = ref_kw["experts_per_token"]

    (got, (counts, chosen)), grads = grad_fn(params, state, (inputs, targets))

    @jax.jit
    def ref_step(p, x, y, picked):
        (value, routed), g = jax.value_and_grad(
            lambda q: reference.loss(q, state, x, y, chosen=picked, **ref_kw),
            has_aux=True,
        )(p)
        bands = [
            selection_band(select, used, k) for select, used in routed.values()
        ]
        return value, g, sum(b[0] for b in bands), \
            jnp.max(jnp.stack([b[1] for b in bands]))

    acc = jax.jit(
        lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,)
    )
    want, ref_grads, differ, below = 0.0, None, 0, 0.0
    for i in range(n_seq):
        value, g, n_differ, worst = ref_step(
            params,
            jax.device_put(stream["check_inputs"][i], rep),
            jax.device_put(stream["check_targets"][i], rep),
            {name: c[i] for name, c in chosen.items()},
        )
        want = want + float(value) / n_seq
        differ += int(n_differ)
        below = max(below, float(worst))
        ref_grads = g if ref_grads is None else acc(ref_grads, g)

    @jax.jit
    def errors(g, r):
        r = jax.tree.map(lambda x: x / n_seq, r)
        sq = jax.tree.map(
            lambda a, b: jnp.stack([
                jnp.sum(jnp.square(a.astype(jnp.float32) - b)),
                jnp.sum(jnp.square(b)),
            ]), g, r,
        )
        total = sum(jax.tree.leaves(sq))
        return jnp.sqrt(total[0] / total[1]), \
            jax.tree.map(lambda x: jnp.sqrt(x[0] / x[1]), sq)

    overall, by_leaf = errors(grads, ref_grads)
    del grads, ref_grads
    leaves = {
        jax.tree_util.keystr(path): float(e)
        for path, e in jax.tree_util.tree_flatten_with_path(by_leaf)[0]
    }
    worst_leaf = max(leaves, key=leaves.get)
    n_selections = len(chosen) * inputs.size
    out = {
        "loss_program": float(got), "loss_reference": want,
        "loss_abs_err": abs(float(got) - want),
        "grad_rel_err": float(overall),
        "grad_leaf_rel_err": leaves[worst_leaf],
        "grad_worst_leaf": worst_leaf,
        "selections": n_selections,
        "selections_differ_share": differ / n_selections,
        "selection_below_sigma": below,
        "check_dropped": int(counts["train_moe_dropped_total"]),
    }
    out["ok"] = bool(
        math.isfinite(out["loss_program"])
        and out["loss_abs_err"] < tol["loss_abs_tol"]
        and out["grad_rel_err"] < tol["grad_rel_tol"]
        and out["grad_leaf_rel_err"] < tol["grad_leaf_rel_tol"]
        and out["selections_differ_share"] < tol["selection_differ_share_max"]
        and out["selection_below_sigma"] < tol["selection_eps_sigma"]
        and out["check_dropped"] == 0
    )
    limits = {k: v for k, v in tol.items() if not k.endswith("_why")}
    log(f"check | {out} (limits: {limits})")
    out["leaves"] = leaves
    return out


# optax.adamw's defaults, written out: the check keeps an AdamW of its
# own, so that it is no copy of the one under test.
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def plain_adamw(grad_fn, params, state, batches, opt):
    """What a fresh optimizer should make of ``params`` over
    ``batches``, one update a batch: AdamW with float32 moments, the
    decay decoupled, the learning rate warmed up linearly from 0 over
    ``opt["warmup_steps"]`` updates -> (the parameters it ends on, each
    leaf's squared change). ``params`` is left as it was."""
    import jax
    import jax.numpy as jnp

    peak, decay = opt["learning_rate"], opt["weight_decay"]
    warm = opt.get("warmup_steps", 0)

    # Gradient and update are ONE program over the donated (p, m, v),
    # as the Trainer's step is: the compiler then knows what is
    # resident while the backward pass runs (a gradient call beside
    # five trees of this size would not fit the chip).
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, m, v, state, batch, count):
        _, g = grad_fn(p, state, batch)
        rate = peak * jnp.minimum(count / warm, 1.0) if warm else peak
        n = count + 1.0

        def leaf(p, m, v, g):
            m = ADAM_B1 * m + (1 - ADAM_B1) * g
            v = ADAM_B2 * v + (1 - ADAM_B2) * jnp.square(g)
            step = (m / (1 - ADAM_B1 ** n)) / (
                jnp.sqrt(v / (1 - ADAM_B2 ** n)) + ADAM_EPS
            ) + decay * p
            return p - rate * step, m, v

        out = jax.tree.map(leaf, p, m, v, g)
        return tuple(
            jax.tree.map(lambda _, o: o[i], p, out) for i in range(3)
        )

    p = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    for count, batch in enumerate(batches):
        p, m, v = update(p, m, v, state, batch, jnp.float32(count))
    del m, v
    moved = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sum(jnp.square(x - y)), a, b
    ))(p, params)
    return p, moved


def update_errors(want, moved, got, tol, log):
    """The parameters a chunk of the Trainer left (``got``) against
    :func:`plain_adamw`'s (``want``, and ``moved``: each leaf's squared
    change there) -> the distance between the two as a share of the
    change, over the tree and for the worst leaf, each beside its
    limit, and ``ok``. A chunk that left its state as it was reads 1."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def errors(a, b, moved):
        off = jax.tree.map(lambda x, y: jnp.sum(jnp.square(x - y)), a, b)
        return jnp.sqrt(
            sum(jax.tree.leaves(off)) / sum(jax.tree.leaves(moved))
        ), jax.tree.map(lambda o, m: jnp.sqrt(o / m), off, moved)

    overall, by_leaf = errors(got, want, moved)
    leaves = {
        jax.tree_util.keystr(path): float(e)
        for path, e in jax.tree_util.tree_flatten_with_path(by_leaf)[0]
    }
    worst_leaf = max(leaves, key=leaves.get)
    out = {
        "update_rel_err": float(overall),
        "update_leaf_rel_err": leaves[worst_leaf],
        "update_worst_leaf": worst_leaf,
    }
    out["ok"] = bool(
        out["update_rel_err"] < tol["update_rel_tol"]
        and out["update_leaf_rel_err"] < tol["update_leaf_rel_tol"]
    )
    log(f"check | the Trainer's first chunk against a plain AdamW: {out}")
    return out


def make_trainer(cell, mesh, forward, params, state, stream, out_dir,
                 optimizer=None):
    """The cell's Trainer over ``params`` (its own placed copy), its
    step counter set where the seed starts the run in the token stream
    (the counter is the stream's index and enters the chunk as data;
    the optimizer's own count starts at 0)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.train import Trainer

    opt = cell["optimizer"]
    tcfg = TrainingConfig(
        epochs=cell["warm_chunks"], steps_per_epoch=cell["steps_per_chunk"],
        global_batch_size=stream["batch_per_data_shard"],
        learning_rate=opt["learning_rate"],
        weight_decay=opt["weight_decay"],
        warmup_steps=opt.get("warmup_steps", 0),
        metrics_path=os.path.join(out_dir, "train.jsonl"),
    )
    if os.path.exists(tcfg.metrics_path):
        os.remove(tcfg.metrics_path)
    trainer = Trainer(
        tcfg, mesh, forward, params, model_state=state,
        optimizer=optimizer, batch_pspec=P("data"),
    )
    trainer.state = trainer.state.replace(step=jax.device_put(
        jax.numpy.int32(stream["start_step"]), NamedSharding(mesh, P())
    ))
    return trainer


def first_chunk(grad_fn, trainer_of, params, state, ds, stream, mesh, cell,
                log):
    """:func:`plain_adamw` over the batches the stream holds where the
    run starts, then the first chunk of the Trainer ``trainer_of()``
    makes over the same ``params`` (its compile and the first of the
    warm-up's chunks), then :func:`update_errors` -> (the trainer, its
    ``fit``'s summary, the readings)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = NamedSharding(mesh, P("data"))
    want, moved = plain_adamw(grad_fn, params, state, [
        jax.device_put(ds.batch_at(
            stream["start_step"] + i, stream["batch_per_data_shard"]
        ), rows) for i in range(cell["steps_per_chunk"])
    ], cell["optimizer"])
    # Off the device while the chunk runs: the timed program's memory
    # is a deployment's, not a deployment's and a check's.
    want = jax.device_get(want)
    trainer = trainer_of()
    warm = trainer.fit(ds, epochs=1)
    return trainer, warm, update_errors(
        jax.device_put(want, NamedSharding(mesh, P())), moved,
        trainer.state.params, cell["check"], log,
    )


def run(ctx):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_hpc.models import datasets
    from tpu_hpc.parallel import tp
    from tpu_hpc.runtime import MeshSpec, build_mesh

    spec, log = ctx["spec"], ctx["log"]
    cell, config = spec["cell"], spec["config"]
    program = config["program"]
    gen = harness.load_module("traffic", f"{spec['traffic']['kind']}.py")
    cfg, arch = build(config, cell, max_seq_len=spec["traffic"]["seq_len"])
    stream = gen.generate(
        spec["traffic"], ctx["seed"], cfg.vocab_size, ctx["seconds"]
    )
    seq_len = stream["seq_len"]

    axes = dict(cell["mesh"])
    if set(axes) != {"data"} or axes["data"] != 1:
        raise SystemExit(
            "benchmark: job train_arch runs mesh {data: 1} (an expert "
            "stack has no sharding plan yet)"
        )
    devices = ctx["devices"]
    mesh = build_mesh(
        MeshSpec(axes=axes),
        devices if len(devices) != jax.device_count() else None,
    )
    flash = cell["flash"]
    attn_fn = tp.make_tp_flash_attn_fn(
        mesh, "data", None, impl=flash["impl"],
        block_q=flash["block_q"], block_k=flash["block_k"],
    )
    rep = NamedSharding(mesh, P())
    phases = {}
    t = time.perf_counter()
    params, state = init(config, cfg, ctx["seed"], rep)
    jax.block_until_ready(params)
    phases["init_s"] = time.perf_counter() - t
    forward = _resolve(program["forward"])(cfg, attn_fn)
    program_loss = _resolve(program["loss"])
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, s, b: program_loss(p, s, b, cfg, attn_fn), has_aux=True
    ))
    steps = cell["steps_per_chunk"]
    batch = stream["batch_per_data_shard"]
    ds = datasets.TokenStream(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        seed=stream["stream_seed"],
    )

    t = time.perf_counter()
    checked = check(
        grad_fn,
        harness.load_module("reference", f"{program['reference']}.py"),
        reference_kwargs(arch), params, state, stream, mesh,
        cell["check"], log,
    )
    phases["reference_s"] = time.perf_counter() - t
    t = time.perf_counter()
    trainer, warm, stepped = first_chunk(
        grad_fn, lambda: make_trainer(
            cell, mesh, forward, params, state, stream, ctx["out_dir"]
        ), params, state, ds, stream, mesh, cell, log,
    )
    del params  # the trainer holds its own placed copy
    phases["first_chunk_s"] = time.perf_counter() - t
    t = time.perf_counter()
    if cell["warm_chunks"] > 1:
        warm = trainer.fit(ds, epochs=cell["warm_chunks"] - 1)
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

    with open(trainer.cfg.metrics_path) as f:
        records = [json.loads(line) for line in f]
    chunks = [r for r in records if r["event"] == "epoch"]
    losses = [r["loss"] for r in chunks]
    window = chunks[cell["warm_chunks"]:]
    # A chunk's record carries its last step's loss (null where it was
    # not finite); a step that went non-finite poisons the parameters
    # and so every later loss, so the chunk's last loss speaks for all
    # of its steps.
    failed = steps * sum(1 for r in window if r["loss"] is None)
    counted = [r.get("counted", {}) for r in window]
    # The Trainer's rule for a chunk, over the window's chunks: a
    # ``*_total`` adds up, a high-water mark takes the largest.
    moe = {
        name: (sum if name.endswith("_total") else max)(
            c.get(name, 0) for c in counted
        ) for name in sorted(set().union(*counted))
    }
    log("moe | a chunk: " + json.dumps(counted))
    if trace:
        by_name = program_trace.load({"trace": trace})
        for dev in (by_name or {"devices": {}})["devices"].values():
            per_step = {
                scope: round(1e3 * s / trace["steps"], 3)
                for scope, s in sorted(
                    dev["scopes_s"].items(), key=lambda kv: -kv[1]
                )
            }
            per_step["unscoped"] = round(1e3 * sum(
                p["unscoped_s"] for p in dev["programs"].values()
            ) / trace["steps"], 3)
            log(f"scopes | ms a step: {json.dumps(per_step)} | kernels "
                + json.dumps({
                    k: round(1e3 * v / trace["steps"], 3)
                    for k, v in dev["kernels_s"].items()
                }))
    end_step = int(jax.device_get(trainer.state.step))
    steps_done = end_step - stream["start_step"] - cell["warm_chunks"] * steps
    correct = bool(
        checked["ok"] and stepped["ok"]
        and compiles_in_window == 0 and failed == 0
        and steps_done == n_chunks * steps and len(window) == n_chunks
        and moe.get("train_moe_assignments_total", 0) > 0
        and moe.get("train_moe_dropped_total") == 0
    )
    return {
        "correct": correct,
        "attempted": n_chunks * steps,
        "failed": failed,
        "window_s": window_s,
        "t_window": t_window,
        "checks": {
            "reference": checked,
            "trainer_step": stepped,
            "compiles_in_window": compiles_in_window,
            "steps_done": steps_done,
            "losses": losses,
            "moe": moe,
            "remat_plan": trainer.remat_plan,
        },
        "phases": phases,
        "arch": arch,
        "flops_bytes": program.get("flops_bytes"),
        "mesh": axes,
        "train": {
            "chunks": [
                {"steps": steps, "seconds": s["total_s"]} for s in summaries
            ],
            "steps": n_chunks * steps,
            "tokens": n_chunks * steps * batch * seq_len,
            "tokens_per_step": batch * seq_len,
            "batch_per_chip": batch,
            "seq_len": seq_len,
            "remat": cfg.remat,
            "model_shards": 1,
            "data_shards": 1,
            "moe": moe,
        },
        "trace": trace,
    }
