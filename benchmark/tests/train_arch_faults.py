"""Job kind ``train_arch``'s check with the system broken on purpose,
one fault at a time: what each limit of a cell's ``check`` is set
UNDER. ``test_train_arch.py`` plants every fault at a toy size and
needs ``ok`` false; on the chip, at the cell's own size,

    chiprun -- python benchmark/tests/train_arch_faults.py \\
        train-lfm2-24b-a2b-1chip <seed> [fault,fault,...]

prints each fault's readings (and a sound run's: ``none`` for the
program, ``none_trainer`` for the Trainer's step) and appends
them to ``chiprun_out/train_arch_faults.jsonl``; those are the upper
readings beside the limits in ``workloads/<cell>.json``.

A fault of the PROGRAM is read by ``job.check`` (the reference's loss,
gradients and selections); a fault of the TRAINER's step, which that
comparison never enters, by ``job.first_chunk`` (a plain AdamW).
"""
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

PROGRAM_FAULTS = (
    "float8_operands",         # the nearest precision below bf16
    "expert_left_out",         # a held expert's rows are not computed
    "gates_term_missing",      # the combine's transpose loses a term
    "selection_without_bias",  # the top-k is taken of the scores alone
)
TRAINER_FAULTS = (
    "state_unchanged",         # the chunk hands its state back as it was
    "half_batch",              # the step reads the first half of a batch
    "no_weight_decay",         # AdamW without its decay
)


@contextlib.contextmanager
def planted(fault):
    """The program with ``fault`` in it, for what is TRACED inside."""
    import jax
    import jax.numpy as jnp

    from tpu_hpc.models import conv_moe, sparse_moe

    dot, ragged = conv_moe._dot, jax.lax.ragged_dot
    slots, layer = sparse_moe._held_slots, conv_moe.expert_layer
    bwd = sparse_moe._combine_bwd

    def round8(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    if fault == "float8_operands":
        conv_moe._dot = lambda x, leaf, cfg, out_dtype=None: dot(
            round8(x.astype(cfg.dtype)),
            {"kernel": round8(leaf["kernel"].astype(cfg.dtype))},
            cfg, out_dtype,
        )
        jax.lax.ragged_dot = lambda x, w, sizes, **kw: ragged(
            round8(x), round8(w), sizes, **kw
        )
    elif fault == "expert_left_out":
        def without_last(cfg):
            held = slots(cfg)
            return jnp.where(held == cfg.n_held - 1, cfg.n_held, held)
        sparse_moe._held_slots = without_last
    elif fault == "gates_term_missing":
        def without_gates(res, d_out):
            out = bwd(res, d_out)
            return (out[0], jnp.zeros_like(out[1])) + out[2:]
        sparse_moe._combine.defvjp(sparse_moe._combine_fwd, without_gates)
    elif fault == "selection_without_bias":
        conv_moe.expert_layer = lambda u, lp, bias, cfg: layer(
            u, lp, jnp.zeros_like(bias), cfg
        )
    elif fault != "none":
        raise ValueError(f"no program fault {fault!r}")
    try:
        yield
    finally:
        conv_moe._dot, jax.lax.ragged_dot = dot, ragged
        sparse_moe._held_slots, conv_moe.expert_layer = slots, layer
        sparse_moe._combine.defvjp(sparse_moe._combine_fwd, bwd)


def grad_fn_of(config, cfg, attn_fn):
    import jax

    loss = harness.load_module("jobs", "train_arch.py")._resolve(
        config["program"]["loss"]
    )
    return jax.jit(jax.value_and_grad(
        lambda p, s, b: loss(p, s, b, cfg, attn_fn), has_aux=True
    ))


def program_readings(job, fault, spec, cfg, arch, attn_fn, params, state,
                     stream, mesh, log=lambda msg: None):
    """``job.check`` of the program with ``fault`` planted."""
    config = spec["config"]
    with planted(fault):
        return job.check(
            grad_fn_of(config, cfg, attn_fn),
            harness.load_module(
                "reference", f"{config['program']['reference']}.py"
            ),
            job.reference_kwargs(arch), params, state, stream, mesh,
            spec["cell"]["check"], log,
        )


def trainer_readings(job, fault, spec, cfg, attn_fn, params, state, stream,
                     mesh, out_dir, log=lambda msg: None, grad_fn=None):
    """``job.first_chunk`` of a Trainer with ``fault`` planted; the
    plain AdamW is fed the SOUND program's gradients (``grad_fn``: one
    compile for every fault, where the caller keeps it)."""
    import jax

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import datasets
    from tpu_hpc.train import trainer as trainer_mod

    cell, config = spec["cell"], spec["config"]
    forward = job._resolve(config["program"]["forward"])(cfg, attn_fn)
    optimizer = None
    if fault == "half_batch":
        whole = forward

        def forward(params, model_state, batch, rng):
            return whole(params, model_state, jax.tree.map(
                lambda a: a[:a.shape[0] // 2], batch
            ), rng)

        forward.config, forward.counters = whole.config, whole.counters
    elif fault == "no_weight_decay":
        opt = cell["optimizer"]
        optimizer = trainer_mod.make_adamw(trainer_mod.make_lr_schedule(
            TrainingConfig(
                learning_rate=opt["learning_rate"],
                warmup_steps=opt.get("warmup_steps", 0),
            )
        ), 0.0)
    elif fault not in ("state_unchanged", "none"):
        raise ValueError(f"no trainer fault {fault!r}")

    def trainer_of():
        trainer = job.make_trainer(
            cell, mesh, forward, params, state, stream, out_dir,
            optimizer=optimizer,
        )
        if fault == "state_unchanged":
            trainer.fit = lambda *args, **kwargs: None
        return trainer

    ds = datasets.TokenStream(
        vocab_size=cfg.vocab_size, seq_len=stream["seq_len"],
        seed=stream["stream_seed"],
    )
    return job.first_chunk(
        grad_fn or grad_fn_of(config, cfg, attn_fn), trainer_of, params,
        state, ds, stream, mesh, cell, log,
    )[2]


def main(cell_name, seed, faults):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_hpc.parallel import tp
    from tpu_hpc.runtime import MeshSpec, build_mesh, require_accelerator

    require_accelerator()
    job = harness.load_module("jobs", "train_arch.py")
    spec = harness.cell_spec(harness.load_manifest(), cell_name)
    cell, config = spec["cell"], spec["config"]
    cfg, arch = job.build(config, cell, spec["traffic"]["seq_len"])
    stream = harness.load_module(
        "traffic", f"{spec['traffic']['kind']}.py"
    ).generate(spec["traffic"], seed, cfg.vocab_size)
    mesh = build_mesh(MeshSpec(axes=dict(cell["mesh"])), None)
    flash = cell["flash"]
    attn_fn = tp.make_tp_flash_attn_fn(
        mesh, "data", None, impl=flash["impl"],
        block_q=flash["block_q"], block_k=flash["block_k"],
    )
    params, state = job.init(
        config, cfg, seed, NamedSharding(mesh, P())
    )
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sound = grad_fn_of(config, cfg, attn_fn)
    for fault in faults:
        if fault in TRAINER_FAULTS or fault == "none_trainer":
            out = trainer_readings(
                job, fault.replace("none_trainer", "none"), spec, cfg,
                attn_fn, params, state, stream, mesh, out_dir,
                log=harness.log, grad_fn=sound,
            )
        else:
            out = program_readings(
                job, fault, spec, cfg, arch, attn_fn, params, state,
                stream, mesh,
            )
            out.pop("leaves")
        out.update(fault=fault, seed=seed, cell=cell_name)
        print("READING " + json.dumps(out), flush=True)
        with open(os.path.join(out_dir, "train_arch_faults.jsonl"), "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main(
        sys.argv[1], int(sys.argv[2]),
        sys.argv[3].split(",") if len(sys.argv) > 3
        else PROGRAM_FAULTS + TRAINER_FAULTS,
    )
