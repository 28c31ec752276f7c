"""The Trainer: one jit-compiled training step over a sharded state.

Capability parity with the reference's Trainer classes
(multinode_ddp_basic.py:114-208, resnet_fsdp_training.py:104-136) and
their instrumented loops (multinode_ddp_unet.py:327-398): epoch loop,
per-batch throughput, periodic checkpointing, snapshot auto-resume.

TPU-first design: the strategy is not a wrapper around the model but a
pair of sharding plans (params spec tree + batch spec) handed to this
one Trainer. The whole update -- forward, backward, collectives,
optimizer -- is a single jitted function; XLA fuses DDP's all-reduce /
FSDP's all-gather+reduce-scatter into it according to the plan.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_hpc import obs
from tpu_hpc.config import TrainingConfig
from tpu_hpc.logging_ import get_logger
from tpu_hpc.models import remat
from tpu_hpc.parallel.fsdp import validate_grad_sync_mode
from tpu_hpc.parallel.plans import derived_pspecs, shardings_for
from tpu_hpc.resilience import guard as guard_lib
from tpu_hpc.resilience.faults import fault_plan_from_env
from tpu_hpc.resilience.guard import GuardPolicy
from tpu_hpc.resilience.heartbeat import (
    ENV_HANG_TIMEOUT,
    HangWatchdog,
    Heartbeat,
    current_attempt,
)
from tpu_hpc.resilience.signals import (
    ENV_ELASTIC_MANAGED,
    PreemptionGuard,
)
from tpu_hpc.runtime import topology
from tpu_hpc.train.metrics import GoodputMeter, ThroughputMeter


class TrainState(struct.PyTreeNode):
    """Carried training state. ``model_state`` holds non-trainable
    collections (BatchNorm stats etc.); step enables exact data-stream
    resume (datasets are step-indexed, SURVEY 5.4)."""

    step: jax.Array
    params: Any
    opt_state: Any
    model_state: Any


# forward(params, model_state, batch, step_rng) -> (loss, new_model_state, aux)
ForwardFn = Callable[[Any, Any, Any, jax.Array], Tuple[jax.Array, Any, Dict]]
# eval_forward(params, model_state, batch) -> (loss, aux) -- inference
# mode, no RNG, no state updates (BatchNorm runs on stored stats).
EvalForwardFn = Callable[[Any, Any, Any], Tuple[jax.Array, Dict]]


def _json_finite(x) -> Optional[float]:
    """JSON-safe float: non-finite becomes None. json.dumps would
    otherwise write a bare ``NaN`` token -- Python reads it back, but
    strict-JSON consumers of the run log (jq, BigQuery, JS) reject
    the whole line, and a poisoned step's record is exactly the one
    a dashboard must be able to parse."""
    x = float(x)
    return x if math.isfinite(x) else None


def _spec_extent(mesh: Mesh, spec: P) -> int:
    """Product of the mesh-axis sizes a spec names on ANY dim: how many
    ways an array under it is split."""
    out = 1
    for entry in spec:
        for n in (entry if isinstance(entry, tuple) else (entry,)):
            if n is not None:
                out *= mesh.shape[n]
    return out


def _leading_spec_extent(mesh: Mesh, spec: P) -> int:
    """Product of mesh-axis sizes sharding a spec's leading dim."""
    return _spec_extent(mesh, spec[:1])


def _bytes_per_device(tree: Any) -> int:
    """Bytes one device holds of a tree of placed arrays."""
    return sum(
        math.prod(leaf.sharding.shard_shape(leaf.shape))
        * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(tree)
    )


def is_counter(name: str) -> bool:
    """A step metric that COUNTS (``*_total``) is summed over a chunk's
    steps and a step's microbatches; a high-water mark
    (:func:`is_high_water`) takes the largest; any other reports the
    chunk's last step and the microbatches' mean."""
    return name.endswith("_total")


def is_high_water(name: str) -> bool:
    return "_max_" in name


def fold_metrics(stacked: Dict, mean: bool = False) -> Dict:
    """Stacked step metrics (leading axis: a chunk's steps, or a step's
    microbatches under ``mean``) -> one value each, by
    :func:`is_counter`'s rule."""
    def fold(name):
        if is_counter(name):
            return lambda a: jnp.sum(a, axis=0)
        if is_high_water(name):
            return lambda a: jnp.max(a, axis=0)
        return (lambda a: jnp.mean(a, axis=0)) if mean else (lambda a: a[-1])

    return {
        name: jax.tree.map(fold(name), value)
        for name, value in stacked.items()
    }


def merge_metrics(chunk: Dict, step: Dict) -> Dict:
    """:func:`fold_metrics` a step at a time, for the host-fed loop."""
    def merge(name, new):
        if name in chunk and is_counter(name):
            return chunk[name] + new
        if name in chunk and is_high_water(name):
            return jnp.maximum(chunk[name], new)
        return new

    return {name: merge(name, new) for name, new in step.items()}


def make_microbatch_constrain(
    mesh: Mesh, batch_sharding: Any
) -> Callable[[Any], Any]:
    """Constraint for a grad-accum microbatched tree [A, B/A, ...]:
    the batch sharding with the accumulation dim replicated. The single
    source for both the Trainer and the fit analyzer, so the step the
    analysis compiles pins microbatches exactly as the training step
    does."""
    micro_sharding = jax.tree.map(
        lambda s: NamedSharding(mesh, P(None, *s.spec)),
        batch_sharding,
        is_leaf=lambda x: isinstance(x, NamedSharding),
    )

    def constrain(tree):
        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(a, micro_sharding),
            tree,
        )

    return constrain


def make_lr_schedule(cfg: TrainingConfig):
    """Scalar or optax schedule from config. ``state.step`` counts
    optimizer updates, so schedules are grad-accum-agnostic and resume
    exactly from a checkpoint (the count rides in the opt state)."""
    total = max(cfg.epochs * cfg.steps_per_epoch, 1)
    if cfg.lr_schedule == "cosine":
        if cfg.warmup_steps >= total:
            raise ValueError(
                f"warmup_steps {cfg.warmup_steps} must be < the run "
                f"length of {total} optimizer updates "
                f"(epochs * steps_per_epoch) for cosine decay"
            )
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=cfg.learning_rate,
            warmup_steps=cfg.warmup_steps,
            decay_steps=total,
        )
    if cfg.lr_schedule != "constant":
        raise ValueError(
            f"unknown lr_schedule {cfg.lr_schedule!r}; "
            "expected 'constant' or 'cosine'"
        )
    if cfg.warmup_steps > 0:
        return optax.join_schedules(
            [
                optax.linear_schedule(
                    0.0, cfg.learning_rate, cfg.warmup_steps
                ),
                optax.constant_schedule(cfg.learning_rate),
            ],
            boundaries=[cfg.warmup_steps],
        )
    return cfg.learning_rate


def make_optimizer(cfg: TrainingConfig) -> optax.GradientTransformation:
    """SGD+momentum or AdamW from config (reference optimizers:
    SGD in the DDP/FSDP examples, AdamW with foreach=False in TP --
    tensor_parallel_vit.py:372-378; no foreach quirk exists here),
    with the configured LR schedule. ``adam_moments_dtype="bfloat16"``
    halves AdamW state HBM (mu AND nu; optax keeps the update math in
    fp32 and rounds the stored moments). ``max_grad_norm > 0``
    prepends a global-norm clip: under grad accumulation it sees the
    full accumulated gradient (the clip lives inside the optimizer
    update, after the accumulation scan), so the threshold means the
    same thing at every accum setting."""
    lr = make_lr_schedule(cfg)
    if cfg.weight_decay > 0:
        base = make_adamw(
            lr, cfg.weight_decay, cfg.adam_moments_dtype
        )
    elif cfg.adam_moments_dtype != "float32":
        # The default optimizer is SGD (weight_decay=0); silently
        # ignoring an explicit HBM-halving request would OOM the very
        # run the knob exists for, with no pointer at the cause.
        raise ValueError(
            f"adam_moments_dtype={cfg.adam_moments_dtype!r} has no "
            "effect on the SGD path -- set weight_decay > 0 to get "
            "AdamW, or drop the moments override"
        )
    else:
        base = optax.sgd(lr, momentum=cfg.momentum)
    if cfg.max_grad_norm < 0:
        raise ValueError(
            f"max_grad_norm {cfg.max_grad_norm} must be >= 0 (0 = off)"
        )
    if cfg.max_grad_norm > 0:
        return optax.chain(
            optax.clip_by_global_norm(cfg.max_grad_norm), base
        )
    return base


def make_adamw(
    lr, weight_decay: float, moments_dtype: str = "float32"
) -> optax.GradientTransformation:
    """AdamW with both moments stored in ``moments_dtype``.

    The single construction point shared by the Trainer and the fit
    analyzer (checks/fit.py) -- the fit report certifies the real
    step, so the two must not drift. ``"bfloat16"`` halves
    optimizer-state HBM (the documented unlock for 70B-class models
    on 16 GiB chips, REPORT_70b_128chip_2M.md): optax's ``mu_dtype``
    covers mu, and :func:`_cast_nu` stores nu in bf16 as well. The
    moment *math* stays fp32 -- the stored carries promote against
    the fp32 gradient inside scale_by_adam; only the carry rounds.
    """
    if moments_dtype == "bfloat16":
        return _cast_nu(
            optax.adamw(
                lr, weight_decay=weight_decay, mu_dtype=jnp.bfloat16
            ),
            jnp.bfloat16,
        )
    if moments_dtype != "float32":
        raise ValueError(
            f"adam_moments_dtype {moments_dtype!r} (float32|bfloat16)"
        )
    return optax.adamw(lr, weight_decay=weight_decay)


def _cast_nu(tx: optax.GradientTransformation, dtype):
    """Store the Adam second moment in ``dtype`` across steps.

    Wraps init/update to round ``ScaleByAdamState.nu`` after each
    update; the inner transform's arithmetic runs at its own (fp32)
    precision because the stored nu promotes on first use."""
    is_adam = lambda s: isinstance(s, optax.ScaleByAdamState)  # noqa: E731

    def cast(state):
        return jax.tree.map(
            lambda s: s._replace(
                nu=jax.tree.map(lambda a: a.astype(dtype), s.nu)
            ) if is_adam(s) else s,
            state,
            is_leaf=is_adam,
        )

    def init(params):
        return cast(tx.init(params))

    def update(updates, state, params=None):
        new_updates, new_state = tx.update(updates, state, params)
        return new_updates, cast(new_state)

    return optax.GradientTransformation(init, update)


def make_step_fn(
    forward: ForwardFn,
    optimizer: optax.GradientTransformation,
    seed: int,
    grad_accum: int = 1,
    microbatch_constrain: Optional[Callable[[Any], Any]] = None,
    log_grad_norm: bool = False,
    value_and_grad_fn: Optional[Callable] = None,
    health: bool = False,
    skip_nonfinite: bool = False,
    numeric_fault: Optional[Callable] = None,
) -> Callable[..., Tuple[Any, Dict]]:
    """The training-step body as a free function: forward, backward,
    optimizer update. The Trainer jits this; checks/fit.py AOT-lowers
    the very same function against abstract 7B-scale inputs, so the fit
    analysis certifies the real step, not a lookalike.

    ``grad_accum > 1`` splits the batch into that many microbatches and
    lax.scans the forward/backward, summing gradients and applying ONE
    optimizer update -- same optimizer trajectory as the full batch
    (gradient of the mean = mean of per-microbatch gradients), at
    1/grad_accum of the activation memory. ``state.step`` counts
    optimizer updates, so checkpoints, the data stream, and LR
    schedules are accumulation-agnostic. ``microbatch_constrain``
    re-pins each [A, B/A, ...] microbatched tree to the batch sharding
    (leading dim replicated); without it the reshape leaves microbatch
    rows spread over only a fraction of the data axis.

    ``value_and_grad_fn`` overrides how the (global) loss and gradient
    are computed from ``(params, model_state, batch, rng)`` -- the
    hook the manual comm modes use
    (comm.overlap.make_synced_value_and_grad: per-shard grads inside
    shard_map + explicit bucketed sync). Default None = plain
    ``jax.value_and_grad`` with GSPMD owning the collectives, the
    byte-identical flat path. Under grad accumulation the override
    runs per microbatch (psum is linear: syncing each microbatch's
    gradient and summing equals syncing the sum).

    ``health`` (the numeric-health guard, resilience.guard): the step
    additionally emits a fused health vector into its metrics --
    ``health_loss_finite`` / ``health_grad_norm`` /
    ``health_update_norm`` / ``health_nonfinite`` (leaves with any
    non-finite gradient element) -- computed inside the same jitted
    program, so guard detection rides the metrics the trainer already
    fetches once per chunk. ``skip_nonfinite`` (guard_mode="skip")
    drops the update on-device when the step is poisoned: params,
    opt state and model state keep their pre-step values while
    ``state.step`` still advances (the data stream moves past the bad
    batch), recorded as ``health_skipped``. ``numeric_fault`` is the
    chaos hook (faults.numeric_fault_fn): perturb (loss, grads) as a
    function of the DATA index.

    When either ``health`` or ``numeric_fault`` is armed the returned
    step takes a third argument, ``data_offset`` (a traced scalar:
    the cumulative guard skip-window shift, so
    ``data_index = state.step + data_offset``); otherwise the
    signature -- and the lowered program -- is byte-identical to a
    pre-guard trainer's.
    """
    if value_and_grad_fn is None:
        def value_and_grad_fn(params, ms, batch, rng):
            def loss_fn(p):
                loss, new_ms, aux = forward(p, ms, batch, rng)
                return loss, (new_ms, aux)

            return jax.value_and_grad(loss_fn, has_aux=True)(params)

    tracked = health or numeric_fault is not None

    def step_body(
        state: "TrainState", batch, data_offset
    ) -> Tuple["TrainState", Dict]:
        step_rng = jax.random.fold_in(jax.random.key(seed), state.step)

        if grad_accum == 1:
            (loss, (new_ms, aux)), grads = value_and_grad_fn(
                state.params, state.model_state, batch, step_rng
            )
        else:
            micro = jax.tree.map(
                lambda a: a.reshape(
                    grad_accum, a.shape[0] // grad_accum, *a.shape[1:]
                ),
                batch,
            )
            if microbatch_constrain is not None:
                micro = microbatch_constrain(micro)
            params = state.params

            def body(carry, xs):
                ms, gsum, lsum = carry
                i, mb = xs
                rng = jax.random.fold_in(step_rng, i)
                (loss, (new_ms, aux)), g = value_and_grad_fn(
                    params, ms, mb, rng
                )
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (new_ms, gsum, lsum + loss), aux

            gzero = jax.tree.map(jnp.zeros_like, state.params)
            (new_ms, gsum, lsum), aux_stack = jax.lax.scan(
                body,
                (state.model_state, gzero, jnp.zeros((), jnp.float32)),
                (jnp.arange(grad_accum), micro),
            )
            loss = lsum / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            aux = fold_metrics(aux_stack, mean=True)

        if numeric_fault is not None:
            # Chaos injection keyed on the DATA index: after a guard
            # rollback the skip window shifts the stream past the
            # poisoned index, so the relaunch genuinely never re-hits
            # it -- which is exactly what the rollback test proves.
            loss, grads = numeric_fault(
                state.step + data_offset, loss, grads
            )
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        new_ms_out = new_ms
        metrics = {"loss": loss, **aux}
        if health:
            # The fused health vector: four scalars riding the
            # stacked chunk metrics the trainer fetches anyway. The
            # norm reductions fuse into the step program like the
            # grad-clip norm does; with clipping on, XLA CSEs the
            # pair.
            loss_finite = jnp.isfinite(loss)
            grad_norm = optax.global_norm(grads)
            update_norm = optax.global_norm(updates)
            nonfinite = sum(
                (
                    jnp.any(~jnp.isfinite(g)).astype(jnp.int32)
                    for g in jax.tree.leaves(grads)
                ),
                jnp.zeros((), jnp.int32),
            )
            metrics["health_loss_finite"] = loss_finite.astype(
                jnp.float32
            )
            metrics["health_grad_norm"] = grad_norm
            metrics["health_update_norm"] = update_norm
            metrics["health_nonfinite"] = nonfinite
            if skip_nonfinite:
                # guard_mode="skip": a poisoned update never touches
                # the carried state -- params, moments AND model
                # state keep their pre-step values -- while step+1
                # still advances the data stream past the bad batch
                # (optax.apply_if_finite's semantics, but fused here
                # so the health vector and the skip share one
                # reduction).
                # update_norm included: finite grads can still
                # overflow the optimizer math (bf16 Adam moments) --
                # a NaN UPDATE poisons params just as surely.
                ok = (
                    loss_finite
                    & (nonfinite == 0)
                    & jnp.isfinite(grad_norm)
                    & jnp.isfinite(update_norm)
                )
                keep = lambda new, old: jax.tree.map(  # noqa: E731
                    lambda n, o: jnp.where(ok, n, o), new, old
                )
                new_params = keep(new_params, state.params)
                new_opt = keep(new_opt, state.opt_state)
                new_ms_out = keep(new_ms_out, state.model_state)
                metrics["health_skipped"] = (~ok).astype(jnp.int32)
        if log_grad_norm:
            if "grad_norm" in metrics:
                # Trace-time guard: silently overwriting a forward's
                # own 'grad_norm' aux would make the metric mean two
                # different things depending on max_grad_norm.
                raise ValueError(
                    "forward() reports an aux metric named "
                    "'grad_norm', which collides with the optimizer-"
                    "level norm logged when max_grad_norm > 0 -- "
                    "rename the aux metric"
                )
            # The PRE-clip norm of the accumulated-mean gradient --
            # the number the clip threshold is judged against. Free
            # when clipping is on: clip_by_global_norm computes the
            # identical reduction and XLA CSEs the pair (which is why
            # the Trainer enables this exactly when max_grad_norm > 0
            # -- unclipped configs keep their pinned collective
            # signatures byte-identical).
            metrics["grad_norm"] = optax.global_norm(grads)
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                model_state=new_ms_out,
            ),
            metrics,
        )

    if tracked:
        return step_body

    def step(state: "TrainState", batch) -> Tuple["TrainState", Dict]:
        # Guard off, no numeric fault: the 2-arg signature (and the
        # lowered program) every existing caller -- checks/fit.py's
        # AOT certification, the HLO no-creep pins -- compiled against.
        # data_offset=0 is dead at trace time: nothing reads it.
        return step_body(state, batch, 0)

    return step


class Trainer:
    def __init__(
        self,
        cfg: TrainingConfig,
        mesh: Mesh,
        forward: ForwardFn,
        params: Any,
        model_state: Any = None,
        param_pspecs: Any = None,
        batch_pspec: P = P("data"),
        optimizer: Optional[optax.GradientTransformation] = None,
        checkpoint_manager: Any = None,
        opt_param_pspecs: Any = None,
        eval_forward: Optional[EvalForwardFn] = None,
        comm_plan: Any = None,
    ):
        """``opt_param_pspecs``: optional separate plan for deriving
        optimizer-state shardings (defaults to ``param_pspecs``). This
        is how SHARD_GRAD_OP works: params replicated for compute,
        moments sharded (see fsdp.grad_op_pspecs).

        ``eval_forward``: inference-mode forward for ``evaluate``
        (models with train/eval behavior differences -- BatchNorm,
        dropout -- must supply one, e.g. resnet.make_eval_forward).
        Defaults to the training forward with state updates discarded,
        which is exact for stateless models (llama, vit).

        ``comm_plan``: a pre-resolved planner decision
        (comm.planner.CommDecision) for ``comm_mode="auto"`` --
        callers that had to resolve the decision BEFORE building the
        mesh (bench.py: the mode picks the mesh family) pass it here
        so the trainer runs exactly that decision instead of
        re-planning. Ignored unless cfg.comm_mode == "auto"."""
        from tpu_hpc.models import (
            conv_moe, hybrid_ssm_moe, latent_moe, sparse_moe,
        )

        hybrid_ssm_moe.refuse_weights(
            params, "the Trainer",
            "no training forward, loss or sharding plan goes through "
            "the state-space mixer or the expert layer",
        )
        sparse_moe.refuse_weights(
            params, "the Trainer",
            "no training forward, loss or sharding plan goes through "
            "the expert layer or the indexer",
        )
        latent_moe.refuse_weights(
            params, "the Trainer",
            "no training forward, loss or sharding plan goes through "
            "latent attention or the expert layer",
        )
        # An expert stack that none of the three served-only trees
        # owns trains only through its own configuration's forward.
        conv_moe.check_forward(params, forward, "the Trainer")
        self.cfg = cfg
        self.mesh = mesh
        self.forward = forward
        if optimizer is not None and cfg.max_grad_norm > 0:
            # The clip lives inside make_optimizer's chain; silently
            # dropping it here would train unclipped while the
            # grad_norm metric (keyed off cfg) implies otherwise --
            # and silently wrapping could double-clip an optimizer
            # that already chains its own.
            raise ValueError(
                f"max_grad_norm={cfg.max_grad_norm} has no effect on "
                "an explicitly passed optimizer -- chain "
                "optax.clip_by_global_norm into it yourself, or drop "
                "one of the two"
            )
        self.optimizer = optimizer or make_optimizer(cfg)
        self.checkpoint_manager = checkpoint_manager
        self.logger = get_logger()
        # Fault injection is read HERE (not at fit time): the numeric
        # chaos kinds (nan_loss / grad_spike) perturb the jitted step
        # itself, so the plan must exist before the step is built.
        self.fault_plan = fault_plan_from_env()
        if self.fault_plan is not None:
            stage_keys = self.fault_plan.stage_fault_keys()
            if stage_keys:
                # Vacuous-pass guard: the stage-scoped chaos kinds
                # target the MPMD pipeline runtime's per-stage fault
                # domains; on this SPMD Trainer they would never fire
                # and the chaos test would pass by doing nothing.
                raise ValueError(
                    f"TPU_HPC_FAULTS arms stage fault(s) "
                    f"{', '.join(stage_keys)}, but this is an SPMD "
                    "Trainer run -- stage faults are consumed only "
                    "by the MPMD pipeline runtime "
                    "(tpu_hpc.parallel.mpmd / bench.py --workload "
                    "llama-pp --pp-runtime mpmd); refusing to run a "
                    "chaos schedule that cannot inject"
                )
            slice_keys = self.fault_plan.slice_fault_keys()
            if slice_keys and os.environ.get(
                ENV_ELASTIC_MANAGED
            ) != "1":
                # Same vacuous-pass contract for the slice-scoped
                # kinds: a fixed-topology Trainer cannot morph, so a
                # slice fault here would never fire. Under the elastic
                # coordinator (which exports ENV_ELASTIC_MANAGED and
                # consumes the fault itself) the guard stands down.
                raise ValueError(
                    f"TPU_HPC_FAULTS arms slice fault(s) "
                    f"{', '.join(slice_keys)}, but this Trainer is "
                    "not running under the elastic coordinator "
                    "(tpu_hpc.elastic) -- a fixed-topology run "
                    "cannot morph; refusing to run a chaos schedule "
                    "that cannot inject"
                )
        # Numeric-health guard (resilience.guard): None when
        # cfg.guard_mode == "off" -- the step program then stays
        # byte-identical to a pre-guard trainer (HLO no-creep pins).
        self.guard_policy = GuardPolicy.from_config(cfg)
        if (
            self.guard_policy is not None
            and checkpoint_manager is None
            and (
                self.guard_policy.mode == "rollback"
                or self.guard_policy.spike_action == "rollback"
            )
        ):
            # Either rollback trigger (poisoned-step action OR the
            # spike action) needs a snapshot to roll back to; failing
            # here beats an AttributeError at anomaly time.
            raise ValueError(
                "guard_mode='rollback' (or guard_spike_action="
                "'rollback') needs a checkpoint_manager: rollback-to-"
                "last-good restores a snapshot; without one the guard "
                "can only skip or record events"
            )
        numeric_fault = (
            self.fault_plan.numeric_fault_fn()
            if self.fault_plan is not None else None
        )
        # The step signature grows a data_offset arg exactly when the
        # guard or a numeric fault is armed (make_step_fn contract).
        self._guard_tracked = (
            self.guard_policy is not None or numeric_fault is not None
        )
        # Skip windows (persisted guard state): loaded per fit() from
        # the checkpoint dir; empty until a rollback ever happened.
        self._skip_windows: list = []
        self._fit_offset = 0
        self._rolled_back = False
        self.batch_sharding = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            batch_pspec,
            is_leaf=lambda x: isinstance(x, P),
        )

        if param_pspecs is None:
            param_pspecs = jax.tree.map(lambda _: P(), params)
        self.param_pspecs = param_pspecs

        # Place state on the mesh per plan, via a jitted reshard rather
        # than device_put: the step donates its input state, and
        # device_put can alias the caller's buffers (deleting them out
        # from under the caller on the first donation); jit outputs are
        # always fresh buffers.
        param_shardings = shardings_for(mesh, param_pspecs)
        params = jax.jit(lambda t: t, out_shardings=param_shardings)(params)
        # Optimizer moments shard like the params they mirror; without
        # explicit out_shardings XLA may park them on one device (they
        # have no data dependence on params).
        opt_abstract = jax.eval_shape(self.optimizer.init, params)
        opt_shardings = shardings_for(
            mesh,
            derived_pspecs(
                opt_abstract, params,
                opt_param_pspecs if opt_param_pspecs is not None
                else param_pspecs,
            ),
        )
        opt_state = jax.jit(self.optimizer.init, out_shardings=opt_shardings)(
            params
        )
        model_state = model_state if model_state is not None else {}
        ms_shardings = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), model_state
        )
        if jax.tree.leaves(model_state):
            model_state = jax.jit(lambda t: t, out_shardings=ms_shardings)(
                model_state
            )
        # step is replicated on the mesh (not left uncommitted): restore
        # paths reshard against this template, and a committed
        # single-device scalar would conflict with mesh-wide params.
        self.state = TrainState(
            step=jax.device_put(
                jnp.zeros((), jnp.int32), NamedSharding(mesh, P())
            ),
            params=params,
            opt_state=opt_state,
            model_state=model_state,
        )

        if eval_forward is None:
            if jax.tree.leaves(
                model_state if model_state is not None else {}
            ):
                # Stateful model (BatchNorm etc.): the train-mode
                # forward normalizes by batch statistics, so defaulting
                # to it would report a wrong "inference" metric.
                self.logger.warning(
                    "no eval_forward given for a stateful model; "
                    "evaluate() will run the TRAIN-mode forward "
                    "(batch statistics, not stored stats) -- pass "
                    "eval_forward (e.g. resnet.make_eval_forward) for "
                    "true inference-mode metrics"
                )

            def eval_forward(p, ms, batch):
                loss, _, aux = forward(
                    p, ms, batch, jax.random.key(cfg.seed)
                )
                return loss, aux
        self.eval_forward = eval_forward
        grad_accum = cfg.grad_accum_steps
        if grad_accum < 1:
            raise ValueError(
                f"grad_accum_steps must be >= 1, got {grad_accum}"
            )
        micro_constrain = None
        if grad_accum > 1:
            if cfg.global_batch_size % grad_accum:
                raise ValueError(
                    f"global_batch_size {cfg.global_batch_size} not "
                    f"divisible by grad_accum_steps {grad_accum}"
                )
            # Each microbatch must still cover the whole data axis --
            # an undersized microbatch shards unevenly (GSPMD pads
            # silently) and idles chips every pass, the half-throughput
            # misconfiguration local_batch_size exists to reject.
            micro_bs = cfg.global_batch_size // grad_accum
            data_extent = max(
                (
                    _leading_spec_extent(mesh, s)
                    for s in jax.tree.leaves(
                        batch_pspec,
                        is_leaf=lambda x: isinstance(x, P),
                    )
                ),
                default=1,
            )
            if micro_bs % data_extent:
                raise ValueError(
                    f"microbatch {micro_bs} (global "
                    f"{cfg.global_batch_size} / grad_accum "
                    f"{grad_accum}) not divisible by the batch-sharding "
                    f"extent {data_extent}"
                )
            # Re-pin each microbatched leaf [A, B/A, ...] to the batch
            # sharding with the accumulation dim replicated: the
            # [B] -> [A, B/A] reshape otherwise leaves each microbatch
            # row on a 1/A fraction of the data axis.
            micro_constrain = make_microbatch_constrain(
                mesh, self.batch_sharding
            )

        # Gradient-sync strategy (cfg.comm_mode, the comm-performance
        # layer): flat keeps GSPMD's fused collectives -- the step
        # program is byte-identical to a trainer that predates the
        # knob (pinned by the HLO no-creep test). Manual modes swap in
        # an explicit value_and_grad: per-shard grads inside shard_map
        # + bucketed (optionally two-phase ICI/DCN) reduction.
        # "auto" asks the collective planner (comm/planner.py): the
        # mode and bucket size come from the topology's measured cost
        # table (alpha-beta fallback when none), the decision rides
        # self.comm_plan and is logged as a schema-stamped comm_plan
        # event below. Numerics are unchanged either way -- every
        # candidate the planner may pick is step-identical to flat
        # (the PR-3 parity pins, re-pinned for auto in
        # tests/test_planner.py).
        comm_mode_cfg = getattr(cfg, "comm_mode", "flat")
        self.comm_plan = None
        bucket_bytes = cfg.comm_bucket_mb * 2 ** 20
        if comm_mode_cfg == "auto":
            if comm_plan is not None:
                self.comm_plan = comm_plan
            else:
                from tpu_hpc.comm.planner import (
                    plan_trainer_grad_sync,
                )

                self.comm_plan = plan_trainer_grad_sync(
                    mesh, batch_pspec, self.param_pspecs,
                    self.state.params, bucket_cap_bytes=bucket_bytes,
                )
            comm_mode_cfg = self.comm_plan.mode
            if self.comm_plan.bucket_bytes:
                bucket_bytes = self.comm_plan.bucket_bytes
        comm_mode = validate_grad_sync_mode(
            comm_mode_cfg, self.param_pspecs
        )
        self.comm_mode_resolved = comm_mode
        value_and_grad_fn = None
        if comm_mode != "flat":
            from tpu_hpc.comm import overlap

            value_and_grad_fn = overlap.make_synced_value_and_grad(
                forward, mesh, batch_pspec, self.state.params,
                comm_mode,
                bucket_bytes=bucket_bytes,
            )

        self._step_impl = make_step_fn(
            forward, self.optimizer, cfg.seed,
            grad_accum=grad_accum,
            microbatch_constrain=micro_constrain,
            log_grad_norm=cfg.max_grad_norm > 0,
            value_and_grad_fn=value_and_grad_fn,
            health=self.guard_policy is not None,
            skip_nonfinite=(
                self.guard_policy is not None
                and self.guard_policy.mode == "skip"
            ),
            numeric_fault=numeric_fault,
        )
        # Pin the output state to the planned layout. Without this the
        # compiler may propagate a *different* layout through the update
        # -- concretely, under SHARD_GRAD_OP the new params inherit the
        # sharded moments' layout from optax.apply_updates, silently
        # turning replicated-params into FULL_SHARD after one step.
        self._state_shardings = TrainState(
            step=NamedSharding(mesh, P()),
            params=param_shardings,
            opt_state=opt_shardings,
            model_state=ms_shardings,
        )
        self._step_fns: Dict[Any, Callable] = {}
        self._epoch_fns: Dict[Any, Callable] = {}
        # How far recomputation by budget engaged in the newest program
        # (models/remat.py); None until one is built on a device that
        # reports a memory limit.
        self.remat_plan: Optional[Dict[str, int]] = None
        self._eval_fns: Dict[Any, Callable] = {}
        self.meter = ThroughputMeter(n_devices=mesh.size)
        self._resumed = False
        # Resilience wiring (tpu_hpc.resilience): goodput accounting
        # always on (zero-cost counters); heartbeat/fault-injection
        # arm themselves from the supervisor's env contract and are
        # no-ops when unsupervised.
        self.goodput = GoodputMeter()
        self.heartbeat = Heartbeat.from_env()
        # (self.fault_plan was read at the top of __init__ -- the
        # numeric chaos kinds are baked into the jitted step.)
        # Checkpoint events (ckpt_fallback / ckpt_integrity) belong in
        # the run log next to the guard verdicts they explain; the
        # manager itself has no sink concept, so the trainer lends it
        # one (host 0 only, like every other run-log write).
        if self.checkpoint_manager is not None and hasattr(
            self.checkpoint_manager, "event_sink"
        ):
            self.checkpoint_manager.event_sink = self._sink()
        # Telemetry spine (tpu_hpc.obs): every record the Trainer
        # writes goes through the process bus -- schema-stamped, into
        # the flight-recorder ring on EVERY host, and to the metrics
        # JSONL on host 0. Flight dumps land next to the checkpoints
        # unless the supervisor already pointed them at its log dir.
        bus = obs.get_bus()
        if bus.flight_dir is None and cfg.checkpoint_dir:
            bus.flight_dir = cfg.checkpoint_dir
        # The planner's comm_mode="auto" verdict, as evidence: which
        # sync strategy this run actually trains under, predicted from
        # which table (or the model) -- next to the epoch records it
        # explains.
        if self.comm_plan is not None:
            self._append_metrics({
                "event": "comm_plan",
                "resolved_from": "auto",
                **self.comm_plan.summary(),
            })
        # Step-time watermark: flags stragglers/stalls (a ``stall``
        # event) and enriches the heartbeat so the supervisor can tell
        # hung from slow without attaching to the process.
        self.stall = obs.StallDetector()
        # HELP once at construction (the ServeMeter.__init__
        # discipline) -- the per-chunk loop must not re-describe
        # under the registry lock.
        reg = obs.get_registry()
        reg.describe("train_steps_total", "Optimizer steps completed")
        reg.describe("train_items_total",
                     "Training items consumed (global batch x steps)")
        reg.describe("train_step", "Current global optimizer step")
        reg.describe("train_step_s",
                     "Per-step wall time within the last chunk (s)")
        reg.describe("train_remat_blocks_kept",
                     "Blocks of the newest step program that keep their "
                     "matmul outputs for the backward pass (the rest "
                     "recompute)")
        reg.describe("train_remat_kept_bytes",
                     "Bytes a chip holds of those kept outputs")
        # What the forward itself counts each step (an expert layer's
        # ``train_moe_*``: models/conv_moe.py), described by the
        # forward that returns it.
        for name, text in getattr(forward, "counters", {}).items():
            reg.describe(name, text)
        # Anomaly-triggered capture (obs/trace.py): a stall-watermark
        # trip or a guard poisoned verdict auto-arms ONE bounded
        # jax.profiler trace + flight dump, keyed by the triggering
        # step's trace id -- symptom to evidence with no operator in
        # the loop. Built per fit() (cfg.capture_on_anomaly), but the
        # knob is validated HERE: a bad capture_steps must fail at
        # construction, not as a mid-fit traceback after bring-up
        # (the guard_mode/manager discipline).
        if cfg.capture_on_anomaly and cfg.capture_steps < 1:
            raise ValueError(
                f"capture_steps {cfg.capture_steps} must be >= 1 "
                "when capture_on_anomaly is set"
            )
        self.capture: Optional[obs.AnomalyCapture] = None
        # Live telemetry plane (obs/digest.py): per-HOST health
        # digests into $TPU_HPC_DIGEST_DIR every chunk boundary, so a
        # fleet rollup (python -m tpu_hpc.obs.live) can compare this
        # host's step watermark against its peers while the run is
        # still going. None (free) unless the env contract arms it.
        self.digest = obs.DigestPublisher.from_env(
            role="host", key=str(jax.process_index())
        )
        # Optional callable(state, step) run when a preemption notice
        # stops the run, BEFORE the emergency snapshot -- the hook for
        # recipe-level cleanup (flush custom logs, export metrics).
        self.on_preempt: Optional[Callable[[Any, int], None]] = None
        # Elastic quiesce hook (tpu_hpc.elastic coordinator):
        # callable(done_step) -> Optional[target_step], polled at
        # every chunk boundary. A target caps the next chunk so the
        # loop lands EXACTLY on it; reaching it stops fit() cleanly
        # with result["quiesced"]=True -- state live, nothing saved,
        # nothing exited -- so the coordinator can morph and resume.
        self.quiesce_check: Optional[
            Callable[[int], Optional[int]]
        ] = None
        self._adopted = False
        self._quiesced = False
        self._watchdog: Optional[HangWatchdog] = None

    def adopt_state(self, state: "TrainState") -> None:
        """Adopt a LIVE state tree (the elastic coordinator's morph
        path). The tree must already lie in this trainer's planned
        shardings -- reshard onto ``self._state_shardings`` first.
        An adopted trainer's fit() trusts the in-memory step over any
        disk checkpoint: a morph never wrote a snapshot, so the newest
        checkpoint predates the transition and resuming from it would
        silently re-train the morphed span."""
        self.state = state
        self._adopted = True

    # -- the HOT LOOP body lives in make_step_fn (SURVEY 3.1/3.4);
    # self._step_impl is bound in __init__ --

    def _get_epoch_fn(self, dataset, n_steps: int) -> Callable:
        """Jit (and cache) ``n_steps`` training steps as one ``lax.scan``,
        generating batches on-device from the dataset's traceable
        generator.

        One dispatch per chunk instead of (datagen + device_put + step)
        per batch: on remote/async transports per-dispatch latency
        otherwise dominates (each host->device round trip costs more
        than the step itself). This is the "minimise host<->device
        transfers" rule applied to the whole hot loop.

        ``state.step`` is the single source of truth for the data/RNG
        index inside the scan, so the stream stays aligned across
        resume regardless of where the checkpoint landed.
        """
        # Datasets are frozen dataclasses, so hash by value: an
        # id()-keyed cache could silently reuse a stale jitted epoch fn
        # after the id is recycled by the allocator. Unhashable datasets
        # fall back to identity keys, with the dataset pinned in the
        # cache entry so its id cannot be recycled while the entry lives.
        key = self._dataset_key(dataset, n_steps)
        if key in self._epoch_fns:
            return self._epoch_fns[key][0]
        gen = dataset.traced_batch
        bs = self.cfg.global_batch_size
        batch_sharding = self.batch_sharding

        if self._guard_tracked:
            # Guard/chaos-armed trainers thread the skip-window offset
            # through the chunk as a TRACED scalar: data and fault
            # indices become step+offset, and a post-rollback offset
            # change re-dispatches the SAME compiled chunk -- the
            # guard must not cost a recompile per rollback (nor any
            # in steady state: same program, one extra scalar input).
            def epoch_fn(state: TrainState, data_offset):
                def body(st, _):
                    batch = gen(st.step + data_offset, bs)
                    batch = jax.tree.map(
                        lambda a: jax.lax.with_sharding_constraint(
                            a, batch_sharding
                        ),
                        batch,
                    )
                    return self._step_impl(st, batch, data_offset)

                return jax.lax.scan(body, state, None, length=n_steps)

            lower_args = (
                self.state,
                jax.ShapeDtypeStruct(
                    (), jnp.int32,
                    sharding=NamedSharding(self.mesh, P()),
                ),
            )
        else:
            def epoch_fn(state: TrainState):
                def body(st, _):
                    batch = gen(st.step, bs)
                    batch = jax.tree.map(
                        lambda a: jax.lax.with_sharding_constraint(
                            a, batch_sharding
                        ),
                        batch,
                    )
                    return self._step_impl(st, batch)

                return jax.lax.scan(body, state, None, length=n_steps)

            lower_args = (self.state,)

        # AOT-compile now, outside the caller's timing window: epoch-0
        # throughput previously included XLA compilation (VERDICT r1
        # metering note), forcing benches to discard the whole first
        # epoch. The compiled executable is what gets cached.
        fn = self._compile_keeping(epoch_fn, lower_args)
        self._epoch_fns[key] = (fn, dataset)
        return fn

    def _remat_budget(self) -> Optional[remat.RematBudget]:
        """What a chip of this mesh has left for activations, for the
        model to spend on blocks that keep their matmul outputs
        (models/remat.py). None where no device reports a limit."""
        # Only what every process of a multi-host run reads alike may
        # decide the count (they must all lower the same program): the
        # limit of the hardware and the state by its shapes, never what
        # happens to be allocated on this host right now.
        limit = min(
            (
                topology.memory_stats(d).get("bytes_limit") or 0
                for d in self.mesh.local_devices
            ),
            default=0,
        )
        if not limit:
            return None
        # The gradient tree mirrors the parameters; under accumulation
        # the scan carries their sum beside each microbatch's own.
        grads = _bytes_per_device(self.state.params) * (
            2 if self.cfg.grad_accum_steps > 1 else 1
        )
        # A manual gradient sync runs the forward inside a whole-mesh
        # shard_map over replicated parameters: the model is handed its
        # own shard of the batch, and nothing is split by a model axis.
        manual = self.comm_mode_resolved != "flat"
        return remat.RematBudget(
            limit_bytes=limit,
            resident_bytes=_bytes_per_device(self.state),
            grad_bytes=grads,
            batch_shards=1 if manual else max(
                _spec_extent(self.mesh, s.spec)
                for s in jax.tree.leaves(self.batch_sharding)
            ),
            model_shards=1 if manual else self.mesh.shape.get("model", 1),
        )

    def _compile_keeping(self, fn: Callable, lower_args: Tuple) -> Callable:
        """Lower ``fn`` (a step or chunk over the donated state) with
        the remat budget held open, so that a model under ``remat``
        keeps what fits, and compile it. A compile refused for memory
        halves the blocks that keep, down to none: the program the
        budget never touched, which is all a device without a limit
        ever gets."""
        budget = self._remat_budget()
        while True:
            jitted = jax.jit(
                fn,
                donate_argnums=(0,),
                out_shardings=(self._state_shardings, None),
            )
            with remat.lowering_under(budget):
                lowered = jitted.lower(*lower_args)
            try:
                compiled = lowered.compile()
                break
            except jax.errors.JaxRuntimeError as e:
                if (
                    budget is None or not budget.blocks_kept
                    or "RESOURCE_EXHAUSTED" not in str(e)
                ):
                    raise
                budget.cap = budget.blocks_kept // 2
                self.logger.warning(
                    "remat | the step with %d of %d blocks keeping their "
                    "matmul outputs was refused for memory; retrying "
                    "with at most %d | %s",
                    budget.blocks_kept, budget.n_blocks, budget.cap,
                    str(e).splitlines()[0],
                )
                # jit keeps a trace by the function's identity: a fresh
                # one (same name, same program text) is traced again,
                # under the lowered cap.
                fn = functools.wraps(fn)(functools.partial(fn))
        self._report_remat(budget)
        return compiled

    def _report_remat(self, budget: Optional[remat.RematBudget]) -> None:
        """How far recomputation by budget engaged in the program just
        built: two gauges, one log line, one run-log record."""
        reg = obs.get_registry()
        reg.set_gauge(
            "train_remat_blocks_kept", budget.blocks_kept if budget else 0
        )
        reg.set_gauge(
            "train_remat_kept_bytes", budget.kept_bytes if budget else 0
        )
        if budget is None:
            return
        room = max(budget.room_bytes, 0)
        self.remat_plan = {
            "blocks_kept": budget.blocks_kept,
            "kept_bytes": budget.kept_bytes,
            "n_blocks": budget.n_blocks,
            "block_bytes": budget.block_bytes,
            "bytes_limit": budget.limit_bytes,
            "budget_bytes": room,
        }
        self.logger.info(
            "remat | %d of %d blocks keep their matmul outputs "
            "(%.3f GiB a chip; room %.3f GiB of a limit of %.3f GiB)",
            budget.blocks_kept, budget.n_blocks,
            budget.kept_bytes / 2 ** 30, room / 2 ** 30,
            budget.limit_bytes / 2 ** 30,
        )
        self._append_metrics(
            {"event": "remat_plan", "time": time.time(), **self.remat_plan}
        )

    def _offset_arg(self, off: int):
        """The chunk's skip-window offset as a mesh-replicated traced
        scalar -- a changed value re-dispatches the same compiled
        program (a baked Python int would recompile per rollback)."""
        return jax.device_put(
            jnp.int32(off), NamedSharding(self.mesh, P())
        )

    def train_step(self, batch) -> Dict:
        batch = jax.tree.map(
            lambda a: jax.device_put(a, self.batch_sharding), batch
        )
        args = (self.state, batch)
        if self._guard_tracked:
            args += (self._offset_arg(self._fit_offset),)
        key = tuple(
            (a.shape, a.dtype.name) for a in jax.tree.leaves(batch)
        )
        fn = self._step_fns.get(key)
        if fn is None:
            fn = self._step_fns[key] = self._compile_keeping(
                self._step_impl, args
            )
        self.state, metrics = fn(*args)
        return metrics

    def _dataset_key(self, dataset, *extra):
        try:
            key = (dataset, *extra)
            hash(key)
            return key
        except TypeError:
            return ((type(dataset).__name__, id(dataset)), *extra)

    def eval_step(self, batch) -> Dict:
        """One jitted inference-mode step (no grads, no state updates)."""
        batch = jax.tree.map(
            lambda a: jax.device_put(a, self.batch_sharding), batch
        )
        if "step" not in self._eval_fns:
            def one(state, b):
                loss, aux = self.eval_forward(
                    state.params, state.model_state, b
                )
                return {"loss": loss, **aux}

            self._eval_fns["step"] = (jax.jit(one), None)
        return self._eval_fns["step"][0](self.state, batch)

    def evaluate(self, dataset, n_steps: Optional[int] = None) -> Dict:
        """Jitted evaluation pass: mean loss (and any aux metrics, e.g.
        accuracy) over ``n_steps`` batches, sharded exactly like
        training.

        Parity: the reference's ``Trainer.test()`` accuracy loop
        (resnet_fsdp_training.py:138-155) and the UNet test-loss pass
        (multinode_fsdp_unet.py) -- under torch each rank loops and
        all-reduces correct-counts; here the whole pass is one scanned
        jit dispatch and the mesh handles the reduction.
        """
        n_steps = n_steps or self.cfg.steps_per_epoch
        bs = self.cfg.global_batch_size
        if hasattr(dataset, "traced_batch"):
            key = self._dataset_key(dataset, n_steps, "eval")
            if key not in self._eval_fns:
                gen = dataset.traced_batch
                batch_sharding = self.batch_sharding
                eval_forward = self.eval_forward

                def eval_fn(state: TrainState):
                    def body(_, i):
                        batch = gen(i, bs)
                        batch = jax.tree.map(
                            lambda a: jax.lax.with_sharding_constraint(
                                a, batch_sharding
                            ),
                            batch,
                        )
                        loss, aux = eval_forward(
                            state.params, state.model_state, batch
                        )
                        return None, {"loss": loss, **aux}

                    _, per_step = jax.lax.scan(
                        body, None, jnp.arange(n_steps)
                    )
                    return jax.tree.map(
                        lambda a: jnp.mean(a, axis=0), per_step
                    )

                self._eval_fns[key] = (jax.jit(eval_fn), dataset)
            metrics = self._eval_fns[key][0](self.state)
        else:
            # Accumulate on-device; one host sync at the end (the
            # module's minimise-host<->device-transfers rule).
            sums: Dict[str, jax.Array] = {}
            for i in range(n_steps):
                m = self.eval_step(dataset.batch_at(i, bs))
                for k, v in m.items():
                    sums[k] = sums[k] + v if k in sums else v
            metrics = {
                k: v / n_steps
                for k, v in jax.device_get(sums).items()
            }
        out = {
            k: float(jax.device_get(v)) for k, v in metrics.items()
        }
        if jax.process_index() == 0:
            self.logger.info(
                "eval | %s",
                " | ".join(f"{k} {v:.5f}" for k, v in sorted(out.items())),
            )
            # Reserved schema fields win over user metric names: an
            # eval aux named 'step'/'time' must not clobber the
            # record's position/timestamp for every consumer.
            self._append_metrics({
                **out,
                "event": "eval",
                "time": time.time(),
                "step": int(jax.device_get(self.state.step)),
                "n_steps": n_steps,
            })
        return out

    def _sink(self) -> Optional[str]:
        """The metrics JSONL path, on the host that owns the run log
        (host 0); None elsewhere, so bus emits ring-buffer only."""
        if self.cfg.metrics_path and jax.process_index() == 0:
            return self.cfg.metrics_path
        return None

    def _append_metrics(self, record: Dict) -> None:
        """Host-0 append-only JSONL run log (``cfg.metrics_path``) --
        the reference's benchmark_results.log discipline
        (scripts/main.py:381-397) as structured records, routed
        through the obs bus: schema-stamped (run_id/host/pid), held in
        the flight-recorder ring, and appended to the file when one is
        configured."""
        obs.get_bus().emit_record(record, sink=self._sink())

    def _emit_span(self, name: str, dur_s: float, step: int,
                   **fields) -> None:
        """One pre-measured phase duration as a ``span`` event (+ a
        registry histogram) -- the report's step-time breakdown reads
        these."""
        obs.emit_span(
            name, dur_s, sink=self._sink(), step=step,
            hist=f"train_{name}_s", **fields,
        )

    def _snapshot_config(self) -> None:
        """Write config.yaml next to the checkpoints -- the exact
        hyperparameters that produced them. Called at save time (a run
        that never saved cannot relabel another run's shards), AFTER
        the async save commits (wait()): a crash while the first save
        is still in flight must not leave this run's label on a
        previous run's shards. Once per fit -- the config cannot
        change mid-run, so later saves keep their async overlap."""
        if getattr(self, "_config_snapshotted", False):
            return
        ckpt_dir = getattr(self.checkpoint_manager, "directory", None)
        if ckpt_dir is None:
            return
        self.checkpoint_manager.wait()
        self._config_snapshotted = True
        if jax.process_index() != 0:
            return
        cfg = getattr(self, "_effective_cfg", self.cfg)
        cfg.to_yaml(os.path.join(ckpt_dir, "config.yaml"))

    def maybe_resume(self) -> int:
        """Snapshot auto-resume: continue from the stored step if a
        checkpoint exists (parity: multinode_ddp_basic.py:144-155)."""
        if self.checkpoint_manager is None or not self.cfg.resume:
            return 0
        with self.goodput.measure("restore"), obs.span(
            "restore", sink=self._sink(), hist="train_restore_s"
        ):
            restored = self.checkpoint_manager.restore_latest(
                self.state,
                max_inflight_bytes=(
                    self.cfg.reshard_max_inflight_mb * (1 << 20)
                    if getattr(self.cfg, "reshard_max_inflight_mb", 0)
                    else None
                ),
            )
        if restored is not None:
            self.state = restored
            step = int(jax.device_get(self.state.step))
            self.logger.info("resumed from checkpoint at step %d", step)
            info = getattr(
                self.checkpoint_manager, "last_restore_info", None
            )
            if info and info.get("elastic"):
                # The cross-topology path ran: this relaunch resumed
                # onto a DIFFERENT mesh shape via tpu_hpc.reshard.
                # Record it in the run log so the goodput report and
                # the elastic-resume test can see which restarts were
                # elastic and what the move cost.
                self.logger.info(
                    "elastic resume: checkpoint mesh %s -> live mesh "
                    "%s", info.get("src_mesh"), info.get("tgt_mesh"),
                )
                self._append_metrics({
                    "event": "elastic_restore",
                    "from_step": step,
                    "src_mesh": info.get("src_mesh"),
                    "tgt_mesh": info.get("tgt_mesh"),
                    "plan": info.get("plan"),
                })
            return step
        return 0

    def fit(
        self, dataset, epochs: Optional[int] = None,
        eval_dataset=None, eval_steps: Optional[int] = None,
    ) -> Dict:
        """Epoch loop with throughput instrumentation.

        Output format parity: per-batch global items/s, per-epoch and
        run summaries incl. per-device rate (multinode_ddp_unet.py:
        334-398). Dataset contract: ``batch_at(step, global_batch)``.

        ``eval_dataset``: run :meth:`evaluate` on it after every
        epoch (``eval_steps`` batches; default a full
        ``steps_per_epoch``) -- each pass logs and appends an
        ``event: eval`` record to the metrics JSONL, giving a train
        AND eval loss curve from one fit call (the convergence-run
        evidence format).
        """
        # Stage spans (obs.schema.STAGE_SPANS): where inside a fit the
        # host is, on the profiler's clock when a trace is on. ``host``
        # holds the open ``chunk.host`` span: everything outside a
        # chunk's dispatch and fetch, so it is open from here to the
        # first dispatch, from each fetch's return to the next
        # dispatch, and from the last fetch to the return, whichever
        # way the fit ends.
        with contextlib.ExitStack() as host:
            host.enter_context(obs.span("chunk.host"))
            return self._fit(host, dataset, epochs, eval_dataset, eval_steps)

    def _fit(self, host, dataset, epochs, eval_dataset, eval_steps) -> Dict:
        cfg = self.cfg
        epochs = epochs or cfg.epochs
        if epochs != cfg.epochs and cfg.lr_schedule == "cosine":
            # The cosine schedule was sized from cfg.epochs at optimizer
            # construction; a longer override would silently flatline at
            # the end value and a shorter one never completes decay.
            raise ValueError(
                f"fit(epochs={epochs}) conflicts with lr_schedule="
                f"'cosine' sized for cfg.epochs={cfg.epochs}: set "
                "cfg.epochs to the intended run length instead"
            )
        # Per-fit accounting: the goodput record is an attempt-scoped
        # trail; carrying buckets (or the wall-clock origin) across
        # fits would misreport every fit after the first.
        self.goodput = GoodputMeter()
        self._rolled_back = False
        self._fit_offset = 0
        self._skip_windows = []
        if self.guard_policy is not None:
            # Persisted guard state: skip windows from earlier
            # rollbacks (this process's or a previous attempt's) keep
            # fast-forwarding the stream past poisoned batches.
            self._skip_windows = guard_lib.load_state(
                self._guard_dir()
            )["skip_windows"]
        self._quiesced = False
        if self._adopted:
            # Live morphed state (adopt_state): the in-memory step IS
            # the data-stream truth. Disk holds only pre-morph
            # snapshots -- restoring one would rewind past the morph.
            start_step = int(jax.device_get(self.state.step))
        else:
            start_step = self.maybe_resume()
        # Preemption safety: TPU-VM spot/maintenance events deliver
        # SIGTERM with a short grace window. Snapshot-then-exit is the
        # recovery model (the reference's PBS-resubmission + snapshot
        # pattern, SURVEY 5.3): the relaunched job auto-resumes from
        # the saved step. Installed only around fit() and only when a
        # checkpoint manager exists; chunk boundaries check the flag
        # (PreemptionGuard handles the non-main-thread and
        # restore-previous-disposition edge cases).
        # (Guard install and watchdog start are deferred to just
        # before the try/finally below: an exception in the remaining
        # setup -- metrics I/O, profiler construction -- must not
        # leak a signal handler or leave an un-ticked watchdog to
        # os._exit the process while the real error propagates.)
        steps_per_epoch = cfg.steps_per_epoch
        total_steps = epochs * steps_per_epoch
        run_summaries = []
        last_metrics: Dict = {}
        # The EFFECTIVE run shape: a fit(epochs=) override must be
        # what the reproducibility records say, or re-running from
        # them trains a different length. Snapshotted next to the
        # checkpoints at SAVE time (not here): a run that dies before
        # its first save must not relabel shards an earlier run left
        # in the same directory.
        self._effective_cfg = dataclasses.replace(cfg, epochs=epochs)
        self._config_snapshotted = False  # per-fit: epochs may differ
        # Emitted on EVERY host (the file write still lands only on
        # host 0 via _sink), and even without a metrics_path: a
        # flight dump from whichever host wedges must carry the run's
        # identity and shape -- the wedging host is rarely the one
        # writing the run log.
        dev = jax.devices()[0]
        self._append_metrics({
            "event": "run_start",
            "time": time.time(),
            "start_step": start_step,
            "total_steps": total_steps,
            "n_devices": jax.device_count(),
            "n_processes": jax.process_count(),
            "device_kind": getattr(
                dev, "device_kind", dev.platform
            ),
            "jax_version": jax.__version__,
            "config": dataclasses.asdict(self._effective_cfg),
        })
        # Fast path: datasets with a traceable generator get whole-epoch
        # lax.scan (one dispatch/epoch); host-fed datasets fall back to
        # the per-step loop. A resume landing mid-epoch runs a shorter
        # first chunk so checkpoint cadence stays epoch-aligned.
        scanned = hasattr(dataset, "traced_batch")
        prof = None
        if cfg.profile:
            from tpu_hpc.profiling import TrainingProfiler

            prof = TrainingProfiler(
                cfg.profile_dir, cfg.profile_start_step,
                cfg.profile_num_steps,
            )
        done = start_step
        if cfg.capture_on_anomaly:
            self.capture = obs.AnomalyCapture(
                profile_dir=os.path.join(
                    cfg.checkpoint_dir or cfg.profile_dir, "anomaly"
                ),
                n_steps=cfg.capture_steps,
            )
        guard: Optional[PreemptionGuard] = None
        if self.checkpoint_manager is not None:
            guard = PreemptionGuard().install()
        # Hang watchdog (supervisor env contract): a train_step or
        # collective that stalls past the timeout aborts the process
        # with stack dumps + EXIT_HANG instead of hanging the
        # allocation. The timeout must cover one epoch chunk plus one
        # XLA compile -- ticks happen at chunk boundaries. Started
        # immediately before the try so the finally below is the only
        # exit path with it running.
        hang_timeout = float(
            os.environ.get(ENV_HANG_TIMEOUT, "0") or 0
        )
        if hang_timeout > 0:
            self._watchdog = HangWatchdog(
                hang_timeout,
                dump_path=os.path.join(
                    self.cfg.checkpoint_dir or ".",
                    f"hang.attempt{current_attempt()}.dump",
                ),
            ).start()
        try:
            last_metrics = self._fit_loop(
                host, dataset, done, total_steps, steps_per_epoch, scanned,
                prof, guard, run_summaries,
                eval_dataset=eval_dataset, eval_steps=eval_steps,
            )
        finally:
            # Always restore the SIGTERM disposition -- a dataset/OOM
            # exception mid-loop must not leave the no-op flag handler
            # installed for the life of the process (a later real
            # SIGTERM would then neither snapshot nor exit).
            if guard is not None:
                guard.restore()
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None
            if prof is not None:
                prof.stop()
            if self.capture is not None:
                # A capture window still open at teardown must not
                # leak its jax.profiler trace.
                self.capture.close()
        preempted = guard is not None and guard.triggered
        goodput = self.goodput.summary()
        end_step = int(jax.device_get(self.state.step))
        if jax.process_index() == 0:
            # Restart accounting: every fit appends one goodput record
            # so a supervised, preempted-and-resumed run leaves an
            # auditable productive-vs-overhead trail per attempt.
            self._append_metrics({
                "event": "run_end",
                "time": time.time(),
                "step": end_step,
                "preempted": preempted,
                "rolled_back": self._rolled_back,
                "attempt": current_attempt(),
                "resumed_from_step": start_step,
                "goodput": goodput,
            })
        # Close the run JSONL with the final counter/gauge/histogram
        # state -- ONE metrics namespace shared with serving, exported
        # the same two ways (JSONL snapshot + Prometheus textfile).
        reg = obs.get_registry()
        reg.emit_snapshot(sink=self._sink(), step=end_step)
        reg.write_prometheus()
        return {
            "epochs": run_summaries,
            "final_loss": float(jax.device_get(last_metrics["loss"]))
            if last_metrics
            else None,
            "preempted": preempted,
            "rolled_back": self._rolled_back,
            "quiesced": self._quiesced,
            "goodput": goodput,
        }

    def _fit_loop(
        self, host, dataset, done, total_steps, steps_per_epoch,
        scanned, prof, guard, run_summaries,
        eval_dataset=None, eval_steps=None,
    ):
        cfg = self.cfg
        last_metrics: Dict = {}
        while done < total_steps:
            if self._watchdog is not None:
                self._watchdog.tick()
            # Elastic quiesce: the coordinator's hook names the step
            # boundary it wants the run stopped at. Reaching it stops
            # the loop with everything live (no save, no exit); a
            # future target caps the chunk so the loop lands exactly
            # on it instead of overshooting into the next epoch.
            quiesce_at = None
            if self.quiesce_check is not None:
                quiesce_at = self.quiesce_check(done)
                if quiesce_at is not None and quiesce_at <= done:
                    self._quiesced = True
                    break
            epoch = done // steps_per_epoch
            chunk = min(steps_per_epoch - done % steps_per_epoch,
                        total_steps - done)
            if quiesce_at is not None:
                chunk = min(chunk, quiesce_at - done)
            # Guard skip windows: the data offset is constant within
            # one dispatched chunk (it rides in as one traced scalar),
            # so a chunk must never span a window boundary -- cap it
            # at the next offset change. Steps before the boundary
            # replay their original batches exactly; steps at/after it
            # fast-forward past the poisoned span.
            off = 0
            if self._guard_tracked and self._skip_windows:
                off = guard_lib.offset_at(self._skip_windows, done)
                nxt = guard_lib.next_boundary(self._skip_windows, done)
                if nxt is not None:
                    chunk = min(chunk, nxt - done)
            self._fit_offset = off
            # Steps are dispatched async and pipelined on-device; the
            # chunk is timed between two host fetches (a fetch forces
            # completion of everything dispatched before it). Per-batch
            # block_until_ready bracketing -- the reference's
            # cuda.synchronize pattern -- both breaks pipelining and
            # under-reports on asynchronous transports. Per-batch
            # variance is invisible by design on this path (one
            # dispatch per chunk); the host-fed fallback below still
            # meters per batch. Compilation happens inside
            # _get_epoch_fn (AOT), before the clock starts.
            if scanned:
                epoch_fn = self._get_epoch_fn(dataset, chunk)
            jax.device_get(self.state.step)  # drain pending work
            if self._watchdog is not None:
                # Compile time (AOT, above) must not eat into the
                # chunk's stall budget.
                self._watchdog.tick()
            if prof is not None:
                # Chunked loops advance a whole epoch per dispatch, so
                # the window opens/closes at chunk boundaries.
                prof.step(done)
            self.meter.reset()
            self.meter.start_batch()
            # The open ``chunk.host`` span (see fit) ends here.
            host.close()
            data_s = 0.0
            health_chunk = None
            with self.goodput.measure("productive"):
                with obs.span("chunk.dispatch"):
                    if scanned:
                        if self._guard_tracked:
                            self.state, stacked = epoch_fn(
                                self.state, self._offset_arg(off)
                            )
                        else:
                            self.state, stacked = epoch_fn(self.state)
                        last_metrics = fold_metrics(stacked)
                        if self.guard_policy is not None:
                            # The guard's per-step evidence: the stacked
                            # health vectors for the WHOLE chunk (a few
                            # scalars per step), fetched in the same
                            # device_get as the loss below.
                            health_chunk = {
                                k: stacked[k]
                                for k in guard_lib.HEALTH_KEYS
                                if k in stacked
                            }
                    else:
                        per_step_health = []
                        for i in range(chunk):
                            t_data = time.perf_counter()
                            batch = dataset.batch_at(
                                done + i + off, cfg.global_batch_size
                            )
                            data_s += time.perf_counter() - t_data
                            last_metrics = merge_metrics(
                                last_metrics if i else {},
                                self.train_step(batch),
                            )
                            if self.guard_policy is not None:
                                per_step_health.append({
                                    k: last_metrics[k]
                                    for k in guard_lib.HEALTH_KEYS
                                    if k in last_metrics
                                })
                        if self.guard_policy is not None and per_step_health:
                            health_chunk = {
                                k: [row[k] for row in per_step_health]
                                for k in per_step_health[0]
                            }
                # Injected straggler delay (chaos matrix): INSIDE the
                # metered window, so the slowness is visible to the
                # stall watermark exactly like a degraded host's.
                if self.fault_plan is not None:
                    self.fault_plan.maybe_straggle(done + chunk)
                # ONE host fetch per chunk, INSIDE the productive
                # window: it is both the chunk barrier (the dispatched
                # work isn't done until the fetch lands) and the
                # source for the log line, the JSONL record AND the
                # guard classification below -- fetching loss for the
                # barrier, loss again for the log, and the health
                # vectors separately would cost three device round
                # trips per chunk.
                with obs.span("chunk.fetch"):
                    last_metrics, health_chunk = jax.device_get(
                        (last_metrics, health_chunk)
                    )
            host.enter_context(obs.span("chunk.host"))
            chunk_s = self.meter.end_batch(chunk * cfg.global_batch_size)
            done += chunk
            s_per_step = chunk_s / max(chunk, 1)
            # The chunk's trace id (obs/trace.py): every phase span,
            # stall verdict and checkpoint bracket of this chunk
            # carries it, so the critical-path analyzer can decompose
            # per-step time and a capture correlates to the step that
            # tripped it. Run_id-scoped, so multi-host flight rings
            # merge on the same ids.
            tid = obs.step_trace_id(done)
            # Phase spans (the report's step-time breakdown). On the
            # scanned path data generation and the grad collectives
            # are fused into the one compiled chunk, so the whole
            # chunk is "compute" -- the report names the fusion
            # rather than silently omitting those phases; the
            # host-fed path meters its host data time separately.
            self._emit_span(
                "compute", max(chunk_s - data_s, 0.0), done, n=chunk,
                trace_id=tid,
            )
            if data_s > 0:
                self._emit_span(
                    "data", data_s, done, n=chunk, trace_id=tid
                )
            # Straggler/stall watermark: a breach emits a ``stall``
            # event (every host -- the straggling host is rarely the
            # one writing the run log).
            stall_info = self.stall.observe(
                done, s_per_step, sink=self._sink(), trace_id=tid
            )
            if stall_info is not None and self.capture is not None:
                # Stall -> evidence: one bounded profiler capture +
                # flight dump keyed by this chunk's trace id.
                self.capture.trigger(
                    "stall", trace_id=tid, step=done,
                    sink=self._sink(),
                )
            if self.capture is not None:
                self.capture.step(done)
            reg = obs.get_registry()
            reg.inc("train_steps_total", chunk)
            reg.inc("train_items_total", chunk * cfg.global_batch_size)
            reg.set_gauge("train_step", done)
            reg.observe("train_step_s", s_per_step)
            # What the forward counts (an expert layer's assignments
            # and rows: models/conv_moe.py) came in the chunk's one
            # fetch, already summed over its steps.
            counted = {
                k: int(v) for k, v in last_metrics.items()
                if is_counter(k) or is_high_water(k)
            }
            for name, value in counted.items():
                if is_counter(name):
                    reg.inc(name, value)
                else:
                    reg.set_gauge(name, max(reg.gauge(name) or 0, value))
            if self._watchdog is not None:
                self._watchdog.tick()
            if self.heartbeat is not None:
                # last-step + step-time enrichment: an outside reader
                # (the supervisor, an operator's cat) can now tell
                # "wedged" from "slower than its own recent past".
                self.heartbeat.tick(done, **self.stall.heartbeat_extra())
            if self.digest is not None:
                # The digest twin of the heartbeat enrichment: the
                # registry's counters/gauges + mergeable sketches and
                # the SAME normalized (step_s, watermark_s) signal,
                # published onto this host's channel for the fleet
                # rollup's cross-host straggler comparison.
                self.digest.publish_registry(
                    step=done, **self.stall.digest_extra()
                )
            summary = self.meter.epoch_summary(skip_first=0)
            run_summaries.append(summary)
            if jax.process_index() == 0:
                loss = float(last_metrics["loss"])
                self.logger.info(
                    "epoch %d | loss %.5f | %.1f items/s global | "
                    "%.1f items/s/device | %.3fs/step",
                    epoch, loss,
                    summary["items_per_s"],
                    summary["items_per_s_per_device"],
                    summary["total_s"] / max(chunk, 1),
                )
                rec = {
                    "event": "epoch",
                    "time": time.time(),
                    "epoch": epoch,
                    "step": done,
                    # A guarded run can legitimately log a poisoned
                    # chunk's NaN loss -- null, not a bare NaN token.
                    "loss": _json_finite(loss),
                    "items_per_s": summary["items_per_s"],
                    "items_per_s_per_device":
                        summary["items_per_s_per_device"],
                    "s_per_step": summary["total_s"] / max(chunk, 1),
                }
                if "grad_norm" in last_metrics:
                    rec["grad_norm"] = _json_finite(
                        last_metrics["grad_norm"]
                    )
                if counted:
                    rec["counted"] = counted
                self._append_metrics(rec)
                reg.set_gauge("train_loss", loss)
                reg.set_gauge(
                    "train_items_per_s", summary["items_per_s"]
                )
            # Prometheus textfile exposition: a no-op unless
            # $TPU_HPC_PROM_FILE names the scrape file.
            reg.write_prometheus()
            # Numeric-health guard: classify every step of the chunk
            # (host-side, against the rolling healthy-norm median)
            # BEFORE the periodic save below -- a poisoned state must
            # never become the newest snapshot. On rollback the loop
            # stops here: quarantine + skip window are durable, the
            # process exits EXIT_ROLLBACK, and the relaunch resumes
            # from the last-good checkpoint.
            if self.guard_policy is not None and health_chunk:
                if self._guard_check(done - chunk, chunk,
                                     health_chunk, off):
                    break
            # Fault injection (no-op unless TPU_HPC_FAULTS is set):
            # fires BEFORE the periodic save so a kill at step N
            # leaves the previous checkpoint as the newest one -- the
            # restart really re-trains the killed span.
            if self.fault_plan is not None:
                self.fault_plan.on_step(done)
            if eval_dataset is not None:
                # evaluate() logs and appends its own 'eval' metrics
                # record (host 0); runs on every host so any sharded
                # collectives inside the eval step stay collective.
                self.evaluate(eval_dataset, n_steps=eval_steps)
            if (
                self.checkpoint_manager is not None
                and cfg.save_every
                and done % (cfg.save_every * steps_per_epoch) == 0
            ):
                with self.goodput.measure("ckpt"), obs.span(
                    "ckpt", sink=self._sink(), step=done,
                    hist="train_ckpt_s", trace_id=tid,
                ):
                    self.checkpoint_manager.save(self.state)
                    self._snapshot_config()
            if guard is not None and guard.triggered:
                self.logger.warning(
                    "preemption notice (SIGTERM): snapshotting at "
                    "step %d and stopping -- exit with "
                    "resilience.EXIT_RESUMABLE; the relaunch "
                    "auto-resumes with --resume",
                    done,
                )
                if self.on_preempt is not None:
                    self.on_preempt(self.state, done)
                # Flight evidence FIRST: the ring holds the events
                # leading up to the notice, and the grace window may
                # not survive the emergency save below.
                obs.dump_flight("preempt")
                with self.goodput.measure("ckpt"), obs.span(
                    "ckpt", sink=self._sink(), step=done,
                    hist="train_ckpt_s", trace_id=tid,
                ):
                    if done not in (
                        self.checkpoint_manager.all_steps() or []
                    ):
                        # Emergency synchronous save: the grace window
                        # may be seconds; save_now blocks until the
                        # snapshot is durable.
                        self.checkpoint_manager.save_now(self.state)
                    self._snapshot_config()
                    self.checkpoint_manager.wait()
                break
        return last_metrics

    # -- numeric-health guard (resilience.guard) ----------------------
    def _guard_dir(self) -> Optional[str]:
        """Where guard state (skip windows) persists: next to the
        checkpoints it rolls back to."""
        return (
            getattr(self.checkpoint_manager, "directory", None)
            or self.cfg.checkpoint_dir
        )

    def _guard_check(
        self, chunk_start: int, chunk: int, health_chunk, offset: int
    ) -> bool:
        """Classify the chunk's per-step health vectors; emit
        guard_verdict events and counters; on a verdict the policy
        wants rolled back, execute the rollback and return True (the
        fit loop stops)."""
        policy = self.guard_policy
        reg = obs.get_registry()
        rows = guard_lib.health_rows(health_chunk, chunk)
        last_bad = rollback_at = None
        for i, row in enumerate(rows):
            step = chunk_start + i
            verdict = policy.classify(step, row)
            if verdict.skipped:
                reg.inc("guard_skipped_total")
            if verdict.healthy:
                continue
            reg.inc(f"guard_{verdict.verdict}_total")
            wants = policy.wants_rollback(verdict)
            rec = {
                "event": "guard_verdict",
                "step": step,
                # The verdict joins the step's causal trace -- a
                # guard-triggered capture is keyed by this exact id,
                # so the symptom record and the evidence bundle grep
                # to each other.
                "trace_id": obs.step_trace_id(step),
                "verdict": verdict.verdict,
                "action": (
                    "rollback" if wants
                    else "skip" if verdict.skipped else "event"
                ),
                "grad_norm": _json_finite(verdict.grad_norm),
                "update_norm": _json_finite(verdict.update_norm),
                "loss_finite": verdict.loss_finite,
                "nonfinite": verdict.nonfinite,
                "data_index": step + offset,
            }
            if verdict.watermark is not None:
                rec["watermark"] = verdict.watermark
            if verdict.ratio is not None:
                rec["ratio"] = verdict.ratio
            self._append_metrics(rec)
            self.logger.warning(
                "guard: step %d classified %s (grad_norm %s, "
                "nonfinite leaves %d) -- action %s",
                step, verdict.verdict, verdict.grad_norm,
                verdict.nonfinite, rec["action"],
            )
            if (
                self.capture is not None
                and verdict.verdict == "poisoned"
            ):
                # Poisoned step -> evidence bundle keyed by the
                # poisoned step's trace id (the rollback below also
                # dumps the ring; the capture's bundle additionally
                # carries the HBM state and, when the run continues
                # in skip mode, a bounded profiler window).
                self.capture.trigger(
                    "guard_poisoned",
                    trace_id=obs.step_trace_id(step),
                    step=step, sink=self._sink(),
                )
            # The rollback window anchors at the first verdict that
            # DEMANDS rollback -- an earlier event-only spike in the
            # same chunk was, by configured policy, fine to train
            # through; rolling its (healthy-by-policy) span back and
            # skipping its data would override that choice.
            if rollback_at is None and wants:
                rollback_at = step
            if rollback_at is not None:
                last_bad = step
        if rollback_at is None:
            return False
        self._guard_rollback(rollback_at, last_bad, offset)
        return True

    def _guard_rollback(
        self, first_bad: int, last_bad: int, offset: int
    ) -> None:
        """Rollback-to-last-good: quarantine any snapshot that
        contains the anomaly, persist the skip window over the
        poisoned data indices, and mark the fit rolled-back -- the
        entry point then exits EXIT_ROLLBACK and the supervisor
        relaunches from the last-good checkpoint (through the
        ordinary restore path, elastic reshard included)."""
        mgr = self.checkpoint_manager
        steps = sorted(mgr.all_steps() or [])
        good = [s for s in steps if s <= first_bad]
        if not good:
            raise guard_lib.GuardError(
                f"guard rollback needed at step {first_bad} but no "
                f"checkpoint predates the anomaly (steps on disk: "
                f"{steps}) -- save more often than anomalies arrive "
                "(cfg.save_every), or run guard_mode='skip'"
            )
        to_step = max(good)
        # A snapshot taken at step S holds S applied updates, so any
        # S > first_bad contains the poisoned one. With the guard on,
        # detection precedes the save at every chunk boundary, so this
        # list is normally empty -- it is belt for emergency preempt
        # saves that may have landed mid-anomaly.
        quarantined = [
            s for s in steps
            if s > first_bad
            and mgr.quarantine_step(s, reason="poisoned") is not None
        ]
        window = {
            "from_step": int(first_bad),
            "data_from": int(first_bad + offset),
            "data_to": int(last_bad + offset),
        }
        n_rollbacks = None
        if jax.process_index() == 0:
            state = guard_lib.record_rollback(self._guard_dir(), window)
            n_rollbacks = state["rollbacks"]
        obs.get_registry().inc("guard_rollbacks_total")
        rec = {
            "event": "guard_rollback",
            "step": last_bad + 1,
            # Keyed like the triggering verdict (the first step that
            # demanded rollback), so verdict, rollback record and any
            # guard-triggered capture join on one trace id.
            "trace_id": obs.step_trace_id(first_bad),
            "to_step": int(to_step),
            "first_bad": int(first_bad),
            "last_bad": int(last_bad),
            "data_from": window["data_from"],
            "data_to": window["data_to"],
            "quarantined": quarantined,
        }
        if n_rollbacks is not None:
            rec["n_rollbacks"] = n_rollbacks
        self._append_metrics(rec)
        self.logger.warning(
            "guard ROLLBACK: anomaly window steps [%d, %d] (data "
            "indices [%d, %d]); last-good checkpoint step %d; %d "
            "poisoned snapshot(s) quarantined -- exiting "
            "EXIT_ROLLBACK for the supervisor to relaunch",
            first_bad, last_bad, window["data_from"],
            window["data_to"], to_step, len(quarantined),
        )
        # Flight evidence: the ring holds the verdicts and the health
        # trail leading up to the anomaly.
        obs.dump_flight("guard_rollback")
        self._rolled_back = True
