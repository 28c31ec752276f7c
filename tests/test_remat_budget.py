"""Recomputation by memory budget (PR 32): how many of a model's
blocks keep their matmul outputs for the backward pass is decided by
the bytes the chip has left, through the trace, and the rest recompute
as ``remat=True`` always did.

The CPU reports no memory limit, so every Trainer of tier-1 gets count
0 (the parent's program); a limit is STATED here by standing in for
``runtime.topology.memory_stats``.
"""
import hashlib
import json
import logging

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from tpu_hpc.checks import fit
from tpu_hpc.config import TrainingConfig
from tpu_hpc.models import datasets, llama2, remat
from tpu_hpc.obs import get_registry, validate_file
from tpu_hpc.parallel import hybrid, tp
from tpu_hpc.runtime import MeshSpec, build_mesh, topology
from tpu_hpc.train import Trainer

# float32 compute: kept and recomputed products are then the same
# numbers on any backend, so equality can be asked to the last bit.
MODEL = llama2.LlamaConfig(
    dim=64, n_layers=3, n_heads=4, n_kv_heads=2, vocab_size=128,
    multiple_of=32, max_seq_len=16, remat=True, dtype=jnp.float32,
)
BATCH, SEQ = 2, MODEL.max_seq_len


def _batch():
    tokens = jax.random.randint(
        jax.random.key(1), (BATCH, SEQ), 0, MODEL.vocab_size
    )
    return tokens, jnp.roll(tokens, -1, axis=1)


def _loss_fn(cfg=MODEL, attn_fn=None):
    forward = llama2.make_forward(cfg, attn_fn=attn_fn)
    batch = _batch()
    return lambda p: forward(p, {}, batch, jax.random.key(0))[0]


def _roomy(cap=None):
    """A budget that holds every block, capped at ``cap``."""
    return remat.RematBudget(
        limit_bytes=1 << 40, resident_bytes=0, grad_bytes=0, cap=cap
    )


def _count(jaxpr, primitive):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, primitive)
    return n


def _grad_jaxpr(loss, params, budget):
    with remat.lowering_under(budget):
        # A fresh function each time: make_jaxpr, like jit, keeps a
        # trace by the function's identity.
        return jax.make_jaxpr(jax.value_and_grad(lambda p: loss(p)))(
            params
        ).jaxpr


@pytest.fixture(scope="module")
def params():
    return llama2.init_llama(jax.random.key(0), MODEL)


@pytest.fixture(scope="module")
def one_device():
    return build_mesh(MeshSpec(axes={"data": 1}), jax.devices()[:1])


# -- (a) the numbers ---------------------------------------------------


@pytest.mark.parametrize("keeping", [0, 1, MODEL.n_layers])
def test_loss_and_gradients_equal_to_the_last_bit(params, keeping):
    loss = _loss_fn()
    want = jax.jit(jax.value_and_grad(loss))(params)
    budget = _roomy(cap=keeping)
    with remat.lowering_under(budget):
        got = jax.jit(jax.value_and_grad(lambda p: loss(p)))(params)
    assert budget.blocks_kept == keeping
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert jnp.array_equal(w, g)


# -- (b) the program ---------------------------------------------------


@pytest.mark.parametrize("keeping", [1, 2, MODEL.n_layers])
def test_backward_recomputes_six_products_fewer_a_keeping_block(
    params, one_device, keeping
):
    """Each keeping block drops the three projections, the output
    projection, gate and up from the recomputation; the flash call is
    recomputed in every block all the same (its output and log-sum-exp
    are not kept: benchmark/flops_bytes.py counts two forward calls a
    layer under remat)."""
    flash = tp.make_tp_flash_attn_fn(
        one_device, "data", None, impl="pallas", block_q=8, block_k=8
    )
    loss = _loss_fn(attn_fn=flash)
    base = _grad_jaxpr(loss, params, None)
    kept = _grad_jaxpr(loss, params, _roomy(cap=keeping))
    assert (
        _count(base, "dot_general") - _count(kept, "dot_general")
        == 6 * keeping
    )
    # Forward, recomputed forward, dQ and dK/dV kernels a layer.
    assert _count(base, "pallas_call") == 4 * MODEL.n_layers
    assert _count(kept, "pallas_call") == 4 * MODEL.n_layers
    assert _count(base, "name") == 0
    assert _count(kept, "name") > 0


def test_remat_false_never_asks(params):
    """``remat=False`` keeps everything and consults no budget."""
    cfg = llama2.LlamaConfig(
        **{**vars(MODEL), "remat": False}
    )
    loss = _loss_fn(cfg)
    budget = _roomy()
    assert str(_grad_jaxpr(loss, params, budget)) == str(
        _grad_jaxpr(loss, params, None)
    )
    assert budget.n_blocks == 0 and budget.blocks_kept == 0


# -- (c) the rule, as arithmetic --------------------------------------


TOKENS = BATCH * SEQ
BLOCK = fit.kept_block_bytes(MODEL, TOKENS)
RECOMPUTE = sum(fit.activation_bytes(MODEL, TOKENS).values())


LIMIT = 1 << 30


def _budget_with_room(room, cap=None, grads=0):
    """A budget whose limit, less what is resident, the gradients, the
    safety share and full recomputation's own activations, leaves
    exactly ``room`` bytes for kept products."""
    resident = (
        LIMIT - int(remat.SAFETY_SHARE * LIMIT) - grads - RECOMPUTE - room
    )
    return remat.RematBudget(LIMIT, resident, grads, cap=cap)


@pytest.mark.parametrize("room,cap,want", [
    (0, None, 0),
    (BLOCK - 1, None, 0),
    (BLOCK * 3 // 2, None, 1),
    (BLOCK * MODEL.n_layers, None, MODEL.n_layers),
    (BLOCK * 100, None, MODEL.n_layers),
    (BLOCK * 100, 1, 1),
    (BLOCK * 100, 0, 0),
])
def test_blocks_keep_while_the_room_holds_one_more(
    params, room, cap, want
):
    budget = _budget_with_room(room, cap)
    _grad_jaxpr(_loss_fn(), params, budget)
    assert budget.n_blocks == MODEL.n_layers
    assert budget.block_bytes == BLOCK
    assert budget.blocks_kept == want
    assert budget.kept_bytes == want * BLOCK


def test_state_and_gradients_come_off_the_limit(params):
    budget = _budget_with_room(BLOCK * MODEL.n_layers, grads=BLOCK // 2)
    assert budget.free_bytes == RECOMPUTE + BLOCK * MODEL.n_layers
    budget.resident_bytes += BLOCK
    budget.grad_bytes += BLOCK // 2
    _grad_jaxpr(_loss_fn(), params, budget)
    assert budget.room_bytes == BLOCK * MODEL.n_layers - BLOCK * 3 // 2
    assert budget.blocks_kept == MODEL.n_layers - 2


def test_kept_block_bytes_is_the_issues_reckoning():
    """Mistral-7B widths: q 4096 + k 1024 + v 1024 + the residual 4096
    + gate 14336 + up 14336 = 38912 bf16 numbers a token a layer."""
    cfg = llama2.LlamaConfig(
        n_kv_heads=8, ffn_dim_multiplier=1.3, n_layers=2, remat=True
    )
    assert fit.kept_block_bytes(cfg, 1) == 38912 * 2
    assert fit.kept_block_bytes(cfg, 4096) == 304 * 2 ** 20
    assert fit.kept_block_bytes(cfg, 4096, tp_size=4) == 76 * 2 ** 20


# -- the Trainer -------------------------------------------------------


def _trainer(mesh, cfg=MODEL, specs=None, constrain=lambda x: x,
             batch=BATCH, **kw):
    tcfg = TrainingConfig(
        epochs=1, steps_per_epoch=2, global_batch_size=batch, **kw
    )
    params = llama2.init_llama(jax.random.key(0), cfg)
    return Trainer(
        tcfg, mesh, llama2.make_forward(cfg, constrain), params,
        param_pspecs=specs, batch_pspec=P("data"),
    )


def _state_limit(monkeypatch, limit):
    """Every device reports a limit of ``limit`` bytes."""
    monkeypatch.setattr(
        topology, "memory_stats", lambda device: {"bytes_limit": limit}
    )


def _stream(cfg=MODEL):
    return datasets.TokenStream(
        vocab_size=cfg.vocab_size, seq_len=cfg.max_seq_len, seed=0
    )


def test_no_limit_no_budget(one_device):
    """The CPU reports no limit: no budget, count 0, and the gauges
    say so."""
    tr = _trainer(one_device)
    assert tr._remat_budget() is None
    tr.fit(_stream())
    assert tr.remat_plan is None
    gauges = get_registry().snapshot()["gauges"]
    assert gauges["train_remat_blocks_kept"] == 0
    assert gauges["train_remat_kept_bytes"] == 0


def test_model_axis_halves_a_blocks_kept_bytes(monkeypatch):
    """Under tensor parallelism and the sequence-parallel constraint
    the kept products are split by the model axis."""
    _state_limit(monkeypatch, 1 << 40)
    plans = {}
    for axes in ({"data": 4}, {"data": 4, "model": 2}):
        mesh = build_mesh(
            MeshSpec(axes=axes), jax.devices()[:4 * axes.get("model", 1)]
        )
        specs, constrain = None, lambda x: x
        if "model" in axes:
            abstract = jax.eval_shape(
                lambda: llama2.init_llama(jax.random.key(0), MODEL)
            )
            specs = hybrid.hybrid_pspecs(
                abstract, tp.llama_rules(), data_size=4
            )
            constrain = tp.sp_constrain(
                mesh, dp_axis="data", sp_axis="model"
            )
        tr = _trainer(mesh, specs=specs, constrain=constrain, batch=8)
        budget = tr._remat_budget()
        assert budget.batch_shards == 4
        assert budget.model_shards == axes.get("model", 1)
        tr.train_step(_stream().batch_at(0, 8))
        plans[len(axes)] = tr.remat_plan
    assert plans[1]["blocks_kept"] == plans[2]["blocks_kept"] == 3
    assert plans[1]["block_bytes"] == fit.kept_block_bytes(MODEL, 2 * SEQ)
    assert plans[2]["block_bytes"] * 2 == plans[1]["block_bytes"]


# -- (d) no limit, no context: the parent's program --------------------

# sha256 of the StableHLO text of this file's tiny step as the commit
# before PR 32 lowered it. Re-pin only where the Trainer's step is
# MEANT to change.
PARENT_STEP_SHA256 = (
    "a46c9ce303d24a5f8a46b2115c7f20774b07705f1a28031aa569415fe06156e1"
)


def _step_text(tr):
    return jax.jit(tr._step_impl, donate_argnums=(0,)).lower(
        tr.state, _stream().batch_at(0, BATCH)
    ).as_text()


def test_without_a_limit_the_step_is_the_parents_text_for_text(
    one_device
):
    cfg = llama2.LlamaConfig(**{**vars(MODEL), "dtype": jnp.bfloat16})
    plain = _step_text(_trainer(one_device, cfg))
    with remat.lowering_under(_roomy(cap=0)):
        forced = _step_text(_trainer(one_device, cfg))
    with remat.lowering_under(_roomy()):
        keeping = _step_text(_trainer(one_device, cfg))
    assert forced == plain
    assert keeping != plain
    assert (
        hashlib.sha256(plain.encode()).hexdigest() == PARENT_STEP_SHA256
    )


# -- (e) a stated limit ------------------------------------------------
# (What the compiled step then HOLDS is asked of the chip's own
# compiler, in tests/test_fit.py::test_keeping_blocks_hold_what_the_
# model_reckons: the CPU's drops the barriers that make recomputation
# real, and its temporaries read the same at every count.)

WIDE = llama2.LlamaConfig(
    dim=128, n_layers=4, n_heads=4, n_kv_heads=2, vocab_size=64,
    multiple_of=64, max_seq_len=64, remat=True,
)


@pytest.mark.parametrize("keeping", [0, 2, 4])
def test_a_stated_limit_decides_the_count(monkeypatch, one_device, keeping):
    """The Trainer's own reckoning, end to end: a limit that holds its
    state, its gradients, full recomputation's activations, the safety
    share and ``keeping`` blocks and a half."""
    tokens = 4 * WIDE.max_seq_len
    block = fit.kept_block_bytes(WIDE, tokens)
    tr = _trainer(one_device, WIDE, batch=4)
    nbytes = lambda tree: sum(  # noqa: E731
        leaf.nbytes for leaf in jax.tree.leaves(tree)
    )
    limit = int(
        (
            nbytes(tr.state) + nbytes(tr.state.params)
            + sum(fit.activation_bytes(WIDE, tokens).values())
            + block * keeping + block // 2
        ) / (1 - remat.SAFETY_SHARE)
    )
    _state_limit(monkeypatch, limit)
    tr._get_epoch_fn(_stream(WIDE), 1)
    assert tr.remat_plan == {
        "blocks_kept": keeping, "kept_bytes": keeping * block,
        "n_blocks": 4, "block_bytes": block, "bytes_limit": limit,
        "budget_bytes": tr.remat_plan["budget_bytes"],
    }
    assert 0 <= tr.remat_plan["budget_bytes"] - keeping * block < block


# -- (f) a compile refused for memory ----------------------------------


def test_refused_compile_falls_back_and_logs(
    monkeypatch, one_device, caplog, tmp_path
):
    _state_limit(monkeypatch, 1 << 40)
    real = jax.stages.Lowered.compile
    refused = []

    def compile_(self, *a, **kw):
        if len(refused) < 2:
            refused.append(1)
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                "Ran out of memory in memory space hbm."
            )
        return real(self, *a, **kw)

    monkeypatch.setattr(jax.stages.Lowered, "compile", compile_)
    path = tmp_path / "train.jsonl"
    tr = _trainer(one_device, metrics_path=str(path))
    # 3 keeping blocks refused -> at most 1 -> refused -> 0, which runs.
    with caplog.at_level(logging.WARNING, logger="tpu_hpc"):
        result = tr.fit(_stream())
    assert len(refused) == 2
    assert tr.remat_plan["blocks_kept"] == 0
    assert result["final_loss"] is not None
    warned = [r.getMessage() for r in caplog.records if "remat" in r.name
              or "remat |" in r.getMessage()]
    assert len(warned) == 2 and "3 of 3" in warned[0]
    assert "at most 1" in warned[0] and "at most 0" in warned[1]
    # The record train.jsonl carries, valid under the schema.
    validate_file(str(path))
    plans = [
        r for r in map(json.loads, path.read_text().splitlines())
        if r["event"] == "remat_plan"
    ]
    assert [p["blocks_kept"] for p in plans] == [0]


def test_a_refusal_with_nothing_kept_is_the_callers(
    monkeypatch, one_device
):
    """The fallback never hides a refusal the parent would have met."""

    def compile_(self, *a, **kw):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: no room")

    monkeypatch.setattr(jax.stages.Lowered, "compile", compile_)
    tr = _trainer(one_device)
    with pytest.raises(jax.errors.JaxRuntimeError):
        tr.train_step(_stream().batch_at(0, BATCH))


def test_plan_and_gauges_of_a_keeping_run(monkeypatch, one_device, tmp_path):
    """The counter that says how far it engaged: gauges, the run log's
    record, and a loss equal to the recomputing run's."""
    path = tmp_path / "train.jsonl"
    base = _trainer(one_device).fit(_stream())["final_loss"]
    _state_limit(monkeypatch, 1 << 40)
    tr = _trainer(one_device, metrics_path=str(path))
    assert tr.fit(_stream())["final_loss"] == base
    gauges = get_registry().snapshot()["gauges"]
    assert gauges["train_remat_blocks_kept"] == MODEL.n_layers
    assert gauges["train_remat_kept_bytes"] == MODEL.n_layers * BLOCK
    validate_file(str(path))
    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["event"] for r in records[:2]] == ["run_start", "remat_plan"]
    assert records[1]["blocks_kept"] == MODEL.n_layers
    assert records[1]["kept_bytes"] == MODEL.n_layers * BLOCK
    assert records[1]["bytes_limit"] == 1 << 40
    assert records[1]["budget_bytes"] > 0
