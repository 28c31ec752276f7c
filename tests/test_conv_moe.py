"""models/conv_moe.py (LiquidAI/LFM2-24B-A2B's block, TRAINED) and the
ragged expert traversal of models/sparse_moe.py, held to the plain
reference ``benchmark/reference/conv_moe_decoder.py`` at small sizes on
the CPU, seeded weights:

(a) loss and every gradient leaf of the program's forward against the
    reference, whole model and each kind of layer alone;
(b) the ragged traversal against ``_dense_experts`` and against the
    reference, under even routing and under a routing skewed so that
    one expert gets most rows and one gets none; its gradients; its
    counts;
(c) the SHARE test: the results the 8 shares of an expert layer give
    add up to the uncut reference's (nothing but the router is
    computed alike on every chip);
(d) ``Trainer.fit`` lowers the loss, fetches the counters with the
    chunk's loss, drops nothing and leaves the selection bias alone;
(e) the Trainer still refuses the three served-only trees and a
    mismatched forward, and ``serve/`` refuses this configuration, by
    name;
(f) recomputation by budget asks block by block.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from benchmark.reference import conv_moe_decoder as ref  # noqa: E402
from tpu_hpc.models import (  # noqa: E402
    conv_moe, hybrid_ssm_moe, latent_moe, remat, sparse_moe,
)

HELD = (0, 1, 2, 3)
TINY = conv_moe.ConvMoEConfig(
    name="tiny-conv-moe", dim=64, n_layers=5, n_heads=4, n_kv_heads=2,
    vocab_size=96, max_seq_len=64, dense_hidden=160, first_dense_layers=1,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    n_experts=16, experts_per_token=4, expert_hidden=48,
    held_experts=HELD, dtype=jnp.float32, param_dtype=jnp.float32,
)
BATCH, SEQ = 2, 32


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def ref_kw(cfg, held=None):
    return dict(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.kv_heads,
        norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        layer_types=cfg.layer_types,
        first_dense_layers=cfg.first_dense_layers,
        experts_per_token=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor,
        held=cfg.held_experts if held is None else held,
    )


def make(cfg, seed=0):
    k_params, k_state = jax.random.split(jax.random.key(seed))
    return conv_moe.init_conv_moe(k_params, cfg), \
        conv_moe.init_state(k_state, cfg, bias_std=0.002)


def batch_of(cfg, seed=0, batch=BATCH, seq=SEQ):
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32
    )
    return jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])


def both_grads(cfg, seed=0, send_choice=False):
    """Loss, counts and each gradient leaf's relative error, program
    against reference; ``send_choice`` hands the reference the experts
    the program chose, as the benchmark's check does."""
    params, state = make(cfg, seed)
    x, y = batch_of(cfg, seed)

    def program(p):
        return conv_moe.loss_and_routing(p, state, (x, y), cfg)

    (got, (counts, chosen)), g = jax.jit(
        jax.value_and_grad(program, has_aux=True)
    )(params)

    def reference(p):
        return sum(
            ref.loss(
                p, state, x[i], y[i], **ref_kw(cfg),
                chosen={k: v[i] for k, v in chosen.items()}
                if send_choice else None,
            )[0] for i in range(x.shape[0])
        ) / x.shape[0]

    want, r = jax.jit(jax.value_and_grad(reference))(params)
    errs = jax.tree.map(
        lambda a, b: float(
            jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)
        ), g, r,
    )
    return float(got), float(want), counts, {
        jax.tree_util.keystr(path): e
        for path, e in jax.tree_util.tree_flatten_with_path(errs)[0]
    }


# -- (a) the program against the reference --------------------------------
KINDS = {
    "whole": TINY,
    "whole_recomputing": dataclasses.replace(TINY, remat=True),
    "conv_dense": dataclasses.replace(
        TINY, n_layers=1, layer_types=("conv",)
    ),
    "attention_dense": dataclasses.replace(
        TINY, n_layers=1, layer_types=("full_attention",)
    ),
    "conv_experts": dataclasses.replace(
        TINY, n_layers=1, layer_types=("conv",), first_dense_layers=0
    ),
    "attention_experts": dataclasses.replace(
        TINY, n_layers=1, layer_types=("full_attention",),
        first_dense_layers=0,
    ),
    "every_expert_held": dataclasses.replace(TINY, held_experts=None),
    "more_chosen_than_held": dataclasses.replace(
        TINY, held_experts=(5, 2), n_layers=2,
    ),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_loss_and_gradients_are_the_references(kind):
    cfg = KINDS[kind]
    got, want, counts, errs = both_grads(cfg)
    assert got == pytest.approx(want, abs=2e-6)
    worst = max(errs, key=errs.get)
    assert errs[worst] < 2e-5, (worst, errs[worst])
    # every leaf takes a gradient of its own (none is all zero)
    assert len(errs) == len(jax.tree.leaves(conv_moe.param_shapes(
        cfg), is_leaf=lambda s: isinstance(s, tuple)))
    if cfg.first_dense_layers < cfg.n_layers:
        assert int(counts["train_moe_dropped_total"]) == 0
        layers = cfg.n_layers - cfg.first_dense_layers
        assert int(counts["train_moe_assignments_total"]) == \
            layers * BATCH * SEQ * cfg.experts_per_token
    else:
        assert counts == {}


def test_bf16_products_stay_close_to_the_reference():
    """The cell's precision: bf16 operands, float32 accumulation and
    stream; the reference is sent the program's choice of experts, as
    in the benchmark's check (a near-tie that bf16 decides otherwise is
    another function, not a rounding). Looser than float32 by the
    operands' 8 bits and no more."""
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    got, want, _, errs = both_grads(cfg, send_choice=True)
    assert got == pytest.approx(want, abs=5e-3)
    assert max(errs.values()) < 0.03


def test_the_reference_sent_the_programs_choice_uses_it():
    """``chosen``: the reference computes with the experts it is sent
    (here: each token's LEAST likely four), and reports its own
    scores beside them."""
    cfg = dataclasses.replace(TINY, n_layers=2)
    params, state = make(cfg)
    x, y = batch_of(cfg)
    own, routed = ref.loss(params, state, x[0], y[0], **ref_kw(cfg))
    select, used = routed["layers_1"]
    assert (used == jax.lax.top_k(select, 4)[1]).all()
    worst = jnp.argsort(select, axis=-1)[:, :4]
    other, routed = ref.loss(
        params, state, x[0], y[0], chosen={"layers_1": worst}, **ref_kw(cfg)
    )
    assert (routed["layers_1"][1] == worst).all()
    assert float(other) != float(own)


def test_the_reference_imports_nothing_of_the_program():
    import ast

    with open(ref.__file__) as f:
        tree = ast.parse(f.read())
    names = {
        n.module if isinstance(n, ast.ImportFrom) else a.name
        for n in ast.walk(tree)
        if isinstance(n, (ast.Import, ast.ImportFrom))
        for a in (n.names if isinstance(n, ast.Import) else [n])
    }
    assert names == {"jax", "jax.numpy"}


# -- (b) the ragged traversal ---------------------------------------------
def _layer(cfg, n_tok, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    moe = {
        "w1": 0.1 * jax.random.normal(
            ks[0], (cfg.n_held, cfg.dim, cfg.expert_hidden)),
        "w3": 0.1 * jax.random.normal(
            ks[1], (cfg.n_held, cfg.dim, cfg.expert_hidden)),
        "w2": 0.1 * jax.random.normal(
            ks[2], (cfg.n_held, cfg.expert_hidden, cfg.dim)),
    }
    h = jax.random.normal(ks[3], (n_tok, cfg.dim))
    gates = jax.nn.softmax(
        jax.random.normal(ks[4], (n_tok, cfg.experts_per_token)), axis=-1
    )
    return moe, h, gates


def _routing(kind, cfg, n_tok):
    """[tokens, k] expert ids, distinct a token."""
    rng = np.random.default_rng(1)
    k = cfg.experts_per_token
    if kind == "even":
        picks = [rng.permutation(cfg.n_experts)[:k] for _ in range(n_tok)]
    elif kind == "skewed":
        # expert 1 gets every token, expert 2 none; the rest by chance
        others = [e for e in range(cfg.n_experts) if e not in (1, 2)]
        picks = [
            np.concatenate([[1], rng.permutation(others)[:k - 1]])
            for _ in range(n_tok)
        ]
    elif kind == "none_held":
        absent = [e for e in range(cfg.n_experts)
                  if e not in cfg.held_experts]
        picks = [rng.permutation(absent)[:k] for _ in range(n_tok)]
    else:  # all_on_held: the row buffer full to its last row
        picks = [rng.permutation(cfg.held_experts)[:k]
                 for _ in range(n_tok)]
    return jnp.asarray(np.stack(picks), jnp.int32)


def _dense(h, gates, experts, moe, cfg):
    slots = sparse_moe._held_slots(cfg)[experts]
    spread = jax.nn.one_hot(slots, cfg.n_held, dtype=jnp.float32)
    held_gates = jnp.einsum("tk,tke->te", gates, spread)
    return sparse_moe._dense_experts(h, held_gates, moe, cfg)


def _reference_layer(h, gates, experts, moe, cfg):
    out = jnp.zeros_like(h)
    for row, e in enumerate(cfg.held_experts):
        gate = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        out += gate[:, None] * ref._swiglu(
            h, moe["w1"][row], moe["w3"][row], moe["w2"][row]
        )
    return out


@pytest.mark.parametrize("routing", [
    "even", "skewed", "none_held", "all_on_held",
])
def test_the_ragged_traversal_is_the_dense_one_and_the_references(routing):
    cfg, n_tok = TINY, 96
    moe, h, gates = _layer(cfg, n_tok)
    experts = _routing(routing, cfg, n_tok)
    out, counts = jax.jit(
        lambda *a: sparse_moe.ragged_expert_ffn(*a, cfg)
    )(h, gates, experts, moe)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(
        out, _dense(h, gates, experts, moe, cfg), atol=2e-5
    )
    np.testing.assert_allclose(
        out, _reference_layer(h, gates, experts, moe, cfg), atol=2e-5
    )
    per_expert = [int(jnp.sum(experts == e)) for e in cfg.held_experts]
    assert int(counts["assignments"]) == n_tok * cfg.experts_per_token
    assert int(counts["assignments_held"]) == sum(per_expert)
    assert int(counts["max_rows_per_expert"]) == max(per_expert)
    assert int(counts["dropped"]) == 0
    tile = sparse_moe.RAGGED_ROW_TILE
    assert int(counts["rows_computed"]) % tile == 0
    assert int(counts["rows_computed"]) >= min(sum(per_expert), 1) * 1
    if routing == "skewed":
        assert per_expert[1] == n_tok and per_expert[2] == 0
    if routing == "none_held":
        assert int(counts["rows_computed"]) == 0 and not out.any()
    if routing == "all_on_held":
        assert sum(per_expert) == sparse_moe.ragged_rows(n_tok, cfg)


@pytest.mark.parametrize("routing", ["even", "skewed"])
def test_the_ragged_traversals_gradients_are_the_dense_ones(routing):
    """Rows, gates and each expert stack: the dispatch and the combine
    are written as the gathers their transposes are, so each of their
    terms is held to autodiff through the dense form."""
    cfg, n_tok = TINY, 64
    moe, h, gates = _layer(cfg, n_tok, seed=3)
    experts = _routing(routing, cfg, n_tok)
    probe = jax.random.normal(jax.random.key(9), h.shape)

    def ragged(h, gates, moe):
        out, _ = sparse_moe.ragged_expert_ffn(h, gates, experts, moe, cfg)
        return jnp.sum(out * probe)

    def dense(h, gates, moe):
        return jnp.sum(_dense(h, gates, experts, moe, cfg) * probe)

    got = jax.jit(jax.grad(ragged, argnums=(0, 1, 2)))(h, gates, moe)
    want = jax.jit(jax.grad(dense, argnums=(0, 1, 2)))(h, gates, moe)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-5)
    if routing == "skewed":     # an expert with no row takes no gradient
        assert not got[2]["w1"][2].any() and got[2]["w1"][1].any()


def test_the_row_tiles_are_counted_as_the_product_visits_them():
    """``rows_computed``: a tile once for each group it overlaps, no
    tile past the last row."""
    cfg = dataclasses.replace(
        TINY, n_experts=4, held_experts=(0, 1, 2), experts_per_token=1
    )
    tile = sparse_moe.RAGGED_ROW_TILE
    n_tok = 3 * tile
    # expert 0: tile + 1 rows (two tiles), expert 1: tile - 2 (sharing
    # expert 0's second tile, ending in the third), expert 2: none,
    # absent expert 3: the rest
    sizes = [tile + 1, tile - 2, 0]
    ids = np.concatenate([
        np.full(sizes[0], 0), np.full(sizes[1], 1),
        np.full(n_tok - sum(sizes), 3),
    ]).astype(np.int32)[:, None]
    moe, h, gates = _layer(cfg, n_tok)
    _, counts = jax.jit(
        lambda *a: sparse_moe.ragged_expert_ffn(*a, cfg)
    )(h, gates, jnp.asarray(ids), moe)
    assert int(counts["assignments_held"]) == 2 * tile - 1
    assert int(counts["rows_computed"]) == (2 + 1) * tile
    assert int(counts["max_rows_per_expert"]) == tile + 1


def test_the_serving_traversals_are_untouched():
    """``expert_ffn`` still chooses between its two forms by shape and
    knows nothing of the third."""
    keye = sparse_moe.KEYE_VL2_30B_A3B
    assert sparse_moe.grouped_by_shape(12, keye)
    assert not sparse_moe.grouped_by_shape(512, keye)
    import inspect

    assert "ragged" not in inspect.getsource(sparse_moe.expert_ffn)


# -- (c) the shares add up to the whole -----------------------------------
def test_the_eight_shares_add_up_to_the_uncut_reference():
    """One expert layer over 64 experts, 8 to a share: every share
    routes over all 64 (the router is the one thing computed alike),
    computes its own experts' part, and the 8 parts add up to what the
    reference gives with every expert held."""
    cfg = dataclasses.replace(
        TINY, n_experts=64, held_experts=None, n_layers=1,
        first_dense_layers=0,
    )
    params, state = make(cfg, seed=5)
    lp, bias = params["layers_0"], state["layers_0"]["router_bias"]
    u = jax.random.normal(jax.random.key(6), (BATCH, SEQ, cfg.dim))
    whole, _, _ = ref._experts(
        u.reshape(-1, cfg.dim), lp["moe"], bias, range(64), 4, 1.0, None
    )
    total, chosen = 0.0, []
    for share in range(8):
        ids = tuple(range(8 * share, 8 * share + 8))
        cut = dataclasses.replace(cfg, held_experts=ids)
        held = {
            **lp["moe"],
            **{w: lp["moe"][w][jnp.asarray(ids)] for w in ("w1", "w3", "w2")},
        }
        out, counts, picked = conv_moe.expert_layer(
            u, {"moe": held}, bias, cut
        )
        total = total + out
        chosen.append(picked)
        assert int(counts["dropped"]) == 0
        part, _, _ = ref._experts(
            u.reshape(-1, cfg.dim), held, bias, ids, 4, 1.0, None
        )
        np.testing.assert_allclose(
            out.reshape(-1, cfg.dim), part, atol=2e-5
        )
    assert all((c == chosen[0]).all() for c in chosen)
    np.testing.assert_allclose(
        total.reshape(-1, cfg.dim), whole, atol=5e-5
    )


# -- (d) through the Trainer ----------------------------------------------
@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


class _OneBatch:
    """The same seeded batch every step: something to memorise."""

    def __init__(self, cfg):
        self.batch = batch_of(cfg, seed=4, batch=4)

    def traced_batch(self, step, batch_size):
        return self.batch

    def batch_at(self, step, batch_size):
        return self.batch

    def __hash__(self):
        return id(self)


@pytest.fixture(scope="module")
def fitted(mesh, tmp_path_factory):
    from tpu_hpc import obs
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.obs import schema
    from tpu_hpc.train import Trainer

    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16, remat=True)
    params, state = make(cfg, seed=2)
    path = str(tmp_path_factory.mktemp("fit") / "train.jsonl")
    tcfg = TrainingConfig(
        epochs=3, steps_per_epoch=4, global_batch_size=4,
        learning_rate=3e-3, weight_decay=0.1, metrics_path=path,
    )
    obs.get_registry().reset()
    trainer = Trainer(
        tcfg, mesh, conv_moe.make_forward(cfg), params, model_state=state,
        batch_pspec=P("data"),
    )
    result = trainer.fit(_OneBatch(cfg), epochs=3)
    return cfg, state, trainer, result, schema.load_records(path, validate=True)


def test_fit_lowers_the_loss(fitted):
    _, _, _, _, records = fitted
    losses = [r["loss"] for r in records if r["event"] == "epoch"]
    assert len(losses) == 3 and losses[-1] < losses[0] - 0.05


def test_fit_fetches_the_counters_with_the_loss(fitted):
    from tpu_hpc import obs

    cfg, _, _, _, records = fitted
    chunks = [r["counted"] for r in records if r["event"] == "epoch"]
    made = 4 * (cfg.n_layers - 1) * 4 * SEQ * cfg.experts_per_token
    reg = obs.get_registry()
    for counted in chunks:
        assert counted["train_moe_assignments_total"] == made
        assert 0 < counted["train_moe_assignments_held_total"] < made
        assert counted["train_moe_rows_computed_total"] >= \
            counted["train_moe_assignments_held_total"]
        assert 0 < counted["train_moe_max_rows_per_expert"] <= 4 * SEQ
    assert reg.counter("train_moe_assignments_total") == 3 * made
    assert reg.gauge("train_moe_max_rows_per_expert") == max(
        c["train_moe_max_rows_per_expert"] for c in chunks
    )


def test_fit_drops_nothing(fitted):
    from tpu_hpc import obs

    records = fitted[4]
    assert all(
        r["counted"]["train_moe_dropped_total"] == 0
        for r in records if r["event"] == "epoch"
    )
    assert obs.get_registry().counter("train_moe_dropped_total") == 0


def test_fit_leaves_the_selection_bias_alone(fitted):
    _, state, trainer, _, _ = fitted
    for name, layer in state.items():
        assert (
            trainer.state.model_state[name]["router_bias"]
            == layer["router_bias"]
        ).all()


@pytest.mark.parametrize("accum", [1, 2])
def test_counters_add_up_over_microbatches(accum):
    """``*_total`` summed and ``_max_`` the largest over a step's
    microbatches (the mean the other metrics take would halve a count)."""
    import optax

    from tpu_hpc.train import trainer

    cfg = dataclasses.replace(TINY, n_layers=2)
    params, state = make(cfg)
    step = trainer.make_step_fn(
        conv_moe.make_forward(cfg), optax.sgd(0.0), 0, grad_accum=accum
    )
    ts = trainer.TrainState(
        step=jnp.int32(0), params=params,
        opt_state=optax.sgd(0.0).init(params), model_state=state,
    )
    _, metrics = jax.jit(step)(ts, batch_of(cfg, batch=4))
    assert int(metrics["train_moe_assignments_total"]) == \
        4 * SEQ * cfg.experts_per_token
    assert int(metrics["train_moe_dropped_total"]) == 0


def test_the_host_fed_loop_folds_a_chunk_the_same_way():
    """``merge_metrics`` a step at a time gives what ``fold_metrics``
    gives of the stacked steps: counters added, a high-water mark's
    largest, anything else the last step's."""
    from tpu_hpc.train import trainer

    steps = [
        {"loss": jnp.float32(3.0), "n_total": jnp.int32(5),
         "n_max_rows": jnp.int32(9)},
        {"loss": jnp.float32(2.0), "n_total": jnp.int32(7),
         "n_max_rows": jnp.int32(4)},
    ]
    merged = {}
    for step in steps:
        merged = trainer.merge_metrics(merged, step)
    stacked = {k: jnp.stack([s[k] for s in steps]) for k in steps[0]}
    folded = trainer.fold_metrics(stacked)
    assert {k: float(v) for k, v in merged.items()} == \
        {k: float(v) for k, v in folded.items()} == \
        {"loss": 2.0, "n_total": 12.0, "n_max_rows": 9.0}
    assert float(trainer.fold_metrics(stacked, mean=True)["loss"]) == 2.5


# -- (e) who refuses what, by name ----------------------------------------
def _served_only_tree(which):
    if which == "keye":
        cfg = dataclasses.replace(
            sparse_moe.KEYE_VL2_30B_A3B, dim=32, n_layers=1, n_heads=2,
            n_kv_heads=1, head_dim=16, vocab_size=64, n_experts=4,
            experts_per_token=2, expert_hidden=16, indexer_heads=2,
            indexer_head_dim=8, indexer_rope_dim=4, indexer_topk=4,
            max_seq_len=32,
        )
        shapes = sparse_moe.param_shapes(cfg)
    elif which == "joyai":
        cfg = dataclasses.replace(
            latent_moe.JOYAI_LLM_FLASH, dim=32, n_layers=2, n_heads=2,
            vocab_size=64, q_lora_rank=16, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            dense_hidden=48, n_experts=4, experts_per_token=2,
            expert_hidden=16, max_seq_len=32,
        )
        shapes = latent_moe.param_shapes(cfg)
    else:
        cfg = dataclasses.replace(
            hybrid_ssm_moe.GRANITE_4_0_H_SMALL, dim=32, n_layers=2,
            n_heads=2, n_kv_heads=1, vocab_size=64,
            layer_types=("mamba", "attention"), ssm_heads=4,
            ssm_head_dim=16, ssm_state=8, n_experts=4,
            experts_per_token=2, expert_hidden=16, shared_hidden=16,
            max_seq_len=32,
        )
        shapes = hybrid_ssm_moe.param_shapes(cfg)
    return jax.tree.map(
        lambda s: jnp.zeros(s), shapes, is_leaf=lambda s: isinstance(s, tuple)
    )


@pytest.mark.parametrize("which, name", [
    ("keye", "keye-vl2-30b-a3b"), ("joyai", "joyai-llm-flash"),
    ("granite", "granite-4.0-h-small"),
])
def test_the_trainer_still_refuses_the_served_only_trees(mesh, which, name):
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.train import Trainer

    with pytest.raises(NotImplementedError, match=name):
        Trainer(
            TrainingConfig(), mesh, conv_moe.make_forward(TINY),
            _served_only_tree(which),
        )


def test_the_trainer_wants_the_configurations_own_forward(mesh):
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.train import Trainer

    params, state = make(TINY)
    with pytest.raises(NotImplementedError, match="expert stack"):
        Trainer(TrainingConfig(), mesh, lambda *a: None, params)
    other = dataclasses.replace(TINY, expert_hidden=32)
    with pytest.raises(ValueError, match="tiny-conv-moe"):
        Trainer(
            TrainingConfig(), mesh, conv_moe.make_forward(other), params,
            model_state=state,
        )


@pytest.mark.parametrize("who", ["slab", "paged"])
def test_serving_refuses_it_by_name(mesh, who):
    from tpu_hpc.serve import Engine, ServeConfig
    from tpu_hpc.serve.paging import PagedConfig, PagedEngine

    params, _ = make(TINY)
    serve = ServeConfig(slots=2, max_seq_len=32, prefill_buckets=(8,))
    with pytest.raises(NotImplementedError, match="tiny-conv-moe"):
        if who == "slab":
            Engine(params, TINY, serve, mesh)
        else:
            PagedEngine(
                params, TINY, serve, mesh,
                PagedConfig(block_size=4, num_blocks=17),
            )


def test_bad_sizes_are_refused():
    with pytest.raises(ValueError, match="held_experts"):
        dataclasses.replace(TINY, held_experts=(0, 0))
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, n_layers=6)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, layer_types=("mamba",) * 5)
    with pytest.raises(ValueError, match="experts_per_token"):
        dataclasses.replace(TINY, experts_per_token=17)


# -- sizes -----------------------------------------------------------------
def test_the_published_sizes_and_the_benchmarks_cut():
    full = conv_moe.LFM2_24B_A2B
    assert full.layer_types.count("full_attention") == 10
    assert full.layer_types[:6] == (
        "conv", "conv", "full_attention", "conv", "conv", "conv"
    )
    assert full.head_dim == 64 and full.assignments_per_token == 4
    cut = dataclasses.replace(
        full, n_layers=5, first_dense_layers=1, vocab_size=8192,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        held_experts=tuple(range(8)),
    )
    counts = conv_moe.count_params(cut)
    assert counts["total"] + counts["state"] == 469285248
    assert counts["conv_dense_layer"] == 89139200
    assert counts["attention_expert_layer"] + 64 == 86118592
    assert counts["conv_expert_layer"] + 64 == 92416064
    assert cut.assignments_per_token == 0.5
    # 186M active parameter-equivalents a token on this chip
    assert counts["active"] == 469284992 - 4 * int(7.5 * 9437184)
    shapes = jax.eval_shape(
        lambda: conv_moe.init_conv_moe(jax.random.key(0), cut)
    )
    assert sum(a.size for a in jax.tree.leaves(shapes)) == counts["total"]
    from tpu_hpc.checks import fit

    assert fit.param_counts(cut) == {
        "total": counts["total"], "active": counts["active"]
    }
    # attention at 8k in one layer of five, the experts at half an
    # assignment a token a layer: 1.2 GFLOP a token
    assert 1.1e9 < cut.flops_per_token(8192) < 1.3e9


# -- (f) recomputation by budget ------------------------------------------
@pytest.mark.parametrize("room_gib, kept", [(0.0, 0), (1.0, 2), (64.0, 5)])
def test_blocks_keep_while_the_room_holds_the_next(room_gib, kept):
    """Blocks of different cost, asked one by one: the dense layer's
    gate and up make block 0 dearer than the four behind it."""
    from tpu_hpc.checks import fit

    cfg = dataclasses.replace(TINY, remat=True, dtype=jnp.bfloat16)
    tokens = BATCH * SEQ
    costs = [
        fit.conv_moe_kept_block_bytes(cfg, i, tokens)
        for i in range(cfg.n_layers)
    ]
    assert costs[0] > costs[2] > costs[1] and costs[2] == costs[3]
    act = fit.conv_moe_activation_bytes(cfg, tokens)
    floor = act["residual_checkpoints"] + max(
        act["block_recompute_live"], act["lm_head_and_loss"]
    )
    room = {0.0: 0, 1.0: costs[0] + costs[1]}.get(room_gib, 64 * 2 ** 30)
    budget = remat.RematBudget(
        limit_bytes=10 * (floor + room) // 9 + 16, resident_bytes=0,
        grad_bytes=0,
    )
    params, state = make(cfg)
    with remat.lowering_under(budget):
        jax.jit(conv_moe.make_forward(cfg)).lower(
            params, state, batch_of(cfg), None
        )
    assert budget.n_blocks == 5 and budget.blocks_kept == kept
    assert budget.kept_bytes <= max(budget.room_bytes, 0)


def test_a_keeping_block_gives_the_same_gradients():
    cfg = dataclasses.replace(TINY, remat=True)
    params, state = make(cfg)
    batch = batch_of(cfg)
    forward = conv_moe.make_forward(cfg)
    grad = jax.grad(lambda p: forward(p, state, batch, None)[0])
    plain = jax.jit(grad)(params)
    with remat.lowering_under(remat.RematBudget(1 << 40, 0, 0)):
        keeping = jax.jit(
            lambda p: grad(p)
        ).lower(params).compile()(params)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(keeping)):
        np.testing.assert_allclose(a, b, atol=1e-6)
