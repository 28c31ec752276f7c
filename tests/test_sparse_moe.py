"""The sparse-expert decoder with a learned token selector
(``models/sparse_moe.py``: Keye-VL-2.0-30B-A3B's language model)
through the paged server, against its plain reference
(``benchmark/reference/sparse_moe_decoder.py``), at a tiny size on the
CPU in float32: counts and agreement, never a time.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.reference import sparse_moe_decoder as reference
from tpu_hpc.kernels.paged_attention import write_tokens
from tpu_hpc.models import llama2, llama_pp, sparse_moe
from tpu_hpc.serve import (
    ContinuousBatcher,
    PagedConfig,
    PagedEngine,
    Request,
    ServeConfig,
    paging,
)
from tpu_hpc.serve.engine import Engine

TOPK = 16
TINY = sparse_moe.SparseMoEConfig(
    name="tiny-sparse", dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    head_dim=32, vocab_size=128, max_seq_len=96, n_experts=8,
    experts_per_token=2, expert_hidden=48, indexer_heads=2,
    indexer_head_dim=16, indexer_rope_dim=8, indexer_topk=TOPK,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
ARCH = dict(
    n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32,
    norm_eps=TINY.norm_eps, rope_theta=TINY.rope_theta, n_experts=8,
    experts_per_token=2, indexer_heads=2, indexer_head_dim=16,
    indexer_rope_dim=8, indexer_topk=TOPK,
)
SERVE = ServeConfig(slots=3, max_seq_len=96, prefill_buckets=(8, 16))
BLOCK = 4


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: sparse_moe.init_sparse_moe(k, TINY))(
        jax.random.key(3)
    )


@pytest.fixture(scope="module")
def mesh(devices):
    return Mesh(np.array(devices[:1]), ("data",))


def _engine(params, mesh, prefix_cache=True):
    eng = PagedEngine(
        params, TINY, SERVE, mesh,
        PagedConfig(block_size=BLOCK, num_blocks=3 * 24 + 1,
                    prefill_chunk=16, prefix_cache=prefix_cache),
    )
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def engine(params, mesh):
    return _engine(params, mesh)


def _serve(eng, prompts, max_new=12):
    batcher = ContinuousBatcher(eng)
    for rid, prompt in prompts.items():
        batcher.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    return batcher.run()


def _reference_logits(params, prompt, emitted, pad=64):
    """The reference's logits at every position the server emitted
    from: [len(emitted), vocab]."""
    tokens = np.zeros(pad, np.int32)
    n = len(prompt)
    tokens[:n] = prompt
    tokens[n:n + len(emitted) - 1] = emitted[:-1]
    hidden, _ = reference.forward(params, jnp.asarray(tokens), ARCH,
                                  q_block=16)
    rows = n - 1 + np.arange(len(emitted))
    return np.asarray(reference.logits(params, hidden[rows], ARCH))


# -- program against reference ------------------------------------------
@pytest.mark.parametrize("prompt_len", [5, 14, 23, 40])
def test_paged_decode_agrees_with_the_references_full_forward(
    params, engine, prompt_len
):
    """Prefill in chunks, then paged decode, against the reference's
    one forward pass over prompt + answer: every emitted token is the
    reference's arg-max. Contexts 5..52 lie on both sides of
    ``indexer_topk`` 16: below it every token is read, above it the
    indexer decides."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, TINY.vocab_size, prompt_len).tolist()
    emitted = _serve(engine, {"r": prompt})["r"]
    logits = _reference_logits(params, prompt, emitted)
    assert logits.argmax(-1).tolist() == emitted


@pytest.mark.parametrize("start,run", [(0, 8), (8, 6), (24, 16)])
def test_chunk_logits_agree_with_the_reference(params, start, run):
    """The chunk program's logits row, compared as numbers: a chunk of
    ``run`` tokens at ``start`` over pages that earlier chunks
    filled."""
    rng = np.random.default_rng(start)
    prompt = rng.integers(0, TINY.vocab_size, start + run)
    per_seq, width = 24, 28
    table = np.zeros(width, np.int32)
    table[:per_seq] = 1 + np.arange(per_seq)
    ks = jnp.zeros((2, 40, 2, BLOCK, 32), jnp.float32)
    xs = jnp.zeros((2, 40, BLOCK, 16), jnp.float32)
    state = (ks, ks, xs)
    logits = None
    for at in list(range(0, start, 8)) + [start]:
        n = run if at == start else 8
        fn = jax.jit(paging.make_chunk_logits_fn(
            TINY, 16 if n > 8 else 8, BLOCK, per_seq, width
        ))
        padded = np.zeros((1, 16 if n > 8 else 8), np.int32)
        padded[0, :n] = prompt[at:at + n]
        *state, logits = fn(
            params, *state, jnp.asarray(padded), jnp.int32(at),
            jnp.int32(n), jnp.asarray(table),
        )
    tokens = np.zeros(64, np.int32)
    tokens[:len(prompt)] = prompt
    hidden, _ = reference.forward(params, jnp.asarray(tokens), ARCH,
                                  q_block=16)
    want = reference.logits(params, hidden[len(prompt) - 1], ARCH)
    np.testing.assert_allclose(logits, want, atol=2e-5, rtol=2e-4)


def test_probe_reports_the_selection_the_reference_makes(params, mesh):
    """``probe_selection`` (the decode program with its masks as a
    result) against the reference's ``S_t`` for the same row."""
    eng = _engine(params, mesh, prefix_cache=False)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, TINY.vocab_size, 37).tolist()
    info = eng.admit(0, prompt, 4)
    first = None
    for _ in range(info["chunks"]):
        first = eng.prefill_step(0)
    active = [True, False, False]
    picked = eng.probe_selection([first, 0, 0], [37, 0, 0], active)
    tokens = np.zeros(64, np.int32)
    tokens[:37], tokens[37] = prompt, first
    _, probes = reference.forward(
        params, jnp.asarray(tokens), ARCH, jnp.asarray([37]), q_block=16
    )
    want = np.asarray(probes["selected"])[:, 0]          # [layers, 64]
    assert picked.shape == (2, 3, SERVE.max_seq_len)
    assert (picked[:, 0, :64] == want).all()
    assert picked[:, 0].sum(-1).tolist() == [TOPK, TOPK]
    assert not picked[:, 0, 38:].any()
    # the probe wrote what the step writes: decoding after it is exact
    nxt = eng.decode_now([first, 0, 0], [37, 0, 0], active)[0]
    logits = _reference_logits(params, prompt, [first, int(nxt)])
    assert int(nxt) == logits[1].argmax()


# -- the expert layer -----------------------------------------------------
def _layer_inputs():
    lp = sparse_moe.init_sparse_moe(jax.random.key(5), TINY)["layers_0"]
    h = jax.random.normal(jax.random.key(6), (11, TINY.dim))
    return lp, h


def _share(lp, held):
    moe = dict(lp["moe"])
    for name in ("w1", "w3", "w2"):
        moe[name] = lp["moe"][name][jnp.asarray(held)]
    return {**lp, "moe": moe}


@pytest.mark.parametrize("which", ["program", "reference"])
def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(which):
    """An expert layer told which experts it holds routes over ALL of
    them and computes its own experts' part: four disjoint shares add
    up to what the layer gives with every expert held."""
    lp, h = _layer_inputs()
    sets = [(0, 5), (1, 2), (3, 7), (4, 6)]

    def layer(held):
        if which == "reference":
            arch = dict(ARCH, held_experts=held)
            return reference._experts(
                h, lp["moe"] if held is None else _share(lp, held)["moe"],
                arch,
            )
        cfg = dataclasses.replace(TINY, held_experts=held)
        gates, experts = sparse_moe.route(h, lp, cfg)
        return sparse_moe.expert_ffn(
            h, gates, experts,
            lp if held is None else _share(lp, held), cfg,
        )[0]

    whole = layer(None)
    parts = sum(layer(held) for held in sets)
    assert float(jnp.abs(whole).max()) > 0
    np.testing.assert_allclose(parts, whole, atol=1e-6, rtol=1e-5)


def test_the_program_and_the_reference_agree_on_one_share():
    lp, h = _layer_inputs()
    held = (1, 2, 6)
    cfg = dataclasses.replace(TINY, held_experts=held)
    gates, experts = sparse_moe.route(h, lp, cfg)
    got, counts = sparse_moe.expert_ffn(
        h, gates, experts, _share(lp, held), cfg
    )
    want = reference._experts(
        h, _share(lp, held)["moe"], dict(ARCH, held_experts=held)
    )
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-5)
    assert int(counts["assignments"]) == 11 * 2
    assert int(counts["dropped"]) == 0


def test_no_assignment_is_dropped_when_every_token_picks_the_same_experts():
    """The worst imbalance: a router with equal logits sends every
    token to experts 0 and 1 (ties to the lower id). All 2 x tokens
    assignments are computed; the counts say so."""
    lp, h = _layer_inputs()
    moe = dict(lp["moe"], router={
        "kernel": jnp.zeros_like(lp["moe"]["router"]["kernel"])
    })
    lp = {**lp, "moe": moe}
    gates, experts = sparse_moe.route(h, lp, TINY)
    assert (np.asarray(experts) == [0, 1]).all()
    weight = jnp.asarray([1] * 9 + [0, 0])
    got, counts = sparse_moe.expert_ffn(h, gates, experts, lp, TINY, weight)
    np.testing.assert_allclose(
        got, reference._experts(h, moe, ARCH), atol=1e-6, rtol=1e-5
    )
    assert {k: int(v) for k, v in counts.items()} == {
        "assignments": 18, "experts_touched": 2,
        "max_tokens_per_expert": 9, "dropped": 0, "assignments_held": 18,
    }


# -- one sum, two traversals ----------------------------------------------
def _both_forms(monkeypatch, h, gates, experts, lp, cfg, weight=None):
    """``expert_ffn`` as the shape has it, and with the shape rule
    saying "dense" on the same inputs."""
    got = sparse_moe.expert_ffn(h, gates, experts, lp, cfg, weight)
    with monkeypatch.context() as m:
        m.setattr(sparse_moe, "grouped_by_shape", lambda *_: False)
        dense = sparse_moe.expert_ffn(h, gates, experts, lp, cfg, weight)
    return got, dense


def _form_of(fn, *args):
    """"grouped" or "dense", from the lowered text: the interpreted
    kernel walks its visit list in a loop, the dense form is three
    contractions and no loop."""
    text = jax.jit(fn).lower(*args).as_text()
    assert ("pallas_call" in str(jax.make_jaxpr(fn)(*args))) \
        == ("stablehlo.while" in text)
    return "grouped" if "stablehlo.while" in text else "dense"


GROUPED_CASES = {
    # tokens, held, every token on experts 0 and 1, weight
    "even": (2, None, False, None),
    "one_expert_pair": (3, None, True, None),
    "inactive_slot": (3, None, False, (1, 0, 1)),
    "no_active_slot": (3, None, False, (0, 0, 0)),
    "share_with_absent": (3, (1, 2, 6), False, None),
    "share_and_inactive": (3, (0, 3, 5, 7), False, (0, 1, 1)),
    "one_token": (1, None, False, None),
    "just_under": (3, None, False, None),
    "just_over": (4, None, False, None),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_the_grouped_form_is_the_dense_sum_without_its_zero_terms(
    monkeypatch, case, seed
):
    """Where the rows' assignments are fewer than the experts
    (``tokens * 2 < 8``) the product visits the touched experts alone
    (the Pallas kernel, interpreted): the rows that count equal the
    dense form's, the counts are identical, nothing is dropped, and an
    inactive slot's experts are not on the list."""
    tokens, held, same, weight = GROUPED_CASES[case]
    lp = sparse_moe.init_sparse_moe(jax.random.key(5 + seed), TINY)["layers_0"]
    if same:
        lp = {**lp, "moe": dict(lp["moe"], router={
            "kernel": jnp.zeros_like(lp["moe"]["router"]["kernel"])
        })}
    cfg = TINY if held is None \
        else dataclasses.replace(TINY, held_experts=held)
    lp = lp if held is None else _share(lp, held)
    h = jax.random.normal(jax.random.key(60 + seed), (tokens, TINY.dim))
    gates, experts = sparse_moe.route(h, lp, cfg)
    w = None if weight is None else jnp.asarray(weight)
    (got, counts), (dense, dense_counts) = _both_forms(
        monkeypatch, h, gates, experts, lp, cfg, w
    )
    form = _form_of(
        lambda h, g, e: sparse_moe.expert_ffn(h, g, e, lp, cfg, w)[0],
        h, gates, experts,
    )
    assert form == ("dense" if case == "just_over" else "grouped")
    assert sparse_moe.grouped_by_shape(tokens, cfg) == (form == "grouped")
    counted = np.ones(tokens, bool) if weight is None \
        else np.asarray(weight, bool)
    np.testing.assert_allclose(
        np.asarray(got)[counted], np.asarray(dense)[counted],
        atol=1e-6, rtol=1e-5,
    )
    if counted.any() and held is None:
        assert float(jnp.abs(dense[counted]).max()) > 0
    assert {k: int(v) for k, v in counts.items()} \
        == {k: int(v) for k, v in dense_counts.items()}
    assert int(counts["dropped"]) == 0
    on_held = set(range(TINY.n_experts) if held is None else held)
    chosen = set(np.asarray(experts)[counted].ravel().tolist())
    assert int(counts["experts_touched"]) == len(chosen & on_held)


@pytest.mark.parametrize("case", [
    "none", "one", "scattered", "all", "last_only",
])
def test_the_visit_list_is_the_touched_rows_then_the_last_repeated(case):
    touched = {
        "none": [0] * 8, "one": [0, 0, 1, 0, 0, 0, 0, 0],
        "scattered": [1, 0, 1, 1, 0, 0, 1, 0], "all": [1] * 8,
        "last_only": [0] * 7 + [1],
    }[case]
    rows = [i for i, t in enumerate(touched) if t]
    n_visit = 6 if case != "all" else 8
    visit, n = sparse_moe.visit_list(jnp.asarray(touched, bool), n_visit)
    assert int(n) == len(rows)
    want = rows + [rows[-1] if rows else 0] * (n_visit - len(rows))
    assert np.asarray(visit).tolist() == want and visit.dtype == jnp.int32


def _cell_configs():
    from tpu_hpc.models import hybrid_ssm_moe, latent_moe

    return {
        # the cell's configuration as it is held, its decode slots
        "keye": (sparse_moe.KEYE_VL2_30B_A3B, 12),
        "joyai": (dataclasses.replace(
            latent_moe.JOYAI_LLM_FLASH, held_experts=tuple(range(64))
        ), 16),
        "granite": (dataclasses.replace(
            hybrid_ssm_moe.GRANITE_4_0_H_SMALL,
            held_experts=tuple(range(18)),
        ), 16),
    }


@pytest.mark.parametrize("rows", ["decode", 128, 256, 512])
@pytest.mark.parametrize("cell", ["keye", "joyai", "granite"])
def test_the_form_at_the_cells_real_shapes(cell, rows):
    """By shape, at the three expert cells' published sizes (abstract
    values only: nothing is allocated): Keye's and JoyAI's decode
    steps visit the touched experts (96 < 128, 128 < 256), Granite's
    reads its eighteen whole (160 >= 72), and every chunk bucket of
    all three is the dense form."""
    cfg, slots = _cell_configs()[cell]
    cfg = dataclasses.replace(
        cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16
    )
    tokens = slots if rows == "decode" else rows
    k, d, f = cfg.experts_per_token, cfg.dim, cfg.expert_hidden

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    lp = {"moe": {
        "w1": sds((cfg.n_held, d, f), cfg.dtype),
        "w3": sds((cfg.n_held, d, f), cfg.dtype),
        "w2": sds((cfg.n_held, f, d), cfg.dtype),
    }}
    jaxpr = str(jax.make_jaxpr(
        lambda h, g, e, lp: sparse_moe.expert_ffn(h, g, e, lp, cfg)
    )(sds((tokens, d), cfg.dtype), sds((tokens, k), jnp.float32),
      sds((tokens, k), jnp.int32), lp))
    grouped = rows == "decode" and cell != "granite"
    assert ("pallas_call" in jaxpr) == grouped
    assert sparse_moe.grouped_by_shape(tokens, cfg) == grouped
    if grouped:
        # the list is as long as the step can touch, and no longer
        assert f"grid=({min(cfg.n_held, tokens * k)},)" in jaxpr


# -- the selection --------------------------------------------------------
@pytest.mark.parametrize("case", ["random", "ties", "few_valid", "zeros"])
def test_select_topk_is_the_exact_top_k_with_ties_to_the_lower_column(case):
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(5, 40)).astype(np.float32)
    valid = np.arange(40)[None, :] <= np.array([39, 30, 12, 5, 39])[:, None]
    if case == "ties":
        scores = np.round(scores)          # many equal values
    elif case == "zeros":
        scores = np.where(scores > 0.5, scores, 0.0)
        scores[0, :3] = -0.0
    elif case == "few_valid":
        valid = np.arange(40)[None, :] <= np.array([3, 0, 7, 8, 9])[:, None]
    got = sparse_moe.select_topk(jnp.asarray(scores), jnp.asarray(valid), 8)
    want = reference.top_mask(jnp.asarray(scores), jnp.asarray(valid), 8)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert (np.asarray(got).sum(-1) == np.minimum(valid.sum(-1), 8)).all()
    # ties to the lower column, by hand: a stable sort of -scores
    for row in range(5):
        order = np.argsort(
            -np.where(valid[row], scores[row] + 0.0, -np.inf), kind="stable"
        )[:min(8, valid[row].sum())]
        assert sorted(order) == np.flatnonzero(np.asarray(got)[row]).tolist()


# -- the third pool array -------------------------------------------------
def test_write_tokens_puts_rows_into_a_pool_without_a_head_axis():
    pool = jnp.zeros((2, 5, 4, 3))
    rows = jnp.arange(6.0).reshape(2, 3) + 1
    out = write_tokens(pool, 1, jnp.asarray([3, 1]), jnp.asarray([2, 0]), rows)
    assert (out[1, 3, 2] == rows[0]).all() and (out[1, 1, 0] == rows[1]).all()
    assert float(out.sum()) == float(rows.sum())


def test_the_pool_counts_the_indexer_keys(engine):
    blocks = engine.paged.num_blocks
    assert engine.xs.shape == (2, blocks, BLOCK, TINY.indexer_head_dim)
    kv = 2 * 2 * blocks * 2 * BLOCK * 32 * 4
    assert engine.cache_bytes == kv + 2 * blocks * BLOCK * 16 * 4


def test_a_prefix_hit_carries_the_indexer_keys(params, mesh, engine):
    """A second request over a cached prefix reads indexer keys it
    never computed, through the trie's shared pages: its tokens are
    those of a run with no prefix cache."""
    rng = np.random.default_rng(21)
    shared = rng.integers(0, TINY.vocab_size, 32).tolist()
    tails = [rng.integers(0, TINY.vocab_size, n).tolist() for n in (6, 9)]
    _serve(engine, {"first": shared + tails[0]})
    hits = engine.paged_stats["prefix_hit_blocks"]
    got = _serve(engine, {"second": shared + tails[1]})["second"]
    assert engine.paged_stats["prefix_hit_blocks"] >= hits + 32 // BLOCK
    plain = _engine(params, mesh, prefix_cache=False)
    assert got == _serve(plain, {"second": shared + tails[1]})["second"]


def test_copy_on_write_carries_the_indexer_keys(params, mesh):
    """A second owner appears on the decode write-target page: the
    engine copies the page, its indexer keys with it, and the answer
    is that of an undisturbed run."""
    rng = np.random.default_rng(22)
    prompt = rng.integers(0, TINY.vocab_size, 22).tolist()
    want = _serve(_engine(params, mesh, False), {"w": prompt}, 8)["w"]
    eng = _engine(params, mesh, prefix_cache=False)
    batcher = ContinuousBatcher(eng)
    batcher.submit(Request(rid="w", prompt=prompt, max_new_tokens=8))
    batcher.step()
    slot = next(i for i, s in enumerate(batcher.slots) if s.rid == "w")
    while batcher.slots[slot].pos < len(prompt) \
            or batcher.slots[slot].pos % BLOCK == 0:
        batcher.step()       # decoding, into a page that holds tokens
    state = eng.slot_state(slot)
    pos = batcher.slots[slot].pos
    page, rows = pos // BLOCK, pos % BLOCK
    target = state.blocks[page]
    eng.allocator.retain([target])
    batcher.step()
    assert eng.paged_stats["cow_copies"] == 1
    copy = state.blocks[page]
    assert copy != target
    assert (eng.xs[:, copy, :rows] == eng.xs[:, target, :rows]).all()
    assert float(jnp.abs(eng.xs[:, copy, :rows]).min(-1).max()) > 0
    assert batcher.run()["w"] == want
    eng.allocator.release([target])
    eng.allocator.check_invariant()


def test_counts_come_back_with_the_tokens(params, mesh):
    eng = _engine(params, mesh, prefix_cache=False)
    rng = np.random.default_rng(23)
    _serve(eng, {"a": rng.integers(0, 128, 30).tolist()}, max_new=5)
    stats = eng.paged_stats
    steps = stats["decode_steps"]
    assert steps == 4                      # the first token is prefill's
    assert stats["serve_moe_assignments_total"] == steps * 2 * 2
    assert stats["serve_moe_dropped_total"] == 0
    assert 1 <= stats["serve_moe_max_tokens_per_expert"] <= 1
    assert stats["serve_moe_experts_touched_total"] == steps * 2 * 2
    # three slots x two < eight experts: the steps' products visited
    # the experts they touched, and read no other
    assert stats["serve_moe_experts_read_total"] == steps * 2 * 2
    assert stats["serve_sparse_selected_tokens_total"] == steps * 2 * TOPK
    assert stats["serve_sparse_candidate_tokens_total"] == 2 * sum(
        30 + j + 1 for j in range(steps)
    )


# -- the programs are the parent's ----------------------------------------
# sha256[:16] of ``lowered.as_text()`` (StableHLO, no debug info: it
# sees neither scope names nor source lines) of every serving program
# at a tiny size. The first sixteen are the paged decode and
# chunk-prefill programs of two dense configurations, taken on the
# parent commit of the PR that made the stages configurable (PR 27). A
# PR that MEANS to change a program re-pins it and says so: PR 28
# re-pinned the eight decode programs (their input token is chosen
# between the host's and the one the step before left on the device);
# the chunk programs are still PR 27's parent's. The last ten (the slab
# programs, the speculative draft and verify programs, the
# sparse-expert decode, probe and chunk programs) were taken on the
# parent commit of the PR that gave every program one layer loop
# (PR 29, on 2a3e19e's code). ``flat<pages>`` are the decode programs
# of the flat rungs an engine of this shape holds (4 slots x 12 pages:
# 18 and 24), as the PR that brought them left them (PR 30). PR 38
# re-pinned ``sparse-decode`` and ``sparse-decode_probe`` (from
# 901f33d0d5597192 / 9317ff59722907af) and no other: a sparse-selection
# row step reads K and V through the kernel that walks the page tables
# (``kernels/sparse_paged_attention.py``: interpreted here, so the
# kernel's own text is part of the program's); ``sparse-prefill`` still
# gathers its one view. The dense ``decode-gather-none`` pair was
# re-pinned (from 81408bbaba39a8c9 / 9816f8bed450484a) when a dense row
# step over a pool in the compute dtype came to read through the same
# kernel, and no other; such a pool keeps no flat rung, so the
# ``flat<pages>`` programs are those of an int8 pool, pinned as the
# parent lowered them.
PROGRAM_DIGESTS = {
    "gqa-decode-gather-none": "7d69c843f7e1f8cd",
    "gqa-prefill-gather-none": "8a8972db9fc5d25e",
    "gqa-decode-gather-int8": "da821b4063db53ed",
    "gqa-prefill-gather-int8": "2144b4b5b662fbda",
    "gqa-decode-pallas-none": "667f2ad9344d9e1a",
    "gqa-prefill-pallas-none": "76d6e8f95829fbe8",
    "gqa-decode-pallas-int8": "35d63b0063e32c5b",
    "gqa-prefill-pallas-int8": "4695e884e5b48fb4",
    "mha-decode-gather-none": "73c3708328131e89",
    "mha-prefill-gather-none": "141949fcab431570",
    "mha-decode-gather-int8": "4ce500996804bdeb",
    "mha-prefill-gather-int8": "71101e9fc0221492",
    "mha-decode-pallas-none": "25537345de2c10ec",
    "mha-prefill-pallas-none": "0e1e073173ce7163",
    "mha-decode-pallas-int8": "7b0f374e3c25c107",
    "mha-prefill-pallas-int8": "bc03f9ccf4d55fa3",
    "gqa-slab_prefill": "18d392421c38a24a",
    "gqa-slab_decode": "e024f76f84beab53",
    "mha-slab_prefill": "84c81d6de834fe78",
    "mha-slab_decode": "7d8df77d288a5f63",
    "gqa-spec_draft": "adf0b8caaeed1392",
    "gqa-spec_verify-onehot": "263c7331d62839f2",
    "gqa-spec_verify-probs": "3b8616518ea90903",
    "sparse-decode": "b7a3b99c22315c58",
    "sparse-decode_probe": "1f367c6a19665171",
    "sparse-prefill": "98bb54d41635cafb",
    "gqa-flat18-gather-int8": "14d6a7965a8ac5b5",
    "gqa-flat24-gather-int8": "2f17b8ed623eb0f0",
    "mha-flat18-gather-int8": "2bd9c0aeb46712ef",
    "mha-flat24-gather-int8": "fec9a8a51a235df5",
}


def _abstract(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _program_text(mesh, tag, program, kernel="gather", quant="none"):
    """The lowered text of one serving program: ``tag`` names the
    configuration (two dense ones, the file's tiny sparse-expert one),
    ``kernel`` is the read path, or for ``spec_verify`` where the
    draft's distributions come from."""
    from tpu_hpc.serve import engine as slab, spec

    if tag == "sparse":
        cfg = TINY
        weights = jax.eval_shape(
            lambda: sparse_moe.init_sparse_moe(jax.random.key(0), cfg)
        )
    else:
        cfg = llama2.LlamaConfig(
            dim=64, n_layers=2, n_heads=4,
            n_kv_heads=2 if tag == "gqa" else None, vocab_size=128,
            multiple_of=16, max_seq_len=64, dtype=jnp.bfloat16,
        )
        weights = jax.eval_shape(
            lambda: llama2.init_llama(jax.random.key(0), cfg)
        )
    slots, block, per_seq, width, bucket, k = 4, 4, 12, 16, 8, 3
    dtype = jnp.int8 if quant == "int8" else cfg.dtype
    cache = _abstract(
        (cfg.n_layers, 48, cfg.kv_heads, block, cfg.head_dim), dtype
    )
    scales = _abstract((cfg.n_layers, 48), jnp.float32)
    state = (cache, cache) + ((scales, scales) if quant == "int8" else ())
    vec, scalar = _abstract((slots,)), _abstract(())
    tables = _abstract((slots, width))
    fvec = _abstract((slots,), jnp.float32)
    if tag == "sparse":
        state += (_abstract(
            (cfg.n_layers, 48, block, cfg.indexer_head_dim), cfg.dtype
        ),)
        vec_prev = _abstract((slots + len(paging.SPARSE_COUNTERS),))
    else:
        vec_prev = vec
    if program in ("decode", "decode_probe") or program.startswith("flat"):
        fn = paging.make_paged_decode_fn(
            cfg, block, per_seq, width, kernel=kernel, kv_quant=quant,
            mesh=mesh, probe=program == "decode_probe",
            flat_pages=int(program[4:]) if program[:4] == "flat" else None,
        )
        args = (*state, vec_prev,
                _abstract((len(paging.STEP_ROWS), slots)), tables)
    elif program == "prefill":
        fn = paging.make_chunk_prefill_fn(
            cfg, bucket, block, per_seq, width, kernel=kernel,
            kv_quant=quant, mesh=mesh,
        )
        args = (*state, _abstract((1, bucket)), scalar, scalar,
                _abstract((width,)))
    elif program.startswith("slab"):
        slab_cache = _abstract(
            (cfg.n_layers, slots, 48, cfg.kv_heads, cfg.head_dim),
            cfg.dtype,
        )
        if program == "slab_prefill":
            fn = slab.make_prefill_fn(cfg, bucket, slots)
            args = (slab_cache, slab_cache, _abstract((1, bucket)),
                    scalar, scalar)
        else:
            fn = slab.make_decode_fn(cfg, 48)
            args = (slab_cache, slab_cache, vec, vec)
    elif program == "spec_draft":
        fn = spec.make_spec_draft_fn(cfg, k, block, per_seq, width)
        args = (*state, vec, vec, tables, vec, vec, vec, fvec, fvec)
    else:
        onehot = kernel == "onehot"
        fn = spec.make_spec_verify_fn(
            cfg, k, block, per_seq, width, onehot_q=onehot
        )
        probs = () if onehot else (
            _abstract((slots, k, cfg.vocab_size), jnp.float32),
        )
        args = (*state, _abstract((slots, k + 1)), vec, tables, vec, vec,
                *probs, vec, fvec, fvec)
    return jax.jit(fn).lower(weights, *args).as_text()


@pytest.mark.parametrize("name", sorted(PROGRAM_DIGESTS))
def test_the_programs_lower_to_the_parents_text(name, mesh):
    text = _program_text(mesh, *name.split("-"))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PROGRAM_DIGESTS[name]


# -- who refuses it, by name ----------------------------------------------
def _refusals(params, mesh):
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.serve import disagg, spec
    from tpu_hpc.train import Trainer

    other = Mesh(np.array(jax.devices()[1:2]), ("data",))

    def paged(**kw):
        return PagedEngine(
            params, TINY, SERVE, mesh,
            PagedConfig(block_size=BLOCK, num_blocks=40, **kw),
        )

    return {
        "slab_engine": lambda: Engine(params, TINY, SERVE, mesh),
        "spec": lambda: spec.attach_spec(
            paged(), spec.SpecConfig(mode="ngram", k=2)
        ),
        "host_tier": lambda: paged(host_blocks=8),
        "pipeline_split": lambda: llama_pp.split_params(params, TINY, 2),
        "trainer": lambda: Trainer(
            TrainingConfig(), mesh, lambda *a: None, params
        ),
        "trainer_forward": lambda: llama2.make_forward(TINY),
        "dense_init": lambda: llama2.init_llama(jax.random.key(0), TINY),
        "disagg": lambda: disagg.DisaggEngine(
            params, TINY, SERVE, mesh, other
        ),
        "pallas_read_path": lambda: paged(kernel="pallas"),
        "int8_pool": lambda: paged(kv_quant="int8"),
    }


@pytest.mark.parametrize("who", [
    "slab_engine", "spec", "host_tier", "pipeline_split", "trainer",
    "trainer_forward", "dense_init", "disagg", "pallas_read_path",
    "int8_pool",
])
def test_refused_by_name(params, mesh, who):
    with pytest.raises(NotImplementedError) as err:
        _refusals(params, mesh)[who]()
    name = "keye-vl2-30b-a3b" if who == "trainer" else "tiny-sparse"
    assert name in str(err.value) and "SparseMoEConfig" in str(err.value)


def test_the_preset_is_the_published_model():
    cfg = sparse_moe.KEYE_VL2_30B_A3B
    counts = sparse_moe.count_params(dataclasses.replace(cfg, n_layers=4))
    assert counts["per_layer"] == 625_381_760        # 625.4M, ISSUE 27
    assert counts["experts_per_layer"] == 128 * 4_718_592
    assert counts["indexer_per_layer"] == 2_261_120
    assert counts["total"] == 4 * 625_381_760 + 2 * 311_164_928 + 2048
    assert round(2 * counts["total"] / 2**30, 2) == 5.82   # GiB in bf16
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.n_experts, cfg.experts_per_token) == (128, 8)
    assert (cfg.indexer_heads, cfg.indexer_head_dim, cfg.indexer_topk) \
        == (16, 64, 2048)
    assert cfg.rope_theta == 1e7 and cfg.ffn_hidden == 768
