"""The decoder with latent attention and a sigmoid-routed expert layer
(``models/latent_moe.py``: JoyAI-LLM-Flash) through the paged server,
against its plain reference
(``benchmark/reference/latent_moe_decoder.py``), at a tiny size on the
CPU in float32: counts and agreement, never a time.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.reference import latent_moe_decoder as reference
from tpu_hpc.models import latent_moe, llama2, llama_pp, sparse_moe
from tpu_hpc.serve import (
    ContinuousBatcher,
    PagedConfig,
    PagedEngine,
    Request,
    ServeConfig,
    decoder,
    paging,
)
from tpu_hpc.serve.engine import Engine

# Six of sixteen experts held: every path below also runs the share.
HELD = (0, 1, 2, 3, 8, 9)
TINY = latent_moe.LatentMoEConfig(
    name="tiny-latent", dim=64, n_layers=3, n_heads=4, vocab_size=128,
    max_seq_len=96, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, dense_hidden=96,
    first_dense_layers=1, n_experts=16, experts_per_token=4,
    expert_hidden=24, held_experts=HELD,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
ARCH = dict(
    n_layers=3, n_heads=4, norm_eps=TINY.norm_eps,
    rope_theta=TINY.rope_theta, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_experts=16,
    experts_per_token=4, routed_scaling_factor=2.5, norm_topk_prob=True,
    held_experts=HELD,
)
SERVE = ServeConfig(slots=3, max_seq_len=96, prefill_buckets=(8, 16))
BLOCK = 4
ROW_BYTES = (32 + 8) * 4            # c and kR in float32
# A page of each pool array, latents and rotary keys:
# ``paging.rope_pack`` (here all 4) tokens a row.
PAGES = ((1, BLOCK * 32), (1, BLOCK * 8))


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: latent_moe.init_latent_moe(k, TINY))(
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


def _agrees(params, prompt, emitted):
    """Every emitted token is the reference's arg-max. Float32 on both
    sides: the two differ by summation order alone (the absorbed read,
    the held experts as one feed-forward), 1e-5 of a logit where the
    two best logits of this seed lie 1e-2 apart or more."""
    logits = _reference_logits(params, prompt, emitted)
    assert logits.argmax(-1).tolist() == list(emitted)


# -- program against reference ------------------------------------------
@pytest.mark.parametrize("prompt_len", [5, 14, 23, 40])
def test_paged_decode_agrees_with_the_references_full_forward(
    params, engine, prompt_len
):
    """An unshared prompt: prefill in chunks (the expanded read), then
    paged decode through the latent cache (the absorbed read) with one
    step in flight, against the reference's one forward pass over
    prompt + answer."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, TINY.vocab_size, prompt_len).tolist()
    before = engine.paged_stats["serve_decode_overlapped_total"]
    emitted = _serve(engine, {"r": prompt})["r"]
    assert engine.paged_stats["serve_decode_overlapped_total"] > before
    _agrees(params, prompt, emitted)


@pytest.mark.parametrize("start,run", [
    (0, 8), (8, 6), (24, 16), (16, 3), (40, 8), (32, 16),
])
def test_chunk_logits_agree_with_the_reference(params, start, run):
    """The chunk program's logits row, compared as numbers: a chunk of
    ``run`` tokens at ``start`` over pages that earlier chunks filled
    (the expanded read). 2e-5 absolute on logits of size ~1: a float32
    sum of a few hundred terms in another order."""
    rng = np.random.default_rng(start)
    prompt = rng.integers(0, TINY.vocab_size, start + run)
    per_seq, width = 24, 28
    table = np.zeros(width, np.int32)
    table[:per_seq] = 1 + np.arange(per_seq)
    state = tuple(jnp.zeros((3, 40, *page), jnp.float32) for page in PAGES)
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


def test_a_prefix_hit_reads_latent_pages_it_never_computed(
    params, mesh, engine
):
    """A trie hit: the second request over a cached prefix reads the
    first one's latent pages by their page ids, and its tokens are the
    reference's."""
    rng = np.random.default_rng(21)
    shared = rng.integers(0, TINY.vocab_size, 32).tolist()
    tails = [rng.integers(0, TINY.vocab_size, n).tolist() for n in (6, 9)]
    _serve(engine, {"first": shared + tails[0]})
    hits = engine.paged_stats["prefix_hit_blocks"]
    got = _serve(engine, {"second": shared + tails[1]})["second"]
    assert engine.paged_stats["prefix_hit_blocks"] >= hits + 32 // BLOCK
    _agrees(params, shared + tails[1], got)


def test_copy_on_write_carries_the_latent_rows(params, mesh):
    """A second owner appears on the decode write-target page: the
    engine copies the page's latent rows, and the answer is still the
    reference's."""
    rng = np.random.default_rng(22)
    prompt = rng.integers(0, TINY.vocab_size, 22).tolist()
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
    live = len(eng._live_pages)
    batcher.step()
    assert eng.paged_stats["cow_copies"] == 1
    copy = state.blocks[page]
    assert copy != target
    for pool, width in ((eng.ks, 32), (eng.vs, 8)):
        # the latents, the rotary keys: a token a row
        pool = pool.reshape(3, -1, BLOCK, width)
        assert (pool[:, copy, :rows] == pool[:, target, :rows]).all()
        assert float(jnp.abs(pool[:, copy, :rows]).min(-1).max()) > 0
    # the copy took the original's place among the pages read
    assert len(eng._live_pages) == live
    assert copy in eng._live_pages.readers
    assert target not in eng._live_pages.readers
    _agrees(params, prompt, batcher.run()["w"])
    eng.allocator.release([target])
    eng.allocator.check_invariant()


# -- absorbed against expanded --------------------------------------------
@pytest.mark.parametrize("seed", [7, 8])
def test_the_absorbed_read_is_the_expanded_read_on_the_same_pages(seed):
    """One layer's read of the same latent pages in both forms, in
    float32: the query carried into the latent space and the value
    brought out of it, against every head's key and value built first.
    The same numbers summed in another order: 1e-5 on outputs of size
    ~0.1."""
    lp = latent_moe.init_latent_moe(jax.random.key(5), TINY)["layers_1"]
    slots, per_seq = 3, 6
    rng = np.random.default_rng(seed)
    pools = [
        jnp.asarray(rng.normal(
            size=(1, 1 + slots * per_seq, *page)
        ), jnp.float32) for page in PAGES
    ]
    tables = jnp.asarray(
        1 + np.arange(slots * per_seq).reshape(slots, per_seq), jnp.int32
    )
    pos = jnp.asarray([9, 22, 3], jnp.int32)
    q = jnp.asarray(rng.normal(
        size=(slots, 1, TINY.n_heads, TINY.qk_head_dim)
    ), jnp.float32)
    col = jnp.arange(per_seq * BLOCK)
    mask = (col[None, :] <= pos[:, None])[:, None, None, None, :]

    state = paging.PagedAttention(TINY, BLOCK, per_seq).on(*pools)
    state.view(tables, pos, jnp.ones(slots, jnp.int32))
    state.mask = mask
    got = state._read_latent(0, lp, q)

    latents, k_rope = (
        pool[0, tables].reshape(slots, per_seq * BLOCK, width)
        for pool, width in zip(pools, (TINY.kv_lora_rank, TINY.rope_dim))
    )
    k, v = latent_moe.expand(latents, k_rope, lp, TINY)
    want = decoder._grouped_attention(
        q, k, v, mask, TINY, scale=TINY.qk_head_dim ** -0.5
    )
    assert got.shape == (slots, 1, TINY.n_heads, TINY.v_head_dim)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


def test_the_engine_holds_no_flat_rung(params, mesh, engine):
    """PR 30's flat list of live pages is the dense gather's: a latent
    page (16 rows x 576) is half its owner's query (32 heads x 576),
    which the flat form copies a page, and on the v5e a flat rung ran
    slower than the rectangle at every occupancy (PERF.md, PR 31). The
    engine holds ONE decode program, whose kernel walks the tables
    (PR 36): a step reads the live pages of its active slots, fewer
    than every slot's whole view, and the factory refuses the flat
    form by reason."""
    assert engine.decode_rungs == ()
    assert set(k for k in engine._execs if k[0] == "decode") == {("decode",)}
    stats = engine.paged_stats
    assert 0 < stats["serve_decode_view_pages_read_total"] \
        < stats["serve_decode_view_pages_total"]
    with pytest.raises(ValueError, match="latent page"):
        paging.make_paged_decode_fn(TINY, BLOCK, 12, 16, flat_pages=18)


# -- the router -------------------------------------------------------------
def test_the_router_selects_by_sigmoid_plus_bias_and_gates_by_sigmoid():
    """A hand-written case, four experts, two a token: selection by
    ``s + b``, gates from ``s`` WITHOUT ``b``, renormalised over the
    chosen, times the scaling factor; ties to the lower id."""
    cfg = dataclasses.replace(
        TINY, dim=4, n_experts=4, experts_per_token=2, held_experts=None,
    )
    logit = np.log(np.array([0.8, 0.6, 0.5, 0.2]) / (1 - np.array(
        [0.8, 0.6, 0.5, 0.2]
    )))                                     # sigmoid -> .8 .6 .5 .2
    kernel = np.zeros((4, 4), np.float32)
    kernel[0] = logit
    lp = {"moe": {"router": {
        "kernel": jnp.asarray(kernel),
        "bias": jnp.asarray([0.0, -0.3, 0.0, 0.5], jnp.float32),
    }}}
    h = jnp.asarray([[1.0, 0, 0, 0], [0.0, 0, 0, 0]], jnp.float32)
    gates, experts = latent_moe.route(h, lp, cfg)
    # token 0: s + b = .8 .3 .5 .7 -> experts 0 and 3; gates .8 and .2
    assert experts[0].tolist() == [0, 3]
    np.testing.assert_allclose(
        gates[0], 2.5 * np.array([0.8, 0.2]) / 1.0, rtol=1e-6
    )
    # token 1: every s is .5; s + b = .5 .2 .5 1.0 -> 3, then 0 (the
    # lower id of the tie with 2); gates equal
    assert experts[1].tolist() == [3, 0]
    np.testing.assert_allclose(gates[1], [1.25, 1.25], rtol=1e-6)
    ref_gates, ref_chosen = reference.router(
        h, lp["moe"], dict(ARCH, n_experts=4, experts_per_token=2)
    )
    assert ref_chosen.tolist() == experts.tolist()
    np.testing.assert_allclose(
        ref_gates[0], [2.0, 0, 0, 0.5], rtol=1e-6
    )


# -- the expert layer and its shares ----------------------------------------
def _layer_inputs():
    lp = latent_moe.init_latent_moe(
        jax.random.key(5), dataclasses.replace(TINY, held_experts=None)
    )["layers_1"]
    x = jax.random.normal(jax.random.key(6), (1, 11, TINY.dim))
    return lp, x


def _share(lp, held):
    moe = dict(lp["moe"])
    for name in ("w1", "w3", "w2"):
        moe[name] = lp["moe"][name][jnp.asarray(held)]
    return {**lp, "moe": moe}


def _norm(x, lp):
    return reference.rmsnorm(x, lp["ffn_norm"]["scale"], TINY.norm_eps)


@pytest.mark.parametrize("which", ["program", "reference"])
def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(which):
    """Four shares of 4 of 16 experts each route over ALL experts and
    compute their own experts' part; the shared expert (and, in a
    dense layer, the whole feed-forward) every chip computes alike and
    the sum counts ONCE: together they are what the uncut reference
    gives for the layer."""
    lp, x = _layer_inputs()
    sets = [(0, 5, 10, 15), (1, 2, 12, 13), (3, 7, 8, 14), (4, 6, 9, 11)]
    whole = reference.feed_forward(
        _norm(x[0], lp), lp, dict(ARCH, held_experts=None)
    )

    def added(held):
        """What the layer adds to the residual stream on one chip."""
        if which == "reference":
            return reference.feed_forward(
                _norm(x[0], lp), _share(lp, held),
                dict(ARCH, held_experts=held),
            )
        cfg = dataclasses.replace(TINY, held_experts=held)
        out, _ = decoder._ffn_stage(x, _share(lp, held), cfg)
        return (out - x)[0]

    shared = reference.swiglu(_norm(x[0], lp), lp["moe"]["shared"])
    parts = sum(added(held) - shared for held in sets) + shared
    assert float(jnp.abs(whole - shared).max()) > 1e-3
    np.testing.assert_allclose(parts, whole, atol=2e-6, rtol=1e-5)


def test_the_dense_layer_is_every_chips_alike(params):
    """Layer 0 has no experts to share out: the loop's feed-forward is
    the reference's dense SwiGLU whatever is held, and counts
    nothing."""
    lp = params["layers_0"]
    x = jax.random.normal(jax.random.key(8), (1, 7, TINY.dim))
    out, counts = decoder._ffn_stage(x, lp, TINY)
    assert counts is None and "feed_forward" in lp and "moe" not in lp
    want = reference.feed_forward(_norm(x[0], lp), lp, ARCH)
    np.testing.assert_allclose((out - x)[0], want, atol=2e-6, rtol=1e-5)


def test_the_program_and_the_reference_agree_on_one_share():
    lp, x = _layer_inputs()
    held = (1, 2, 6, 11, 15)
    cfg = dataclasses.replace(TINY, held_experts=held)
    h = _norm(x[0], lp)
    gates, experts = latent_moe.route(h, lp, cfg)
    got, counts = sparse_moe.expert_ffn(
        h, gates, experts, _share(lp, held), cfg
    )
    want = reference.routed_experts(
        h, _share(lp, held)["moe"], dict(ARCH, held_experts=held)
    )
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)
    on_held = int(np.isin(np.asarray(experts), held).sum())
    assert int(counts["assignments"]) == 11 * 4
    assert int(counts["assignments_held"]) == on_held < 11 * 4
    assert int(counts["experts_touched"]) == len(
        set(np.asarray(experts).ravel().tolist()) & set(held)
    )
    assert int(counts["dropped"]) == 0


@pytest.mark.parametrize("case", [
    "share_even", "share_inactive_slot", "whole_stack", "just_over",
])
def test_a_share_visits_the_touched_experts_it_holds(monkeypatch, case):
    """Through the sigmoid router: three rows x four < sixteen experts,
    so the product visits the HELD experts the counted rows touched (a
    share sees its share of the assignments: the same test of the
    shape), and gives what the whole-stack form gives with identical
    counts; four rows x four read the whole stack."""
    lp, x = _layer_inputs()
    held = None if case == "whole_stack" else (1, 2, 6, 11, 15)
    tokens = 4 if case == "just_over" else 3
    cfg = dataclasses.replace(TINY, held_experts=held)
    lp = lp if held is None else _share(lp, held)
    h = _norm(x[0, :tokens], lp)
    gates, experts = latent_moe.route(h, lp, cfg)
    weight = jnp.asarray([1, 0, 1]) if case == "share_inactive_slot" \
        else None
    got, counts = sparse_moe.expert_ffn(h, gates, experts, lp, cfg, weight)
    with monkeypatch.context() as m:
        m.setattr(sparse_moe, "grouped_by_shape", lambda *_: False)
        dense, dense_counts = sparse_moe.expert_ffn(
            h, gates, experts, lp, cfg, weight
        )
    jaxpr = str(jax.make_jaxpr(
        lambda h, g, e: sparse_moe.expert_ffn(h, g, e, lp, cfg, weight)
    )(h, gates, experts))
    assert ("pallas_call" in jaxpr) == (case != "just_over")
    counted = np.ones(tokens, bool) if weight is None \
        else np.asarray(weight, bool)
    np.testing.assert_allclose(
        np.asarray(got)[counted], np.asarray(dense)[counted],
        atol=2e-6, rtol=1e-5,
    )
    assert {k: int(v) for k, v in counts.items()} \
        == {k: int(v) for k, v in dense_counts.items()}
    on_held = set(range(TINY.n_experts) if held is None else held)
    chosen = set(np.asarray(experts)[counted].ravel().tolist())
    assert int(counts["experts_touched"]) == len(chosen & on_held)
    assert int(counts["dropped"]) == 0


# -- counters -----------------------------------------------------------------
def test_counts_come_back_with_the_tokens(params, mesh):
    """``assignments_held`` + the absent ones = ``assignments``;
    ``dropped`` 0; ``experts_touched`` <= held; the live pages are the
    distinct pages the decoding slot reads, step by step."""
    eng = _engine(params, mesh, prefix_cache=False)
    rng = np.random.default_rng(23)
    _serve(eng, {"a": rng.integers(0, 128, 30).tolist()}, max_new=5)
    stats = eng.paged_stats
    steps, expert_layers = stats["decode_steps"], TINY.n_layers - 1
    assert steps == 4                      # the first token is prefill's
    assert stats["serve_moe_assignments_total"] == steps * expert_layers * 4
    held = stats["serve_moe_assignments_held_total"]
    assert 0 < held < stats["serve_moe_assignments_total"]
    assert stats["serve_moe_dropped_total"] == 0
    assert 0 < stats["serve_moe_experts_touched_total"] \
        <= min(held, steps * expert_layers * len(HELD))
    assert stats["serve_moe_max_tokens_per_expert"] == 1
    # three slots x four < sixteen experts: the products read the held
    # experts the steps touched, and no other
    assert stats["serve_moe_experts_read_total"] \
        == stats["serve_moe_experts_touched_total"]
    assert stats["serve_latent_pages_live_total"] == sum(
        (30 + j) // BLOCK + 1 for j in range(steps)
    )
    assert "serve_sparse_selected_tokens_total" not in stats
    assert len(eng._live_pages) == 0       # released with the request


def test_a_shared_page_is_live_once(params, mesh):
    """Two slots decoding over one cached prefix: its pages count once
    among the live pages, each slot's own pages once each."""
    eng = _engine(params, mesh)
    rng = np.random.default_rng(24)
    shared = rng.integers(0, TINY.vocab_size, 32).tolist()
    _serve(eng, {"warm": shared + [1, 2]}, max_new=2)
    for slot, tail in enumerate(([3, 4, 5], [6, 7])):
        eng.admit(slot, shared + tail, 4)
    firsts = []
    for slot in (0, 1):
        tok = None
        while tok is None:
            tok = eng.prefill_step(slot)
        firsts.append(tok)
    before = eng.paged_stats["serve_latent_pages_live_total"]
    eng.decode_now(firsts + [0], [35, 34, 0], [True, True, False])
    # 8 shared pages once, and page 8 of each slot (positions 32..35)
    assert eng.paged_stats["serve_latent_pages_live_total"] - before == 10
    eng.release(0)
    assert len(eng._live_pages) == 9
    eng.release(1)
    assert len(eng._live_pages) == 0


# -- the page -----------------------------------------------------------------
def test_a_page_holds_the_latent_row_and_nothing_per_head(engine):
    """576 numbers a token a layer at the published sizes, here 32 + 8:
    pool bytes = tokens x layers x the row's bytes, the latent and its
    rotary key in two arrays with no head axis."""
    blocks = engine.paged.num_blocks
    assert engine.ks.shape == (3, blocks, *PAGES[0])
    assert engine.vs.shape == (3, blocks, *PAGES[1])
    assert paging.rope_pack(TINY, BLOCK) == 4
    assert paging.rope_pack(latent_moe.JOYAI_LLM_FLASH, 16) == 2
    assert engine.xs is None and len(engine._state()) == 2
    assert engine.cache_bytes == blocks * BLOCK * 3 * ROW_BYTES \
        == engine.ks.nbytes + engine.vs.nbytes
    cfg = latent_moe.JOYAI_LLM_FLASH
    assert cfg.latent_dim == 576 and 2 * cfg.latent_dim == 1152


def test_the_projection_caches_the_normed_latent_and_the_rotated_key(
    params
):
    """What the layer loop hands the attention state: ``c`` after its
    norm and ``kR`` after its rotation, as the reference computes
    them."""
    lp = params["layers_1"]
    h = jax.random.normal(jax.random.key(9), (1, 6, TINY.dim))
    cos, sin = decoder._rope_tables(TINY, 6)
    assert cos.shape == (6, TINY.qk_rope_head_dim // 2)
    q, c, kr = latent_moe.project(h, lp, TINY, cos, sin)
    att = lp["attention"]
    ckv = h[0] @ att["wkv_a"]["kernel"]
    want_c = reference.rmsnorm(
        ckv[:, :32], att["kv_norm"]["scale"], TINY.norm_eps
    )
    want_kr = reference.rope(ckv[:, None, 32:], TINY.rope_theta)[:, 0]
    np.testing.assert_allclose(c[0], want_c, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(kr[0], want_kr, atol=1e-6, rtol=1e-5)
    assert q.shape == (1, 6, 4, 24)


def test_what_is_added_up_stays_float32():
    """bf16 weights and products, float32 sums: the residual stream
    (from the embedding row on), the logits and the router's scores
    come out float32; what a page keeps and every product's operands
    are bf16. The dense decoder's stream stays in its compute dtype."""
    cfg = dataclasses.replace(
        TINY, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16
    )
    params = jax.jit(lambda k: latent_moe.init_latent_moe(k, cfg))(
        jax.random.key(4)
    )
    tokens = jnp.asarray([[3, 5, 7]])
    x = decoder._embed(params, tokens, cfg)
    assert x.dtype == jnp.float32
    cos, sin = decoder._rope_tables(cfg, 3)

    def attend(layer, h, lp, q, k, v):
        assert h.dtype == jnp.float32 and q.dtype == jnp.bfloat16
        assert k.dtype == v.dtype == jnp.bfloat16
        return jnp.zeros((1, 3, cfg.n_heads, cfg.v_head_dim), cfg.dtype)

    x, counts = decoder.decoder_layers(params, cfg, x, cos, sin, attend)
    assert x.dtype == jnp.float32 and int(counts["dropped"]) == 0
    assert decoder._logits_head(x, params, cfg).dtype == jnp.float32
    h = jax.random.normal(jax.random.key(2), (5, TINY.dim), jnp.float32)
    gates, _ = latent_moe.route(h, params["layers_1"], cfg)
    assert gates.dtype == jnp.float32
    dense = llama2.LlamaConfig(
        dim=64, n_layers=1, n_heads=4, vocab_size=128, multiple_of=16,
        dtype=jnp.bfloat16,
    )
    table = {"tok_embeddings": {"embedding": jnp.ones((128, 64))}}
    assert decoder._embed(table, tokens, dense).dtype == jnp.bfloat16


# -- the programs as they lower -----------------------------------------------
# sha256[:16] of ``lowered.as_text()`` of the latent programs at this
# file's tiny size: tests/test_sparse_moe.py holds the dense and
# sparse-expert programs' the same way. A PR that MEANS to change one
# re-pins it and says so. Both re-pinned at PR 36 (from PR 31's
# ce3317758663fc68 / 5177233dc2839a56): the decode step reads through
# the kernel that walks the page tables (interpreted here, so the
# kernel's own text is part of the program's), and the latents lie two
# tokens a row, which the chunk's write and gather see too.
PROGRAM_DIGESTS = {
    "decode": "da731408af9ce5bc",
    "prefill": "549bdc3c1a8c73e8",
}


def _lowered(program, weights):
    slots, per_seq, width, bucket = 4, 12, 16, 8
    abstract = jax.ShapeDtypeStruct
    pools = tuple(
        abstract((TINY.n_layers, 50, *page), TINY.dtype) for page in PAGES
    )
    if program == "prefill":
        fn = paging.make_chunk_prefill_fn(TINY, bucket, BLOCK, per_seq, width)
        args = (abstract((1, bucket), jnp.int32), abstract((), jnp.int32),
                abstract((), jnp.int32), abstract((width,), jnp.int32))
    else:
        fn = paging.make_paged_decode_fn(TINY, BLOCK, per_seq, width)
        args = (
            abstract((slots + len(paging.LATENT_COUNTERS),), jnp.int32),
            abstract((len(paging.STEP_ROWS), slots), jnp.int32),
            abstract((slots, width), jnp.int32),
        )
    return jax.jit(fn).lower(weights, *pools, *args)


def _program_text(program):
    weights = jax.eval_shape(
        lambda: latent_moe.init_latent_moe(jax.random.key(0), TINY)
    )
    return _lowered(program, weights).as_text()


@pytest.mark.parametrize("name", sorted(PROGRAM_DIGESTS))
def test_the_programs_lower_to_the_pinned_text(name):
    text = _program_text(name)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PROGRAM_DIGESTS[name]


def test_decode_builds_no_per_head_key_or_value():
    """The decode program contracts queries against latent rows: no
    tensor of it has the expanded keys' or values' shape (cached
    tokens x heads x a head's width), which the chunk program's
    expanded read does build."""
    tokens = 12 * BLOCK
    decode, prefill = _program_text("decode"), _program_text("prefill")
    for width in (TINY.qk_head_dim, TINY.v_head_dim):
        per_head = f"x{tokens}x{TINY.n_heads}x{width}x"
        assert per_head not in decode
        assert per_head in prefill


# -- stage names and counters -------------------------------------------------
# docs/guide/observability.md, "Stage names": the benchmark's readers
# know thirteen names, and this configuration files its work under ten
# of them (no indexer; not trained).
LATENT_SCOPES = (
    "embed", "qkv", "kv_write", "kv_read", "attention", "attn_out", "mlp",
    "router", "experts", "head",
)


def _op_paths(program):
    """``op_name`` paths of the program's operations, from the lowered
    text's locations."""
    import re

    weights = jax.eval_shape(
        lambda: latent_moe.init_latent_moe(jax.random.key(0), TINY)
    )
    text = _lowered(program, weights).as_text(debug_info=True)
    return re.findall(r'loc\("([^"]+)"', text)


@pytest.fixture(scope="module")
def op_paths():
    return {name: _op_paths(name) for name in ("decode", "prefill")}


@pytest.mark.parametrize("program,scope", [
    (p, s) for p in ("decode", "prefill") for s in LATENT_SCOPES
])
def test_latent_program_carries_scope(op_paths, program, scope):
    assert any(scope in path.split("/") for path in op_paths[program])


def test_the_absorb_is_filed_under_qkv_in_decode_alone(op_paths):
    """``qN W_UK^T`` is the decode program's, under ``qkv``; the chunk
    program expands its view under ``attention`` instead; neither has
    an ``indexer``."""
    def contraction(program, scope, spec):
        return any(
            scope in path.split("/") and spec in path
            for path in op_paths[program]
        )

    assert contraction("decode", "qkv", "hn,rhn->")
    assert not contraction("prefill", "qkv", "hn,rhn->")
    assert contraction("prefill", "attention", "r,rhn->")
    assert not contraction("decode", "attention", "r,rhn->")
    for paths in op_paths.values():
        assert not any("indexer" in path.split("/") for path in paths)


def test_the_counters_are_described_and_in_the_table_of_record(engine):
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "guide", "observability.md",
    )
    with open(path) as f:
        table = f.read()
    names = [n for _, n, _ in paging.LATENT_COUNTERS] \
        + [paging.LATENT_PAGES_LIVE[0]]
    assert names[-2:] == [
        "serve_moe_assignments_held_total", "serve_latent_pages_live_total",
    ]
    for name in names:
        assert f"`{name}`" in table, name
        assert name in engine.paged_stats
    assert paging.step_counters(TINY) == paging.LATENT_COUNTERS
    assert paging.step_counters(llama2.LlamaConfig()) == ()


# -- who refuses it, by name --------------------------------------------------
def _refusals(params, mesh):
    from tpu_hpc.checks import fit
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.serve import disagg, spec
    from tpu_hpc.train import Trainer

    other = Mesh(np.array(jax.devices()[1:2]), ("data",))
    tensor = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                  ("data", "model"))

    def paged(on=mesh, **kw):
        return PagedEngine(
            params, TINY, SERVE, on,
            PagedConfig(block_size=BLOCK, num_blocks=40, **kw),
        )

    return {
        "slab_engine": lambda: Engine(params, TINY, SERVE, mesh),
        "spec": lambda: spec.attach_spec(
            paged(), spec.SpecConfig(mode="ngram", k=2)
        ),
        "host_tier": lambda: paged(host_blocks=8),
        "pipeline_split": lambda: llama_pp.split_params(params, TINY, 3),
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
        "tensor_axis": lambda: paged(on=tensor),
        "fit_slab_cache": lambda: fit.kv_cache_bytes(TINY, 2, 64),
        "fit_int8_pool": lambda: fit.kv_paged_bytes(
            TINY, 64, 16, kv_quant="int8"
        ),
    }


@pytest.mark.parametrize("who", [
    "slab_engine", "spec", "host_tier", "pipeline_split", "trainer",
    "trainer_forward", "dense_init", "disagg", "pallas_read_path",
    "int8_pool", "tensor_axis", "fit_slab_cache", "fit_int8_pool",
])
def test_refused_by_name(params, mesh, who):
    if who == "pallas_read_path":
        # The option that picked that read is refused before an engine
        # sees the configuration: the read was removed.
        with pytest.raises(ValueError, match="kernel must be 'gather'"):
            _refusals(params, mesh)[who]()
        return
    with pytest.raises(NotImplementedError) as err:
        _refusals(params, mesh)[who]()
    name = "joyai-llm-flash" if who == "trainer" else "tiny-latent"
    assert name in str(err.value) and "LatentMoEConfig" in str(err.value)


def test_the_preset_is_the_published_model():
    """ISSUE 31's arithmetic: 26.35M of attention a layer, a 70.4M
    dense layer, 333.6M an expert layer at 64 held experts, 2.601B =
    4.85 GiB in bf16 at 1 dense + 6 expert layers."""
    cfg = dataclasses.replace(
        latent_moe.JOYAI_LLM_FLASH, n_layers=7,
        held_experts=tuple(range(64)),
    )
    counts = latent_moe.count_params(cfg)
    assert counts["attention_per_layer"] == 26_347_520
    assert counts["dense_layer"] == 26_347_520 + 3 * 2048 * 7168 + 2 * 2048
    one = 3 * 2048 * 768
    assert counts["experts_per_layer"] == 64 * one
    assert counts["expert_layer"] == (
        26_347_520 + 2 * 2048 + 2048 * 256 + 256 + one + 64 * one
    )
    assert counts["total"] == (
        counts["dense_layer"] + 6 * counts["expert_layer"]
        + 2 * 129280 * 2048 + 2048
    )
    assert round(counts["total"] / 1e9, 3) == 2.601
    assert round(2 * counts["total"] / 2**30, 2) == 4.85
    shapes = latent_moe.param_shapes(cfg)
    leaves = jax.tree.leaves(shapes, is_leaf=lambda s: isinstance(s, tuple))
    assert counts["total"] == sum(int(np.prod(s)) for s in leaves)
    whole = latent_moe.count_params(latent_moe.JOYAI_LLM_FLASH)
    assert round(whole["total"] / 1e9, 1) == 48.9
    assert (cfg.n_heads, cfg.qk_head_dim, cfg.v_head_dim) == (32, 192, 128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.n_held) == (256, 8, 64)
    assert cfg.rope_theta == 32e6 and cfg.rope_dim == 64
    assert cfg.is_dense_layer(0) and not cfg.is_dense_layer(1)
