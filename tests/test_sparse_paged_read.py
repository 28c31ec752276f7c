"""A sparse-selection decode step walks its slots' page tables (PR 38):
the read of ``kernels/sparse_paged_attention.py`` against the form it
replaced, ``decoder._grouped_attention_paged`` over a gathered view of
the SAME pool under the SAME selection.

  * one layer's attended rows agree, in float32 (1e-5: the same
    products in another summation order) and in bf16 (where the walk
    keeps its scores in float32 and the gathered form rounds them: the
    walk is held to be no further from the float32 answer than the
    gathered form is), over ragged positions, positions on both sides
    of a page edge, position 0, a slot below ``indexer_topk`` (its mask
    is every column ``<= pos``), a full slot, an inactive slot between
    active ones, nobody active, slots that share a prefix's pages, a
    selection that names nothing in the walk's first block, and no mask
    at all (the columns ``<= pos``);
  * what the walk must not read is poisoned: every page past a slot's
    position, every page of an inactive slot and the scratch page hold
    NaN in the pool the walk reads, and its rows stay finite; the rows
    behind ``pos`` in a slot's last page hold an earlier tenant's
    numbers, large ones;
  * 24 greedy steps through ``PagedEngine`` say the tokens of an engine
    whose row steps gather and attend as the parent's did, and
    ``probe_selection`` reads the same selection from either;
  * the engine counts the pages its walks read (the live pages of the
    active slots, a shared page once a slot), and nothing compiles
    after ``warmup``;
  * the decode program calls the kernel once a layer and gathers no
    view of K or V.

CPU, the kernel interpreted, tiny sizes: values and counts, never a
time.
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tpu_hpc.kernels import sparse_paged_attention
from tpu_hpc.models import sparse_moe
from tpu_hpc.serve import PagedConfig, PagedEngine, ServeConfig, decoder
from tpu_hpc.serve import paging

BLOCK = 4
SLOTS, CAPACITY = 4, 48
MAX_BLOCKS = CAPACITY // BLOCK           # 12 pages a slot
VIEW = SLOTS * MAX_BLOCKS
TOPK = 8
SERVE = ServeConfig(slots=SLOTS, max_seq_len=CAPACITY, prefill_buckets=(8, 16))
TINY = sparse_moe.SparseMoEConfig(
    name="tiny-sparse", dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
    head_dim=32, vocab_size=128, max_seq_len=CAPACITY, n_experts=8,
    experts_per_token=2, expert_hidden=48, indexer_heads=2,
    indexer_head_dim=16, indexer_rope_dim=8, indexer_topk=TOPK,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
CONFIGS = {
    "f32": TINY,
    "bf16": dataclasses.replace(TINY, dtype=jnp.bfloat16),
}
(_, _, PAGES_READ, PAGES_TOTAL) = [
    name for name, _ in paging.DECODE_COUNTERS
]

# (positions, active[, slots that share slot 0's leading pages]) of one
# step, at 12 pages a slot of 4 tokens and a selection of 8.
STEPS = {
    # 2 + 3 + 6 + 1 pages, every position inside a page
    "ragged": ([5, 10, 22, 1], [1, 1, 1, 1]),
    # positions 3, 7: the last row of a page; 4, 8: the first of the next
    "page_edges": ([3, 4, 7, 8], [1, 1, 1, 1]),
    # one token cached: the step's own
    "position_0": ([0, 9, 0, 30], [1, 1, 1, 1]),
    # slots 0 and 2 hold fewer tokens than the indexer keeps
    "below_topk": ([6, 40, 7, 21], [1, 1, 1, 1]),
    # the last row of the last page: every block of the walk is whole
    "a_full_slot": ([47, 6, 47, 21], [1, 1, 1, 1]),
    # slots 0 and 2 are free; their positions are whatever was left
    "an_inactive_slot": ([40, 13, 9, 30], [0, 1, 0, 1]),
    "nobody": ([5, 17, 0, 47], [0, 0, 0, 0]),
    # slots 0 and 1 read the same five leading pages, then their own
    "two_share_a_prefix": ([29, 33, 12, 3], [1, 1, 1, 1], (1,)),
    "four_share_a_prefix": ([25, 38, 21, 47], [1, 1, 1, 1], (1, 2, 3)),
}
SHARED_PAGES = 5
STALE = 1e3      # what an earlier tenant left behind ``pos`` in a page


def _live(positions, active):
    return sum(p // BLOCK + 1 for p, a in zip(positions, active) if a)


@pytest.fixture(scope="module")
def mesh(devices):
    return Mesh(np.array(devices[:1]), ("data",))


def _pool(positions, active, sharing=(), seed=0):
    """A seeded float32 K and V pool of ``VIEW + 1`` pages in the
    engine's layout, tables that name each slot's pages in a shuffled
    order (``sharing`` slots read slot 0's first ``SHARED_PAGES``
    pages) with the scratch page behind a slot's live pages, and the
    same pool with NaN wherever this step's walk has no business: the
    scratch page and every page no active slot reaches. The rows behind
    a slot's position in its last page (its own, never shared) hold
    ``STALE`` in both."""
    rng = np.random.default_rng(seed)
    pools = [
        rng.normal(
            size=(TINY.n_layers, VIEW + 1, TINY.kv_heads, BLOCK,
                  TINY.head_dim)
        ).astype(np.float32) for _ in range(2)
    ]
    owned = 1 + rng.permutation(VIEW).reshape(SLOTS, MAX_BLOCKS)
    for s in sharing:
        owned[s, :SHARED_PAGES] = owned[0, :SHARED_PAGES]
    tables = np.full(
        (SLOTS, MAX_BLOCKS + 4), paging.SCRATCH_BLOCK, np.int32
    )
    read = set()
    for s, (pos, on) in enumerate(zip(positions, active)):
        n = pos // BLOCK + 1
        tables[s, :n] = owned[s, :n]
        if n > SHARED_PAGES or not sharing:     # a page of its own
            for pool in pools:
                pool[:, owned[s, n - 1], :, pos % BLOCK + 1:] = STALE
        if on:
            read.update(owned[s, :n].tolist())
    unread = sorted(set(range(VIEW + 1)) - read)
    poisoned = [pool.copy() for pool in pools]
    for pool in poisoned:
        pool[:, unread] = np.nan
    return pools, poisoned, jnp.asarray(tables)


def _selection(positions, seed=0, first=0):
    """What an indexer might have kept: ``TOPK`` of each slot's columns
    ``first <= c <= pos`` (all of them where there are no more), bool
    ``[slots, 1, 1, 1, capacity]`` as ``PagedAttention._select`` hands
    it to the read."""
    rng = np.random.default_rng(seed)
    chosen = np.zeros((SLOTS, CAPACITY), bool)
    for s, pos in enumerate(positions):
        columns = np.arange(min(first, pos), pos + 1)
        chosen[s, rng.permutation(columns)[:TOPK]] = True
    return jnp.asarray(chosen)[:, None, None, None, :]


def _gathered(self, layer, q, mask):
    """A row step's read as the parent ran it: the view's pages
    gathered and attended over under the selection."""
    return decoder._grouped_attention_paged(
        q, self.ks[layer, self.view_ids].astype(self.cfg.dtype),
        self.vs[layer, self.view_ids].astype(self.cfg.dtype), mask,
        self.cfg,
    )


def _attended(cfg, pools, tables, q, positions, active, mask, read):
    state = paging.PagedAttention(cfg, BLOCK, MAX_BLOCKS).on(
        *(jnp.asarray(pool, cfg.dtype) for pool in pools)
    )
    state.view(
        tables, jnp.asarray(positions, jnp.int32),
        jnp.asarray(active, jnp.int32),
    )
    return np.asarray(read(state, 1, q, mask), np.float32)


def _queries(dtype, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(SLOTS, 1, TINY.n_heads, TINY.head_dim)
    ), dtype)


@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("dtype", sorted(CONFIGS))
def test_the_walk_attends_as_the_gathered_view_does(dtype, step):
    cfg = CONFIGS[dtype]
    positions, active, *sharing = STEPS[step]
    pools, poisoned, tables = _pool(positions, active, *sharing)
    mask = _selection(positions)
    q = _queries(cfg.dtype)
    # The walk reads the poisoned pool; the gathered view the clean one
    # (it reads every page and multiplies the masked ones by zero).
    got = _attended(
        cfg, poisoned, tables, q, positions, active, mask,
        paging.PagedAttention._read_selected,
    )
    want = _attended(
        cfg, pools, tables, q, positions, active, mask, _gathered
    )
    on = np.asarray(active, bool)
    assert got.shape == (SLOTS, 1, TINY.n_heads, TINY.head_dim)
    assert np.isfinite(got).all()
    # A slot that is not active reads nothing: zeros, not 0/0.
    assert not got[~on].any()
    if dtype == "f32":
        np.testing.assert_allclose(got[on], want[on], rtol=0, atol=1e-5)
        return
    # bf16: both forms against the float32 answer on the same (bf16)
    # numbers; the walk keeps float32 scores where the gathered form
    # rounds them, so it may not be the further of the two.
    exact = _attended(
        TINY, [np.asarray(jnp.asarray(p, jnp.bfloat16), np.float32)
               for p in pools],
        tables, q.astype(jnp.float32), positions, active, mask, _gathered,
    )
    if on.any():
        far = np.abs(want[on] - exact[on]).max()
        assert np.abs(got[on] - exact[on]).max() <= max(far, 2e-2)
        np.testing.assert_allclose(got[on], want[on], rtol=0, atol=5e-2)


def test_the_cases_cover_what_they_name():
    pages = sparse_paged_attention.PAGES_PER_BLOCK
    assert MAX_BLOCKS < pages       # here a slot's walk is one block:
    # the walks of several blocks are test_a_walk_of_several_blocks'
    assert _live(*STEPS["nobody"][:2]) == 0
    assert STEPS["a_full_slot"][0][0] == CAPACITY - 1
    for p in STEPS["page_edges"][0]:
        assert p % BLOCK in (0, BLOCK - 1)
    below = np.asarray(_selection(STEPS["below_topk"][0]))[:, 0, 0, 0]
    for s, pos in enumerate(STEPS["below_topk"][0]):
        assert below[s].sum() == min(pos + 1, TOPK)
        assert not below[s, pos + 1:].any()
    assert STEPS["below_topk"][0][0] + 1 < TOPK
    # a page partly written: rows behind the position hold STALE
    positions, active = STEPS["ragged"]
    pools, _, tables = _pool(positions, active)
    last = int(tables[0, positions[0] // BLOCK])
    assert (pools[0][:, last, :, positions[0] % BLOCK + 1:] == STALE).all()


@pytest.mark.parametrize("pages", [1, 2, 3, 5])
def test_a_walk_of_several_blocks(monkeypatch, pages):
    """The same rows whatever the block: a page a block, blocks that
    divide a slot's pages and blocks that do not (an odd number of
    blocks; a last block of fewer pages, whose tail of the buffer holds
    an earlier block's rows under a selection that names none)."""
    positions, active = STEPS["ragged"]
    pools, poisoned, tables = _pool(positions, active)
    mask = _selection(positions)
    q = _queries(jnp.float32, seed=2)
    want = _attended(
        TINY, pools, tables, q, positions, active, mask, _gathered
    )
    monkeypatch.setattr(sparse_paged_attention, "PAGES_PER_BLOCK", pages)
    got = _attended(
        TINY, poisoned, tables, q, positions, active, mask,
        paging.PagedAttention._read_selected,
    )
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("pages", [2, 64])
def test_a_first_block_with_nothing_selected(monkeypatch, pages):
    """A selection whose columns all lie behind the walk's first block
    (and, in blocks of two pages, behind several): the blocks before
    add nothing, and no ``-inf - -inf`` comes of them."""
    positions, active = [38, 47, 30, 21], [1, 1, 1, 1]
    pools, poisoned, tables = _pool(positions, active)
    mask = _selection(positions, first=4 * BLOCK)
    assert not np.asarray(mask)[..., :4 * BLOCK].any()
    q = _queries(jnp.float32, seed=3)
    want = _attended(
        TINY, pools, tables, q, positions, active, mask, _gathered
    )
    monkeypatch.setattr(sparse_paged_attention, "PAGES_PER_BLOCK", pages)
    got = _attended(
        TINY, poisoned, tables, q, positions, active, mask,
        paging.PagedAttention._read_selected,
    )
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("pages", [3, 64])
def test_without_a_mask_the_walk_attends_up_to_the_position(
    monkeypatch, pages
):
    """The kernel as a caller with no selection would call it: the
    columns ``<= pos``, under another score scale."""
    positions, active = STEPS["an_inactive_slot"]
    pools, poisoned, tables = _pool(positions, active)
    q = _queries(jnp.float32, seed=4)
    col = jnp.arange(CAPACITY)
    valid = (
        col[None, :] <= jnp.asarray(positions)[:, None]
    )[:, None, None, None, :]
    scale = 1 / 64
    view = tables[:, :MAX_BLOCKS]
    want = np.asarray(decoder._grouped_attention_paged(
        q, jnp.asarray(pools[0])[1, view], jnp.asarray(pools[1])[1, view],
        valid, types.SimpleNamespace(
            kv_heads=TINY.kv_heads, n_heads=TINY.n_heads,
            head_dim=TINY.head_dim, dtype=jnp.float32,
            attention_multiplier=scale,
        ),
    ))
    monkeypatch.setattr(sparse_paged_attention, "PAGES_PER_BLOCK", pages)
    got = np.asarray(sparse_paged_attention.sparse_paged_decode(
        q[:, 0].reshape(SLOTS, TINY.kv_heads, -1, TINY.head_dim),
        *(jnp.asarray(pool) for pool in poisoned), jnp.int32(1), view,
        jnp.asarray(positions, jnp.int32),
        jnp.asarray(active, jnp.int32), scale=scale, interpret=True,
    )).reshape(want.shape)
    on = np.asarray(active, bool)
    assert not got[~on].any()
    np.testing.assert_allclose(got[on], want[on], rtol=0, atol=1e-5)


# -- through the engine ---------------------------------------------------
@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: sparse_moe.init_sparse_moe(k, TINY))(
        jax.random.key(3)
    )


def _engine(params, mesh):
    eng = PagedEngine(
        params, TINY, SERVE, mesh,
        PagedConfig(block_size=BLOCK, num_blocks=VIEW + 1, prefill_chunk=16),
    )
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def engine(params, mesh):
    return _engine(params, mesh)


def test_greedy_tokens_agree_with_the_gathered_form(
    params, mesh, engine, monkeypatch
):
    """Four prompts, two of them on one shared prefix of three pages,
    decode 24 tokens each (contexts on both sides of ``indexer_topk``):
    the engine whose row steps walk the tables and one whose row steps
    gather and attend as the parent's did say the same tokens at every
    step, and ``probe_selection`` reads the same selection."""
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 128, 3 * BLOCK).tolist()
    prompts = [
        shared + rng.integers(0, 128, 2).tolist(),
        rng.integers(0, 128, 9).tolist(),
        shared + rng.integers(0, 128, 5).tolist(),
        rng.integers(0, 128, 6).tolist(),
    ]
    def run(eng):
        tokens = []
        for s, prompt in enumerate(prompts):
            eng.admit(s, prompt, 24)
            tokens.append(eng.prefill_step(s))
        positions = [len(p) for p in prompts]
        stream = [list(tokens)]
        for step in range(24):
            if step == 20:
                probe = eng.probe_selection(
                    tokens, positions, [True] * SLOTS
                )
            tokens = eng.decode_now(tokens, positions).tolist()
            positions = [p + 1 for p in positions]
            stream.append(tokens)
        for s in range(SLOTS):
            eng.release(s)
        return stream, probe

    streams, probes = {}, {}
    streams["walk"], probes["walk"] = run(engine)
    # Every program of this engine, the probe's too, is built with the
    # row steps' read patched back to the gathered form.
    with monkeypatch.context() as patched:
        patched.setattr(
            paging.PagedAttention, "_read_selected",
            paging.PagedAttention._read,
        )
        streams["gather"], probes["gather"] = run(_engine(params, mesh))
    assert streams["walk"] == streams["gather"]
    assert probes["walk"].shape == (TINY.n_layers, SLOTS, CAPACITY)
    assert (probes["walk"].sum(-1) == TOPK).all()
    np.testing.assert_array_equal(probes["walk"], probes["gather"])


def test_the_engine_counts_what_its_walks_read(engine):
    """Two sessions on one prefix of three pages and one on its own:
    every step adds the live pages of the active slots to what was
    read (a shared page once a SLOT) and the rectangle to the total;
    the distinct live pages are a latent configuration's count alone;
    nothing compiles after ``warmup``."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 128, 3 * BLOCK).tolist()
    prompts = [
        shared + [5, 6], shared + [7, 8, 9], rng.integers(0, 128, 6).tolist()
    ]
    warmed = engine.compile_count_total
    tokens = []
    for s, prompt in enumerate(prompts):
        engine.admit(s, prompt, 8)
        tokens.append(engine.prefill_step(s))
    before = dict(engine.paged_stats)
    positions = [len(p) for p in prompts]
    active = [True, True, True, False]
    read = steps = 0
    for _ in range(6):
        tokens = engine.decode_now(
            tokens + [0], positions + [0], active
        ).tolist()[:3]
        read += _live(positions, [1, 1, 1])
        positions = [p + 1 for p in positions]
        steps += 1
    grown = {k: v - before.get(k, 0) for k, v in engine.paged_stats.items()}
    assert grown[PAGES_READ] == read
    assert grown[PAGES_TOTAL] == VIEW * steps
    assert 0 < read < VIEW * steps
    assert paging.LATENT_PAGES_LIVE[0] not in engine.paged_stats
    # A step with nobody active walks nothing, whatever the positions
    # say.
    engine.decode_now([1] * SLOTS, [47] * SLOTS, [False] * SLOTS)
    assert engine.paged_stats[PAGES_READ] - before[PAGES_READ] == read
    assert engine.compile_count_total == warmed
    for s in range(3):
        engine.release(s)


@pytest.mark.parametrize("probe", [False, True])
def test_the_decode_program_holds_the_walk_and_no_view(params, probe):
    """The decode program (and the one that also returns its
    selections) calls the kernel once a layer, traced and lowered once
    for all of them, and gathers no view of K or V: the one ``[slots,
    max_blocks, ...]`` array left is the indexer's keys."""
    width = MAX_BLOCKS + 4
    fn = paging.make_paged_decode_fn(
        TINY, BLOCK, MAX_BLOCKS, width, probe=probe
    )
    page = (TINY.kv_heads, BLOCK, TINY.head_dim)
    abstract = jax.ShapeDtypeStruct
    pools = [
        abstract((TINY.n_layers, VIEW + 1, *page), jnp.float32)
        for _ in range(2)
    ] + [abstract(
        (TINY.n_layers, VIEW + 1, BLOCK, TINY.indexer_head_dim),
        jnp.float32,
    )]
    i32 = jnp.int32
    jaxpr = jax.make_jaxpr(fn)(
        params, *pools,
        abstract((SLOTS + len(paging.SPARSE_COUNTERS),), i32),
        abstract((len(paging.STEP_ROWS), SLOTS), i32),
        abstract((SLOTS, width), i32),
    )
    text = str(jaxpr)
    assert text.count("name=_walk") == TINY.n_layers
    assert "name=sparse_paged_decode" in text
    # A gathered view is ``pool[layer, tables[:, :MAX_BLOCKS]]``.
    views = {
        tuple(var.aval.shape)
        for eqn in jaxpr.jaxpr.eqns for var in eqn.outvars
        if tuple(getattr(var.aval, "shape", ()))[:2] == (SLOTS, MAX_BLOCKS)
        and len(var.aval.shape) > 2
    }
    assert (SLOTS, MAX_BLOCKS, BLOCK, TINY.indexer_head_dim) in views
    assert (SLOTS, MAX_BLOCKS, *page) not in views
