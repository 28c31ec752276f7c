"""The decode step reads the pages that are live, in place of the
``[slots, pages a slot]`` rectangle: over a pool in the compute dtype
its row step WALKS each slot's page table in a kernel
(``kernels/sparse_paged_attention.py`` with no mask), over an int8 pool
it gathers a flat list of every slot's live pages in one of a few sizes
the occupancy chooses (the ladder).

The oracle is the rectangle on the SAME pool, so every difference is
the walk's or the flat read's:

  * the walk's attended rows agree to 1e-5 in float32 over ragged
    lengths that cross page edges, a length exactly on an edge,
    inactive slots between active ones, full slots and nobody active,
    for MHA and GQA 4:1, with every page it must not read NaN;
  * the flat read's agree the same way rung by rung, for float32 and
    int8 pages (nobody active: finite rows, only the scratch page
    written);
  * greedy tokens through ``PagedEngine.decode`` agree with an engine
    that reads the rectangle, over 24 steps that climb in occupancy
    (through the rungs, for an int8 pool);
  * a walking engine builds one decode program and nothing after
    ``warmup``, and counts the live pages it read; a ladder engine
    takes the smallest rung that holds a step's live pages, compiles
    nothing after ``warmup``, counts what it read, and keeps one step
    in flight across a change of rung;
  * an indexer, a table-walking kernel, a walking dense engine and a
    speculative engine keep the one decode program they had.

CPU, float32, tiny sizes: values and counts, never a time.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tpu_hpc.kernels.paged_attention import quantize_pages_int8
from tpu_hpc.models import llama2, sparse_moe
from tpu_hpc.serve import PagedConfig, PagedEngine, ServeConfig, paging
from tpu_hpc.serve.spec import SpecConfig, attach_spec

BLOCK = 4
SLOTS, CAPACITY = 4, 64
MAX_BLOCKS = CAPACITY // BLOCK           # 16 pages a slot, 64 in all
VIEW = SLOTS * MAX_BLOCKS
RUNGS = (24, 32)
SERVE = ServeConfig(slots=SLOTS, max_seq_len=CAPACITY, prefill_buckets=(16,))
_MHA = llama2.LlamaConfig(
    dim=64, n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=128,
    multiple_of=16, max_seq_len=CAPACITY, dtype=jnp.float32,
)
CONFIGS = {"mha": _MHA, "gqa": dataclasses.replace(_MHA, n_kv_heads=1)}
(OVERLAPPED, _, PAGES_READ, PAGES_TOTAL) = [
    name for name, _ in paging.DECODE_COUNTERS
]

# (positions, active) of one step: what each must show, and the live
# pages it comes to (position p holds p // 4 + 1 pages).
STEPS = {
    # 2 + 3 + 6 + 1 pages, every length inside a page
    "ragged": ([5, 10, 22, 1], [1, 1, 1, 1]),
    # positions 3, 7: the last row of a page; 4, 8: the first of the next
    "page_edges": ([3, 4, 7, 8], [1, 1, 1, 1]),
    # slots 0 and 2 are free; their positions are whatever was left
    "gaps": ([40, 13, 9, 30], [0, 1, 0, 1]),
    # 6 + 6 + 6 + 6 = 24 pages: the lowest rung, full
    "fills_a_rung": ([23, 20, 23, 21], [1, 1, 1, 1]),
    # 16 + 16 pages: the top rung, full, from two whole slots
    "fills_the_top": ([63, 2, 63, 60], [1, 0, 1, 0]),
    # every entry of the list is nobody's
    "nobody": ([5, 17, 0, 63], [0, 0, 0, 0]),
}


def _live(positions, active):
    return sum(p // BLOCK + 1 for p, a in zip(positions, active) if a)


@pytest.fixture(scope="module")
def mesh(devices):
    return Mesh(np.array(devices[:1]), ("data",))


def _pool(cfg, quant, seed=0):
    """A seeded pool of ``VIEW + 1`` pages and tables that name each
    slot's pages in a shuffled order -> the programs' pool arguments
    ``(ks, vs[, ksc, vsc])`` and ``tables [slots, width]``."""
    rng = np.random.default_rng(seed)
    shape = (cfg.n_layers, VIEW + 1, cfg.kv_heads, BLOCK, cfg.head_dim)
    state = [jnp.asarray(rng.normal(size=shape), jnp.float32)
             for _ in range(2)]
    if quant:
        (ks, ksc), (vs, vsc) = map(quantize_pages_int8, state)
        state = [ks, vs, ksc, vsc]
    tables = np.full((SLOTS, MAX_BLOCKS + 4), paging.SCRATCH_BLOCK, np.int32)
    tables[:, :MAX_BLOCKS] = 1 + rng.permutation(VIEW).reshape(
        SLOTS, MAX_BLOCKS
    )
    return state, jnp.asarray(tables)


def _attended(cfg, quant, state, tables, q, positions, active, pages):
    """The rows ``PagedAttention`` reads for layer 1 of a one-row step:
    through the rectangle (``pages`` None) or a flat rung."""
    pos = jnp.asarray(positions, jnp.int32)
    act = jnp.asarray(active, jnp.int32)
    pool = paging.PagedAttention(
        cfg, BLOCK, MAX_BLOCKS, kv_quant="int8" if quant else "none"
    ).on(*state)
    mask = None
    if pages is None:
        pool.view(tables, pos, act)
        col = jnp.arange(CAPACITY)
        mask = (col[None, :] <= pos[:, None])[:, None, None, None, :]
    else:
        pool.live_pages(tables, pos, act, pages)
    return np.asarray(pool._read(1, q, mask))


# Every rung that holds the step (the engine never hands a step to a
# rung it overflows).
@pytest.mark.parametrize("step,pages", [
    (name, pages) for name in sorted(STEPS) for pages in RUNGS
    if _live(*STEPS[name]) <= pages
])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_flat_read_attends_as_the_rectangle_does(arch, quant, step, pages):
    cfg = CONFIGS[arch]
    positions, active = STEPS[step]
    state, tables = _pool(cfg, quant)
    q = jnp.asarray(np.random.default_rng(1).normal(
        size=(SLOTS, 1, cfg.n_heads, cfg.head_dim)
    ), jnp.float32)
    want = _attended(cfg, quant, state, tables, q, positions, active, None)
    got = _attended(cfg, quant, state, tables, q, positions, active, pages)
    on = np.asarray(active, bool)
    np.testing.assert_allclose(got[on], want[on], rtol=0, atol=1e-5)
    # A slot that owns no page reads nothing: zeros, not 0/0.
    assert not got[~on].any()


@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_the_walk_attends_as_the_rectangle_does(arch, step):
    """A dense row step's read over a float32 pool: the kernel that
    walks the tables (``PagedAttention._read_walked``, no mask: the
    columns ``<= pos``) against the gathered rectangle on the same
    pool. The walk reads a copy in which every page no active slot
    reaches is NaN; an inactive slot's rows are zeros."""
    cfg = CONFIGS[arch]
    positions, active = STEPS[step]
    state, tables = _pool(cfg, False)
    q = jnp.asarray(np.random.default_rng(1).normal(
        size=(SLOTS, 1, cfg.n_heads, cfg.head_dim)
    ), jnp.float32)
    want = _attended(cfg, False, state, tables, q, positions, active, None)
    read = {
        int(page) for s, (p, a) in enumerate(zip(positions, active)) if a
        for page in np.asarray(tables)[s, :p // BLOCK + 1]
    }
    unread = sorted(set(range(VIEW + 1)) - read)
    poisoned = [np.asarray(a).copy() for a in state]
    for pool in poisoned:
        pool[:, unread] = np.nan
    pool = paging.PagedAttention(cfg, BLOCK, MAX_BLOCKS).on(
        *map(jnp.asarray, poisoned)
    )
    pool.view(tables, jnp.asarray(positions, jnp.int32),
              jnp.asarray(active, jnp.int32))
    got = np.asarray(pool._read_walked(1, q))
    on = np.asarray(active, bool)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[on], want[on], rtol=0, atol=1e-5)
    assert not got[~on].any()


def test_the_cases_cover_what_they_name():
    live = {name: _live(*step) for name, step in STEPS.items()}
    assert live["fills_a_rung"] == RUNGS[0]
    assert live["fills_the_top"] == RUNGS[-1]
    assert 0 < live["ragged"] < RUNGS[0]


# A float32 pool's step walks (no rung); an int8 pool's reads the
# rectangle or a rung.
@pytest.mark.parametrize("quant,pages", [
    (False, None), (True, None), *((True, pages) for pages in RUNGS),
], ids=["f32-None", "int8-None", *(f"int8-{p}" for p in RUNGS)])
@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_nobody_active_writes_the_scratch_page_only(arch, quant, pages):
    """Nobody is active: the walk reads no page, every entry of a
    rung's list is nobody's (it reads the scratch page); the rows come
    out finite and no live page changes."""
    cfg = CONFIGS[arch]
    params = llama2.init_llama(jax.random.key(0), cfg)
    state, tables = _pool(cfg, quant)
    program = jax.jit(paging.make_paged_decode_fn(
        cfg, BLOCK, MAX_BLOCKS, tables.shape[1],
        kv_quant="int8" if quant else "none", flat_pages=pages,
    ))
    step = jnp.asarray([[1, 2, 3, 4], [5, 17, 0, 63], [0] * 4, [1] * 4],
                       jnp.int32)
    *after, tokens = program(
        params, *state, jnp.zeros(SLOTS, jnp.int32), step, tables
    )
    assert tokens.shape == (SLOTS,)
    for before, now in zip(state, after):
        changed = np.any(
            np.asarray(before) != np.asarray(now),
            axis=(0, *range(2, before.ndim)),
        )
        assert not changed[1:].any()
        assert np.isfinite(np.asarray(now, np.float32)).all()


# -- through the engine ---------------------------------------------------
class _Rectangle(PagedEngine):
    """The engine as it was: one decode program, every slot's whole
    capacity a step (:func:`_engine` builds its programs with the
    row step's walk swapped for the gathered read)."""

    decode_rungs = ()


def _gathered(self, layer, q, mask=None):
    """A row step's read as the rectangle ran it: the view's pages
    gathered and attended over under the columns ``<= pos``."""
    return self._read(layer, q, self.mask)


def _engine(arch, mesh, quant=False, cls=PagedEngine):
    cfg = CONFIGS[arch]
    eng = cls(
        llama2.init_llama(jax.random.key(2), cfg), cfg, SERVE, mesh,
        PagedConfig(block_size=BLOCK, num_blocks=VIEW + 1, prefill_chunk=16,
                    kv_quant="int8" if quant else "none"),
    )
    with pytest.MonkeyPatch.context() as patch:
        if cls is _Rectangle:
            patch.setattr(paging.PagedAttention, "_read_walked", _gathered)
        eng.warmup()
    return eng


def _decode_keys(engine):
    return sorted(k for k in engine._execs if k[0] == "decode")


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_greedy_tokens_agree_over_a_climb_through_the_rungs(
    arch, quant, mesh
):
    """Four prompts of 3, 9, 14 and 6 tokens (10 live pages: an int8
    pool's first rung) decode 24 tokens each to 34 live pages (past
    the top rung: the rectangle); the engine and one that reads the
    rectangle at every step say the same tokens at every step. Over a
    float32 pool the engine walks the tables in its one decode
    program."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 128, n).tolist() for n in (3, 9, 14, 6)]
    streams, used = {}, None
    for cls in (PagedEngine, _Rectangle):
        eng = _engine(arch, mesh, quant, cls)
        keys = []
        get = eng._get_exec
        eng._get_exec = lambda key: keys.append(key) or get(key)
        tokens = []
        for s, prompt in enumerate(prompts):
            eng.admit(s, prompt, 24)
            tokens.append(eng.prefill_step(s))
        positions = [len(p) for p in prompts]
        stream = [list(tokens)]
        for _ in range(24):
            tokens = eng.decode_now(tokens, positions).tolist()
            positions = [p + 1 for p in positions]
            stream.append(tokens)
        streams[cls] = stream
        if cls is PagedEngine:
            used = [k for k in keys if k[0] == "decode"]
    assert streams[PagedEngine] == streams[_Rectangle]
    if not quant:
        assert set(used) == {("decode",)}
        return
    assert used[0] == ("decode", 24) and used[-1] == ("decode",)
    assert ("decode", 32) in used


@pytest.fixture(scope="module")
def ladder(mesh):
    # An int8 pool's dense read gathers: the one engine with a ladder.
    return _engine("gqa", mesh, quant=True)


def _positions(live_pages):
    """Positions of four active slots that hold ``live_pages`` pages
    between them, as evenly as they divide."""
    share, rest = divmod(live_pages, SLOTS)
    return [
        (share + (s < rest)) * BLOCK - 1 for s in range(SLOTS)
    ]


def test_the_smallest_rung_that_holds_the_step_and_never_a_smaller(ladder):
    """A climb through every rung and back: the program each step ran,
    nothing compiled after ``warmup``, and the counters' ratio equal to
    the rungs' shares summed by hand."""
    assert ladder.decode_rungs == RUNGS
    assert _decode_keys(ladder) == [
        ("decode",), *(("decode", p) for p in RUNGS)
    ]
    warmed = ladder.compile_count_total
    before = dict(ladder.paged_stats)
    keys = []
    get = ladder._get_exec
    ladder._get_exec = lambda key: keys.append(key) or get(key)
    climb = [4, 24, 25, 32, 33, 64, 32, 5]
    try:
        for live in climb:
            positions = _positions(live)
            assert _live(positions, [1] * SLOTS) == live
            ladder.decode_now([1] * SLOTS, positions)
        # Inactive slots hold no page, whatever their positions say.
        ladder.decode_now([1] * SLOTS, [63, 63, 63, 3], [0, 0, 0, 1])
    finally:
        del ladder._get_exec
    read = [24, 24, 32, 32, VIEW, VIEW, 32, 24, 24]
    assert [k for k in keys if k[0] == "decode"] == [
        ("decode",) if p == VIEW else ("decode", p) for p in read
    ]
    assert ladder.compile_count_total == warmed
    grown = {k: v - before[k] for k, v in ladder.paged_stats.items()}
    assert grown[PAGES_READ] == sum(read)
    assert grown[PAGES_TOTAL] == VIEW * len(read)


def test_one_step_stays_in_flight_across_a_change_of_rung(ladder):
    """Three lagged steps on three programs: each call hands back the
    step before, from whichever program ran it, and the engine counts
    two overlaps."""
    steps = [_positions(live) for live in (6, 30, 40)]
    want = [ladder.decode_now([7] * SLOTS, p).tolist() for p in steps]
    before = ladder.paged_stats[OVERLAPPED]
    got = [ladder.decode([7] * SLOTS, p) for p in steps]
    got.append(ladder.flush())
    assert got[0] is None
    # A step in flight feeds its own tokens to the next, so only the
    # first agrees with the synchronous steps' input; its output does.
    assert got[1].tolist() == want[0]
    assert all(g is not None and len(g) == SLOTS for g in got[1:])
    assert ladder.paged_stats[OVERLAPPED] - before == 2


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_a_walking_engine_builds_one_decode_program(arch, mesh):
    """A dense engine over a float32 pool: one decode program whatever
    is live, nothing compiled after ``warmup`` over a climb in
    occupancy and back, and each step counted by the live pages of
    its active slots (what its walks read), no ladder counters."""
    engine = _engine(arch, mesh)
    assert engine.decode_rungs == ()
    assert _decode_keys(engine) == [("decode",)]
    assert not {n for n, _ in paging.LADDER_COUNTERS} & set(
        engine.paged_stats
    )
    warmed = engine.compile_count_total
    before = dict(engine.paged_stats)
    climb = [4, 24, 25, 33, 64, 5]
    for live in climb:
        engine.decode_now([1] * SLOTS, _positions(live))
    engine.decode_now([1] * SLOTS, [63, 63, 63, 3], [0, 0, 0, 1])
    assert engine.compile_count_total == warmed
    assert _decode_keys(engine) == [("decode",)]
    grown = {k: v - before[k] for k, v in engine.paged_stats.items()}
    assert grown[PAGES_READ] == sum(climb) + 1
    assert grown[PAGES_TOTAL] == VIEW * (len(climb) + 1)


def test_engines_that_keep_their_one_decode_program(mesh):
    """An indexer, a dense engine whose row step walks the tables and
    a speculative engine run the program they had: no flat rung is
    built for them."""
    sparse_cfg = sparse_moe.SparseMoEConfig(
        name="tiny-sparse", dim=64, n_layers=1, n_heads=4, n_kv_heads=2,
        head_dim=32, vocab_size=128, max_seq_len=CAPACITY, n_experts=4,
        experts_per_token=2, expert_hidden=32, indexer_heads=2,
        indexer_head_dim=16, indexer_rope_dim=8, indexer_topk=16,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    paged = PagedConfig(block_size=BLOCK, num_blocks=VIEW + 1,
                        prefill_chunk=16)
    sparse = PagedEngine(
        sparse_moe.init_sparse_moe(jax.random.key(0), sparse_cfg),
        sparse_cfg, SERVE, mesh, paged,
    )
    cfg = CONFIGS["mha"]
    params = llama2.init_llama(jax.random.key(0), cfg)
    walking = PagedEngine(params, cfg, SERVE, mesh, paged)
    spec = PagedEngine(params, cfg, SERVE, mesh, paged)
    attach_spec(spec, SpecConfig(mode="ngram", k=2))
    for engine in (sparse, walking):
        assert engine.decode_rungs == ()
        engine.warmup()
        assert _decode_keys(engine) == [("decode",)]
    assert spec.decode_rungs == ()
    spec.warmup()
    assert _decode_keys(spec) == []     # its step is the verify program
    for arch in (sparse_cfg, cfg):
        with pytest.raises(ValueError, match="flat_pages"):
            paging.make_paged_decode_fn(
                arch, BLOCK, MAX_BLOCKS, MAX_BLOCKS + 4, flat_pages=8
            )


@pytest.mark.parametrize("name", [PAGES_READ, PAGES_TOTAL])
def test_counter_is_described_and_in_the_table_of_record(name, ladder):
    """The two counts have HELP text in the registry (set when an
    engine is built), a row in the guide and a reader in the
    benchmark."""
    import os

    from tpu_hpc.obs import get_registry

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "guide", "observability.md")) as f:
        assert f"`{name}`" in f.read()
    with open(os.path.join(
        root, "benchmark", "layer_metrics", "view_pages_read_pct.serve.py"
    )) as f:
        assert name in f.read()
    ladder.decode_now([1] * SLOTS, _positions(4))
    assert f"# HELP tpu_hpc_{name} " in get_registry().prometheus_text()
