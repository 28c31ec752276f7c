"""A latent decode step walks its slots' page tables (PR 36): the read
of ``kernels/latent_paged_attention.py`` against the form it replaced,
``decoder._latent_attention`` over a gathered view of the SAME pool.

  * one layer's attended rows agree, in float32 (1e-5: the same
    products in another summation order, as ``test_paged_flat_read.py``
    holds the flat read) and in bf16 (where the walk keeps its scores
    in float32 and the gathered form rounds them: the walk is held to
    be no further from the float32 answer than the gathered form is),
    over ragged positions, positions on both sides of a page edge,
    position 0, a full slot, an inactive slot between active ones,
    nobody active, two and four slots that share a prefix's pages, and
    tables whose tails name the scratch page;
  * what the walk must not read is poisoned: every page past a slot's
    position, every page of an inactive slot and the scratch page hold
    NaN in the pool the walk reads, and its rows stay finite;
  * 24 greedy steps through ``PagedEngine`` say the tokens of an engine
    whose row steps gather and attend as the parent's did;
  * the engine counts the pages its walks read (the live pages of the
    active slots, a shared page once a slot), the benchmark's ratio of
    them to the distinct pages is what a hand count gives, and nothing
    compiles after ``warmup``.

CPU, the kernel interpreted, tiny sizes: values and counts, never a
time.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tpu_hpc.kernels import latent_paged_attention
from tpu_hpc.models import latent_moe
from tpu_hpc.serve import PagedConfig, PagedEngine, ServeConfig, decoder
from tpu_hpc.serve import paging

BLOCK = 4
SLOTS, CAPACITY = 4, 48
MAX_BLOCKS = CAPACITY // BLOCK           # 12 pages a slot
VIEW = SLOTS * MAX_BLOCKS
SERVE = ServeConfig(slots=SLOTS, max_seq_len=CAPACITY, prefill_buckets=(8, 16))
TINY = latent_moe.LatentMoEConfig(
    name="tiny-latent", dim=64, n_layers=3, n_heads=4, vocab_size=128,
    max_seq_len=CAPACITY, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    dense_hidden=96, first_dense_layers=1, n_experts=16,
    experts_per_token=4, expert_hidden=24, held_experts=(0, 1, 2, 3, 8, 9),
    dtype=jnp.float32, param_dtype=jnp.float32,
)
CONFIGS = {
    "f32": TINY,
    "bf16": dataclasses.replace(TINY, dtype=jnp.bfloat16),
}
PACK = paging.rope_pack(TINY, BLOCK)
(_, _, PAGES_READ, PAGES_TOTAL) = [
    name for name, _ in paging.DECODE_COUNTERS
]
PAGES_LIVE = paging.LATENT_PAGES_LIVE[0]

# (positions, active[, slots that share slot 0's leading pages]) of one
# step, at 12 pages a slot of 4 tokens.
STEPS = {
    # 2 + 3 + 6 + 1 pages, every position inside a page
    "ragged": ([5, 10, 22, 1], [1, 1, 1, 1]),
    # positions 3, 7: the last row of a page; 4, 8: the first of the next
    "page_edges": ([3, 4, 7, 8], [1, 1, 1, 1]),
    # one token cached: the step's own
    "position_0": ([0, 9, 0, 30], [1, 1, 1, 1]),
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


def _live(positions, active):
    return sum(p // BLOCK + 1 for p, a in zip(positions, active) if a)


@pytest.fixture(scope="module")
def mesh(devices):
    return Mesh(np.array(devices[:1]), ("data",))


def _pool(positions, active, sharing=(), seed=0):
    """A seeded float32 pool of ``VIEW + 1`` pages in the engine's
    layout, tables that name each slot's pages in a shuffled order
    (``sharing`` slots read slot 0's first ``SHARED_PAGES`` pages) with
    the scratch page behind a slot's live pages, and the same pool with
    NaN wherever this step's walk has no business: the scratch page and
    every page no active slot reaches."""
    rng = np.random.default_rng(seed)
    rows = BLOCK // PACK
    pools = [
        rng.normal(size=(TINY.n_layers, VIEW + 1, rows, PACK * width))
        .astype(np.float32)
        for width in (TINY.kv_lora_rank, TINY.rope_dim)
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
        if on:
            read.update(owned[s, :n].tolist())
    unread = sorted(set(range(VIEW + 1)) - read)
    poisoned = [pool.copy() for pool in pools]
    for pool in poisoned:
        pool[:, unread] = np.nan
    return pools, poisoned, jnp.asarray(tables)


def _gathered(self, layer, lp, q):
    """A row step's read as the parent ran it: the view's pages
    gathered, unpacked a token a row, and attended over by
    ``decoder._latent_attention`` under the program's mask."""
    cfg = self.cfg
    scale = cfg.qk_head_dim ** -0.5
    q, q_rope = latent_moe.absorb(q, lp, cfg)
    latents, k_rope = (
        pool[layer, self.view_ids].astype(cfg.dtype).reshape(
            q.shape[0], -1, width
        ) for pool, width in (
            (self.ks, cfg.kv_lora_rank), (self.vs, cfg.rope_dim)
        )
    )
    u = decoder._latent_attention(
        q, q_rope, latents, k_rope, self.mask, cfg, scale
    )
    return latent_moe.unabsorb(u, lp, cfg)


def _attended(cfg, pools, tables, lp, q, positions, active, read):
    pos = jnp.asarray(positions, jnp.int32)
    state = paging.PagedAttention(cfg, BLOCK, MAX_BLOCKS).on(
        *(jnp.asarray(pool, cfg.dtype) for pool in pools)
    )
    state.view(tables, pos, jnp.asarray(active, jnp.int32))
    col = jnp.arange(CAPACITY)
    state.mask = (col[None, :] <= pos[:, None])[:, None, None, None, :]
    return np.asarray(read(state, 1, lp, q), np.float32)


@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("dtype", sorted(CONFIGS))
def test_the_walk_attends_as_the_gathered_view_does(dtype, step):
    cfg = CONFIGS[dtype]
    positions, active, *sharing = STEPS[step]
    pools, poisoned, tables = _pool(positions, active, *sharing)
    lp = jax.tree.map(
        lambda a: a.astype(cfg.dtype),
        latent_moe.init_latent_moe(jax.random.key(5), TINY)["layers_1"],
    )
    q = jnp.asarray(np.random.default_rng(1).normal(
        size=(SLOTS, 1, TINY.n_heads, TINY.qk_head_dim)
    ), cfg.dtype)
    # The walk reads the poisoned pool; the gathered view the clean one
    # (it reads every page and multiplies the masked ones by zero).
    got = _attended(
        cfg, poisoned, tables, lp, q, positions, active,
        paging.PagedAttention._read_latent,
    )
    want = _attended(cfg, pools, tables, lp, q, positions, active, _gathered)
    on = np.asarray(active, bool)
    assert got.shape == (SLOTS, 1, TINY.n_heads, TINY.v_head_dim)
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
        tables, jax.tree.map(lambda a: a.astype(jnp.float32), lp),
        q.astype(jnp.float32), positions, active, _gathered,
    )
    if on.any():
        far = np.abs(want[on] - exact[on]).max()
        assert np.abs(got[on] - exact[on]).max() <= max(far, 2e-2)
        np.testing.assert_allclose(got[on], want[on], rtol=0, atol=5e-2)


def test_the_cases_cover_what_they_name():
    pages = latent_paged_attention.PAGES_PER_BLOCK
    assert MAX_BLOCKS < pages       # here a slot's walk is one block:
    # the walks of several blocks are test_a_walk_of_several_blocks'
    assert _live(*STEPS["nobody"][:2]) == 0
    assert STEPS["a_full_slot"][0][0] == CAPACITY - 1
    for p in STEPS["page_edges"][0]:
        assert p % BLOCK in (0, BLOCK - 1)


@pytest.mark.parametrize("pages", [1, 2, 3, 5])
def test_a_walk_of_several_blocks(monkeypatch, pages):
    """The same rows whatever the block: a page a block, blocks that
    divide a slot's pages and blocks that do not (a last block of
    fewer pages, whose tail of the buffer holds an earlier block's
    rows under a probability of zero)."""
    positions, active = STEPS["ragged"]
    pools, poisoned, tables = _pool(positions, active)
    lp = latent_moe.init_latent_moe(jax.random.key(5), TINY)["layers_1"]
    q = jnp.asarray(np.random.default_rng(2).normal(
        size=(SLOTS, 1, TINY.n_heads, TINY.qk_head_dim)
    ), jnp.float32)
    want = _attended(TINY, pools, tables, lp, q, positions, active, _gathered)
    monkeypatch.setattr(latent_paged_attention, "PAGES_PER_BLOCK", pages)
    got = _attended(
        TINY, poisoned, tables, lp, q, positions, active,
        paging.PagedAttention._read_latent,
    )
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


# -- through the engine ---------------------------------------------------
@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: latent_moe.init_latent_moe(k, TINY))(
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
    decode 24 tokens each: the engine whose row steps walk the tables
    and one whose row steps gather and attend as the parent's did say
    the same tokens at every step."""
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 128, 3 * BLOCK).tolist()
    prompts = [
        shared + rng.integers(0, 128, 2).tolist(),
        rng.integers(0, 128, 9).tolist(),
        shared + rng.integers(0, 128, 5).tolist(),
        rng.integers(0, 128, 6).tolist(),
    ]

    def read(self, layer, lp, q):
        if self.chunk:
            return walking(self, layer, lp, q)
        return _gathered(self, layer, lp, q)

    walking = paging.PagedAttention._read_latent
    with monkeypatch.context() as patched:
        patched.setattr(paging.PagedAttention, "_read_latent", read)
        gathering = _engine(params, mesh)
    streams = {}
    for name, eng in (("walk", engine), ("gather", gathering)):
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
        streams[name] = stream
        for s in range(SLOTS):
            eng.release(s)
    assert streams["walk"] == streams["gather"]


def _reader():
    path = os.path.join(
        os.path.dirname(__file__), os.pardir, "benchmark", "layer_metrics",
        "latent_page_reads_per_live.serve.py",
    )
    spec = importlib.util.spec_from_file_location("reads_per_live", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_engine_counts_what_its_walks_read(engine):
    """Two sessions on one prefix of three pages and one on its own:
    every step adds the live pages of the active slots to what was
    read (a shared page once a SLOT), the distinct ones among them to
    the live pages, and the benchmark's ratio is their quotient;
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
    read = live = steps = 0
    for _ in range(6):
        tokens = engine.decode_now(
            tokens + [0], positions + [0], active
        ).tolist()[:3]
        read += _live(positions, [1, 1, 1])
        # the shared pages once, then each slot's own
        live += 3 + sum(p // BLOCK + 1 - 3 for p in positions[:2]) \
            + positions[2] // BLOCK + 1
        positions = [p + 1 for p in positions]
        steps += 1
    grown = {k: v - before.get(k, 0) for k, v in engine.paged_stats.items()}
    assert grown[PAGES_READ] == read
    assert grown[PAGES_TOTAL] == VIEW * steps
    assert grown[PAGES_LIVE] == live
    # A step with nobody active walks nothing, whatever the positions
    # say (the sessions still hold their pages: those stay live).
    engine.decode_now([1] * SLOTS, [47] * SLOTS, [False] * SLOTS)
    assert engine.paged_stats[PAGES_READ] - before[PAGES_READ] == read
    assert engine.compile_count_total == warmed
    reader = _reader()
    ratio = reader({"serve": {"stats": grown}})
    assert ratio == pytest.approx(read / live)
    assert 1.0 < ratio < 2.0
    # The parent's engine read every slot's whole capacity a step: it
    # walked no table, and the reader says nothing.
    assert reader({"serve": {"stats": {
        **grown, PAGES_READ: grown[PAGES_TOTAL]
    }}}) is None
    assert reader({"serve": {"stats": {}}}) is None
    for s in range(3):
        engine.release(s)


def test_the_decode_program_holds_the_walk_and_no_view(params):
    """The decode program calls the kernel once a layer (traced and
    lowered once for all of them) and gathers no view."""
    width = MAX_BLOCKS + 4
    fn = paging.make_paged_decode_fn(TINY, BLOCK, MAX_BLOCKS, width)
    rows = BLOCK // PACK
    pools = [
        jax.ShapeDtypeStruct(
            (TINY.n_layers, VIEW + 1, rows, PACK * w), jnp.float32
        ) for w in (TINY.kv_lora_rank, TINY.rope_dim)
    ]
    i32 = jnp.int32
    jaxpr = jax.make_jaxpr(fn)(
        params, *pools,
        jax.ShapeDtypeStruct((SLOTS + len(paging.LATENT_COUNTERS),), i32),
        jax.ShapeDtypeStruct((len(paging.STEP_ROWS), SLOTS), i32),
        jax.ShapeDtypeStruct((SLOTS, width), i32),
    )
    # one call a layer of the one traced kernel
    text = str(jaxpr)
    assert text.count("name=_walk") == TINY.n_layers
    assert "name=latent_paged_decode" in text
    # A gathered view is ``pool[layer, tables[:, :MAX_BLOCKS]]``.
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            assert shape[:2] != (SLOTS, MAX_BLOCKS) or len(shape) == 2, (
                eqn.primitive.name, shape
            )
