"""The decoder whose every layer runs attention and a Mamba-2 mixer side
by side on one normed input (``models/hybrid_ssm_moe.py``'s
``"parallel"`` layers: Falcon-H1-34B-Instruct) through the paged server,
against its plain reference
(``benchmark/reference/parallel_hybrid_decoder.py``), at the
``falcon-h1-tiny`` size on the CPU: every multiplier off 1, heads of 24
numbers where ``dim / n_heads`` is 16, a mixer of two groups 96 wide
where ``ssm_expand * dim`` is 128, and prompts that no chunk divides.
Counts and agreement, never a time.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.reference import parallel_hybrid_decoder as reference
from tpu_hpc.models import hybrid_ssm_moe as hybrid
from tpu_hpc.models import llama2, llama_pp
from tpu_hpc.obs import get_registry
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dataclasses.replace(
    hybrid.PRESETS["falcon-h1-tiny"], name="tiny-parallel", vocab_size=128,
    max_seq_len=96, dtype=jnp.float32, param_dtype=jnp.float32,
)
SERVE = ServeConfig(slots=3, max_seq_len=96, prefill_buckets=(8, 16))
BLOCK, PER_SEQ, WIDTH = 4, 24, 28
# Every multiplier of the equations, as (the program's field, what
# dropping it means: the field at 1).
MULTIPLIERS = (
    "embedding_multiplier", "key_multiplier", "attention_out_multiplier",
    "ssm_in_multiplier", "ssm_z_multiplier", "ssm_x_multiplier",
    "ssm_b_multiplier", "ssm_c_multiplier", "ssm_dt_multiplier",
    "ssm_out_multiplier", "mlp_gate_multiplier", "mlp_down_multiplier",
    "logits_scaling",
)


def arch_of(cfg):
    """The reference's sizes, by the names the benchmark's job gives
    them."""
    names = (
        "dim", "n_layers", "n_heads", "n_kv_heads", "head_dim",
        "vocab_size", "norm_eps", "rope_theta", "key_multiplier",
        "attention_out_multiplier", "embedding_multiplier",
        "lm_head_multiplier", "ssm_heads", "ssm_head_dim", "ssm_state",
        "ssm_groups", "ssm_conv", "ssm_in_multiplier", "ssm_z_multiplier",
        "ssm_x_multiplier", "ssm_b_multiplier", "ssm_c_multiplier",
        "ssm_dt_multiplier", "ssm_out_multiplier", "mlp_hidden",
        "mlp_gate_multiplier", "mlp_down_multiplier", "n_ssm_layers",
        "n_attention_layers",
    )
    return {k: getattr(cfg, k) for k in names}


ARCH = arch_of(TINY)


def _init(cfg, seed=3):
    """The configuration's seeded weights with every matrix times
    ``sqrt(4096 / fan_in)``: the init's Normal(0.02) is sized for a
    decoder thousands wide, and at 64 every product would be a sixth of
    its size there and every nonlinearity near its linear part (silu of
    the gate, the convolution's, the softmax of nearly equal scores),
    where a dropped multiplier hides behind the next norm."""
    params = jax.jit(lambda k: hybrid.init_hybrid_ssm_moe(k, cfg))(
        jax.random.key(seed)
    )

    def widen(path, leaf):
        if getattr(path[-1], "key", None) != "kernel" or leaf.ndim != 2:
            return leaf
        return leaf * (4096 / leaf.shape[0]) ** 0.5

    return jax.tree.map_with_path(widen, params)


@pytest.fixture(scope="module")
def params():
    return _init(TINY)


@pytest.fixture(scope="module")
def mesh(devices):
    return Mesh(np.array(devices[:1]), ("data",))


def _engine(params, mesh, cfg=TINY, **paged):
    eng = PagedEngine(
        params, cfg, SERVE, mesh,
        PagedConfig(block_size=BLOCK, num_blocks=3 * 24 + 1,
                    prefill_chunk=16, **paged),
    )
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def engine(params, mesh):
    return _engine(params, mesh)


def _serve(eng, prompts, max_new=8):
    batcher = ContinuousBatcher(eng)
    for rid, prompt in prompts.items():
        batcher.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    return batcher.run()


def _reference_logits(params, tokens, rows, arch=ARCH, pad=64):
    padded = np.zeros(pad, np.int32)
    padded[:len(tokens)] = tokens
    hidden, _ = reference.forward(params, jnp.asarray(padded), arch,
                                  q_block=16)
    return np.asarray(reference.logits(params, hidden[np.asarray(rows)], arch))


def _emitted_from(params, prompt, emitted):
    n = len(prompt)
    return _reference_logits(
        params, list(prompt) + list(emitted[:-1]),
        n - 1 + np.arange(len(emitted)),
    )


def _gap(got, want):
    """``got`` against ``want`` in units of the logits' own size."""
    return float(np.abs(got - want).max() / np.abs(want).max())


# Float32 on both sides, another order of sums (the chunked recurrence
# against a token-by-token scan, page-major attention, products in
# other groupings): 1e-4 of the logits' size, as the state-space
# decoder's own tests hold theirs (they read ~1e-6 here).
TOL = 1e-4


def _chunk_programs(cfg=TINY):
    return {
        bucket: jax.jit(paging.make_chunk_logits_fn(
            cfg, bucket, BLOCK, PER_SEQ, WIDTH
        )) for bucket in SERVE.prefill_buckets
    }


def _fresh_state(cfg=TINY, slots=3):
    page = (cfg.n_attention_layers, 30, cfg.kv_heads, BLOCK, cfg.head_dim)
    s, conv = cfg.state_shapes(slots)
    return [
        jnp.zeros(page, cfg.dtype), jnp.zeros(page, cfg.dtype),
        jnp.zeros(s, cfg.ssm_state_dtype),
        jnp.zeros(conv, cfg.ssm_state_dtype),
    ]


def _plan(n, chunk=16):
    """Chunks of ``chunk`` over ``n`` tokens, each in the smallest
    bucket that holds it."""
    plan = []
    for start in range(0, n, chunk):
        run = min(chunk, n - start)
        plan.append((start, run, 8 if run <= 8 else 16))
    return plan


def _prefill(programs, params, prompt, plan, slot=1, snap_at=0, state=None,
             table=None):
    state = state or _fresh_state()
    if table is None:
        table = jnp.asarray(np.arange(1, WIDTH + 1), jnp.int32)
    logits = snap = None
    for start, run, bucket in plan:
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :run] = prompt[start:start + run]
        at = snap_at - start if start < snap_at <= start + run else 0
        *state, logits, s, conv = programs[bucket](
            params, *state, jnp.asarray(tokens), jnp.int32(start),
            jnp.int32(run), table, jnp.int32(slot), jnp.int32(at),
        )
        if at:
            snap = (s, conv)
    return logits, state, snap


# -- program against reference --------------------------------------------
@pytest.mark.parametrize("n", [5, 8, 13, 27, 40])
def test_chunk_logits_agree_with_the_reference(params, n):
    """(a) The chunk programs' logits row: a prompt prefilled in chunks
    of 16 that do not divide it (pages and state carried from chunk to
    chunk in every layer) against the reference's one forward pass."""
    prompt = np.random.default_rng(n).integers(0, TINY.vocab_size, n)
    logits, _, _ = _prefill(_chunk_programs(), params, prompt, _plan(n))
    want = _reference_logits(params, prompt, [n - 1])[0]
    assert _gap(np.asarray(logits), want) < TOL


def _decode_logits(params, state, tokens, pos, active, tables, cfg=TINY):
    """The decode program's layer loop with the logits for a result
    (the program returns their arg-max): every slot, one row."""
    pool = paging.PagedAttention(cfg, BLOCK, PER_SEQ).on(*state[:2])
    recur = paging.RecurrentState(cfg).on(*state[2:])
    slots = tokens.shape[0]
    x = decoder._embed(params, tokens[:, None], cfg)
    cos, sin = decoder._rope_tables(cfg, 1, pos)
    mask = (jnp.arange(PER_SEQ * BLOCK)[None, :] <= pos[:, None])[
        :, None, None, None, :
    ]
    pb = jnp.where(active > 0, tables[jnp.arange(slots), pos // BLOCK], 0)
    pool.view(tables, pos, active)
    pool.rows(pb, pos % BLOCK, mask, slot=jnp.arange(slots))
    recur.rows(active)
    x, _ = decoder.decoder_layers(
        params, cfg, x, cos[:, None, :], sin[:, None, :], pool,
        weight=active, recur=recur,
    )
    return decoder._logits_head(x, params, cfg)[:, 0], \
        [*pool.state()[:2], *recur.state()]


def test_decode_logits_agree_with_the_reference(params):
    """(a) Paged decode as numbers: a prompt prefilled in chunks, then
    eight rows one at a time through the one-step form, the page pool
    and the rotation at each slot's own position, against the
    reference's one forward pass."""
    tokens = np.random.default_rng(7).integers(0, TINY.vocab_size, 27)
    _, state, _ = _prefill(_chunk_programs(), params, tokens, _plan(19))
    tables = jnp.zeros((3, WIDTH), jnp.int32).at[1].set(
        jnp.arange(1, WIDTH + 1)
    )
    active = jnp.asarray([0, 1, 0], jnp.int32)
    step = jax.jit(_decode_logits)
    want = _reference_logits(params, tokens, np.arange(19, 27))
    for j, pos in enumerate(range(19, 27)):
        logits, state = step(
            params, state, jnp.full((3,), tokens[pos], jnp.int32),
            jnp.full((3,), pos, jnp.int32), active, tables,
        )
        assert _gap(np.asarray(logits[1]), want[j]) < TOL


@pytest.mark.parametrize("prompt_len", [5, 14, 27, 40])
def test_paged_decode_agrees_with_the_references_full_forward(
    params, engine, prompt_len
):
    """(a) Through ``ContinuousBatcher`` and ``PagedEngine`` with one
    step in flight: every emitted token is the reference's arg-max
    (float32 on both sides)."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, TINY.vocab_size, prompt_len).tolist()
    emitted = _serve(engine, {"r": prompt})["r"]
    logits = _emitted_from(params, prompt, emitted)
    assert logits.argmax(-1).tolist() == list(emitted)


def test_bf16_products_stay_within_the_regret_they_can_cost(params, mesh):
    """(a) The same weights served with bfloat16 products (the state
    and the residual stream still float32): the reference's best logit
    minus the emitted token's, in standard deviations of the row, stays
    under 0.15 (a flipped near-tie costs a few hundredths of a sigma:
    ``test_hybrid_ssm_moe.py`` has the argument)."""
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    eng = _engine(params, mesh, cfg)
    prompt = np.random.default_rng(11).integers(0, TINY.vocab_size, 30)
    emitted = _serve(eng, {"r": prompt.tolist()})["r"]
    logits = _emitted_from(params, prompt, emitted)
    regret = logits.max(-1) - logits[np.arange(len(emitted)), emitted]
    assert (regret / logits.std(-1)).max() < 0.15


# -- the multipliers --------------------------------------------------------
@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_program_without_one_multiplier_fails_the_comparison(params, name):
    """Each multiplier of the equations is applied where they put it:
    the program with that ONE multiplier set to 1 reads logits at least
    ten times further from the reference's than the tolerance the
    whole program is held to (``TOL``; it reads ~1e-6)."""
    n = 27
    prompt = np.random.default_rng(n).integers(0, TINY.vocab_size, n)
    want = _reference_logits(params, prompt, [n - 1])[0]
    cfg = dataclasses.replace(TINY, **{name: 1.0})
    logits, _, _ = _prefill(_chunk_programs(cfg), params, prompt, _plan(n))
    assert _gap(np.asarray(logits), want) > 10 * TOL


def test_the_rotation_is_rotate_half(params, monkeypatch):
    """The published family rotates number ``j`` of a head with ``j +
    head_dim / 2``; the program rotating adjacent pairs instead fails
    the comparison."""
    q = jnp.asarray(np.random.default_rng(0).standard_normal((1, 3, 2, 24)),
                    jnp.float32)
    cos, sin = decoder._rope_tables(TINY, 3)
    np.testing.assert_allclose(
        decoder.conv_moe.rope_half(q, cos, sin)[0],
        reference.rotate_half(q[0], jnp.arange(3), TINY.rope_theta),
        rtol=1e-5, atol=1e-6,
    )
    n = 13
    prompt = np.random.default_rng(n).integers(0, TINY.vocab_size, n)
    want = _reference_logits(params, prompt, [n - 1])[0]
    monkeypatch.setattr(decoder.conv_moe, "rope_half", llama2.apply_rope)
    logits, _, _ = _prefill(_chunk_programs(), params, prompt, _plan(n))
    assert _gap(np.asarray(logits), want) > 100 * TOL


# -- the prefix trie ----------------------------------------------------------
def test_a_trie_hit_gives_the_logits_of_a_cold_prefill(params):
    """A document's pages AND its state at the document's end, restored
    into another slot (the state through the engine's restore program,
    the pages by sharing the table's leading entries), then only the
    question prefilled: the logits of the same prompt prefilled cold in
    another slot, and the reference's."""
    rng = np.random.default_rng(12)
    doc = rng.integers(0, TINY.vocab_size, 24)
    question = rng.integers(0, TINY.vocab_size, 11)
    programs = _chunk_programs()
    _, state, snap = _prefill(programs, params, doc, _plan(24), slot=0,
                              snap_at=24)
    restore = jax.jit(paging.make_restore_state_fn())
    state[2:] = restore(*state[2:], *snap, jnp.int32(2))
    # Slot 2 reads the document's six pages, then pages of its own.
    table = jnp.asarray(
        np.r_[np.arange(1, 7), np.arange(20, 20 + WIDTH - 6)], jnp.int32
    )
    hit, _, _ = _prefill(
        programs, params, np.r_[doc, question], [(24, 11, 16)], slot=2,
        state=state, table=table,
    )
    prompt = np.r_[doc, question]
    cold, _, _ = _prefill(programs, params, prompt, _plan(35), slot=1)
    want = _reference_logits(params, prompt, [34])[0]
    assert _gap(np.asarray(hit), np.asarray(cold)) < TOL
    assert _gap(np.asarray(hit), want) < TOL


def test_a_request_admitted_on_a_snapshot_is_the_request_prefilled_whole(
    params, mesh
):
    """Through the engine: a prompt whose first 36 tokens another
    request left in the trie, pages and state, restores the snapshot,
    shares nine pages and prefills its own tokens alone, and emits
    what an engine that has seen nothing emits (the reference's
    arg-max)."""
    rng = np.random.default_rng(5)
    doc = rng.integers(0, TINY.vocab_size, 36).tolist()
    eng = _engine(params, mesh)
    _serve(eng, {"first": doc + rng.integers(0, 128, 7).tolist()})
    _serve(eng, {"second": doc + rng.integers(0, 128, 10).tolist()})
    stats = eng.paged_stats
    before = (stats["prefix_hit_blocks"], stats["prefill_chunks"])
    tail = rng.integers(0, TINY.vocab_size, 9).tolist()
    got = _serve(eng, {"third": doc + tail})["third"]
    assert stats["serve_ssm_restores_total"] == 1
    assert stats["serve_ssm_restored_tokens_total"] == 36
    assert stats["prefix_hit_blocks"] - before[0] == 9
    assert stats["prefill_chunks"] - before[1] == 1
    fresh = _serve(_engine(params, mesh), {"third": doc + tail})["third"]
    assert got == fresh
    assert _emitted_from(params, doc + tail, got).argmax(-1).tolist() == got


# -- the counters, by hand --------------------------------------------------
def test_the_counters_count_every_layer(params, mesh):
    """Every layer of the stack keeps pages AND a state: the pool has a
    row for each of the three layers and so has the state, and the
    gauges are three layers' state a slot and a snapshot. The live
    pages, the slots a step advanced, the snapshots and the restores
    counted by hand for three requests on one 16-token document, one
    slot at a time (a step's first token comes from its prompt's last
    chunk, so ``max_new`` tokens are ``max_new - 1`` decode steps)."""
    d_inner, conv = TINY.d_inner, TINY.conv_dim
    one = 3 * (d_inner * TINY.ssm_state + 3 * conv) * 4
    eng = _engine(params, mesh, ssm_snapshot_bytes=10 * one)
    assert eng.ks.shape[0] == eng.ssm_s.shape[0] == 3
    assert TINY.n_attention_layers == TINY.n_ssm_layers == TINY.n_layers
    assert [TINY.state_layer(i, k) for i in range(3)
            for k in ("attention", "mamba")] == [0, 0, 1, 1, 2, 2]
    assert eng.ssm_state_bytes == 3 * one == TINY.state_bytes(3)
    assert get_registry().gauge("serve_ssm_state_bytes") == 3 * one
    rng = np.random.default_rng(8)
    doc = rng.integers(0, TINY.vocab_size, 16).tolist()
    stats = eng.paged_stats

    def own(n):
        return rng.integers(0, TINY.vocab_size, n).tolist()

    # A, 21 tokens: a snapshot at its last full block (20); decode at
    # 21, 22, 23: six live pages (of four tokens) each.
    _serve(eng, {"a": doc + own(5)}, max_new=4)
    assert stats["serve_ssm_snapshots_total"] == 1
    assert eng.trie.snapshot_bytes == one
    assert get_registry().gauge("serve_ssm_snapshot_bytes") == one
    assert stats["decode_steps"] == 3
    assert stats["serve_ssm_slot_steps_total"] == 3
    assert stats["serve_kv_pages_live_total"] == 3 * 6
    # B, 22 tokens: no snapshot on its chain (A's is past the document),
    # so it restores nothing, cuts a chunk at the branch (16) and leaves
    # snapshots there and at 20; decode at 22, 23.
    _serve(eng, {"b": doc + own(6)}, max_new=3)
    assert stats["serve_ssm_restores_total"] == 0
    assert stats["serve_ssm_snapshots_total"] == 3
    assert stats["serve_ssm_slot_steps_total"] == 3 + 2
    assert stats["serve_kv_pages_live_total"] == 3 * 6 + 2 * 6
    # C, 23 tokens: restores the snapshot at 16 with the document's four
    # pages, prefills its own 7 in one chunk and leaves a snapshot at
    # 20; decodes at 23.
    hits, chunks = stats["prefix_hit_blocks"], stats["prefill_chunks"]
    _serve(eng, {"c": doc + own(7)}, max_new=2)
    assert stats["serve_ssm_restores_total"] == 1
    assert stats["serve_ssm_restored_tokens_total"] == 16
    assert stats["prefix_hit_blocks"] - hits == 4
    assert stats["prefill_chunks"] - chunks == 1
    assert stats["serve_ssm_snapshots_total"] == 4
    assert eng.trie.snapshot_bytes == 4 * one
    assert get_registry().gauge("serve_ssm_snapshot_bytes") == 4 * one
    assert stats["serve_ssm_slot_steps_total"] == 3 + 2 + 1
    assert stats["serve_kv_pages_live_total"] == 3 * 6 + 2 * 6 + 6
    assert stats["decode_steps"] == 6


# -- the vocabulary share ---------------------------------------------------
def test_the_sliced_vocabulary_is_the_uncut_models_leading_columns():
    """The chip's share of the vocabulary: the table's leading rows and
    the head's leading columns. On ids from the slice, the sliced
    program's logits are the uncut reference's leading columns."""
    whole = _init(TINY, seed=9)
    cut = dataclasses.replace(TINY, vocab_size=48)
    sliced = dict(whole)
    sliced["tok_embeddings"] = {
        "embedding": whole["tok_embeddings"]["embedding"][:48]
    }
    sliced["output"] = {"kernel": whole["output"]["kernel"][:, :48]}
    prompt = np.random.default_rng(3).integers(0, 48, 21)
    logits, _, _ = _prefill(_chunk_programs(cut), sliced, prompt, _plan(21))
    want = _reference_logits(whole, prompt, [20])[0]
    assert want.shape == (128,)
    assert _gap(np.asarray(logits), want[:48]) < TOL


# -- the count of parameters ------------------------------------------------
def _flops_bytes():
    path = os.path.join(ROOT, "benchmark", "flops_bytes_parallel_hybrid.py")
    spec = importlib.util.spec_from_file_location("fb_parallel", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("case", ["tiny", "published_cut"])
def test_the_benchmarks_count_is_the_programs(case):
    """``flops_bytes_parallel_hybrid.n_params`` (shapes alone, the head
    width from ``head_dim``) equals ``count_params`` and the init's
    own tree; the published cut is 2,388,952,448."""
    cfg = TINY if case == "tiny" else dataclasses.replace(
        hybrid.FALCON_H1_34B, n_layers=4, vocab_size=65280
    )
    counts = hybrid.count_params(cfg)
    assert _flops_bytes().n_params(arch_of(cfg)) == counts["total"]
    shapes = jax.eval_shape(
        lambda: hybrid.init_hybrid_ssm_moe(jax.random.key(0), cfg)
    )
    assert sum(a.size for a in jax.tree.leaves(shapes)) == counts["total"]
    if case == "published_cut":
        assert counts["total"] == 2_388_952_448
        assert counts["attention_per_layer"] == 31_457_280
        assert counts["ssm_per_layer"] == 68_351_072
        assert counts["feed_forward_per_layer"] == 330_301_440
        assert cfg.state_bytes() == 4 * (4096 * 256 + 3 * 5120) * 4


def test_the_benchmark_configuration_is_the_preset():
    """The cell's configuration file builds the published preset cut to
    four layers and the vocabulary slice, every size and multiplier
    held to ``published`` (or, for the two lists, to their entries)."""
    with open(os.path.join(
        ROOT, "benchmark", "configs", "falcon-h1-34b-instruct.json"
    )) as f:
        config = json.load(f)
    kwargs = dict(config["program"]["config_kwargs"])
    cfg = hybrid.HybridSSMMoEConfig(**kwargs, n_layers=4)
    want = dataclasses.replace(
        hybrid.FALCON_H1_34B, name=cfg.name, n_layers=4, vocab_size=65280,
        max_seq_len=cfg.max_seq_len,
    )
    assert cfg == want
    pub = config["published"]
    for k, v in config["arch"].items():
        assert getattr(cfg, k) == (65280 if k == "vocab_size" else pub[v]), k
    segments = ("z", "x", "b", "c", "dt")
    assert [getattr(cfg, f"ssm_{s}_multiplier") for s in segments] \
        == pub["ssm_multipliers"]
    assert [cfg.mlp_gate_multiplier, cfg.mlp_down_multiplier] \
        == pub["mlp_multipliers"]


# -- the stages ---------------------------------------------------------------
def test_a_parallel_layer_carries_its_scopes(params):
    """Both halves of a parallel layer land under the scopes the
    benchmark's readers know: the attention half's five, the mixer
    half's four, the feed-forward's ``mlp``."""
    fn = paging.make_paged_decode_fn(TINY, BLOCK, 12, 16)
    state = _fresh_state(slots=4)
    args = (jnp.zeros((4,), jnp.int32),
            jnp.zeros((len(paging.STEP_ROWS), 4), jnp.int32),
            jnp.zeros((4, 16), jnp.int32))
    text = jax.jit(fn).lower(params, *state, *args).as_text(debug_info=True)
    for scope in ("qkv", "kv_write", "kv_read", "attention", "attn_out",
                  "ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "mlp",
                  "embed", "head"):
        assert f"/{scope}/" in text, scope
    assert "router" not in text and "experts" not in text
    assert "cosine" in text and "sine" in text


# -- the row step's read: a walk of the page tables -------------------------
def test_the_decode_program_holds_the_walk_and_no_view(params, monkeypatch):
    """A walk in every layer (each keeps pages), and no gathered view."""
    from test_hybrid_ssm_moe import _holds_the_walk_and_no_view

    _holds_the_walk_and_no_view(
        TINY, params, _fresh_state(slots=4), monkeypatch
    )


def _shared_prompts(seed):
    """Two prompts on one prefix of three pages, one of its own, none a
    whole number of chunks."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, TINY.vocab_size, 3 * BLOCK).tolist()
    return [
        shared + rng.integers(0, TINY.vocab_size, 3).tolist(),
        shared + rng.integers(0, TINY.vocab_size, 9).tolist(),
        rng.integers(0, TINY.vocab_size, 11).tolist(),
    ]


def test_greedy_tokens_agree_with_the_gathered_form(
    params, mesh, monkeypatch
):
    """Three requests served together, two of them on one prefix: the
    walk and the gathered form emit the same tokens, and they are the
    reference's arg-max."""
    from test_hybrid_ssm_moe import _tokens_of_both_forms

    prompts = dict(zip("abc", _shared_prompts(5)))
    walked, gathered = _tokens_of_both_forms(
        lambda: _engine(params, mesh), _serve, prompts, monkeypatch
    )
    assert walked == gathered
    for rid, prompt in prompts.items():
        got = walked[rid]
        assert _emitted_from(params, prompt, got).argmax(-1).tolist() == got


def test_the_engine_counts_what_its_walks_read(engine):
    from test_hybrid_ssm_moe import _counts_what_its_walks_read

    _counts_what_its_walks_read(engine, _shared_prompts(7)[:2])


def test_the_decode_step_packs_no_expert_count():
    assert paging.step_counters(TINY) == ()
    assert hybrid.has_experts(hybrid.GRANITE_4_0_H_SMALL)
    assert not hybrid.has_experts(TINY)


# -- who refuses it, by name ----------------------------------------------
def _refusals(params, mesh):
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.serve import disagg, spec, tier
    from tpu_hpc.train.trainer import Trainer

    def paged(**kw):
        return lambda: PagedEngine(
            params, TINY, SERVE, mesh,
            PagedConfig(block_size=BLOCK, num_blocks=73, **kw),
        )

    def tensor_axis():
        devices = np.array(jax.devices()[:2]).reshape(1, 2)
        PagedEngine(
            params, TINY, SERVE, Mesh(devices, ("data", "model")),
            PagedConfig(block_size=BLOCK, num_blocks=73),
        )

    return {
        "trainer": lambda: Trainer(
            TrainingConfig(), mesh, lambda *a: None, params
        ),
        "make_forward": lambda: llama2.make_forward(TINY),
        "init_llama": lambda: llama2.init_llama(jax.random.key(0), TINY),
        "llama_pp": lambda: llama_pp.layers_per_stage(TINY, 2),
        "slab_engine": lambda: Engine(params, TINY, SERVE, mesh),
        "spec": lambda: spec.SpecRunner(
            _engine(params, mesh), spec.SpecConfig("ngram")
        ),
        "tier": lambda: tier.HostTier(_engine(params, mesh)),
        "disagg": lambda: disagg.DisaggEngine(
            params, TINY, SERVE, Mesh(np.array(jax.devices()[:1]), ("data",)),
            Mesh(np.array(jax.devices()[1:2]), ("data",)),
        ),
        "pallas": paged(kernel="pallas"),
        "int8": paged(kv_quant="int8"),
        "tensor_axis": tensor_axis,
    }


@pytest.mark.parametrize("who", [
    "trainer", "make_forward", "init_llama", "llama_pp", "slab_engine",
    "spec", "tier", "disagg", "pallas", "int8", "tensor_axis",
])
def test_every_other_path_refuses_it_by_name(params, mesh, who):
    """Never a silent run of another decoder on these weights; the
    error says what the layers keep."""
    if who == "pallas":
        # The option that picked that read is refused before an engine
        # sees the configuration: the read was removed.
        with pytest.raises(ValueError, match="kernel must be 'gather'"):
            _refusals(params, mesh)[who]()
        return
    with pytest.raises(NotImplementedError,
                       match="(tiny-parallel|falcon-h1-34b).*side by side"):
        _refusals(params, mesh)[who]()


def test_the_flat_read_and_bad_sizes_are_refused():
    with pytest.raises(ValueError, match="state-space"):
        paging.make_paged_decode_fn(TINY, BLOCK, 12, 16, flat_pages=18)
    with pytest.raises(ValueError, match="ssm_groups"):
        dataclasses.replace(TINY, ssm_groups=3)
    with pytest.raises(ValueError, match="ssm_d_ssm"):
        dataclasses.replace(TINY, ssm_d_ssm=128)
    with pytest.raises(ValueError, match="mlp_hidden"):
        dataclasses.replace(TINY, mlp_hidden=0)
    with pytest.raises(ValueError, match="rope_half"):
        dataclasses.replace(TINY, position_embedding="rope")


# -- the command line -------------------------------------------------------
def test_the_command_line_names_the_published_preset(monkeypatch):
    """``python -m tpu_hpc.serve --model falcon-h1-34b`` builds the
    published configuration (stopped where the mesh is built: 34B
    parameters are no CPU's)."""
    from tpu_hpc.serve import server

    class Built(Exception):
        pass

    def stop(n_devices, cfg):
        raise Built(cfg)

    monkeypatch.setattr(server, "build_serving_mesh", stop)
    with pytest.raises(Built) as built:
        server.main(["--model", "falcon-h1-34b", "--paged",
                     "--prefill-chunk", "16", "--buckets", "16"])
    cfg = built.value.args[0]
    assert cfg is hybrid.FALCON_H1_34B
    assert (cfg.n_layers, cfg.dim, cfg.head_dim, cfg.vocab_size) \
        == (72, 5120, 128, 261120)
    assert set(cfg.layer_types) == {"parallel"}


def test_the_command_line_serves_the_tiny_preset(capsys):
    """The same path end to end at the tiny preset's size: the summary
    carries the recurrent state's counters and no recompile."""
    from tpu_hpc.serve import server

    rc = server.main([
        "--model", "falcon-h1-tiny", "--paged", "--kv-block-size", "4",
        "--prefill-chunk", "8", "--requests", "4", "--max-new", "3",
        "--slots", "2", "--buckets", "8,16", "--prompt-lens", "3,6,20",
        "--max-seq-len", "32",
    ])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert summary["recompiles"] == 0
    assert summary["tokens"] == 12
