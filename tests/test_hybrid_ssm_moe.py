"""The decoder of state-space and attention layers over an expert
feed-forward (``models/hybrid_ssm_moe.py``: granite-4.0-h-small)
through the paged server, against its plain reference
(``benchmark/reference/hybrid_ssm_moe_decoder.py``: the recurrence
token by token), at a tiny size with every kind of layer present, on
the CPU: counts and agreement, never a time.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.reference import hybrid_ssm_moe_decoder as reference
from tpu_hpc.models import hybrid_ssm_moe as hybrid
from tpu_hpc.models import llama2, llama_pp, sparse_moe
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

# Five of eight experts held: every path below also runs the share.
HELD = (0, 1, 2, 5, 6)
TINY = hybrid.HybridSSMMoEConfig(
    name="tiny-hybrid", dim=64, n_layers=4,
    layer_types=("mamba", "mamba", "attention", "mamba"),
    n_heads=4, n_kv_heads=2, vocab_size=128, max_seq_len=96,
    attention_multiplier=1 / 16, ssm_heads=8, ssm_head_dim=16,
    ssm_state=16, ssm_chunk=8, n_experts=8, experts_per_token=3,
    expert_hidden=32, shared_hidden=48, held_experts=HELD,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
ARCH = dict(
    dim=64, n_layers=4, n_heads=4, n_kv_heads=2, norm_eps=TINY.norm_eps,
    attention_multiplier=1 / 16, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=16.0, ssm_heads=8,
    ssm_head_dim=16, ssm_state=16, ssm_conv=4, n_experts=8,
    experts_per_token=3, held_experts=HELD,
)
SERVE = ServeConfig(slots=3, max_seq_len=96, prefill_buckets=(8, 16))
BLOCK, PER_SEQ, WIDTH = 4, 24, 28


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: hybrid.init_hybrid_ssm_moe(k, TINY))(
        jax.random.key(3)
    )


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


def _serve(eng, prompts, max_new=12):
    batcher = ContinuousBatcher(eng)
    for rid, prompt in prompts.items():
        batcher.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    return batcher.run()


def _reference_logits(params, tokens, rows, pad=64):
    padded = np.zeros(pad, np.int32)
    padded[:len(tokens)] = tokens
    hidden, _ = reference.forward(params, jnp.asarray(padded), ARCH,
                                  q_block=16)
    return np.asarray(reference.logits(params, hidden[np.asarray(rows)],
                                       ARCH))


def _emitted_from(params, prompt, emitted):
    """The reference's logits at every position the server emitted
    from: [len(emitted), vocab]."""
    n = len(prompt)
    return _reference_logits(
        params, list(prompt) + list(emitted[:-1]),
        n - 1 + np.arange(len(emitted)),
    )


def _close(got, want, rel):
    """``got`` against ``want`` to ``rel`` of the logits' own size."""
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


# -- the two forms of the mixer -------------------------------------------
def _rows(seed, n):
    rng = np.random.default_rng(seed)
    h, p, s = TINY.ssm_heads, TINY.ssm_head_dim, TINY.ssm_state

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return dict(
        x=draw(n, h, p), dt=jax.nn.softplus(draw(n, h) - 2.0),
        a=-jnp.exp(draw(h)), b=draw(n, s), c=draw(n, s),
        state=draw(h, p, s),
    )


@pytest.mark.parametrize("n,block", [(8, 8), (16, 8), (16, 4), (16, 16)])
def test_the_chunked_form_is_the_one_step_form_over_the_same_rows(n, block):
    """(b) ``scan_chunk`` (blocks of ``block`` rows, the state carried
    between them and in from the caller) against ``scan_step`` row by
    row: the same ``y`` and the same final state, in another order of
    float32 sums (1e-5 of their size)."""
    r = _rows(n, n)
    y, after, snap = hybrid.scan_chunk(
        r["x"], r["dt"], r["a"], r["b"], r["c"], r["state"], block, 5
    )
    state, ys, at5 = r["state"][None], [], None
    for t in range(n):
        out, state = hybrid.scan_step(
            r["x"][t][None], r["dt"][t][None], r["a"], r["b"][t][None],
            r["c"][t][None], state,
        )
        ys.append(out[0])
        if t == 4:
            at5 = state[0]
    _close(np.asarray(y), np.asarray(jnp.stack(ys)), 1e-5)
    _close(np.asarray(after), np.asarray(state[0]), 1e-5)
    _close(np.asarray(snap), np.asarray(at5), 1e-5)


def test_the_chunked_convolution_is_the_one_step_one(params):
    """(b) ``conv_chunk`` over a run behind kept rows against
    ``conv_step`` row by row, and the rows each keeps."""
    lp = params["layers_0"]
    rng = np.random.default_rng(0)
    xbc = jnp.asarray(rng.standard_normal((8, TINY.conv_dim)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((3, TINY.conv_dim)), jnp.float32)
    out, kept, at5 = hybrid.conv_chunk(xbc, rows, lp, 8, 5)
    step_rows, outs = rows[None], []
    for t in range(8):
        o, step_rows = hybrid.conv_step(xbc[t][None], step_rows, lp)
        outs.append(o[0])
        if t == 4:
            np.testing.assert_array_equal(at5, step_rows[0])
    np.testing.assert_allclose(out, jnp.stack(outs), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(kept, step_rows[0])
    np.testing.assert_array_equal(kept, xbc[5:])


def test_a_short_run_keeps_rows_from_before_it(params):
    """Two real rows of a bucket of eight: the kept rows are the last
    THREE real ones, one of them from before the run."""
    lp = params["layers_0"]
    xbc = jnp.arange(8.0)[:, None] * jnp.ones((1, TINY.conv_dim)) + 10
    rows = jnp.arange(3.0)[:, None] * jnp.ones((1, TINY.conv_dim))
    _, kept, _ = hybrid.conv_chunk(xbc, rows, lp, 2, 0)
    assert kept[:, 0].tolist() == [2.0, 10.0, 11.0]


# -- program against reference --------------------------------------------
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


def _prefill(programs, params, prompt, plan, slot=1, snap_at=0, state=None):
    """``plan``: ``(start, run, bucket)`` chunks over ``prompt`` ->
    (the last chunk's logits, the state arrays, the snapshot the chunk
    holding ``snap_at`` rows kept)."""
    state = state or _fresh_state()
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


@pytest.mark.parametrize("plan", [
    [(0, 8, 8)],                                    # fills its bucket
    [(0, 5, 8)],                                    # does not
    [(0, 16, 16), (16, 16, 16), (32, 3, 8)],        # state through chunks
    [(0, 16, 16), (16, 11, 16)],                    # a padded second block
    [(0, 8, 8), (8, 8, 8), (16, 8, 8), (24, 6, 8)],
])
def test_chunk_logits_agree_with_the_reference(params, plan):
    """(a) The chunk programs' logits row as numbers: a prompt
    prefilled in chunks (the chunked recurrence, its state and
    convolution rows carried from chunk to chunk, the pages of the one
    attention layer) against the reference's token-by-token scan over
    the whole prompt. Float32 on both sides, another order of sums:
    1e-4 of the logits' size."""
    n = plan[-1][0] + plan[-1][1]
    prompt = np.random.default_rng(n).integers(0, TINY.vocab_size, n)
    logits, _, _ = _prefill(_chunk_programs(), params, prompt, plan)
    want = _reference_logits(params, prompt, [n - 1])[0]
    _close(np.asarray(logits), want, 1e-4)


@pytest.mark.parametrize("run,bucket", [(5, 8), (5, 16), (8, 16), (13, 16)])
def test_a_padded_bucket_leaves_the_state_the_unpadded_run_does(
    params, run, bucket
):
    """(c) ``run`` real rows in a bucket with padded rows behind them
    against the same rows one at a time through the one-step form (no
    padding at all): the state and the convolution rows the chunk
    leaves are those of the real rows alone (to 1e-5 of the state's
    size: two forms of the recurrence)."""
    prompt = np.random.default_rng(run).integers(0, TINY.vocab_size, run)
    _, padded, _ = _prefill(
        _chunk_programs(), params, prompt, [(0, run, bucket)]
    )
    state = _fresh_state()
    tables = jnp.zeros((3, WIDTH), jnp.int32).at[1].set(
        jnp.arange(1, WIDTH + 1)
    )
    step = jax.jit(_decode_logits)
    for t in range(run):
        _, state = step(
            params, state, jnp.full((3,), prompt[t], jnp.int32),
            jnp.full((3,), t, jnp.int32), jnp.asarray([0, 1, 0]), tables,
        )
    for got, want in zip(padded[2:], state[2:]):
        _close(np.asarray(got[:, 1]), np.asarray(want[:, 1]), 1e-5)
        assert not np.asarray(got[:, 0]).any()    # no other slot's moved


def _decode_logits(params, state, tokens, pos, active, tables):
    """The decode program's layer loop with the logits for a result
    (the program returns their arg-max): every slot, one row."""
    pool = paging.PagedAttention(TINY, BLOCK, PER_SEQ).on(*state[:2])
    recur = paging.RecurrentState(TINY).on(*state[2:])
    slots = tokens.shape[0]
    x = decoder._embed(params, tokens[:, None], TINY)
    mask = (jnp.arange(PER_SEQ * BLOCK)[None, :] <= pos[:, None])[
        :, None, None, None, :
    ]
    pb = jnp.where(active > 0, tables[jnp.arange(slots), pos // BLOCK], 0)
    pool.view(tables, pos, active)
    pool.rows(pb, pos % BLOCK, mask, slot=jnp.arange(slots))
    recur.rows(active)
    x, _ = decoder.decoder_layers(
        params, TINY, x, None, None, pool, weight=active, recur=recur
    )
    return decoder._logits_head(x, params, TINY)[:, 0], \
        [*pool.state()[:2], *recur.state()]


def test_decode_logits_agree_with_the_reference(params):
    """(a) Paged decode as numbers: a prompt prefilled in chunks, then
    eight rows one at a time through the one-step form and the page
    pool, against the reference's one forward pass. 1e-4 of the logits'
    size."""
    tokens = np.random.default_rng(7).integers(0, TINY.vocab_size, 27)
    _, state, _ = _prefill(
        _chunk_programs(), params, tokens, [(0, 16, 16), (16, 3, 8)]
    )
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
        _close(np.asarray(logits[1]), want[j], 1e-4)


@pytest.mark.parametrize("prompt_len", [5, 14, 23, 40])
def test_paged_decode_agrees_with_the_references_full_forward(
    params, engine, prompt_len
):
    """(a) Through ``ContinuousBatcher`` and ``PagedEngine`` with one
    step in flight: every emitted token is the reference's arg-max
    (float32 on both sides: the two differ by 1e-5 of a logit, where
    the two best logits of these seeds lie further apart)."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, TINY.vocab_size, prompt_len).tolist()
    before = engine.paged_stats["serve_decode_overlapped_total"]
    emitted = _serve(engine, {"r": prompt})["r"]
    assert engine.paged_stats["serve_decode_overlapped_total"] > before
    logits = _emitted_from(params, prompt, emitted)
    assert logits.argmax(-1).tolist() == list(emitted)


def test_bf16_products_stay_within_the_regret_they_can_cost(params, mesh):
    """(a) The same weights served with bfloat16 products (the state
    and the residual stream still float32): an emitted token may differ
    from the reference's arg-max where two logits lie within bf16's
    rounding of the products that made them, so the measure is the
    benchmark's: the reference's best logit minus the emitted token's,
    in standard deviations of the row. bf16 keeps 8 bits: each product
    is off by up to 2 ** -9 of its size, a logit is a sum of such over
    four layers, and a flipped near-tie costs what that sum is off by:
    a few hundredths of a sigma. 0.15 sigma is five times the largest
    this seed reads (0.03) and a tenth of what a wrong row reads
    (test_a_snapshot... reads > 1 where the state is another
    request's)."""
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    eng = _engine(params, mesh, cfg)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, TINY.vocab_size, 30).tolist()
    emitted = _serve(eng, {"r": prompt})["r"]
    logits = _emitted_from(params, prompt, emitted)
    regret = logits.max(-1) - logits[np.arange(len(emitted)), emitted]
    assert (regret / logits.std(-1)).max() < 0.15


# -- what a decode step may not touch -------------------------------------
def test_a_decode_step_leaves_other_slots_state_bit_for_bit(params, mesh):
    """(d) Slot 0 decodes; slot 1 is in the middle of its prompt (one
    chunk of three run), slot 2 is free: the step advances slot 0's
    state and leaves the other two's as they were, bit for bit."""
    eng = _engine(params, mesh)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, TINY.vocab_size, n).tolist() for n in (9, 40)]
    eng.admit(0, prompts[0], 8)
    first = None
    while first is None:
        first = eng.prefill_step(0)
    eng.admit(1, prompts[1], 8)
    assert eng.prefill_step(1) is None                # mid-prefill
    before = [np.asarray(a) for a in (eng.ssm_s, eng.ssm_conv)]
    steps = eng.paged_stats["serve_ssm_slot_steps_total"]
    eng.decode_now([first, 0, 0], [9, 0, 0], [True, False, False])
    assert eng.paged_stats["serve_ssm_slot_steps_total"] == steps + 1
    for was, now in zip(before, (eng.ssm_s, eng.ssm_conv)):
        now = np.asarray(now)
        np.testing.assert_array_equal(was[:, 1:], now[:, 1:])
        assert (was[:, 0] != now[:, 0]).any()
    # ... and slot 1's prompt finishes as if nothing had run between.
    while eng.prefill_step(1) is None:
        pass
    alone = _engine(params, mesh)
    alone.admit(1, prompts[1], 8)
    while alone.prefill_step(1) is None:
        pass
    for a, b in ((eng.ssm_s, alone.ssm_s), (eng.ssm_conv, alone.ssm_conv)):
        np.testing.assert_array_equal(np.asarray(a)[:, 1], np.asarray(b)[:, 1])


# -- snapshots in the prefix trie -----------------------------------------
def _first_token(eng, slot, prompt, max_new=4):
    info = eng.admit(slot, prompt, max_new)
    tok = None
    while tok is None:
        tok = eng.prefill_step(slot)
    return info, tok


def test_a_request_admitted_on_a_snapshot_is_the_request_prefilled_whole(
    params, mesh
):
    """(e) A prompt whose first 36 tokens another request left in the
    trie WITH the state at token 36: it shares nine pages, restores the
    snapshot, prefills the rest, and emits what the same prompt emits
    on an engine that has seen nothing (the reference's arg-max)."""
    rng = np.random.default_rng(5)
    doc = rng.integers(0, TINY.vocab_size, 36).tolist()
    tails = [rng.integers(0, TINY.vocab_size, n).tolist() for n in (7, 10)]
    eng = _engine(params, mesh)
    _serve(eng, {"first": doc + tails[0]})
    stats = eng.paged_stats
    assert stats["serve_ssm_snapshots_total"] == 1    # at token 40
    # The first 40 tokens are shared as PAGES, but the only snapshot on
    # that chain is at 40, beyond the 36 the second prompt shares...
    got = _serve(eng, {"second": doc + tails[1]})["second"]
    fresh = _serve(_engine(params, mesh), {"second": doc + tails[1]})
    assert got == fresh["second"]
    logits = _emitted_from(params, doc + tails[1], got)
    assert logits.argmax(-1).tolist() == got
    # ... so it shared nothing, left a snapshot at the branch (36) and
    # one at its own last full block (44); the third restores at 36.
    assert stats["prefix_hit_blocks"] == 0
    assert stats["serve_ssm_snapshots_total"] == 3
    assert stats["serve_ssm_restores_total"] == 0
    tail = rng.integers(0, TINY.vocab_size, 9).tolist()
    third = _serve(eng, {"third": doc + tail})["third"]
    assert stats["serve_ssm_restores_total"] == 1
    assert stats["serve_ssm_restored_tokens_total"] == 36
    assert stats["prefix_hit_blocks"] == 9
    logits = _emitted_from(params, doc + tail, third)
    assert logits.argmax(-1).tolist() == third


def test_a_page_match_deeper_than_any_snapshot_is_cut_back(params, mesh):
    """(e) The chunk plan of a prompt whose pages match deeper than its
    deepest snapshot ends a chunk where the match ends, and the state
    there goes into the trie: the branch point is found without a
    warm-up."""
    rng = np.random.default_rng(9)
    doc = rng.integers(0, TINY.vocab_size, 24).tolist()
    eng = _engine(params, mesh)
    _first_token(eng, 0, doc + rng.integers(0, 128, 21).tolist())
    assert eng.trie.deepest_snapshot(doc, 6) == (0, None)
    info, _ = _first_token(eng, 1, doc + rng.integers(0, 128, 30).tolist())
    assert info["shared_blocks"] == 0 and info["chunks"] == 4
    assert [c[:2] for c in eng.slot_state(1).plan] == [
        (0, 16), (16, 8), (24, 16), (40, 14),
    ]
    depth, snapshot = eng.trie.deepest_snapshot(doc, 6)
    assert depth == 6 and snapshot.cost == 24
    info, _ = _first_token(eng, 2, doc + rng.integers(0, 128, 5).tolist())
    assert info["shared_tokens"] == 24 and info["chunks"] == 1
    assert eng.slot_state(2).restored == 24


def test_the_restored_state_is_the_snapshots(params, mesh):
    """(e) The snapshot a chunk keeps at a block boundary INSIDE it is
    the state a prefill that stops there leaves, and a restore puts it
    into the new slot's rows."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, TINY.vocab_size, 30).tolist()
    eng = _engine(params, mesh)
    _first_token(eng, 0, prompt)                      # snapshot at 28
    stop = _engine(params, mesh)
    _first_token(stop, 0, prompt[:28])                # ends at 28
    eng.release(0)
    info, _ = _first_token(eng, 2, prompt[:28] + [1, 2, 3])
    assert info["shared_tokens"] == 28
    _, snapshot = eng.trie.deepest_snapshot(prompt, 7)
    for snap, whole in zip(snapshot.state, (stop.ssm_s, stop.ssm_conv)):
        np.testing.assert_allclose(
            snap, np.asarray(whole)[:, 0], rtol=0, atol=2e-6
        )


def test_snapshots_live_under_their_budget(params, mesh):
    """(e) A budget of two snapshots: the third drops the one worth
    least, which is the cheapest to rebuild, not the oldest; a budget
    of none takes none, and a node's snapshot dies with the node."""
    one = TINY.state_bytes()
    eng = _engine(params, mesh, ssm_snapshot_bytes=2 * one)
    rng = np.random.default_rng(6)
    doc = rng.integers(0, TINY.vocab_size, 40).tolist()
    _first_token(eng, 0, doc)                         # cost 40
    eng.release(0)
    for k in range(2):                                # cost 4 each
        _first_token(eng, 0, doc + rng.integers(0, 128, 5).tolist())
        eng.release(0)
    stats = eng.paged_stats
    assert stats["serve_ssm_snapshots_total"] == 3
    assert stats["serve_ssm_snapshot_evictions_total"] == 1
    assert eng.trie.snapshot_bytes == 2 * one
    assert eng.trie.deepest_snapshot(doc, 10)[0] == 10    # the document's
    assert stats["serve_ssm_restores_total"] == 2
    freed = eng.trie.evict(eng.allocator, 10 ** 6)
    assert freed and eng.trie.snapshot_bytes == 0
    none = _engine(params, mesh, ssm_snapshot_bytes=0)
    _first_token(none, 0, doc)
    assert none.paged_stats["serve_ssm_snapshots_total"] == 0
    none.release(0)
    info, _ = _first_token(none, 0, doc + [1, 2])
    assert info["shared_tokens"] == 0


def test_a_dense_configuration_sees_no_snapshot(mesh):
    """A configuration with no state-space layer: the trie's budget is
    0, a hit is as deep as its pages, no state array exists."""
    cfg = llama2.LlamaConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
        multiple_of=16, max_seq_len=96, dtype=jnp.float32,
    )
    eng = PagedEngine(
        llama2.init_llama(jax.random.key(0), cfg), cfg, SERVE, mesh,
        PagedConfig(block_size=BLOCK, num_blocks=73, prefill_chunk=16),
    )
    prompt = list(range(30))
    _first_token(eng, 0, prompt)
    info, _ = _first_token(eng, 1, prompt[:28] + [7, 8, 9])
    assert info["shared_tokens"] == 28
    assert eng.trie.snapshot_budget == 0 and eng.ssm_s is None
    assert len(eng._state()) == 2


# -- the share and the tied table -----------------------------------------
def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(params):
    """(f) Four chips share a layer's eight experts, two each: each
    computes its own experts' routed part over ALL the router's
    outputs, and the four parts plus the shared expert ONCE are the
    uncut reference's layer output."""
    whole = dataclasses.replace(TINY, held_experts=None)
    full = jax.jit(lambda k: hybrid.init_hybrid_ssm_moe(k, whole))(
        jax.random.key(3)
    )["layers_1"]
    h = jnp.asarray(
        np.random.default_rng(0).standard_normal((10, 64)), jnp.float32
    )
    want = reference.feed_forward(
        h, full, dict(ARCH, held_experts=None)
    )
    total = decoder._mlp(h, full["moe"]["shared"], whole)
    for held in ((0, 1), (2, 3), (4, 5), (6, 7)):
        cfg = dataclasses.replace(TINY, held_experts=held)
        lp = {"moe": {
            **full["moe"],
            **{w: full["moe"][w][jnp.asarray(held)]
               for w in ("w1", "w3", "w2")},
        }}
        gates, experts = sparse_moe.route(h, lp, cfg)
        part, counts = sparse_moe.expert_ffn(h, gates, experts, lp, cfg)
        assert int(counts["dropped"]) == 0
        total = total + part
    _close(np.asarray(total), np.asarray(want), 1e-5)


@pytest.mark.parametrize("case", [
    "two_rows", "two_rows_one_inactive", "three_rows", "ten_rows",
])
def test_the_expert_product_reads_by_the_shape_of_the_step(
    monkeypatch, params, case
):
    """Three rows x three experts a token cover the eight experts (9 >=
    8): the product reads its whole share, as Granite's decode step
    does (160 >= 72). Two rows (6 < 8) visit the held experts they
    touched, and give the same rows and the same counts."""
    lp = params["layers_1"]
    rows = {"two_rows": 2, "two_rows_one_inactive": 2, "three_rows": 3,
            "ten_rows": 10}[case]
    h = jnp.asarray(
        np.random.default_rng(4).standard_normal((rows, 64)), jnp.float32
    )
    weight = jnp.asarray([0, 1]) if case == "two_rows_one_inactive" \
        else None
    gates, experts = sparse_moe.route(h, lp, TINY)
    got, counts = sparse_moe.expert_ffn(h, gates, experts, lp, TINY, weight)
    with monkeypatch.context() as m:
        m.setattr(sparse_moe, "grouped_by_shape", lambda *_: False)
        dense, dense_counts = sparse_moe.expert_ffn(
            h, gates, experts, lp, TINY, weight
        )
    jaxpr = str(jax.make_jaxpr(
        lambda h, g, e: sparse_moe.expert_ffn(h, g, e, lp, TINY, weight)
    )(h, gates, experts))
    assert ("pallas_call" in jaxpr) == (rows == 2)
    counted = np.ones(rows, bool) if weight is None \
        else np.asarray(weight, bool)
    _close(np.asarray(got)[counted], np.asarray(dense)[counted], 1e-5)
    assert {k: int(v) for k, v in counts.items()} \
        == {k: int(v) for k, v in dense_counts.items()}
    assert int(counts["dropped"]) == 0


def test_a_step_that_covers_its_share_reads_every_held_expert(params, mesh):
    """Three slots x three >= eight experts: the decode program keeps
    the whole-stack product, and the host counts every held expert of
    every layer read a step (``moe_experts_read_pct.serve`` 100)."""
    eng = _engine(params, mesh, prefix_cache=False)
    rng = np.random.default_rng(29)
    _serve(eng, {"a": rng.integers(0, TINY.vocab_size, 9).tolist()},
           max_new=4)
    stats = eng.paged_stats
    assert stats["decode_steps"] == 3
    assert stats["serve_moe_experts_read_total"] \
        == 3 * len(HELD) * TINY.n_layers
    assert 0 < stats["serve_moe_experts_touched_total"] \
        <= stats["serve_moe_experts_read_total"]


def test_the_router_is_the_softmax_over_the_chosen_logits(params):
    lp = params["layers_0"]
    h = jnp.asarray(
        np.random.default_rng(1).standard_normal((6, 64)), jnp.float32
    )
    gates, experts = sparse_moe.route(h, lp, TINY)
    want, chosen = reference.router(h, lp["moe"], ARCH)
    np.testing.assert_array_equal(experts, chosen)
    np.testing.assert_allclose(
        gates, jnp.take_along_axis(want, chosen, axis=-1), rtol=1e-6
    )


def test_one_table_is_both_ends(params, engine):
    """(g) No ``output`` matrix exists: the head contracts the
    embedding table's second axis and divides by ``logits_scaling``;
    the embedding is the row times ``embedding_multiplier``."""
    assert "output" not in params and "output" not in engine.params
    table = params["tok_embeddings"]["embedding"]
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((2, 1, 64)), jnp.float32
    )
    normed = decoder._rmsnorm(x, params["norm"]["scale"], TINY.norm_eps)
    np.testing.assert_allclose(
        decoder._logits_head(x, params, TINY),
        jnp.einsum("bsd,vd->bsv", normed, table) / 16.0, rtol=1e-5,
    )
    tokens = jnp.asarray([[3, 9]])
    np.testing.assert_array_equal(
        decoder._embed(params, tokens, TINY), 12.0 * table[tokens]
    )
    text = jax.jit(
        lambda p, x: decoder._logits_head(x, p, TINY)
    ).lower(params, x).as_text()
    assert "transpose" not in text


def test_the_published_cut_counts_its_parameters():
    cut = hybrid.HybridSSMMoEConfig(
        n_layers=10, held_experts=tuple(range(18))
    )
    counts = hybrid.count_params(cut)
    assert counts["total"] == 3_264_039_552
    assert counts["ssm_per_layer"] == 102_286_976
    assert counts["attention_per_layer"] == 41_943_040
    assert cut.n_ssm_layers == 9 and cut.n_attention_layers == 1
    assert cut.state_bytes() == 9 * (128 * 64 * 128 + 3 * 8448) * 4
    shapes = jax.eval_shape(
        lambda: hybrid.init_hybrid_ssm_moe(jax.random.key(0), cut)
    )
    assert sum(a.size for a in jax.tree.leaves(shapes)) == counts["total"]
    assert hybrid.GRANITE_4_0_H_SMALL.layer_types.count("attention") == 4


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

    def spec_runner():
        spec.SpecRunner(_engine(params, mesh), spec.SpecConfig("ngram"))

    def host_tier():
        tier.HostTier(_engine(params, mesh))

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
        "spec": spec_runner,
        "tier": host_tier,
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
    """(h) Never a silent run of another decoder on these weights."""
    with pytest.raises(NotImplementedError, match="tiny-hybrid|granite"):
        _refusals(params, mesh)[who]()


def test_the_flat_read_and_bad_sizes_are_refused():
    with pytest.raises(ValueError, match="state-space"):
        paging.make_paged_decode_fn(TINY, BLOCK, 12, 16, flat_pages=18)
    with pytest.raises(ValueError, match="ssm_groups"):
        dataclasses.replace(TINY, ssm_groups=2)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, n_layers=5)
    with pytest.raises(ValueError, match="nope"):
        dataclasses.replace(TINY, position_embedding="rope")
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        dataclasses.replace(TINY, tie_word_embeddings=False)


# -- the programs as they lower -------------------------------------------
# sha256[:16] of ``lowered.as_text()`` of this decoder's two programs at
# this file's tiny size, as the PR that brought them left them (PR 33).
# A PR that MEANS to change one re-pins it and says so.
PROGRAM_DIGESTS = {
    "decode": "6f474bd72d54ae74",
    "prefill": "595b050c7836eb2d",
}


def _program_text(program):
    weights = jax.eval_shape(
        lambda: hybrid.init_hybrid_ssm_moe(jax.random.key(0), TINY)
    )
    abstract = jax.ShapeDtypeStruct
    state = tuple(abstract(a.shape, a.dtype) for a in _fresh_state(slots=4))
    i32 = jnp.int32
    if program == "prefill":
        fn = paging.make_chunk_prefill_fn(TINY, 8, BLOCK, 12, 16)
        args = (abstract((1, 8), i32), abstract((), i32), abstract((), i32),
                abstract((16,), i32), abstract((), i32), abstract((), i32))
    else:
        fn = paging.make_paged_decode_fn(TINY, BLOCK, 12, 16)
        args = (abstract((4 + len(paging.LATENT_COUNTERS),), i32),
                abstract((len(paging.STEP_ROWS), 4), i32),
                abstract((4, 16), i32))
    return jax.jit(fn).lower(weights, *state, *args).as_text()


@pytest.mark.parametrize("name", sorted(PROGRAM_DIGESTS))
def test_the_programs_lower_to_the_pinned_text(name):
    text = _program_text(name)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PROGRAM_DIGESTS[name]


@pytest.mark.parametrize("pins,name", [
    ("test_sparse_moe", "gqa-decode-gather-none"),
    ("test_sparse_moe", "gqa-prefill-gather-none"),
    ("test_sparse_moe", "mha-flat18-gather-none"),
    ("test_latent_moe", "decode"),
    ("test_latent_moe", "prefill"),
])
def test_the_other_configurations_programs_did_not_move(pins, name, mesh):
    """(i) A dense and a latent configuration's programs lower to the
    text their own files pin (every multiplier this decoder brought
    defaults to 1 and is not applied when 1; the score scale, the
    rotation and the untied head are theirs as they were)."""
    import importlib

    module = importlib.import_module(pins)
    text = module._program_text(mesh, *name.split("-")) \
        if pins == "test_sparse_moe" else module._program_text(name)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == module.PROGRAM_DIGESTS[name]


def test_the_decode_program_rotates_nothing():
    """NoPE: no sine or cosine reaches the lowered programs."""
    for program in PROGRAM_DIGESTS:
        text = _program_text(program)
        assert "cosine" not in text and "sine" not in text


def test_a_state_space_layer_carries_its_scopes(params):
    """The four stage names of a state-space layer are on its
    operations, in place of an attention layer's, and no other scope
    wraps them."""
    fn = paging.make_paged_decode_fn(TINY, BLOCK, 12, 16)
    state = _fresh_state(slots=4)
    args = (jnp.zeros((4 + len(paging.LATENT_COUNTERS),), jnp.int32),
            jnp.zeros((len(paging.STEP_ROWS), 4), jnp.int32),
            jnp.zeros((4, 16), jnp.int32))
    text = jax.jit(fn).lower(params, *state, *args).as_text(debug_info=True)
    for scope in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "qkv",
                  "kv_read", "attention", "attn_out", "router", "experts",
                  "mlp", "embed", "head"):
        assert f"/{scope}/" in text, scope
    assert "ssm_in/ssm" not in text and "ssm_scan/ssm" not in text
