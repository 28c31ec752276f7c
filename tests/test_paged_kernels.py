"""Pallas paged-attention kernels (kernels/paged_attention.py): direct
kernel parity, int8 page quantization, and the engine-level contract.

Five invariant families:
  * **direct kernel parity** -- property-style: random block tables
    (ragged lengths, pages recycled across slots, inactive slots, dead
    table entries pointing at a NaN-poisoned page) through
    ``paged_decode_attention`` / ``paged_prefill_attention`` in
    interpret mode match a dense gather-softmax reference. The poison
    page proves the scalar-prefetch index map redirects every dead
    read to the scratch page -- if the kernel touched it, NaN leaks.
  * **int8 quantization** -- per-page quantize/dequantize round trip
    bounded by half a scale step, the all-zero-page scale floor, and
    kernel-side in-register dequant matching the dequantized-pool
    reference exactly (same math, different read path).
  * **bounded divergence** -- the deterministic ``int8_logit_rmse``
    probe at TINY's attention dims stays under the pinned tolerance,
    and greedy decode through an int8 pool is token-exact across
    kernels (pallas vs gather on the SAME quantized pool -- the kernel
    contract) and vs the fp oracle at this scale.
  * **engine token exactness + compile discipline** -- a churn mix
    (more requests than slots, a fully-cached prompt, a shared-prefix
    CoW divergence, a chunk-stride crosser) through kernel="pallas"
    matches kernel="gather" token for token and the no-cache oracle,
    with ZERO new executables after warmup.
  * **sweep** (``-m kernels``, slow) -- the block-size x dtype grid;
    tier-1 keeps the (block_size=4, float32) representative per
    kernel family above.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hpc.kernels.attention import pick_block_sizes
from tpu_hpc.kernels.paged_attention import (
    INT8_SCALE_FLOOR,
    SCRATCH_PAGE,
    dequantize_pages_int8,
    int8_logit_rmse,
    paged_decode_attention,
    paged_prefill_attention,
    quantize_pages_int8,
    write_tokens,
)
from tpu_hpc.models import llama2
from tpu_hpc.runtime import MeshSpec, build_mesh
from tpu_hpc.serve import (
    ContinuousBatcher,
    PagedConfig,
    PagedEngine,
    Request,
    ServeConfig,
    SpecConfig,
    attach_spec,
)
from tpu_hpc.serve.paging import SCRATCH_BLOCK


TINY = llama2.LlamaConfig(
    dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
    multiple_of=16, max_seq_len=64, dtype=jnp.float32,
)
SERVE = ServeConfig(slots=4, max_seq_len=48, prefill_buckets=(8, 16))

# Bounded-divergence pin: int8_logit_rmse at TINY's attention dims
# (head_dim=16, kv_heads=2, n_heads=4, block_size=4) measures ~0.007;
# the pin leaves ~3x headroom without admitting a broken quantizer
# (a scale bug shows up at >0.1 immediately).
INT8_LOGIT_TOL = 0.02


@pytest.fixture(scope="module")
def serve_mesh(devices):
    return build_mesh(MeshSpec(axes={"data": 4, "model": 2}))


@pytest.fixture(scope="module")
def tiny_params():
    return llama2.init_llama(jax.random.key(0), TINY)


def _engine(tiny_params, serve_mesh, **kw):
    eng = PagedEngine(
        tiny_params, TINY, SERVE, serve_mesh,
        PagedConfig(
            block_size=4, num_blocks=48, prefill_chunk=8, **kw
        ),
    )
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def gather_engine(tiny_params, serve_mesh):
    return _engine(tiny_params, serve_mesh)


@pytest.fixture(scope="module")
def pallas_engine(tiny_params, serve_mesh):
    return _engine(tiny_params, serve_mesh, kernel="pallas")


@pytest.fixture(scope="module")
def pallas_q8_engine(tiny_params, serve_mesh):
    return _engine(
        tiny_params, serve_mesh, kernel="pallas", kv_quant="int8"
    )


@pytest.fixture(scope="module")
def gather_q8_engine(tiny_params, serve_mesh):
    return _engine(
        tiny_params, serve_mesh, kernel="gather", kv_quant="int8"
    )


_ORACLE_LEN = 48


@pytest.fixture(scope="module")
def greedy_oracle(tiny_params):
    """Greedy continuation via the full NO-CACHE forward pass -- the
    same fixed-padded-length oracle tests/test_paging.py pins the
    gather path against."""
    fwd = jax.jit(
        lambda toks: llama2.apply_llama(tiny_params, toks, TINY)
    )

    def oracle(prompt, steps):
        toks = list(prompt)
        out = []
        for _ in range(steps):
            assert len(toks) <= _ORACLE_LEN
            padded = np.zeros((1, _ORACLE_LEN), np.int32)
            padded[0, :len(toks)] = toks
            logits = fwd(jnp.asarray(padded))
            t = int(jnp.argmax(logits[0, len(toks) - 1]))
            out.append(t)
            toks.append(t)
        return out

    return oracle


def _drain(engine, reqs):
    batcher = ContinuousBatcher(engine)
    return batcher, batcher.run(reqs)


def _churn_mix():
    """More requests than slots; a fully-cached repeat prompt; a
    shared-prefix divergence (CoW on the partially-shared page); a
    prompt crossing the prefill chunk stride. Deterministic."""
    rng = np.random.default_rng(20)
    base = rng.integers(0, TINY.vocab_size, size=12).tolist()
    tail = rng.integers(0, TINY.vocab_size, size=2).tolist()
    short = rng.integers(0, TINY.vocab_size, size=4).tolist()
    longp = rng.integers(0, TINY.vocab_size, size=13).tolist()
    mid = rng.integers(0, TINY.vocab_size, size=7).tolist()
    return [
        Request(rid="r0", prompt=base, max_new_tokens=6),
        Request(rid="r1", prompt=list(base), max_new_tokens=6),
        Request(rid="r2", prompt=base[:8] + tail, max_new_tokens=6),
        Request(rid="r3", prompt=short, max_new_tokens=6),
        Request(rid="r4", prompt=longp, max_new_tokens=5),
        Request(rid="r5", prompt=mid, max_new_tokens=4),
    ]


# ---------------------------------------------------------------------
# Dense references (numpy, fp32, no flash tricks)
# ---------------------------------------------------------------------


def _softmax(x, axis=-1):
    m = np.max(x, axis=axis, keepdims=True)
    e = np.exp(x - m)
    return e / np.sum(e, axis=axis, keepdims=True)


def _rows(pages):
    """[n, hkv, block_size, d] pages -> [n * block_size, hkv, d] token
    rows (numpy twin of kernels.paged_attention.pages_to_tokens)."""
    n, hkv, bs, d = pages.shape
    return pages.transpose(0, 2, 1, 3).reshape(n * bs, hkv, d)


def _ref_decode(q, k_pages, v_pages, tables, pos, active, block_size):
    slots, hkv, g, d = q.shape
    out = np.zeros(q.shape, np.float32)
    for s in range(slots):
        if not active[s]:
            continue
        length = int(pos[s]) + 1
        n = -(-length // block_size)
        ids = tables[s, :n]
        k = _rows(k_pages[ids])[:length]
        v = _rows(v_pages[ids])[:length]
        scores = np.einsum("hgd,thd->hgt", q[s], k) * d ** -0.5
        out[s] = np.einsum(
            "hgt,thd->hgd", _softmax(scores), v
        )
    return out


def _ref_prefill(q, k_pages, v_pages, table, start, block_size):
    hkv, bucket, g, d = q.shape
    ctx = start + bucket
    n = -(-ctx // block_size)
    k = _rows(k_pages[table[:n]])[:ctx]
    v = _rows(v_pages[table[:n]])[:ctx]
    scores = np.einsum("hqgd,thd->hqgt", q, k) * d ** -0.5
    qpos = start + np.arange(bucket)
    causal = np.arange(ctx)[None, :] <= qpos[:, None]  # (bucket, ctx)
    scores = np.where(causal[None, :, None, :], scores, -1e30)
    return np.einsum("hqgt,thd->hqgd", _softmax(scores), v)


def _random_case(
    rng, *, slots=4, hkv=2, g=2, d=16, block_size=4, max_blocks=6,
    num_blocks=24, dtype=np.float32, poison=True,
):
    """Random pool + tables. Page 0 is scratch (zeros, the engine
    contract); the LAST page is NaN-poisoned and never allocated --
    every dead table entry points at it, so a kernel that fails to
    redirect dead reads to scratch poisons its output."""
    pool = rng.standard_normal(
        (num_blocks, hkv, block_size, d)
    ).astype(dtype)
    pool[SCRATCH_PAGE] = 0.0
    poison_page = num_blocks - 1
    if poison:
        pool[poison_page] = np.nan
    k_pages = pool
    v_pages = rng.standard_normal(pool.shape).astype(dtype)
    v_pages[SCRATCH_PAGE] = 0.0
    if poison:
        v_pages[poison_page] = np.nan
    q = rng.standard_normal((slots, hkv, g, d)).astype(dtype)
    pos = rng.integers(
        0, max_blocks * block_size, size=slots
    ).astype(np.int32)
    active = (rng.random(slots) < 0.75).astype(np.int32)
    active[0], active[-1] = 1, 0  # force one live, one dead slot
    tables = np.zeros((slots, max_blocks), np.int32)
    for s in range(slots):
        # pages drawn per-slot from the same small pool: overlap
        # across slots is the recycled/shared-page case
        tables[s] = rng.choice(
            np.arange(1, poison_page), size=max_blocks, replace=False
        )
        n_live = -(-(int(pos[s]) + 1) // block_size)
        tables[s, n_live:] = poison_page
        if not active[s]:
            tables[s] = poison_page  # dead slot: every entry poison
    return q, k_pages, v_pages, tables, pos, active


def _fresh_table_row(rng, num_blocks, max_blocks, ctx_pages):
    """A prefill table row: ``ctx_pages`` live pages, every later
    entry pointed at the poison page (the engine pads dead entries
    with scratch; poison proves the index map never reads them)."""
    poison_page = num_blocks - 1
    row = rng.choice(
        np.arange(1, poison_page), size=max_blocks, replace=False
    ).astype(np.int32)
    row[ctx_pages:] = poison_page
    return row


# ---------------------------------------------------------------------
# Direct kernel parity
# ---------------------------------------------------------------------


class TestDecodeKernelParity:
    def test_random_tables_match_dense_reference(self):
        rng = np.random.default_rng(0)
        for trial in range(4):
            q, kp, vp, tables, pos, active = _random_case(rng)
            out = np.asarray(paged_decode_attention(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(tables), jnp.asarray(pos),
                jnp.asarray(active),
                block_size=4, max_blocks=6, interpret=True,
            ))
            ref = _ref_decode(q, kp, vp, tables, pos, active, 4)
            assert np.isfinite(out).all(), trial  # poison stayed out
            np.testing.assert_allclose(
                out, ref, atol=2e-5, rtol=2e-5, err_msg=f"trial {trial}"
            )
            assert not out[active == 0].any()  # dead slots exact zeros

    def test_int8_pool_matches_dequantized_reference(self):
        """In-register dequant is the same math as reading a
        dequantized pool: parity is tight, not merely bounded."""
        rng = np.random.default_rng(1)
        q, kp, vp, tables, pos, active = _random_case(rng, poison=False)
        kq, ksc = quantize_pages_int8(jnp.asarray(kp))
        vq, vsc = quantize_pages_int8(jnp.asarray(vp))
        out = np.asarray(paged_decode_attention(
            jnp.asarray(q), kq, vq,
            jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(active),
            block_size=4, max_blocks=6,
            k_scale=ksc, v_scale=vsc, interpret=True,
        ))
        ref = _ref_decode(
            q, np.asarray(dequantize_pages_int8(kq, ksc)),
            np.asarray(dequantize_pages_int8(vq, vsc)),
            tables, pos, active, 4,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        # and bounded divergence vs the UNQUANTIZED pool
        exact = _ref_decode(q, kp, vp, tables, pos, active, 4)
        assert np.max(np.abs(out - exact)) < 0.05


class TestPrefillKernelParity:
    @pytest.mark.parametrize("start", [0, 8, 16])
    def test_chunk_matches_dense_causal_reference(self, start):
        """One compiled shape serves every chunk: ``start`` is data.
        start=0 is the first chunk, 8/16 are continuation chunks whose
        q rows attend across earlier pages."""
        rng = np.random.default_rng(2)
        hkv, bucket, g, d, bs, mb = 2, 8, 2, 16, 4, 6
        _, kp, vp, _, _, _ = _random_case(rng)
        ctx_pages = -(-(start + bucket) // bs)
        table = _fresh_table_row(rng, kp.shape[0], mb, ctx_pages)
        q = rng.standard_normal((hkv, bucket, g, d)).astype(np.float32)
        out = np.asarray(paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(start, jnp.int32),
            block_size=bs, max_blocks=mb, interpret=True,
        ))
        ref = _ref_prefill(q, kp, vp, table, start, bs)
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_odd_bucket_falls_back_to_one_q_block(self):
        """bucket % block_q != 0 collapses to a single q block rather
        than padding games -- the engine's odd trailing chunk."""
        rng = np.random.default_rng(3)
        hkv, bucket, g, d, bs, mb = 2, 6, 2, 16, 4, 6
        _, kp, vp, _, _, _ = _random_case(rng)
        table = _fresh_table_row(rng, kp.shape[0], mb, -(-bucket // bs))
        q = rng.standard_normal((hkv, bucket, g, d)).astype(np.float32)
        out = np.asarray(paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(0, jnp.int32),
            block_size=bs, max_blocks=mb, block_q=4, interpret=True,
        ))
        ref = _ref_prefill(q, kp, vp, table, 0, bs)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_multi_q_block_accumulators_reinit_per_block(self):
        """bucket=8 at block_q=4 runs two q blocks over the same kv
        walk: the VMEM accumulators must re-init at j==0 of EACH q
        block, and the causal mask must track the block offset."""
        rng = np.random.default_rng(8)
        hkv, bucket, g, d, bs, mb = 2, 8, 2, 16, 4, 6
        _, kp, vp, _, _, _ = _random_case(rng)
        table = _fresh_table_row(
            rng, kp.shape[0], mb, -(-(8 + bucket) // bs)
        )
        q = rng.standard_normal((hkv, bucket, g, d)).astype(np.float32)
        out = np.asarray(paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(8, jnp.int32),
            block_size=bs, max_blocks=mb, block_q=4, interpret=True,
        ))
        ref = _ref_prefill(q, kp, vp, table, 8, bs)
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_int8_chunk_matches_dequantized_reference(self):
        rng = np.random.default_rng(4)
        hkv, bucket, g, d, bs, mb = 2, 8, 2, 16, 4, 6
        _, kp, vp, _, _, _ = _random_case(rng, poison=False)
        table = _fresh_table_row(
            rng, kp.shape[0], mb, -(-(8 + bucket) // bs)
        )
        kq, ksc = quantize_pages_int8(jnp.asarray(kp))
        vq, vsc = quantize_pages_int8(jnp.asarray(vp))
        q = rng.standard_normal((hkv, bucket, g, d)).astype(np.float32)
        out = np.asarray(paged_prefill_attention(
            jnp.asarray(q), kq, vq,
            jnp.asarray(table), jnp.asarray(8, jnp.int32),
            block_size=bs, max_blocks=mb,
            k_scale=ksc, v_scale=vsc, interpret=True,
        ))
        ref = _ref_prefill(
            q, np.asarray(dequantize_pages_int8(kq, ksc)),
            np.asarray(dequantize_pages_int8(vq, vsc)),
            table, 8, bs,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------
# int8 quantization + the divergence probe
# ---------------------------------------------------------------------


class TestInt8Quantization:
    def test_roundtrip_bounded_by_half_a_scale_step(self):
        rng = np.random.default_rng(5)
        pages = jnp.asarray(
            rng.standard_normal((6, 4, 2, 16)).astype(np.float32)
        )
        q8, sc = quantize_pages_int8(pages)
        assert q8.dtype == jnp.int8
        assert sc.shape == (6,)
        back = dequantize_pages_int8(q8, sc)
        err = np.abs(np.asarray(back) - np.asarray(pages))
        assert np.all(
            err <= np.asarray(sc)[:, None, None, None] * 0.5 + 1e-7
        )

    def test_zero_page_scale_floor_no_nans(self):
        q8, sc = quantize_pages_int8(jnp.zeros((3, 4, 2, 16)))
        assert np.all(np.asarray(sc) == INT8_SCALE_FLOOR)
        assert not np.asarray(q8).any()
        assert np.isfinite(
            np.asarray(dequantize_pages_int8(q8, sc))
        ).all()

    def test_logit_rmse_probe_pins_the_tolerance(self):
        """The probe is deterministic (no engine, no clock) and stays
        under the pinned bound at TINY's attention dims -- this is the
        number docs/guide/serving.md quotes for when int8 is safe."""
        kw = dict(
            head_dim=16, kv_heads=2, n_heads=4,
            seq_len=48, block_size=4,
        )
        r = int8_logit_rmse(**kw)
        assert r == int8_logit_rmse(**kw)
        assert 0.0 < r < INT8_LOGIT_TOL

    def test_probe_validates_shapes(self):
        with pytest.raises(ValueError, match="multiple of block_size"):
            int8_logit_rmse(head_dim=16, kv_heads=2, seq_len=50,
                            block_size=4)
        with pytest.raises(ValueError, match="multiple of kv_heads"):
            int8_logit_rmse(head_dim=16, kv_heads=2, n_heads=3)


# ---------------------------------------------------------------------
# The token write (the decode programs' one spelling of it)
# ---------------------------------------------------------------------


def _write_tokens_oracle(pool, layer, page_ids, offsets, rows):
    """The spelling the package had before PR 26, kept here as the
    oracle: a token-granular scatter with the row dimension between
    the two index arrays. Right everywhere; on the TPU the compiler
    re-lays the whole pool round it, which is why it left the
    package."""
    return pool.at[layer, page_ids, :, offsets].set(
        rows.astype(pool.dtype)
    )


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))


class TestWriteTokens:
    @pytest.mark.parametrize("kv_heads", [32, 8])
    def test_bit_identical_to_token_scatter_off_scratch(self, kv_heads):
        """Distinct live pages at random rows, several slots
        redirected to the scratch page at once (what inactive slots
        do): every page but scratch, in every layer, comes out
        bit-identical to the token-granular scatter, at both serve
        cells' head counts."""
        layers, num_blocks, bs, d, slots, dead = 2, 24, 16, 128, 12, 4
        rng = np.random.default_rng(26 + kv_heads)
        pool = jnp.asarray(
            rng.standard_normal(
                (layers, num_blocks, kv_heads, bs, d), np.float32
            ),
            jnp.bfloat16,
        )
        page_ids = rng.choice(
            np.arange(1, num_blocks), size=slots, replace=False
        ).astype(np.int32)
        page_ids[rng.choice(slots, size=dead, replace=False)] = (
            SCRATCH_BLOCK
        )
        offsets = rng.integers(0, bs, size=slots).astype(np.int32)
        rows = jnp.asarray(
            rng.standard_normal((slots, kv_heads, d), np.float32)
        )
        for layer in range(layers):
            got = jax.jit(write_tokens, static_argnums=1)(
                pool, layer, page_ids, offsets, rows
            )
            want = _write_tokens_oracle(
                pool, layer, page_ids, offsets, rows
            )
            assert got.dtype == pool.dtype and got.shape == pool.shape
            np.testing.assert_array_equal(
                _bits(got)[:, 1:], _bits(want)[:, 1:]
            )
            # The write landed, and only in its own layer.
            live = page_ids != SCRATCH_BLOCK
            np.testing.assert_array_equal(
                _bits(got)[layer, page_ids[live], :, offsets[live]],
                _bits(rows.astype(jnp.bfloat16))[live],
            )
            np.testing.assert_array_equal(
                _bits(got)[1 - layer], _bits(pool)[1 - layer]
            )

    def test_rows_of_one_page_land_in_successive_calls(self):
        """Several rows of one slot in one page (the speculative
        verify program's k + 1 candidate rows): one call a row, as
        the one-writer-per-page rule asks, and every row lands."""
        rng = np.random.default_rng(27)
        pool = jnp.asarray(
            rng.standard_normal((1, 9, 2, 4, 16)), jnp.float32
        )
        page_ids = np.array([[3, 3, 5], [7, 7, 7]], np.int32)
        offsets = np.array([[2, 3, 0], [0, 1, 2]], np.int32)
        rows = jnp.asarray(
            rng.standard_normal((2, 3, 2, 16)), jnp.float32
        )
        got = pool
        for j in range(page_ids.shape[1]):
            got = write_tokens(
                got, 0, page_ids[:, j], offsets[:, j], rows[:, j]
            )
        want = _write_tokens_oracle(pool, 0, page_ids, offsets, rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestOneWriterPerPage:
    """What every page-granular write leans on (the int8 requantize
    always, the bf16 token write wherever it moves whole pages): in
    one decode step no two active slots name the same write-target
    page, and none names a page somebody else can read."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_active_slots_write_distinct_private_pages(
        self, gather_engine, monkeypatch, seed
    ):
        eng = gather_engine
        bs = eng.paged.block_size
        rng = np.random.default_rng(260 + seed)
        # Shared prefixes of whole pages and of part of a page, exact
        # repeats (a fully cached prompt), more requests than slots.
        bases = [
            rng.integers(0, TINY.vocab_size, size=14).tolist()
            for _ in range(2)
        ]
        reqs = []
        for i in range(10):
            base = bases[int(rng.integers(2))]
            keep = int(rng.integers(3, len(base) + 1))
            tail = rng.integers(
                0, TINY.vocab_size, size=int(rng.integers(0, 4))
            ).tolist()
            reqs.append(Request(
                rid=f"q{seed}.{i}", prompt=base[:keep] + tail,
                max_new_tokens=int(rng.integers(2, 9)),
            ))
        steps = []
        held = []
        decode = eng.decode

        def live(slot):
            try:
                eng.slot_state(slot)
            except KeyError:
                return False
            return True

        def checked(tokens, positions, active=None):
            on = [
                s for s in range(len(tokens))
                if (active is None or active[s]) and live(s)
            ]
            # A second owner appears on some slot's target page (a
            # sharing policy to come; the CoW guard's case).
            if on and rng.random() < 0.3:
                s = on[int(rng.integers(len(on)))]
                page = eng.slot_state(s).blocks[int(positions[s]) // bs]
                eng.allocator.retain([page])
                held.append(page)
            out = decode(tokens, positions, active)
            # ``decode`` ran the CoW guard before it dispatched, so
            # the tables now name what the program wrote.
            targets = [
                eng.slot_state(s).blocks[int(positions[s]) // bs]
                for s in on
            ]
            assert SCRATCH_BLOCK not in targets
            assert len(set(targets)) == len(targets), targets
            for page in targets:
                assert eng.allocator.refcount(page) == 1, page
            steps.append(len(on))
            return out

        monkeypatch.setattr(eng, "decode", checked)
        hits0 = eng.paged_stats["prefix_hits"]
        cows0 = eng.paged_stats["cow_copies"]
        _, got = _drain(eng, reqs)
        eng.allocator.release(held)
        assert sorted(got) == sorted(r.rid for r in reqs)
        assert max(steps) > 1  # several writers in one step
        assert eng.paged_stats["prefix_hits"] > hits0
        assert eng.paged_stats["cow_copies"] >= cows0 + len(held) > cows0
        eng.allocator.check_invariant()


# ---------------------------------------------------------------------
# Engine-level contract
# ---------------------------------------------------------------------


class TestEngineParity:
    def test_scratch_sentinels_agree(self):
        assert SCRATCH_PAGE == SCRATCH_BLOCK == 0

    def test_pick_block_sizes_single_source(self):
        assert pick_block_sizes(512, 512, 40, 200) == (128, 256)

    def test_pallas_token_exact_vs_gather_and_oracle(
        self, gather_engine, pallas_engine, greedy_oracle
    ):
        """The churn mix (slot churn, fully-cached prompt, CoW
        divergence, chunk crosser) decodes identically through both
        read paths, and both match the no-cache oracle."""
        _, want = _drain(gather_engine, _churn_mix())
        _, got = _drain(pallas_engine, _churn_mix())
        assert got == want
        for r in _churn_mix():
            assert got[r.rid] == greedy_oracle(
                r.prompt, r.max_new_tokens
            ), r.rid

    def test_pallas_prefix_hits_and_zero_recompiles(
        self, pallas_engine, greedy_oracle
    ):
        """Replaying the mix hits the prefix trie (pages written by
        the previous drain, read back through the Pallas kernels) with
        ZERO new executables: tables, positions and chunk starts are
        all data."""
        n0 = pallas_engine.compile_count
        hits0 = pallas_engine.paged_stats["prefix_hits"]
        for _ in range(2):
            reqs = _churn_mix()
            _, got = _drain(pallas_engine, reqs)
            for r in reqs:
                assert got[r.rid] == greedy_oracle(
                    r.prompt, r.max_new_tokens
                ), r.rid
        assert pallas_engine.compile_count == n0
        assert pallas_engine.paged_stats["prefix_hits"] > hits0

    def test_summary_reports_kernel_and_quant(
        self, pallas_q8_engine, gather_engine
    ):
        s = pallas_q8_engine.paged_summary()
        assert s["kv_kernel"] == "pallas"
        assert s["kv_quant"] == "int8"
        s = gather_engine.paged_summary()
        assert s["kv_kernel"] == "gather"
        assert s["kv_quant"] == "none"

    def test_config_validation(self):
        with pytest.raises(ValueError, match="kernel"):
            PagedConfig(block_size=4, num_blocks=8, kernel="triton")
        with pytest.raises(ValueError, match="kv_quant"):
            PagedConfig(block_size=4, num_blocks=8, kv_quant="fp4")
        # The one page size Mosaic refuses (bf16/int8 pools); every
        # size from 2 up lowers and matched the oracle on the chip.
        with pytest.raises(ValueError, match="block_size >= 2"):
            PagedConfig(block_size=1, num_blocks=8, kernel="pallas")
        PagedConfig(block_size=1, num_blocks=8)  # gather takes any
        PagedConfig(block_size=2, num_blocks=8, kernel="pallas")


class TestEngineInt8:
    def test_int8_token_exact_across_kernels(
        self, gather_q8_engine, pallas_q8_engine
    ):
        """The kernel contract under quantization: pallas and gather
        read the SAME int8 pool, so their streams are token-exact
        even where quantization drifts from fp."""
        _, want = _drain(gather_q8_engine, _churn_mix())
        _, got = _drain(pallas_q8_engine, _churn_mix())
        assert got == want

    def test_int8_bounded_divergence_vs_fp_oracle(
        self, pallas_q8_engine, greedy_oracle
    ):
        """int8 vs fp is a BOUNDED-divergence contract (the probe pin
        above); at TINY's scale the drift flips no greedy argmax, so
        the streams happen to stay token-exact -- pinned as such."""
        reqs = _churn_mix()
        _, got = _drain(pallas_q8_engine, reqs)
        for r in reqs:
            assert got[r.rid] == greedy_oracle(
                r.prompt, r.max_new_tokens
            ), r.rid

    def test_int8_zero_recompiles_under_churn(self, pallas_q8_engine):
        n0 = pallas_q8_engine.compile_count
        _drain(pallas_q8_engine, _churn_mix())
        assert pallas_q8_engine.compile_count == n0

    def test_spec_rejects_quantized_pool(self, pallas_q8_engine):
        with pytest.raises(ValueError, match="quantized KV pool"):
            attach_spec(pallas_q8_engine, SpecConfig(mode="ngram"))


# ---------------------------------------------------------------------
# Sweep: block-size x dtype grid (-m kernels; slowlisted)
# ---------------------------------------------------------------------

_SWEEP = [(4, "bfloat16"), (8, "float32"), (8, "bfloat16")]


@pytest.mark.kernels
class TestKernelSweep:
    """The grid beyond tier-1's (block_size=4, float32)
    representative. bf16 pools compare against an fp32 reference over
    the SAME bf16-rounded pages; tolerance covers the p-matrix
    bf16 cast in the flash inner loop."""

    @pytest.mark.parametrize("block_size,dtype", _SWEEP)
    def test_decode_grid(self, block_size, dtype):
        rng = np.random.default_rng(6)
        tol = 2e-5 if dtype == "float32" else 6e-2
        for trial in range(2):
            q, kp, vp, tables, pos, active = _random_case(
                rng, block_size=block_size,
                dtype=np.float32,
            )
            qj = jnp.asarray(q).astype(dtype)
            kj = jnp.asarray(kp).astype(dtype)
            vj = jnp.asarray(vp).astype(dtype)
            out = np.asarray(paged_decode_attention(
                qj, kj, vj,
                jnp.asarray(tables), jnp.asarray(pos),
                jnp.asarray(active),
                block_size=block_size, max_blocks=6, interpret=True,
            )).astype(np.float32)
            ref = _ref_decode(
                np.asarray(qj, np.float32), np.asarray(kj, np.float32),
                np.asarray(vj, np.float32), tables, pos, active,
                block_size,
            )
            assert np.isfinite(out).all(), (trial, dtype)
            np.testing.assert_allclose(
                out, ref, atol=tol, rtol=tol,
                err_msg=f"trial {trial} bs={block_size} {dtype}",
            )

    @pytest.mark.parametrize("block_size,dtype", _SWEEP)
    def test_prefill_grid(self, block_size, dtype):
        rng = np.random.default_rng(7)
        tol = 2e-5 if dtype == "float32" else 6e-2
        hkv, bucket, g, d = 2, 8, 2, 16
        _, kp, vp, _, _, _ = _random_case(
            rng, block_size=block_size
        )
        for start in (0, 8):
            ctx_pages = -(-(start + bucket) // block_size)
            table = _fresh_table_row(rng, kp.shape[0], 6, ctx_pages)
            q = rng.standard_normal(
                (hkv, bucket, g, d)
            ).astype(np.float32)
            qj = jnp.asarray(q).astype(dtype)
            kj = jnp.asarray(kp).astype(dtype)
            vj = jnp.asarray(vp).astype(dtype)
            out = np.asarray(paged_prefill_attention(
                qj, kj, vj,
                jnp.asarray(table), jnp.asarray(start, jnp.int32),
                block_size=block_size, max_blocks=6, interpret=True,
            )).astype(np.float32)
            ref = _ref_prefill(
                np.asarray(qj, np.float32), np.asarray(kj, np.float32),
                np.asarray(vj, np.float32), table, start, block_size,
            )
            assert np.isfinite(out).all(), (start, dtype)
            np.testing.assert_allclose(
                out, ref, atol=tol, rtol=tol,
                err_msg=f"start {start} bs={block_size} {dtype}",
            )
