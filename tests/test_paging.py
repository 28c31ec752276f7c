"""The paged KV cache (serve/paging.py): page accounting, prefix
reuse, chunked prefill, and the compile discipline.

Four invariant families:
  * **page accounting** -- a property suite over random
    admit/evict/CoW sequences: the allocator never double-frees or
    leaks (scratch + free + referenced == num_blocks after every
    operation);
  * **token exactness** -- greedy decode through the paged cache is
    token-exact against the no-cache forward (llama2.apply_llama),
    with and without prefix hits, with chunked prefill, and after the
    prefix's original owner was evicted;
  * **compile discipline** -- block tables are DATA: a warmed paged
    engine serves a mix with slot churn, hits, chunking and pool
    pressure with ZERO new executables;
  * **budget discipline** -- submit() hard-rejects only the truly
    unservable (typed error naming prompt+max_new vs the page
    budget); transient pool exhaustion queues (block stalls) and
    drains.

All on the 8-device simulated mesh (KV heads shard over ``model``;
the page pool stays whole), fp32 compute so "token-exact" means
exact.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hpc.models import llama2
from tpu_hpc.runtime import MeshSpec, build_mesh
from tpu_hpc.serve import (
    BlockAllocator,
    BlockBudgetError,
    ContinuousBatcher,
    Engine,
    PagedConfig,
    PagedEngine,
    PrefixTrie,
    Request,
    ServeConfig,
    UnservableRequestError,
)
from tpu_hpc.serve.paging import SCRATCH_BLOCK, paged_kv_cache_pspec


TINY = llama2.LlamaConfig(
    dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
    multiple_of=16, max_seq_len=64, dtype=jnp.float32,
)
SERVE = ServeConfig(slots=4, max_seq_len=48, prefill_buckets=(8, 16))


@pytest.fixture(scope="module")
def serve_mesh(devices):
    return build_mesh(MeshSpec(axes={"data": 4, "model": 2}))


@pytest.fixture(scope="module")
def tiny_params():
    return llama2.init_llama(jax.random.key(0), TINY)


@pytest.fixture(scope="module")
def warm_paged(tiny_params, serve_mesh):
    """One chunked paged engine serves the whole module: chunked
    prefill generalizes plain prefill (stride >= prompt is one
    chunk), so every parity case runs through it."""
    engine = PagedEngine(
        tiny_params, TINY, SERVE, serve_mesh,
        PagedConfig(block_size=4, num_blocks=48, prefill_chunk=8),
    )
    engine.warmup()
    return engine


_ORACLE_LEN = 48


@pytest.fixture(scope="module")
def greedy_oracle(tiny_params):
    """Greedy continuation via the full NO-CACHE forward pass (the
    training model) -- the same fixed-padded-length oracle
    tests/test_serve.py pins the slab engine against."""
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


# ---------------------------------------------------------------------
# Page accounting: the property suite
# ---------------------------------------------------------------------


class TestBlockAllocator:
    def test_random_admit_evict_cow_never_leaks(self):
        """The allocator invariant under a random operation stream:
        scratch + free + referenced == num_blocks after EVERY op, with
        a shadow model cross-checking refcounts."""
        rng = np.random.default_rng(7)
        alloc = BlockAllocator(32)
        held = []          # (blocks, extra_refs) per live "request"
        for _ in range(600):
            op = rng.integers(0, 4)
            if op == 0 and alloc.free_blocks:       # admit
                n = int(rng.integers(1, alloc.free_blocks + 1))
                held.append((alloc.alloc(n), []))
            elif op == 1 and held:                  # share (retain)
                blocks, extra = held[
                    int(rng.integers(0, len(held)))
                ]
                b = blocks[int(rng.integers(0, len(blocks)))]
                alloc.retain([b])
                extra.append(b)
            elif op == 2 and held:                  # evict (release)
                i = int(rng.integers(0, len(held)))
                blocks, extra = held.pop(i)
                alloc.release(blocks)
                alloc.release(extra)
            elif op == 3 and held:                  # copy-on-write
                i = int(rng.integers(0, len(held)))
                blocks, extra = held[i]
                j = int(rng.integers(0, len(blocks)))
                try:
                    new, copied = alloc.cow(blocks[j])
                except BlockBudgetError:
                    continue  # pool full: legal, nothing changed
                if copied:
                    blocks[j] = new
            alloc.check_invariant()
        for blocks, extra in held:
            alloc.release(blocks)
            alloc.release(extra)
        alloc.check_invariant()
        assert alloc.free_blocks == 31  # everything returned

    def test_double_free_and_foreign_retain_raise(self):
        alloc = BlockAllocator(8)
        blocks = alloc.alloc(2)
        alloc.release(blocks)
        with pytest.raises(ValueError, match="double free"):
            alloc.release([blocks[0]])
        with pytest.raises(ValueError, match="unreferenced"):
            alloc.retain([blocks[0]])
        alloc.check_invariant()

    def test_overdraw_raises_budget_error(self):
        alloc = BlockAllocator(4)  # 3 usable
        with pytest.raises(BlockBudgetError, match="free pages"):
            alloc.alloc(4)
        alloc.check_invariant()

    def test_cow_exclusive_is_noop_shared_copies(self):
        alloc = BlockAllocator(8)
        (b,) = alloc.alloc(1)
        assert alloc.cow(b) == (b, False)
        alloc.retain([b])
        new, copied = alloc.cow(b)
        assert copied and new != b
        assert alloc.refcount(b) == 1  # the other owner's ref
        alloc.release([b])
        alloc.release([new])
        alloc.check_invariant()


class TestPrefixTrie:
    def _setup(self):
        alloc = BlockAllocator(16)
        trie = PrefixTrie(block_size=2)
        return alloc, trie

    def test_match_insert_roundtrip_full_blocks_only(self):
        alloc, trie = self._setup()
        blocks = alloc.alloc(2)
        prompt = [1, 2, 3, 4, 5]  # 2 full blocks + 1 partial token
        assert trie.insert(prompt, blocks, alloc) == 2
        assert trie.match(prompt) == blocks
        assert trie.match([1, 2, 3, 4, 9, 9]) == blocks
        assert trie.match([1, 2, 9, 9]) == blocks[:1]
        assert trie.match([9, 9]) == []
        alloc.check_invariant()

    def test_pages_survive_owner_release(self):
        """The trie's reference keeps a finished request's prompt
        pages allocated -- the host-side half of
        prefix-hit-after-eviction."""
        alloc, trie = self._setup()
        blocks = alloc.alloc(2)
        trie.insert([1, 2, 3, 4], blocks, alloc)
        freed = alloc.release(blocks)     # the request evicts
        assert freed == 0                 # trie still holds both
        assert trie.match([1, 2, 3, 4]) == blocks
        alloc.check_invariant()

    def test_evict_is_lru_leaf_first_and_respects_live_refs(self):
        alloc, trie = self._setup()
        b1 = alloc.alloc(2)               # chain a: two blocks
        trie.insert([1, 2, 3, 4], b1, alloc)
        b2 = alloc.alloc(1)               # chain b: one block
        trie.insert([5, 6], b2, alloc)
        alloc.release(b1)
        alloc.release(b2)
        trie.match([1, 2, 3, 4])          # chain a is now MRU
        free_before = alloc.free_blocks
        assert trie.evict(alloc, 1) == 1
        assert alloc.free_blocks == free_before + 1
        assert trie.match([5, 6]) == []   # LRU leaf went first
        assert trie.match([1, 2, 3, 4]) == b1
        # A leaf whose page a live request shares is PROTECTED:
        # releasing it would free nothing toward the shortage, and
        # deleting the node would throw away a hot prefix (review
        # finding). The inner block stays reachable only through it,
        # so nothing evicts.
        alloc.retain([b1[1]])
        assert trie.evict(alloc, 2) == 0
        assert trie.match([1, 2, 3, 4]) == b1  # chain survived
        # Once the live request releases, the chain evicts leaf-first.
        alloc.release([b1[1]])
        assert trie.evict(alloc, 2) == 2
        assert trie.match([1, 2, 3, 4]) == []
        alloc.check_invariant()


class TestTrieSnapshots:
    """A node's optional snapshot of a recurrent state
    (``models/hybrid_ssm_moe.py``), host side: the states here are
    plain strings."""

    def _setup(self, budget):
        alloc = BlockAllocator(32)
        trie = PrefixTrie(block_size=2, snapshot_budget=budget)
        return alloc, trie

    def _chain(self, alloc, trie, prompt):
        blocks = alloc.alloc(len(prompt) // 2)
        trie.insert(prompt, blocks, alloc)
        alloc.release(blocks)
        return blocks

    def test_a_trie_without_a_budget_takes_none(self):
        alloc, trie = self._setup(0)
        self._chain(alloc, trie, [1, 2, 3, 4])
        assert not trie.put_snapshot([1, 2, 3, 4], 2, ("s",), 10, cost=4)
        assert trie.deepest_snapshot([1, 2, 3, 4], 2) == (0, None)
        assert trie.snapshot_bytes == 0

    def test_deepest_snapshot_on_the_chain_and_no_deeper_than_asked(self):
        alloc, trie = self._setup(100)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        self._chain(alloc, trie, prompt)
        assert trie.put_snapshot(prompt, 1, ("at2",), 10, cost=2)
        assert trie.put_snapshot(prompt, 3, ("at6",), 10, cost=4)
        assert not trie.put_snapshot(prompt, 3, ("again",), 10, cost=4)
        assert not trie.put_snapshot([9, 9], 1, ("gone",), 10, cost=2)
        assert not trie.put_snapshot(prompt, 0, ("root",), 10, cost=0)
        depth, snap = trie.deepest_snapshot(prompt, 4)
        assert (depth, snap.state) == (3, ("at6",))
        depth, snap = trie.deepest_snapshot(prompt, 2)
        assert (depth, snap.state) == (1, ("at2",))
        # another branch shares the first block only
        depth, snap = trie.deepest_snapshot([1, 2, 9, 9], 2)
        assert (depth, snap.state) == (1, ("at2",))
        assert trie.snapshot_bytes == 20

    def test_over_the_budget_the_cheapest_to_rebuild_goes_first(self):
        """Room for two: a document's snapshot (cost 6) outlasts the
        never-reused ones of the questions about it (cost 2 each),
        which go oldest first; plain LRU would drop the document's for
        the second question."""
        alloc, trie = self._setup(20)
        doc = [1, 2, 3, 4, 5, 6]
        self._chain(alloc, trie, doc)
        assert trie.put_snapshot(doc, 3, ("doc",), 10, cost=6)
        for q in range(4):
            prompt = doc + [10 + q, 20 + q]
            self._chain(alloc, trie, prompt)
            assert trie.put_snapshot(prompt, 4, (f"q{q}",), 10, cost=2)
            assert trie.snapshot_bytes <= 20
            assert trie.deepest_snapshot(doc, 3)[1].state == ("doc",)
        assert trie.snapshot_evictions == 3
        assert trie.deepest_snapshot(doc + [13, 23], 4)[1].state == ("q3",)
        assert trie.deepest_snapshot(doc + [12, 22], 4)[0] == 3
        # ... but not for ever: the floor rises with every drop, and a
        # document nobody asks about any more goes in its turn.
        other = [7, 7, 8, 8, 9, 9]
        self._chain(alloc, trie, other)
        for q in range(12):
            prompt = other + [30 + q, 40 + q]
            self._chain(alloc, trie, prompt)
            trie.put_snapshot(prompt, 4, (f"o{q}",), 10, cost=2)
        assert trie.deepest_snapshot(doc, 3) == (0, None)

    def test_a_snapshot_dies_with_its_node(self):
        alloc, trie = self._setup(100)
        self._chain(alloc, trie, [1, 2, 3, 4])
        trie.put_snapshot([1, 2, 3, 4], 2, ("s",), 10, cost=4)
        assert trie.evict(alloc, 1) == 1          # the leaf, LRU
        assert trie.snapshot_bytes == 0
        assert trie.deepest_snapshot([1, 2, 3, 4], 2) == (0, None)
        alloc.check_invariant()


# ---------------------------------------------------------------------
# Token exactness
# ---------------------------------------------------------------------


class TestPagedParity:
    def test_single_request_token_exact(self, warm_paged, greedy_oracle):
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, TINY.vocab_size, size=11).tolist()
        _, got = _drain(
            warm_paged,
            [Request(rid="a", prompt=prompt, max_new_tokens=4)],
        )
        assert got["a"] == greedy_oracle(prompt, 4)

    def test_prompt_of_one_token(self, warm_paged, greedy_oracle):
        _, got = _drain(
            warm_paged, [Request(rid="a", prompt=[5], max_new_tokens=4)]
        )
        assert got["a"] == greedy_oracle([5], 4)

    def test_mixed_stream_with_churn_matches_solo_oracles(
        self, warm_paged, greedy_oracle
    ):
        """More requests than slots, staggered lengths (one crossing
        the chunk stride): every request still generates exactly its
        solo greedy continuation -- pages are isolated."""
        rng = np.random.default_rng(2)
        shapes = [(5, 3), (11, 6), (7, 1), (13, 4), (4, 5), (9, 2)]
        reqs = [
            Request(
                rid=f"r{i}",
                prompt=rng.integers(
                    0, TINY.vocab_size, size=plen
                ).tolist(),
                max_new_tokens=mnew,
            )
            for i, (plen, mnew) in enumerate(shapes)
        ]
        batcher, got = _drain(warm_paged, reqs)
        for r in reqs:
            assert got[r.rid] == greedy_oracle(
                r.prompt, r.max_new_tokens
            ), r.rid
        assert batcher.stats["admitted"] == len(shapes)
        assert batcher.stats["admitted"] > SERVE.slots

    def test_prefix_hit_is_token_exact_and_counted(
        self, warm_paged, greedy_oracle
    ):
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, TINY.vocab_size, size=13).tolist()
        _, first = _drain(
            warm_paged,
            [Request(rid="cold", prompt=prompt, max_new_tokens=3)],
        )
        hits_before = warm_paged.paged_stats["prefix_hits"]
        _, again = _drain(
            warm_paged,
            [Request(rid="warm", prompt=prompt, max_new_tokens=3)],
        )
        want = greedy_oracle(prompt, 3)
        assert first["cold"] == want
        assert again["warm"] == want
        assert warm_paged.paged_stats["prefix_hits"] == hits_before + 1
        # 13 tokens = 3 full pages of 4; all three resolve physically.
        assert warm_paged.paged_stats["prefix_hit_blocks"] >= 3

    def test_prefix_hit_after_owner_eviction(
        self, tiny_params, serve_mesh, greedy_oracle
    ):
        """The trie's reference outlives the original request: a
        fresh engine serves request A, fully drains (A's pages
        released), then a same-prompt request B hits the cached
        prefix and still decodes token-exact."""
        engine = PagedEngine(
            tiny_params, TINY, SERVE, serve_mesh,
            PagedConfig(block_size=4, num_blocks=32),
        )
        warmed = engine.warmup()
        rng = np.random.default_rng(4)
        prompt = rng.integers(0, TINY.vocab_size, size=12).tolist()
        _drain(
            engine, [Request(rid="a", prompt=prompt, max_new_tokens=2)]
        )
        assert engine.allocator.used_blocks > 0  # trie holds pages
        _, got = _drain(
            engine, [Request(rid="b", prompt=prompt, max_new_tokens=4)]
        )
        assert got["b"] == greedy_oracle(prompt, 4)
        assert engine.paged_stats["prefix_hits"] == 1
        assert engine.compile_count == warmed

    def test_fully_cached_prompt_still_reprefills_last_page(
        self, warm_paged, greedy_oracle
    ):
        """A prompt whose EVERY page is cached must still forward at
        least one token (the first greedy token needs the last
        position's logits): the hit caps at all-but-one page."""
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, TINY.vocab_size, size=8).tolist()
        _drain(
            warm_paged,
            [Request(rid="c1", prompt=prompt, max_new_tokens=2)],
        )
        _, got = _drain(
            warm_paged,
            [Request(rid="c2", prompt=prompt, max_new_tokens=2)],
        )
        assert got["c2"] == greedy_oracle(prompt, 2)

    def test_chunked_prefill_interleaves_with_decode(
        self, warm_paged, greedy_oracle
    ):
        """A long admission must not stall in-flight decode: while a
        16-token prompt prefills in 8-token chunks, the short request
        already decoding keeps receiving tokens every tick."""
        rng = np.random.default_rng(6)
        short = rng.integers(0, TINY.vocab_size, size=3).tolist()
        long = rng.integers(0, TINY.vocab_size, size=16).tolist()
        batcher = ContinuousBatcher(warm_paged)
        batcher.submit(Request(rid="s", prompt=short,
                               max_new_tokens=8))
        batcher.step()  # admit + one-chunk prefill + first decode
        tokens_before = len(batcher.results["s"])
        batcher.submit(Request(rid="l", prompt=long, max_new_tokens=3))
        batcher.step()  # long: chunk 1 of 2 -- short still decodes
        assert len(batcher.results["s"]) == tokens_before + 1
        assert "l" not in batcher.results  # still prefilling
        # Chunk 2 completes -> first token, and the slot joins the
        # same tick's decode (the slab admission-tick behavior).
        batcher.step()
        assert len(batcher.results["s"]) == tokens_before + 2
        # ... whose token is in flight for one tick more (the paged
        # engine hands a step's tokens back with the next dispatch).
        assert len(batcher.results["l"]) == 2 - warm_paged.decode_lag
        batcher.step()
        assert len(batcher.results["s"]) == tokens_before + 3
        assert len(batcher.results["l"]) == 3 - warm_paged.decode_lag
        got = batcher.run()
        assert got["s"] == greedy_oracle(short, 8)
        assert got["l"] == greedy_oracle(long, 3)

    def test_cow_guard_copies_and_stays_exact(
        self, warm_paged, greedy_oracle
    ):
        """Force the copy-on-write guard: another owner appears on the
        decode write-target page mid-request; the engine must copy the
        page (not corrupt the other owner) and stay token-exact."""
        rng = np.random.default_rng(8)
        prompt = rng.integers(0, TINY.vocab_size, size=6).tolist()
        batcher = ContinuousBatcher(warm_paged)
        batcher.submit(Request(rid="w", prompt=prompt,
                               max_new_tokens=5))
        batcher.step()
        slot = next(
            i for i, s in enumerate(batcher.slots) if s.rid == "w"
        )
        st = warm_paged.slot_state(slot)
        pos = batcher.slots[slot].pos
        target = st.blocks[pos // 4]
        warm_paged.allocator.retain([target])  # simulated second owner
        before = warm_paged.paged_stats["cow_copies"]
        batcher.step()
        assert warm_paged.paged_stats["cow_copies"] == before + 1
        got = batcher.run()
        assert got["w"] == greedy_oracle(prompt, 5)
        warm_paged.allocator.release([target])
        warm_paged.allocator.check_invariant()

    def test_paged_matches_slab_engine_exactly(
        self, tiny_params, serve_mesh, warm_paged
    ):
        """The seeded paged-vs-slab parity smoke: one request mix
        through both engines, identical token streams."""
        slab = Engine(tiny_params, TINY, SERVE, serve_mesh)
        slab.warmup()
        rng = np.random.default_rng(9)
        reqs = [
            Request(
                rid=f"p{i}",
                prompt=rng.integers(
                    0, TINY.vocab_size, size=3 + (7 * i) % 14
                ).tolist(),
                max_new_tokens=1 + i % 4,
            )
            for i in range(8)
        ]
        _, got_slab = _drain(slab, reqs)
        _, got_paged = _drain(warm_paged, reqs)
        assert got_slab == got_paged


# ---------------------------------------------------------------------
# Compile + budget discipline
# ---------------------------------------------------------------------


class TestPagedCompileDiscipline:
    def test_zero_recompiles_across_mix(self, warm_paged):
        """Block tables, positions and the active mask are data: a mix
        with churn, hits, chunked prompts and CoW adds NO executables
        after warmup (buckets + the ONE decode program, whose row step
        walks the tables whatever is live, + copy_block)."""
        warmed = warm_paged.compile_count
        assert warm_paged.decode_rungs == ()
        assert warmed == len(SERVE.prefill_buckets) + 1 + 1
        rng = np.random.default_rng(10)
        reqs = [
            Request(
                rid=f"z{i}",
                prompt=rng.integers(
                    0, TINY.vocab_size, size=2 + (5 * i) % 15
                ).tolist(),
                max_new_tokens=1 + i % 5,
            )
            for i in range(9)
        ]
        _drain(warm_paged, reqs)
        assert warm_paged.compile_count == warmed

    def test_pool_layout_on_mesh(self, warm_paged, serve_mesh):
        spec = paged_kv_cache_pspec(serve_mesh, TINY.kv_heads)
        assert spec == jax.sharding.PartitionSpec(
            None, None, "model", None, None
        )
        assert warm_paged.ks.sharding.spec == spec
        # Heads ahead of rows inside a page: the layout the Mosaic
        # kernels need (kernels/paged_attention.py).
        assert warm_paged.ks.shape == (
            TINY.n_layers, 48, TINY.kv_heads, 4, TINY.head_dim
        )
        assert warm_paged.cache_bytes == (
            2 * TINY.n_layers * 48 * 4 * TINY.kv_heads
            * TINY.head_dim * 4
        )

    def test_config_validation(self, tiny_params, serve_mesh):
        with pytest.raises(ValueError, match="multiple of block_size"):
            PagedConfig(block_size=4, num_blocks=8, prefill_chunk=6)
        with pytest.raises(ValueError, match="num_blocks"):
            PagedConfig(block_size=4, num_blocks=1)
        with pytest.raises(ValueError, match="multiple of "):
            PagedEngine(
                tiny_params, TINY,
                ServeConfig(slots=2, max_seq_len=50,
                            prefill_buckets=(8,)),
                serve_mesh, PagedConfig(block_size=4, num_blocks=16),
            )
        with pytest.raises(ValueError, match="not multiples"):
            PagedEngine(
                tiny_params, TINY,
                ServeConfig(slots=2, max_seq_len=48,
                            prefill_buckets=(6,)),
                serve_mesh, PagedConfig(block_size=4, num_blocks=16),
            )
        with pytest.raises(ValueError, match="exceeds the largest"):
            PagedEngine(
                tiny_params, TINY, SERVE, serve_mesh,
                PagedConfig(block_size=4, num_blocks=16,
                            prefill_chunk=32),
            )


class TestBlockBudget:
    def test_unservable_submit_is_typed_and_names_numbers(
        self, tiny_params, serve_mesh
    ):
        """The fail-at-submit discipline, paged edition: only a
        request the pool can NEVER hold is rejected, with both sides
        of the inequality in the message."""
        engine = PagedEngine(
            tiny_params, TINY, SERVE, serve_mesh,
            PagedConfig(block_size=4, num_blocks=10),  # 9 usable
        )
        batcher = ContinuousBatcher(engine)
        with pytest.raises(
            UnservableRequestError,
            match=r"prompt 16 \+ max_new 32 needs 12 pages",
        ) as ei:
            batcher.submit(
                Request(rid="huge", prompt=[1] * 16,
                        max_new_tokens=32)
            )
        assert "9 usable pages" in str(ei.value)
        # The slab-era cache-capacity check still guards first.
        with pytest.raises(ValueError, match="cache capacity"):
            batcher.submit(
                Request(rid="cap", prompt=[1] * 16,
                        max_new_tokens=40)
            )

    def test_pool_pressure_stalls_then_drains(
        self, tiny_params, serve_mesh, greedy_oracle
    ):
        """Admissions the pool cannot seat QUEUE (block stalls) and
        admit as in-flight requests free pages -- token streams stay
        exact throughout, and the accounting invariant holds after
        the drain."""
        engine = PagedEngine(
            tiny_params, TINY, SERVE, serve_mesh,
            PagedConfig(block_size=4, num_blocks=14),  # 13 usable
        )
        warmed = engine.warmup()
        rng = np.random.default_rng(11)
        reqs = [
            Request(
                rid=f"q{i}",
                prompt=rng.integers(
                    0, TINY.vocab_size, size=12
                ).tolist(),
                max_new_tokens=8,  # 5 pages each; 2 fit at once
            )
            for i in range(5)
        ]
        batcher, got = _drain(engine, reqs)
        for r in reqs:
            assert got[r.rid] == greedy_oracle(
                r.prompt, r.max_new_tokens
            ), r.rid
        assert batcher.stats["block_stalls"] > 0
        assert engine.compile_count == warmed
        engine.allocator.check_invariant()

    def test_trie_eviction_reclaims_pages_for_admission(
        self, tiny_params, serve_mesh, greedy_oracle
    ):
        """A pool whose free pages all sit in the prefix trie must
        reclaim them (LRU leaves first) rather than stall forever."""
        engine = PagedEngine(
            tiny_params, TINY, SERVE, serve_mesh,
            PagedConfig(block_size=4, num_blocks=12),  # 11 usable
        )
        engine.warmup()
        rng = np.random.default_rng(12)
        a = rng.integers(0, TINY.vocab_size, size=12).tolist()
        _drain(engine, [Request(rid="a", prompt=a, max_new_tokens=4)])
        assert engine.allocator.used_blocks == 3  # trie: a's 3 pages
        b = rng.integers(0, TINY.vocab_size, size=16).tolist()
        _, got = _drain(
            engine, [Request(rid="b", prompt=b, max_new_tokens=20)]
        )  # needs 9 pages; only 8 free -> must evict a trie page
        assert got["b"] == greedy_oracle(b, 20)
        assert engine.paged_stats["trie_evictions"] > 0
        engine.allocator.check_invariant()


class TestKvBlockTelemetry:
    def test_kv_block_events_ride_the_schema(self):
        from tpu_hpc.obs.schema import validate_record, stamp

        for action in ("alloc", "free", "cow", "prefix_hit"):
            validate_record(stamp({
                "event": "kv_block", "action": action, "n": 2,
                "slot": 1,
            }))

    def test_summary_fields_flow_to_report_and_gate(self):
        """paged_summary -> serve_summary -> report serve section ->
        regress namespace, with hit rate higher-is-better and
        block_stalls lower-is-better."""
        from tpu_hpc.obs.regress import lower_is_better
        from tpu_hpc.obs.report import _serve

        assert not lower_is_better("serve.prefix_hit_rate")
        assert lower_is_better("serve.block_stalls")
        rec = {
            "event": "serve_summary", "requests": 2, "tokens": 4,
            "tokens_per_s": 1.0, "kv_layout": "paged",
            "kv_block_size": 4, "kv_blocks": 16,
            "kv_blocks_free_min": 3, "prefix_hit_rate": 0.5,
            "prefix_hits": 1, "prefix_hit_blocks": 3,
            "prefill_chunks": 4,
            "batcher": {"block_stalls": 2},
        }
        out = _serve([rec])
        assert out["prefix_hit_rate"] == 0.5
        assert out["block_stalls"] == 2
        assert out["kv_layout"] == "paged"

    def test_block_occupancy_excludes_trie_parked_pages(
        self, tiny_params, serve_mesh
    ):
        """Occupancy is the admission policy's shed input: it must
        count pages held by LIVE requests only -- the trie's parked
        pages are a reclaimable cache, and counting them would read
        as permanent saturation once the trie warms (review
        finding)."""
        engine = PagedEngine(
            tiny_params, TINY, SERVE, serve_mesh,
            PagedConfig(block_size=4, num_blocks=16),
        )
        engine.warmup()
        rng = np.random.default_rng(14)
        prompt = rng.integers(0, TINY.vocab_size, size=12).tolist()
        batcher = ContinuousBatcher(engine)
        batcher.submit(
            Request(rid="a", prompt=prompt, max_new_tokens=6)
        )
        batcher.step()  # request still mid-flight: pages are live
        assert engine.block_occupancy > 0.0
        batcher.run()
        # Trie still holds the prompt's pages...
        assert engine.allocator.used_blocks > 0
        # ...but nothing live references the pool.
        assert engine.block_occupancy == 0.0
        assert batcher.occupancy == 0.0

    def test_scratch_block_reserved(self):
        alloc = BlockAllocator(8)
        taken = alloc.alloc(7)
        assert SCRATCH_BLOCK not in taken
        with pytest.raises(BlockBudgetError):
            alloc.alloc(1)
        alloc.release(taken)
        alloc.check_invariant()


class TestPagedDisagg:
    def test_paged_disagg_parity_hits_and_compile_pin(
        self, tiny_params, greedy_oracle
    ):
        """The cross-tier hop ships block tables + referenced pages:
        token parity (including a prompt LONGER than the largest
        bucket, which only chunked paged mode can serve), a
        prefill-tier prefix hit, and zero steady-state recompiles."""
        from tpu_hpc.serve.disagg import (
            DisaggEngine,
            split_serving_meshes,
        )

        pm, dm = split_serving_meshes(8, TINY)
        scfg = ServeConfig(
            slots=2, max_seq_len=48, prefill_buckets=(8, 16)
        )
        engine = DisaggEngine(
            tiny_params, TINY, scfg, pm, dm,
            paged=PagedConfig(block_size=4, num_blocks=32,
                              prefill_chunk=8),
        )
        warmed = engine.warmup()
        rng = np.random.default_rng(13)
        shapes = [(5, 3), (11, 4), (18, 2)]  # 18 > largest bucket
        reqs = [
            Request(
                rid=f"d{i}",
                prompt=rng.integers(
                    0, TINY.vocab_size, size=p
                ).tolist(),
                max_new_tokens=m,
            )
            for i, (p, m) in enumerate(shapes)
        ]
        batcher, got = _drain(engine, reqs)
        for r in reqs:
            assert got[r.rid] == greedy_oracle(
                r.prompt, r.max_new_tokens
            ), r.rid
        assert engine.transfer_stats["kv_transfers"] > 0
        assert engine.compile_count == warmed
        # Prefill-tier prefix hit on a repeat, still exact.
        _, again = _drain(
            engine,
            [Request(rid="hit", prompt=reqs[0].prompt,
                     max_new_tokens=3)],
        )
        assert again["hit"] == greedy_oracle(reqs[0].prompt, 3)
        assert engine.paged_summary()["prefix_hits"] >= 1
        assert engine.compile_count == warmed


class TestPagedReplayCLI:
    def test_paged_flags_end_to_end(self, capsys):
        from tpu_hpc.serve import server

        rc = server.main([
            "--requests", "4", "--max-new", "2", "--slots", "2",
            "--buckets", "8", "--prompt-lens", "3,6", "--vocab", "64",
            "--paged", "--kv-block-size", "4",
        ])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert summary["kv_layout"] == "paged"
        assert summary["recompiles"] == 0
        assert summary["kv_block_size"] == 4
        # bucket(8) + decode (one program: its row step walks the
        # tables, no flat rung) + copy_block
        assert summary["compiled_programs"] == 3

    def test_misplaced_paged_flags_are_cli_errors(self):
        from tpu_hpc.serve import server

        for flags in (
            ["--kv-block-size", "4"],
            ["--kv-blocks", "16"],
            ["--prefill-chunk", "8"],
        ):
            with pytest.raises(SystemExit):
                server.main(["--requests", "1", *flags])
        # Misaligned sizing fails at parse, not post-bring-up.
        with pytest.raises(SystemExit):
            server.main([
                "--paged", "--kv-block-size", "5", "--buckets", "8",
            ])
