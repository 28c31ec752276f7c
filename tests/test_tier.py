"""The host-DRAM KV page tier (serve/tier.py): cross-tier page
accounting, trie spill/refill semantics, and the engine round trip.

Four invariant families:
  * **cross-tier accounting** -- a property suite over random
    alloc/release/spill/refill/drop streams: ``scratch + free +
    referenced + host == total`` after EVERY op; spilling a page a
    live request still shares is refused (the next decode gather
    would read a recycled page);
  * **trie spill semantics** -- ``spillable`` is leaf-first and
    refcount-guarded, ``match`` stops at the first host-resident
    node, ``spilled_chain`` walks in chain order, re-insert ADOPTS
    the recomputed device page (dropping the stale host copy), and
    ``evict`` drops host-resident leaves to expose device parents;
  * **token exactness** -- a prompt whose whole parked chain was
    spilled to host DRAM decodes token-exact against the no-cache
    oracle after the prefetch refill, with the prefix hit counted;
  * **compile discipline** -- the tier's gather/scatter programs
    build at warmup through the engine's executable table, and the
    spill -> refill round trip adds ZERO executables.

All on the 8-device simulated mesh (KV heads shard over ``model``,
host buffers are plain numpy), fp32 so "token-exact" means exact.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hpc.loadgen.scenarios import SCENARIOS, build_scenario
from tpu_hpc.models import llama2
from tpu_hpc.runtime import MeshSpec, build_mesh
from tpu_hpc.serve import (
    BlockAllocator,
    BlockBudgetError,
    ContinuousBatcher,
    PagedConfig,
    PagedEngine,
    PrefixTrie,
    Request,
    ServeConfig,
)
from tpu_hpc.serve.tier import HostTier


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


_ORACLE_LEN = 48


@pytest.fixture(scope="module")
def greedy_oracle(tiny_params):
    """Greedy continuation via the full NO-CACHE forward pass -- the
    same fixed-padded-length oracle tests/test_paging.py pins the
    paged engine against."""
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


@pytest.fixture(scope="module")
def tiered(tiny_params, serve_mesh):
    """One SMALL tiered engine serves the whole module: a 15-usable-
    page pool over a 15-slot host tier, so pool pressure (and the
    spill path) is reachable with a handful of requests."""
    engine = PagedEngine(
        tiny_params, TINY, SERVE, serve_mesh,
        PagedConfig(
            block_size=4, num_blocks=16, prefill_chunk=8,
            host_blocks=16,
        ),
    )
    warmed = engine.warmup()
    return engine, warmed


def _drain(engine, reqs):
    batcher = ContinuousBatcher(engine)
    return batcher, batcher.run(reqs)


# ---------------------------------------------------------------------
# Cross-tier page accounting: the property suite
# ---------------------------------------------------------------------


class TestHostTierAllocator:
    def test_spill_refill_roundtrip_holds_invariant(self):
        alloc = BlockAllocator(8, host_blocks=4)
        blocks = alloc.alloc(3)
        slots = []
        for b in blocks:
            slots.append(alloc.spill(b))
            alloc.check_invariant()
        assert alloc.host_used_slots == 3
        assert alloc.free_blocks == 7  # device pages all came back
        back = [alloc.refill(s) for s in slots]
        alloc.check_invariant()
        assert alloc.host_used_slots == 0
        assert all(alloc.refcount(b) == 1 for b in back)
        alloc.release(back)
        alloc.check_invariant()

    def test_spill_of_shared_live_page_refused(self):
        """The PR-8 shared-leaf lesson applied to spill: a page a live
        request still reads through its block table must stay in HBM,
        or the next decode gather reads a recycled page."""
        alloc = BlockAllocator(8, host_blocks=4)
        (b,) = alloc.alloc(1)
        alloc.retain([b])  # the live request's share
        with pytest.raises(ValueError, match="shared block"):
            alloc.spill(b)
        alloc.check_invariant()
        alloc.release([b])
        alloc.release([b])

    def test_spill_with_host_full_raises_budget_error(self):
        alloc = BlockAllocator(8, host_blocks=2)  # 1 resident slot
        b1, b2 = alloc.alloc(2)
        alloc.spill(b1)
        with pytest.raises(BlockBudgetError, match="host tier full"):
            alloc.spill(b2)
        alloc.check_invariant()

    def test_refill_and_drop_require_residency(self):
        alloc = BlockAllocator(8, host_blocks=4)
        with pytest.raises(ValueError, match="non-resident"):
            alloc.refill(1)
        with pytest.raises(ValueError, match="non-resident"):
            alloc.host_drop(1)
        (b,) = alloc.alloc(1)
        slot = alloc.spill(b)
        alloc.host_drop(slot)
        assert alloc.host_drops == 1
        with pytest.raises(ValueError, match="non-resident"):
            alloc.host_drop(slot)
        alloc.check_invariant()

    def test_single_slot_host_tier_rejected(self):
        # Slot 0 is scratch: a 1-slot tier could never hold a page.
        with pytest.raises(ValueError, match="host_blocks"):
            BlockAllocator(8, host_blocks=1)

    def test_random_cross_tier_stream_never_leaks(self):
        """The allocator invariant under a random operation stream
        spanning both tiers -- the test_paging property suite with
        spill/refill/host_drop in the op mix."""
        rng = np.random.default_rng(11)
        alloc = BlockAllocator(16, host_blocks=8)
        held = []     # device pages at refcount 1
        resident = []  # host slots
        for _ in range(600):
            op = rng.integers(0, 5)
            if op == 0 and alloc.free_blocks:
                n = int(rng.integers(
                    1, min(3, alloc.free_blocks) + 1
                ))
                held.extend(alloc.alloc(n))
            elif op == 1 and held:
                i = int(rng.integers(0, len(held)))
                alloc.release([held.pop(i)])
            elif op == 2 and held and alloc.host_free_slots:
                i = int(rng.integers(0, len(held)))
                resident.append(alloc.spill(held.pop(i)))
            elif op == 3 and resident and alloc.free_blocks:
                i = int(rng.integers(0, len(resident)))
                held.append(alloc.refill(resident.pop(i)))
            elif op == 4 and resident:
                i = int(rng.integers(0, len(resident)))
                alloc.host_drop(resident.pop(i))
            alloc.check_invariant()
        for s in resident:
            alloc.host_drop(s)
        alloc.release(held)
        alloc.check_invariant()
        assert alloc.free_blocks == 15
        assert alloc.host_free_slots == 7


# ---------------------------------------------------------------------
# Trie spill semantics
# ---------------------------------------------------------------------


def _spill_node(alloc, node):
    """What serve/tier.py does per page, minus the byte movement."""
    slot = alloc.spill(node.block)
    node.host = slot
    node.block = -1
    return slot


class TestTrieSpill:
    def _parked_chain(self, n_blocks=3, host_blocks=8):
        """A cached chain only the trie holds (the just-drained
        state): ``n_blocks`` full blocks of 2 tokens each."""
        alloc = BlockAllocator(16, host_blocks=host_blocks)
        trie = PrefixTrie(block_size=2)
        prompt = list(range(1, 2 * n_blocks + 1))
        blocks = alloc.alloc(n_blocks)
        trie.insert(prompt, blocks, alloc)
        alloc.release(blocks)  # park: only the trie's refs remain
        return alloc, trie, prompt, blocks

    def test_spillable_is_leaf_first_and_rewalk_reaches_parents(self):
        alloc, trie, prompt, blocks = self._parked_chain()
        # Only the leaf qualifies: inner nodes still have a device-
        # resident child, so spilling them would break the chain's
        # device-prefix/host-suffix shape.
        cands = trie.spillable(alloc)
        assert [n.block for n in cands] == [blocks[2]]
        _spill_node(alloc, cands[0])
        # Spilling the leaf exposes its parent -- the re-walk rule
        # serve/tier.py's spill_parked loop depends on.
        cands = trie.spillable(alloc)
        assert [n.block for n in cands] == [blocks[1]]
        alloc.check_invariant()

    def test_shared_page_never_offered_for_spill(self):
        alloc, trie, prompt, blocks = self._parked_chain()
        alloc.retain([blocks[2]])  # a live request shares the leaf
        assert trie.spillable(alloc) == []
        alloc.release([blocks[2]])
        assert len(trie.spillable(alloc)) == 1

    def test_match_stops_at_first_spilled_node(self):
        alloc, trie, prompt, blocks = self._parked_chain()
        for want_prefix in (blocks[:2], blocks[:1], []):
            _spill_node(alloc, trie.spillable(alloc)[0])
            assert trie.match(prompt) == want_prefix
        alloc.check_invariant()

    def test_spilled_chain_returns_chain_order(self):
        alloc, trie, prompt, blocks = self._parked_chain()
        # Spill leaf-first (the only legal order)...
        _spill_node(alloc, trie.spillable(alloc)[0])
        _spill_node(alloc, trie.spillable(alloc)[0])
        chain = trie.spilled_chain(prompt)
        # ...but the refill walk must go chain order (parent first):
        # match() extends the served prefix only through a refilled
        # parent.
        assert len(chain) == 2
        assert chain[0].host is not None and chain[1].host is not None
        assert trie.match(prompt) == blocks[:1]

    def test_reinsert_adopts_recomputed_page_and_drops_host_copy(self):
        alloc, trie, prompt, blocks = self._parked_chain()
        while trie.spillable(alloc):
            _spill_node(alloc, trie.spillable(alloc)[0])
        assert alloc.host_used_slots == 3
        # A same-prompt request re-prefilled the whole chain into its
        # own fresh pages (match() returned nothing): insert adopts
        # them and the stale host copies drop.
        fresh = alloc.alloc(3)
        assert trie.insert(prompt, fresh, alloc) == 0  # no new nodes
        assert alloc.host_drops == 3
        assert alloc.host_used_slots == 0
        assert trie.match(prompt) == fresh
        alloc.release(fresh)
        alloc.check_invariant()

    def test_evict_drops_spilled_leaves_to_expose_parents(self):
        alloc, trie, prompt, blocks = self._parked_chain(n_blocks=2)
        _spill_node(alloc, trie.spillable(alloc)[0])
        free_before = alloc.free_blocks
        # No device-resident leaf exists (the leaf is host-resident),
        # yet the parent's HBM page must still be reclaimable: evict
        # drops the spilled leaf, re-walks, and frees the parent.
        assert trie.evict(alloc, 1) == 1
        assert alloc.free_blocks == free_before + 1
        assert alloc.host_drops == 1
        assert trie.nodes == 0
        alloc.check_invariant()


# ---------------------------------------------------------------------
# Engine round trip: token exactness + compile discipline
# ---------------------------------------------------------------------


class TestHostTierEngine:
    def test_warmup_compiles_tier_programs_through_engine_table(
        self, tiered
    ):
        engine, warmed = tiered
        # Buckets + the decode ladder (two flat rungs, the
        # rectangle) + copy_block (the test_paging pin) plus the
        # tier's spill gather + refill scatter -- same table, same
        # counter, so the steady-state pins below cover the tier.
        assert warmed == len(SERVE.prefill_buckets) + 2 + 2 + 2
        assert engine.host_tier is not None
        assert engine.host_tier.group >= 1
        # "auto" sized the transfer group from the topology cost
        # tables (comm/planner.py), not a hardcoded constant.
        assert engine.host_tier.inflight_source == "planner"
        assert engine.host_tier.max_inflight_bytes > 0

    def test_spill_refill_round_trip_token_exact_zero_recompile(
        self, tiered, greedy_oracle
    ):
        """The tentpole acceptance: serve, park, spill the WHOLE
        chain to host DRAM, return with the same prompt -- the
        prefetch refills, the decode is token-exact, and no new
        executable was built."""
        engine, warmed = tiered
        rng = np.random.default_rng(21)
        prompt = rng.integers(0, TINY.vocab_size, size=16).tolist()
        want = greedy_oracle(prompt, 4)
        _, first = _drain(
            engine,
            [Request(rid="first", prompt=prompt, max_new_tokens=4)],
        )
        assert first["first"] == want
        parked = engine.allocator.used_blocks
        assert parked == 4  # 16 prompt tokens / 4-token pages
        # spill_parked's re-walk must drain the whole chain even
        # though spillable() only offers one layer per pass.
        assert engine.host_tier.spill_parked(parked) == parked
        engine.allocator.check_invariant()
        assert engine.allocator.host_used_slots == parked
        assert engine.allocator.used_blocks == 0
        # A spilled page has no device id to share until the refill.
        assert engine.trie.match(prompt) == []
        hits = engine.paged_stats["prefix_hits"]
        batcher, again = _drain(
            engine,
            [Request(rid="again", prompt=prompt, max_new_tokens=4)],
        )
        assert again["again"] == want
        assert engine.paged_stats["prefix_hits"] == hits + 1
        t = engine.host_tier.stats
        assert t["kv_spill_pages"] == parked
        assert t["kv_refill_pages"] == parked
        assert t["kv_spill_wire_bytes"] > 0
        assert t["kv_refill_wire_bytes"] > 0
        assert engine.allocator.host_used_slots == 0
        engine.allocator.check_invariant()
        # Zero steady-state recompiles across the whole round trip.
        assert engine.compile_count == warmed
        # The batcher folds the tier's counters into its stats (what
        # the serve summary and the banked regress rows read).
        assert batcher.stats["kv_refill_pages"] == parked

    def test_paged_summary_carries_the_tier_block(self, tiered):
        engine, _ = tiered
        s = engine.paged_summary()
        assert s["kv_host_blocks"] == 16
        assert s["kv_host_inflight_source"] == "planner"
        for key in (
            "kv_host_used", "kv_host_free", "kv_host_drops",
            "kv_host_inflight_bytes", "kv_spills", "kv_spill_pages",
            "kv_spill_wire_bytes", "kv_refills", "kv_refill_pages",
            "kv_refill_wire_bytes", "kv_hop_ms_p50", "kv_hop_ms_p95",
        ):
            assert key in s, key

    def test_prefetch_and_headroom_precheck(self, tiered):
        engine, _ = tiered
        # Nothing spilled on this prompt's chain: the prefetch is a
        # cheap no-op, not an error.
        assert engine.prefetch_prompt([7] * 12) == 0
        assert engine.admission_headroom([1] * 8, 4)
        # More pages than the whole pool holds: the scheduler skips
        # the prefetch hop for a request about to block-stall anyway.
        assert not engine.admission_headroom([1] * 44, 20)

    def test_admission_pressure_spills_before_evicting(
        self, tiered, greedy_oracle
    ):
        """Distinct prompts overflow the 15-page pool: admission must
        SPILL parked chains (cheap hop on return) instead of evicting
        them (full re-prefill), and every stream stays exact."""
        engine, warmed = tiered
        evictions_before = engine.paged_stats["trie_evictions"]
        spills_before = engine.host_tier.stats["kv_spills"]
        rng = np.random.default_rng(31)
        reqs = [
            Request(
                rid=f"p{i}",
                prompt=rng.integers(
                    0, TINY.vocab_size, size=8 + (4 * i) % 8
                ).tolist(),
                max_new_tokens=1 + i % 3,
            )
            for i in range(8)
        ]
        _, got = _drain(engine, reqs)
        for r in reqs:
            assert got[r.rid] == greedy_oracle(
                r.prompt, r.max_new_tokens
            ), r.rid
        assert engine.host_tier.stats["kv_spills"] > spills_before
        # The host tier absorbed the pressure the evictor used to.
        assert (
            engine.paged_stats["trie_evictions"] == evictions_before
        )
        engine.allocator.check_invariant()
        assert engine.compile_count == warmed

    def test_reset_pool_flushes_the_tier(self, tiered):
        """The weight-swap contract: host pages encode old-weight
        K/V too, so reset_pool must flush them with the pool."""
        engine, _ = tiered
        assert engine.host_tier.stats["kv_spill_pages"] > 0
        engine.reset_pool()
        assert engine.allocator.host_used_slots == 0
        assert engine.allocator.host_drops == 0
        assert all(v == 0 for v in engine.host_tier.stats.values())
        engine.allocator.check_invariant()


class TestTierConfig:
    def test_single_slot_tier_rejected(self):
        with pytest.raises(ValueError, match="host_blocks"):
            PagedConfig(block_size=4, num_blocks=16, host_blocks=1)

    def test_tier_requires_prefix_cache(self):
        # A pool with no trie has nothing parked to spill.
        with pytest.raises(ValueError, match="prefix_cache"):
            PagedConfig(
                block_size=4, num_blocks=16, host_blocks=16,
                prefix_cache=False,
            )

    def test_host_tier_refuses_trieless_engine(
        self, tiny_params, serve_mesh
    ):
        engine = PagedEngine(
            tiny_params, TINY, SERVE, serve_mesh,
            PagedConfig(
                block_size=4, num_blocks=16, prefix_cache=False
            ),
        )
        with pytest.raises(ValueError, match="prefix trie"):
            HostTier(engine)


# ---------------------------------------------------------------------
# The acceptance scenario (loadgen/scenarios.py)
# ---------------------------------------------------------------------


class TestLongIdleScenario:
    def test_registered_and_deterministic(self):
        assert "long_idle_sessions" in SCENARIOS
        a = build_scenario(
            "long_idle_sessions", seed=5, n_requests=24,
            max_prompt=16, max_new=8,
        )
        b = build_scenario(
            "long_idle_sessions", seed=5, n_requests=24,
            max_prompt=16, max_new=8,
        )
        assert a.requests == b.requests
        assert a.tenants == b.tenants

    def test_three_phases_and_return_prompts_extend_first_visits(
        self,
    ):
        sc = build_scenario(
            "long_idle_sessions", seed=5, n_requests=24,
            max_prompt=16, max_new=8,
        )
        assert {t.name for t in sc.tenants} == {
            "chat", "filler", "return"
        }
        # The tight backlog bound IS the acceptance signal: an
        # unbounded queue would absorb the shed-vs-zero-shed
        # contrast.
        assert sc.queue_limit == max(2, 24 // 8)
        by = {
            name: [r for r in sc.requests if r.tenant == name]
            for name in ("chat", "filler", "return")
        }
        assert all(len(v) == 8 for v in by.values())
        # Idle gaps separate the waves: every filler arrives after
        # every first visit, every return after every filler.
        assert max(r.arrival_ms for r in by["chat"]) < min(
            r.arrival_ms for r in by["filler"]
        )
        assert max(r.arrival_ms for r in by["filler"]) < min(
            r.arrival_ms for r in by["return"]
        )
        arrivals = [r.arrival_ms for r in sc.requests]
        assert arrivals == sorted(arrivals)
        # Every return replays a first-visit prompt plus a short new
        # turn -- the prefix the trie (or the host tier) must serve.
        firsts = {tuple(r.prompt) for r in by["chat"]}
        for r in by["return"]:
            assert any(
                len(r.prompt) > len(f)
                and tuple(r.prompt[:len(f)]) == f
                for f in firsts
            ), r.rid
