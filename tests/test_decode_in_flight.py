"""One decode step in flight (``PagedEngine.decode`` /
``ContinuousBatcher._tick``, PR 28): step k+1 is dispatched from the
tokens step k left on the device, before the host has fetched them.

The oracle is the SAME engine behind a synchronous ``decode`` (lag 0:
dispatch, fetch, emit, next tick), so every difference is the lag's:

  * the lagged tick is token-exact, request for request, over a trace
    with ends by length, an end of sequence in mid-stream, admissions
    into slots and pages that were just freed, a shared prefix, a
    forced copy-on-write, for dense MHA, dense GQA and a sparse-expert
    decoder (whose counters also agree);
  * on a steady tick the next step is dispatched before the last one's
    tokens are fetched (span ring), and the engine counts it;
  * the slot-step computed past an end of sequence is dropped, counted,
    and harms nobody;
  * ``done``, ``run()`` and a tick with nothing to decode take the step
    in flight off the engine: no token lost, none emitted twice;
  * ``probe_selection`` reads the tokens ``decode`` will read.

CPU, float32, tiny sizes: tokens and counts, never a time.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tpu_hpc import obs
from tpu_hpc.models import llama2, sparse_moe
from tpu_hpc.obs.events import EventBus, set_bus
from tpu_hpc.serve import (
    ContinuousBatcher,
    PagedConfig,
    PagedEngine,
    Request,
    ServeConfig,
    paging,
)

BLOCK = 4
TOPK = 16
SERVE = ServeConfig(slots=3, max_seq_len=64, prefill_buckets=(8, 16))
_DENSE = llama2.LlamaConfig(
    dim=64, n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=128,
    multiple_of=16, max_seq_len=64, dtype=jnp.float32,
)
CONFIGS = {
    "mha": _DENSE,
    "gqa": dataclasses.replace(_DENSE, n_kv_heads=2),
    "sparse": sparse_moe.SparseMoEConfig(
        name="tiny-sparse", dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=32, vocab_size=128, max_seq_len=64, n_experts=8,
        experts_per_token=2, expert_hidden=48, indexer_heads=2,
        indexer_head_dim=16, indexer_rope_dim=8, indexer_topk=TOPK,
        dtype=jnp.float32, param_dtype=jnp.float32,
    ),
}
COUNTERS = [name for name, _ in paging.DECODE_COUNTERS]
OVERLAPPED, DISCARDED, *VIEW_PAGES = COUNTERS
LADDER = [name for name, _ in paging.LADDER_COUNTERS]


@pytest.fixture(scope="module")
def mesh(devices):
    return Mesh(np.array(devices[:1]), ("data",))


def _engine(name, mesh):
    cfg = CONFIGS[name]
    init = sparse_moe.init_sparse_moe if sparse_moe.is_sparse_moe(cfg) \
        else llama2.init_llama
    eng = PagedEngine(
        jax.jit(lambda key: init(key, cfg))(jax.random.key(3)), cfg, SERVE,
        mesh, PagedConfig(block_size=BLOCK, num_blocks=3 * 16 + 1,
                          prefill_chunk=8),
    )
    eng.warmup()
    return eng


@pytest.fixture(scope="module", params=list(CONFIGS))
def engine(request, mesh):
    return _engine(request.param, mesh)


@pytest.fixture(scope="module")
def gqa(mesh):
    return _engine("gqa", mesh)


@pytest.fixture
def ring():
    """A fresh bus with no sink: spans land in its ring only."""
    bus = EventBus(path="", ring_size=4096)
    prev = set_bus(bus)
    yield bus
    set_bus(prev)


class Synchronous:
    """The engine as one that keeps nothing in flight looks to the
    batcher: ``decode`` returns its own step's tokens, lag 0."""

    decode_lag = 0

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def decode(self, tokens, positions, active=None):
        return self._engine.decode_now(tokens, positions, active)


def _drive(engine, requests, cow=None):
    """``requests`` through a fresh batcher, tick by tick, on an empty
    pool -> (batcher, what ``paged_stats`` grew by). ``cow`` = (rid,
    position): before the tick that writes that position a second
    owner appears on the request's write-target page."""
    engine.reset_pool()
    peaks = [k for k in engine.paged_stats if k.startswith("serve_moe_max")]
    for k in peaks:
        engine.paged_stats[k] = 0
    before = dict(engine.paged_stats)
    batcher = ContinuousBatcher(engine)
    for req in requests:
        batcher.submit(req)
    shared, ticks = None, 0
    while not batcher.done:
        if cow is not None and shared is None:
            for idx, slot in enumerate(batcher.slots):
                if (slot.rid, slot.pos) == cow and slot.decoding:
                    shared = engine.slot_state(idx).blocks[
                        slot.pos // BLOCK
                    ]
                    engine.allocator.retain([shared])
        batcher.step()
        ticks += 1
        assert ticks < 400, "the batcher does not drain"
    if shared is not None:
        engine.allocator.release([shared])
    engine.allocator.check_invariant()
    return batcher, {
        k: v - before[k] for k, v in engine.paged_stats.items()
    }


def _trace(eos=None):
    """Nine seeded requests for three slots: two of them open with
    r2's first three pages; ``eos`` = {rid: token}."""
    rng = np.random.default_rng(28)
    lens = [3, 9, 17, 5, 12, 20, 7, 14, 4]
    news = [6, 2, 9, 1, 12, 5, 8, 3, 10]
    prompts = [rng.integers(0, 128, n).tolist() for n in lens]
    for k in (4, 7):
        prompts[k][:12] = prompts[2][:12]
    return [
        Request(rid=f"r{k}", prompt=p, max_new_tokens=n,
                eos_id=(eos or {}).get(f"r{k}"))
        for k, (p, n) in enumerate(zip(prompts, news))
    ]


def _per_request(grown):
    """The counts that do not depend on which requests share a step."""
    return {k: v for k, v in grown.items() if k not in (
        "decode_steps", OVERLAPPED, *VIEW_PAGES, *LADDER,
        "serve_moe_experts_touched_total",
        "serve_moe_experts_read_total",
        "serve_moe_max_tokens_per_expert",
    )}


def _but_gaps(stats):
    return {k: v for k, v in stats.items() if "_gap_" not in k}


def _gap_tokens(stats):
    return sum(v for k, v in stats.items() if "_gap_tokens_" in k)


# -- (a) token-exact against the synchronous tick ------------------------
def test_every_count_agrees_where_the_steps_are_the_same(engine):
    """As many requests as slots, ends by length: both ticks run the
    same steps, so every count agrees (a sparse-expert engine's six
    among them) but the overlap's own."""
    requests = _trace()[4:7]
    sync, sync_grown = _drive(Synchronous(engine), requests)
    lag, lag_grown = _drive(engine, requests)
    assert lag.results == sync.results
    assert sync_grown.pop(OVERLAPPED) == 0
    assert lag_grown.pop(OVERLAPPED) == lag_grown["decode_steps"] - 1
    assert lag_grown == sync_grown
    # The filing of token gaps (PR 35) is the lag's own too: which
    # emission a chunk is ahead of, and the wall between emissions.
    assert _but_gaps(lag.stats) == _but_gaps(sync.stats)
    assert _gap_tokens(lag.stats) == _gap_tokens(sync.stats) > 0


def test_lagged_tick_is_token_exact_against_the_synchronous_one(engine):
    cow = ("r4", 12 + 3)
    sync, sync_grown = _drive(Synchronous(engine), _trace(), cow)
    lag, lag_grown = _drive(engine, _trace(), cow)
    whole = sync.results
    assert [len(whole[f"r{k}"]) for k in range(9)] == [
        6, 2, 9, 1, 12, 5, 8, 3, 10
    ]
    assert lag.results == whole
    # Ends by length only: the same slot-steps ran. A slot whose last
    # step is in flight is held one tick longer, so its next tenant
    # joins one step later and the steps are composed otherwise: what
    # is counted a request agrees, what is counted a step need not.
    assert sync_grown[OVERLAPPED] == 0 < lag_grown[OVERLAPPED]
    assert _per_request(lag_grown) == _per_request(sync_grown)
    assert lag_grown["prefix_hits"] >= 1 and lag_grown["cow_copies"] == 1
    assert lag_grown[DISCARDED] == 0
    assert lag.stats["admitted"] == 9 == lag.stats["evicted"]

    # The same trace with an end of sequence in mid-stream: r2 stops
    # at the first token of its answer that it has not said before.
    stream = whole["r2"]
    cut = next(
        j for j in range(2, len(stream) - 1) if stream[j] not in stream[:j]
    )
    eos = {"r2": stream[cut]}
    sync, sync_grown = _drive(Synchronous(engine), _trace(eos), cow)
    lag, lag_grown = _drive(engine, _trace(eos), cow)
    assert sync.results == {**whole, "r2": stream[:cut + 1]}
    assert lag.results == sync.results
    assert (sync_grown[DISCARDED], lag_grown[DISCARDED]) == (0, 1)
    assert lag_grown["decode_steps"] >= sync_grown["decode_steps"]
    if "serve_moe_assignments_total" in lag_grown:
        # What the dropped slot-step added: its experts, layer by layer.
        cfg = engine.cfg
        assert (
            lag_grown["serve_moe_assignments_total"]
            - sync_grown["serve_moe_assignments_total"]
        ) == cfg.n_layers * cfg.experts_per_token
        assert lag_grown["serve_moe_dropped_total"] == 0


# -- (b) the next step goes before the last one's tokens come ------------
def test_a_steady_tick_dispatches_before_it_fetches(gqa, ring):
    steps = 50
    rng = np.random.default_rng(5)
    requests = [
        Request(rid=f"b{k}", prompt=rng.integers(0, 128, 5 + k).tolist(),
                max_new_tokens=steps + 1)
        for k in range(SERVE.slots)
    ]
    batcher, grown = _drive(gqa, requests)
    assert all(len(v) == steps + 1 for v in batcher.results.values())
    spans = [r for r in ring.ring() if r["event"] == "span"]
    dispatch = [s for s in spans if s["name"] == "decode.dispatch"]
    fetch = [s for s in spans if s["name"] == "decode.fetch"]
    assert len(dispatch) == len(fetch) == steps
    # Nothing is fetched between the first two dispatches, then one
    # step's tokens between any two, and the last step's by the flush.
    order = [
        s["name"] for s in spans
        if s["name"] in ("decode.dispatch", "decode.fetch", "prefill.fetch")
    ]
    assert order == ["prefill.fetch"] * SERVE.slots + ["decode.dispatch"] \
        + ["decode.dispatch", "decode.fetch"] * (steps - 1) \
        + ["decode.fetch"]
    for k in range(steps - 1):
        # step k's tokens are asked for only once step k+1 is queued
        opened = fetch[k]["t_mono"] - fetch[k]["dur_s"]
        assert dispatch[k + 1]["t_mono"] <= opened
    # Every fetch but the last (the flush) sits inside a ``decode``.
    assert [s.get("parent") for s in fetch] == \
        ["decode"] * (steps - 1) + ["tick"]
    assert grown["decode_steps"] == steps
    assert grown[OVERLAPPED] / grown["decode_steps"] > 0.9
    counters = obs.get_registry().snapshot()["counters"]
    assert counters[OVERLAPPED] >= grown[OVERLAPPED]


# -- (c) the step past an end of sequence ---------------------------------
def test_a_dropped_step_leaves_the_next_tenant_alone(gqa):
    """Three slots are full and ``late`` waits; ``early`` ends on an
    end of sequence, so one step more was dispatched for it: into a
    page ``late`` is then given."""
    rng = np.random.default_rng(6)
    prompts = {
        rid: rng.integers(0, 128, n).tolist()
        for rid, n in (("early", 6), ("f1", 9), ("f2", 4), ("late", 7))
    }

    def requests(eos=None):
        return [
            Request(rid=rid, prompt=p, eos_id=eos if rid == "early" else None,
                    max_new_tokens={"early": 20, "late": 14}.get(rid, 40))
            for rid, p in prompts.items()
        ]

    whole = _drive(Synchronous(gqa), requests())[0].results["early"]
    cut = next(j for j in range(3, 19) if whole[j] not in whole[:j])
    want = _drive(Synchronous(gqa), requests(whole[cut]))[0].results
    assert want["early"] == whole[:cut + 1]

    gqa.reset_pool()
    before = gqa.paged_stats[DISCARDED]
    batcher = ContinuousBatcher(gqa)
    for req in requests(whole[cut]):
        batcher.submit(req)
    batcher.step()
    slot = next(i for i, s in enumerate(batcher.slots) if s.rid == "early")
    pages = list(gqa.slot_state(slot).blocks)
    while batcher.slots[slot].rid == "early":
        batcher.step()
    assert batcher.results["early"] == want["early"]
    assert gqa.paged_stats[DISCARDED] == before + 1
    batcher.step()
    # ``late`` sits where ``early`` sat, on the page the dropped step
    # wrote (the row after the end of sequence's own).
    assert batcher.slots[slot].rid == "late"
    dropped_row = len(prompts["early"]) + cut
    assert pages[dropped_row // BLOCK] in gqa.slot_state(slot).blocks
    assert batcher.run() == want
    assert gqa.paged_stats[DISCARDED] == before + 1


# -- (d) nothing stays in flight behind the batcher's back ----------------
def test_done_run_and_an_idle_tick_flush_the_step_in_flight(gqa):
    rng = np.random.default_rng(7)
    first = [
        Request(rid="x", prompt=rng.integers(0, 128, 3).tolist(),
                max_new_tokens=2),
        Request(rid="y", prompt=rng.integers(0, 128, 20).tolist(),
                max_new_tokens=4),
    ]
    second = [
        Request(rid=f"z{k}", prompt=rng.integers(0, 128, 6 + k).tolist(),
                max_new_tokens=3 + k)
        for k in range(4)
    ]
    oracle = ContinuousBatcher(Synchronous(gqa))
    gqa.reset_pool()
    want = dict(oracle.run(first))
    want.update(oracle.run(second))

    gqa.reset_pool()
    batcher = ContinuousBatcher(gqa)
    for req in first:
        batcher.submit(req)
    batcher.step()      # x: its chunk, its first token, its one step
    assert batcher.results["x"] == want["x"][:1]
    batcher.step()      # y still prefills, nobody decodes: x's token
    assert batcher.results["x"] == want["x"]
    assert "y" not in batcher.results
    # ``done`` takes what is in flight; after it the engine holds none.
    while batcher.active or batcher.pending:
        batcher.step()
    assert batcher.done and gqa.flush() is None
    assert batcher.results == {k: want[k] for k in "xy"}
    # Idle, then busy again: the first step back reads host tokens.
    assert batcher.run(second) == want
    assert gqa.flush() is None
    assert [len(want[r.rid]) for r in first + second] == [
        r.max_new_tokens for r in first + second
    ]


def test_done_takes_a_dropped_step_off_the_engine(gqa):
    """The one thing that can be in flight when no slot is held: the
    step past an end of sequence."""
    prompt = np.random.default_rng(8).integers(0, 128, 5).tolist()
    whole = _drive(
        Synchronous(gqa), [Request(rid="e", prompt=prompt, max_new_tokens=9)]
    )[0].results["e"]
    cut = next(j for j in range(2, 8) if whole[j] not in whole[:j])
    gqa.reset_pool()
    batcher = ContinuousBatcher(gqa)
    batcher.submit(Request(rid="e", prompt=prompt, max_new_tokens=9,
                           eos_id=whole[cut]))
    while batcher.active or batcher.pending:
        batcher.step()
    assert batcher.results["e"] == whole[:cut + 1]
    assert batcher.done
    assert gqa.flush() is None


# -- (e) the probe reads what the step will read --------------------------
@pytest.mark.parametrize("engine", ["sparse"], indirect=True)
def test_probe_selection_sees_the_tokens_decode_will_use(engine):
    prompt = np.random.default_rng(11).integers(0, 128, 37).tolist()
    active = [True, False, False]

    def start():
        engine.reset_pool()
        info = engine.admit(0, prompt, 6)
        for _ in range(info["chunks"]):
            first = engine.prefill_step(0)
        return first

    # Synchronously: every step's token is on the host before the next.
    first = start()
    t1 = int(engine.decode_now([first, 0, 0], [37, 0, 0], active)[0])
    want = engine.probe_selection([t1, 0, 0], [38, 0, 0], active)
    t2 = int(engine.decode_now([t1, 0, 0], [38, 0, 0], active)[0])
    other = engine.probe_selection([t1 ^ 1, 0, 0], [38, 0, 0], active)
    assert (other != want).any(), "the selection does not see the token"
    engine.release(0)

    # One step in flight: t1 exists on the device only, the host's
    # entry is the stale ``first``, and probe and step agree on t1.
    assert start() == first
    assert engine.decode([first, 0, 0], [37, 0, 0], active) is None
    got = engine.probe_selection([first, 0, 0], [38, 0, 0], active)
    assert (got == want).all()
    assert int(engine.decode([first, 0, 0], [38, 0, 0], active)[0]) == t1
    assert int(engine.flush()[0]) == t2
    engine.release(0)
