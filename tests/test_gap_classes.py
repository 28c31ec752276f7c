"""Every token gap is filed under what the device ran ahead of it
(``ContinuousBatcher._file_gap``, ``serve_gap_*``; the ladder pair of
``PagedEngine.decode``).

The oracle is a stub engine over a DEVICE MODEL on a virtual clock: one
queue that runs what it is handed in order, a decode step of 20 ms and
a prefill chunk of 14 ms, a host that costs nothing and waits only
where the engine fetches. On it every gap between two emissions is
exactly ``step + chunks x chunk`` for the chunks the rule files with
the later one, so a filing that is off by a tick shows as a class whose
seconds do not add up. The stub keeps one step in flight (``decode_lag``
1, as ``PagedEngine``) and its ``prefill_step`` returns ``None`` or a
token; ``loadgen``'s cost-model engine over it is the lag-0 case.

CPU, virtual clock: counts and modelled seconds, never a device time.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tpu_hpc.loadgen.harness import (
    VirtualClock,
    _CostModelEngine,
    parse_faults,
)
from tpu_hpc.models import llama2
from tpu_hpc.obs.events import EventBus, set_bus
from tpu_hpc.obs.registry import MetricsRegistry, set_registry
from tpu_hpc.obs.schema import validate_record
from tpu_hpc.serve import (
    ContinuousBatcher,
    PagedConfig,
    PagedEngine,
    Request,
    ServeConfig,
    paging,
)
from tpu_hpc.serve.metrics import ServeMeter
from tpu_hpc.serve.scheduler import GAP_CLASSES, GAP_COUNTERS

STEP_S, CHUNK_S = 0.020, 0.014
CHUNK = 8          # prompt tokens a chunk program holds
EOS = 9999


class Device:
    """One in-order queue on the virtual clock."""

    def __init__(self, clock):
        self.clock, self.free_at = clock, 0.0

    def run(self, cost):
        self.free_at = max(self.clock(), self.free_at) + cost
        return self.free_at

    def wait(self, done_at):
        self.clock.advance(max(done_at - self.clock(), 0.0))


class StubEngine:
    """The paged protocol over ``Device``. A slot's token is its
    position + 1 (so an end of sequence is a position), or ``EOS``
    once the position reaches ``eos_at[slot]``."""

    is_paged = True
    spec = host_tier = None
    block_occupancy = 0.0

    def __init__(self, clock, slots=4, lag=1, step_s=STEP_S,
                 chunk_s=CHUNK_S):
        self.serve_cfg = ServeConfig(
            slots=slots, max_seq_len=4096, prefill_buckets=(CHUNK,)
        )
        self.decode_lag = lag
        self.device = Device(clock)
        self.step_s, self.chunk_s = step_s, chunk_s
        self.paged_stats = {"prefill_chunks": 0, "decode_steps": 0}
        self.prefill_forwarded_total = 0
        self.eos_at = {}
        self._left, self._prompt = {}, {}
        self._pending = None

    def validate_request(self, prompt_len, max_new, rid=None):
        pass

    def admit(self, idx, prompt, max_new):
        self._left[idx] = -(-len(prompt) // CHUNK)
        self._prompt[idx] = len(prompt)
        return {"planned_prefill_tokens": self._left[idx] * CHUNK}

    def prefill_step(self, idx):
        done_at = self.device.run(self.chunk_s)
        self.paged_stats["prefill_chunks"] += 1
        self.prefill_forwarded_total += CHUNK
        self._left[idx] -= 1
        if self._left[idx]:
            return None
        self.device.wait(done_at)
        return self._prompt[idx]

    def release(self, idx):
        self.eos_at.pop(idx, None)

    def decode(self, tokens, positions, active=None):
        out = np.array([
            EOS if p >= self.eos_at.get(s, 1 << 30) else p + 1
            for s, p in enumerate(positions)
        ])
        before, self._pending = self._pending, (
            self.device.run(self.step_s), out
        )
        if not self.decode_lag:
            return self.flush()
        return None if before is None else self._take(before)

    def _take(self, pending):
        self.device.wait(pending[0])
        self.paged_stats["decode_steps"] += 1
        return pending[1]

    def flush(self):
        pending, self._pending = self._pending, None
        return None if pending is None else self._take(pending)

    def decode_now(self, tokens, positions, active=None):
        out = self.decode(tokens, positions, active)
        return self.flush() if self.decode_lag else out


@pytest.fixture
def ring():
    bus = EventBus(path="", ring_size=1 << 14)
    prev = set_bus(bus)
    yield bus
    set_bus(prev)


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    yield reg
    set_registry(prev)


def _engine(kind, clock, slots=4):
    """``lag1``: the stub with one step in flight. ``lag0``:
    ``loadgen``'s cost-model engine, which charges the modelled time
    itself, over a stub whose device costs nothing."""
    if kind == "lag1":
        return StubEngine(clock, slots)
    return _CostModelEngine(
        StubEngine(clock, slots, step_s=0.0, chunk_s=0.0), clock,
        decode_step_ms=1e3 * STEP_S,
        prefill_ms_per_token=1e3 * CHUNK_S / CHUNK,
        faults=parse_faults(""),
    )


def _drive(kind, arrivals, slots=4, eos=None):
    """Run ``arrivals`` ({tick: [(rid, prompt tokens, max_new)]}) to
    the end; ``eos`` = {rid: position at which its token is EOS}.
    Returns (batcher, meter, emission clock readings)."""
    clock = VirtualClock()
    engine = _engine(kind, clock, slots)
    meter = ServeMeter(clock=clock)
    batcher = ContinuousBatcher(engine, meter=meter)
    emitted_at = []
    file_gap = batcher._file_gap

    def spy(ahead, kept):
        emitted_at.append(clock())
        file_gap(ahead, kept)

    batcher._file_gap = spy
    stub = getattr(engine, "_engine", engine)
    tick = 0
    while tick <= max(arrivals) or not batcher.done:
        for rid, n, max_new in arrivals.get(tick, ()):
            batcher.submit(Request(
                rid=rid, prompt=[1] * n, max_new_tokens=max_new,
                eos_id=EOS if eos and rid in eos else None,
            ))
        batcher.step()
        for idx, slot in enumerate(batcher.slots):
            if eos and slot.rid in eos:
                stub.eos_at[idx] = eos[slot.rid]
        tick += 1
        assert tick < 2000, "the batcher does not drain"
    return batcher, meter, emitted_at


def _ticks(ring):
    return [
        r for r in ring.ring()
        if r["event"] == "span" and r["name"] == "tick"
    ]


def _filed(ring):
    """The class each emitting tick filed, in order, as names."""
    return [
        GAP_CLASSES[r["gap_class"]] for r in _ticks(ring)
        if "gap_class" in r
    ]


def _by_class(stats, what):
    return [stats[f"serve_gap_{what}_{c}_total"] for c in GAP_CLASSES]


# -- the filing rule, case by case ---------------------------------------
# A is decoding throughout; what arrives at tick 3 decides the classes
# from there on. ``lag1`` / ``lag0``: the classes of the emissions in
# order. A's first emission follows its own fetched chunk (c1; the
# first has no gap behind it); tick 3's is the third under a lag of 1
# (tick 0 emits nothing), the fourth under 0. One chunk holds 8 prompt
# tokens.
A = ("A", 4, 12)
RULE_CASES = {
    # B's first chunk is not fetched: it delays the step dispatched
    # behind it, whose tokens come one tick later under a lag of 1, not
    # tick 3's. Its second completes the prompt and is fetched in tick
    # 4, whose emission (step 3's tokens) has then waited for both.
    "unfetched_then_fetched": (
        {0: [A], 3: [("B", 12, 2)]},
        "c1 c0 c0 c2 c0 c0",
        "c1 c0 c0 c1 c1 c0",
    ),
    "fetched_chunk_is_this_emission": (
        {0: [A], 3: [("B", 5, 2)]},
        "c1 c0 c1 c0 c0 c0",
        "c1 c0 c0 c1 c0 c0",
    ),
    # Two prompts complete in one tick: two fetches, one emission.
    "two_fetched_are_c2": (
        {0: [A], 3: [("B", 5, 2), ("C", 5, 2)]},
        "c1 c0 c2 c0 c0 c0",
        "c1 c0 c0 c2 c0 c0",
    ),
    "three_fetched_are_c3": (
        {0: [A], 3: [("B", 5, 2), ("C", 5, 2), ("D", 5, 2)]},
        "c1 c0 c3 c0 c0 c0",
        "c1 c0 c0 c3 c0 c0",
    ),
    # Slot order is dispatch order. B (two chunks) goes first, unfetched;
    # C's one chunk behind it is fetched, and the host has then waited
    # for both: this emission. B's second is the next tick's.
    "unfetched_before_a_fetch_is_waited_for": (
        {0: [A], 3: [("B", 12, 2), ("C", 5, 2)]},
        "c1 c0 c2 c1 c0 c0",
        "c1 c0 c0 c2 c1 c0",
    ),
    # C first (fetched), then B's unfetched chunk: behind the fetch, so
    # ahead of the step dispatched after it, and that step's tokens
    # come in tick 4 behind B's second chunk too.
    "unfetched_behind_a_fetch_rides_with_the_step": (
        {0: [A], 3: [("C", 5, 2), ("B", 12, 2)]},
        "c1 c0 c1 c2 c0 c0",
        "c1 c0 c0 c2 c1 c0",
    ),
    # Three unfetched chunks of three long prompts ride with one step;
    # under a lag of 1 its tokens come behind their second chunks too.
    "three_unfetched_are_c3": (
        {0: [A], 3: [("B", 12, 2), ("C", 12, 2), ("D", 12, 2)]},
        "c1 c0 c0 c3 c0 c0",
        "c1 c0 c0 c3 c3 c0",
    ),
}


@pytest.mark.parametrize("kind", ["lag1", "lag0"])
@pytest.mark.parametrize("case", list(RULE_CASES))
def test_filing_rule(case, kind, ring):
    arrivals, lag1, lag0 = RULE_CASES[case]
    _drive(kind, arrivals)
    want = (lag1 if kind == "lag1" else lag0).split()
    assert _filed(ring)[:len(want)] == want


@pytest.mark.parametrize("kind", ["lag1", "lag0"])
def test_a_prefill_only_tick_carries_its_chunks_over(kind, ring):
    """A ends, B (three chunks) arrives into ticks with nothing to
    decode: two unfetched chunks and the fetched one are all filed with
    B's first emission, which follows A's last."""
    batcher, _, _ = _drive(kind, {0: [("A", 4, 3)], 6: [("B", 20, 3)]})
    assert _filed(ring) == ["c1", "c0", "c3", "c0"]
    prefill_only = [
        r for r in _ticks(ring) if r["chunks"] and "gap_class" not in r
    ]
    assert len(prefill_only) >= 2
    stats = batcher.stats
    assert stats["serve_gap_chunks_total"] == 3
    assert stats["serve_gap_emissions_c3_total"] == 1
    assert stats["serve_gap_first_fetch_tokens_total"] == 2


@pytest.mark.parametrize("kind", ["lag1", "lag0"])
def test_an_end_of_sequence_drops_the_token_and_not_the_emission(kind, ring):
    """A's token at position 8 is its end of sequence. With a step in
    flight the host sees it one step late: the step after ran for
    nobody, its token is dropped, and the emission still counts (the
    device ran it, and the wall went by)."""
    batcher, meter, _ = _drive(kind, {0: [("A", 4, 12)]}, eos={"A": 7})
    assert batcher.results["A"] == [4, 5, 6, 7, EOS]
    emissions = sum(_by_class(batcher.stats, "emissions")) + 1
    assert sum(_by_class(batcher.stats, "tokens")) == 4
    assert emissions == (5 if kind == "lag1" else 4)
    # The empty one is taken off the engine by ``done``, outside any
    # tick: no tick's record holds it.
    assert [r["emitted"] for r in _ticks(ring) if "gap_class" in r] \
        == [1, 1, 1, 1]
    assert sum(_by_class(batcher.stats, "seconds")) == pytest.approx(
        (emissions - 1) * STEP_S
    )


# -- conservation -----------------------------------------------------------
def _mixed():
    """Short and long prompts into four slots, some ending early."""
    rng = np.random.default_rng(35)
    arrivals = {}
    for k in range(24):
        arrivals.setdefault(int(rng.integers(0, 40)), []).append(
            (f"r{k}", int(rng.integers(1, 30)), int(rng.integers(1, 12)))
        )
    arrivals.setdefault(0, []).append(("first", 3, 60))
    return arrivals, {"r3": 20, "r8": 9, "r15": 12}


@pytest.mark.parametrize("kind", ["lag1", "lag0"])
def test_tokens_are_the_meters_gaps(kind, registry):
    arrivals, eos = _mixed()
    batcher, meter, _ = _drive(kind, arrivals, eos=eos)
    gaps = sum(len(t.token_times) - 1 for t in meter.traces.values())
    assert sum(_by_class(batcher.stats, "tokens")) == gaps > 100
    assert batcher.stats["serve_gap_first_fetch_tokens_total"] <= gaps


@pytest.mark.parametrize("kind", ["lag1", "lag0"])
def test_seconds_are_last_emission_less_first(kind, registry):
    arrivals, eos = _mixed()
    batcher, _, emitted_at = _drive(kind, arrivals, eos=eos)
    assert sum(_by_class(batcher.stats, "seconds")) == pytest.approx(
        emitted_at[-1] - emitted_at[0]
    )
    assert sum(_by_class(batcher.stats, "emissions")) \
        == len(emitted_at) - 1


@pytest.mark.parametrize("kind", ["lag1", "lag0"])
def test_every_class_costs_a_step_and_its_chunks(kind, registry):
    """The device model's verdict on the rule: 'first' decodes from tick
    0 to the end, so the queue never runs dry and every gap is one step
    plus the chunks filed with it, class by class."""
    arrivals, eos = _mixed()
    batcher, _, _ = _drive(kind, arrivals, eos=eos)
    stats = batcher.stats
    seconds, emissions = (
        _by_class(stats, what) for what in ("seconds", "emissions")
    )
    assert seconds[0] == pytest.approx(emissions[0] * STEP_S)
    assert emissions[1] and emissions[2] and emissions[3]
    assert seconds[1] == pytest.approx(emissions[1] * (STEP_S + CHUNK_S))
    assert seconds[2] == pytest.approx(
        emissions[2] * (STEP_S + 2 * CHUNK_S)
    )
    # What the benchmark's reader makes of them: the chunk's own time.
    extra = sum(seconds[1:]) - sum(emissions[1:]) * seconds[0] / emissions[0]
    assert extra / stats["serve_gap_chunks_total"] \
        == pytest.approx(CHUNK_S)


@pytest.mark.parametrize("kind", ["lag1", "lag0"])
def test_registry_mirrors_stats(kind, registry):
    arrivals, eos = _mixed()
    batcher, _, _ = _drive(kind, arrivals, eos=eos)
    for name, _ in GAP_COUNTERS:
        assert registry.counter(name) == pytest.approx(
            batcher.stats[name]
        ), name
        assert f"# HELP tpu_hpc_{name} " in registry.prometheus_text()
    assert len(GAP_COUNTERS) == 3 * len(GAP_CLASSES) + 2
    assert all(
        isinstance(batcher.stats[name], float) == ("_seconds_" in name)
        for name, _ in GAP_COUNTERS
    )


@pytest.mark.parametrize("kind", ["lag1", "lag0"])
def test_tick_record_says_what_the_tick_ran(kind, ring, registry):
    arrivals, eos = _mixed()
    batcher, meter, _ = _drive(kind, arrivals, eos=eos)
    ticks = _ticks(ring)
    for r in ticks:
        validate_record(r)
        assert {"chunks", "firsts", "admitted", "emitted"} <= set(r)
        assert r["firsts"] <= r["chunks"]
    engine = getattr(batcher.engine, "_engine", batcher.engine)
    assert sum(r["chunks"] for r in ticks) \
        == engine.paged_stats["prefill_chunks"]
    assert sum(r["firsts"] for r in ticks) == batcher.stats["admitted"] \
        == sum(r["admitted"] for r in ticks) == len(meter.traces)
    assert sum(r["emitted"] for r in ticks) \
        == sum(_by_class(batcher.stats, "tokens"))
    by_class = [0] * len(GAP_CLASSES)
    for r in ticks:
        if "gap_class" in r:
            by_class[r["gap_class"]] += r["emitted"]
    assert by_class == _by_class(batcher.stats, "tokens")


# -- the other ticks: slab admission, speculative emission ------------------
class SlabStub:
    """A slab engine: ``prefill`` is one synchronous program."""

    def __init__(self, clock):
        self.serve_cfg = ServeConfig(
            slots=2, max_seq_len=64, prefill_buckets=(16,)
        )
        self.clock = clock

    def prefill(self, idx, prompt):
        self.clock.advance(CHUNK_S)
        return len(prompt)

    def decode(self, tokens, positions):
        self.clock.advance(STEP_S)
        return np.asarray(positions) + 1


def test_a_slab_prefill_is_a_fetched_chunk_of_its_tick(ring):
    clock = VirtualClock()
    batcher = ContinuousBatcher(
        SlabStub(clock), meter=ServeMeter(clock=clock)
    )
    batcher.submit(Request(rid="A", prompt=[1] * 4, max_new_tokens=6))
    for tick in range(8):
        if tick == 2:
            batcher.submit(
                Request(rid="B", prompt=[1] * 4, max_new_tokens=2)
            )
        batcher.step()
    assert batcher.done
    assert _filed(ring) == ["c1", "c0", "c1", "c0", "c0"]
    stats = batcher.stats
    assert stats["serve_gap_seconds_c1_total"] == pytest.approx(
        STEP_S + CHUNK_S
    )
    assert stats["serve_gap_first_fetch_tokens_total"] == 1 + 2


class SpecStub(StubEngine):
    """A speculative engine as the batcher sees one: lag 0, and every
    verify step accepts one draft, so a slot gets two tokens a tick."""

    def __init__(self, clock):
        super().__init__(clock, slots=2, lag=0)
        self.spec = types.SimpleNamespace(
            cfg=types.SimpleNamespace(k=2, mode="draft"), stats={},
        )

    def admit(self, idx, prompt, max_new, sampling=None):
        return super().admit(idx, prompt, max_new)

    def spec_decode(self, tokens, positions, active, n_valid, *sampling,
                    proposals=None):
        self.device.wait(self.device.run(self.step_s))
        out = np.asarray(positions)[:, None] + np.arange(1, 4)[None]
        accepted = np.minimum(1, np.asarray(n_valid))
        return out, accepted, np.asarray(n_valid)


def test_a_speculative_tick_files_every_token_it_commits(ring):
    clock = VirtualClock()
    meter = ServeMeter(clock=clock)
    batcher = ContinuousBatcher(SpecStub(clock), meter=meter)
    batcher.run([
        Request(rid="A", prompt=[1] * 4, max_new_tokens=9),
        Request(rid="B", prompt=[1] * 12, max_new_tokens=5),
    ])
    gaps = sum(len(t.token_times) - 1 for t in meter.traces.values())
    assert gaps == 8 + 4
    assert sum(_by_class(batcher.stats, "tokens")) == gaps
    # Tick 0: A's chunk fetched, B's first unfetched, then the verify
    # step behind both; tick 1: B's second, fetched.
    assert _filed(ring)[:3] == ["c2", "c1", "c0"]
    # B's first token comes in tick 1 and B decodes from that tick on.
    assert [r["emitted"] for r in _ticks(ring)][:3] == [2, 4, 4]


# -- a real engine: the chunks it counts, and the ladder pair ---------------
CFG = llama2.LlamaConfig(
    dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
    multiple_of=16, max_seq_len=64, dtype=jnp.float32,
)
LADDER = [name for name, _ in paging.LADDER_COUNTERS]


@pytest.fixture(scope="module")
def mesh(devices):
    return Mesh(np.array(devices[:1]), ("data",))


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda key: llama2.init_llama(key, CFG))(
        jax.random.key(35)
    )


def _paged(params, mesh, kernel="gather", kv_quant="none"):
    engine = PagedEngine(
        params, CFG,
        ServeConfig(slots=4, max_seq_len=64, prefill_buckets=(8, 16)),
        mesh, PagedConfig(block_size=4, num_blocks=4 * 16 + 1,
                          prefill_chunk=8, kernel=kernel,
                          kv_quant=kv_quant),
    )
    engine.warmup()
    return engine


def test_paged_engine_chunks_are_all_filed(params, mesh, ring, registry):
    """Everything ``paged_stats["prefill_chunks"]`` counts reaches a
    tick's record, and all of it but what ran ahead of the FIRST
    emission (which has no gap behind it) is in
    ``serve_gap_chunks_total``."""
    engine = _paged(params, mesh)
    rng = np.random.default_rng(5)
    lens = [5, 20, 11, 30, 7, 17, 3, 26]
    batcher = ContinuousBatcher(engine, meter=ServeMeter())
    filed = []
    file_gap = batcher._file_gap

    def spy(ahead, kept):
        filed.append(ahead + batcher._waited)
        file_gap(ahead, kept)

    batcher._file_gap = spy
    batcher.run([
        Request(rid=f"r{k}", prompt=rng.integers(0, 128, n).tolist(),
                max_new_tokens=6 + k)
        for k, n in enumerate(lens)
    ])
    ticks = _ticks(ring)
    chunks = engine.paged_stats["prefill_chunks"]
    assert chunks >= sum(-(-n // 8) for n in lens) - 1
    assert sum(r["chunks"] for r in ticks) == chunks
    assert sum(filed) == chunks and filed[0] > 3
    assert batcher.stats["serve_gap_chunks_total"] == chunks - filed[0]
    assert batcher._ahead == batcher._waited == 0
    assert batcher.stats["prefill_chunks"] == chunks
    gaps = sum(
        len(t.token_times) - 1 for t in batcher.meter.traces.values()
    )
    assert sum(_by_class(batcher.stats, "tokens")) == gaps


def test_ladder_pair_counts_the_steps_no_rung_held(params, mesh, registry):
    # An int8 pool's dense read gathers: the one engine with a ladder.
    engine = _paged(params, mesh, kv_quant="int8")
    assert engine.decode_rungs == (24, 32)
    slots = engine.serve_cfg.slots
    for slot in range(slots):
        engine.admit(slot, list(range(1, 6)), 50)
        while engine.prefill_step(slot) is None:
            pass
    before = {k: engine.paged_stats[k] for k in LADDER}
    assert before == dict.fromkeys(LADDER, 0)
    on = [True] * slots
    engine.decode_now([1] * slots, [5] * slots, on)      # 8 live pages
    engine.decode_now([1] * slots, [31] * slots, on)     # 32: top rung
    engine.decode_now([1] * slots, [32] * slots, on)     # 36: rectangle
    engine.decode_now([1] * slots, [40] * slots, on)     # 44: rectangle
    assert [engine.paged_stats[k] for k in LADDER] == [4, 2]
    assert [registry.counter(k) for k in LADDER] == [4, 2]


def test_an_engine_without_a_ladder_holds_neither_name(params, mesh):
    engine = _paged(params, mesh, kernel="pallas")
    assert engine.decode_rungs == ()
    assert not set(LADDER) & set(engine.paged_stats)


# -- the analyzer reads the tick records ---------------------------------
def test_trace_analyzer_names_the_class_p95_falls_in(ring, registry):
    from tpu_hpc.obs import trace

    arrivals, eos = _mixed()
    batcher, _, _ = _drive("lag1", arrivals, eos=eos)
    rep = trace.analyze(ring.ring())
    classes = rep["requests"]["itl_classes"]
    tokens = _by_class(batcher.stats, "tokens")
    assert classes["tokens"] == {
        c: n for c, n in zip(GAP_CLASSES, tokens) if n
    }
    cum, total = 0, sum(tokens)
    for c, n in zip(GAP_CLASSES, tokens):
        cum += n
        if 100 * cum >= 95 * total:
            break
    assert classes["p95_class"] == c
    assert f"p95 falls in **{c}**" in trace.format_analysis(rep)
    assert trace._gap_classes([{"event": "span", "name": "tick"}]) is None
