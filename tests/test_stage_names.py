"""The program's stage names (docs/guide/observability.md, "Stage
names"): ``jax.named_scope`` names inside every serving program and the
train-step program, ``name=`` on every ``pallas_call``, ``tpu_hpc:``
annotations from ``obs.span``, and the stage spans inside one serve
tick, one engine call and one train chunk. All on the CPU at a tiny
size: names and nesting, never a time.
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpu_hpc import obs
from tpu_hpc.config import TrainingConfig
from tpu_hpc.kernels.attention import blockwise_attention
from tpu_hpc.kernels.grouped_experts import grouped_expert_ffn
from tpu_hpc.kernels.latent_paged_attention import latent_paged_decode
from tpu_hpc.kernels.sparse_paged_attention import sparse_paged_decode
from tpu_hpc.models import datasets, llama2
from tpu_hpc.obs import schema as schema_mod
from tpu_hpc.obs.events import EventBus, set_bus
from tpu_hpc.parallel import tp
from tpu_hpc.runtime import MeshSpec, build_mesh
from tpu_hpc.serve import (
    ContinuousBatcher,
    PagedConfig,
    PagedEngine,
    Request,
    ServeConfig,
    paging,
)
from tpu_hpc.train import Trainer, trainer as trainer_mod

TINY = llama2.LlamaConfig(
    dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
    multiple_of=16, max_seq_len=64, dtype=jnp.float32,
)
SERVE = ServeConfig(slots=4, max_seq_len=48, prefill_buckets=(8, 16))
BLOCK, PER_SEQ, WIDTH = 4, 12, 16

SERVE_SCOPES = (
    "embed", "qkv", "kv_write", "kv_read", "attention", "attn_out",
    "mlp", "head",
)
TRAIN_SCOPES = (
    "embed", "qkv", "attention", "attn_out", "mlp", "head", "optimizer",
    "sp_constrain",
)


def _scopes_in(lowered) -> set:
    """Every path component of every op's name in the lowered text."""
    text = lowered.as_text(debug_info=True)
    return {
        part for loc in re.findall(r'loc\("([^"]+)"', text)
        for part in loc.split("/")
    }


@pytest.fixture(scope="module")
def tiny_abstract():
    return jax.eval_shape(
        lambda: llama2.init_llama(jax.random.key(0), TINY)
    )


@pytest.fixture(scope="module")
def programs(devices, tiny_abstract):
    """Every serving program and the train step: ``(function,
    abstract arguments)`` by name."""
    from tpu_hpc.serve import engine as slab, spec

    i32, f32 = jnp.int32, jnp.float32
    slots, k = SERVE.slots, 2
    cache = jax.ShapeDtypeStruct(
        (TINY.n_layers, 48, TINY.kv_heads, BLOCK, TINY.head_dim), f32
    )
    # An int8 pool and its scales: the one whose dense read keeps the
    # flat rungs.
    cache_q = jax.ShapeDtypeStruct(cache.shape, jnp.int8)
    scales = jax.ShapeDtypeStruct((TINY.n_layers, 48), f32)
    slab_cache = jax.ShapeDtypeStruct(
        (TINY.n_layers, slots, 48, TINY.kv_heads, TINY.head_dim), f32
    )
    vec = jax.ShapeDtypeStruct((slots,), i32)
    fvec = jax.ShapeDtypeStruct((slots,), f32)
    scalar = jax.ShapeDtypeStruct((), i32)
    tables = jax.ShapeDtypeStruct((slots, WIDTH), i32)
    chunk = jax.ShapeDtypeStruct((1, 8), i32)
    mesh = build_mesh(MeshSpec(axes={"data": 4, "model": 2}))
    forward = llama2.make_forward(
        TINY, tp.sp_constrain(mesh, dp_axis="data", sp_axis="model")
    )
    tx = optax.adamw(1e-3)
    state = jax.eval_shape(
        lambda p: trainer_mod.TrainState(
            step=jnp.int32(0), params=p, opt_state=tx.init(p),
            model_state={},
        ),
        tiny_abstract,
    )
    tokens = jax.ShapeDtypeStruct((4, 16), i32)
    return {
        "decode": (
            paging.make_paged_decode_fn(TINY, BLOCK, PER_SEQ, WIDTH),
            (tiny_abstract, cache, cache, vec,
             jax.ShapeDtypeStruct((len(paging.STEP_ROWS), slots), i32),
             tables),
        ),
        "decode_flat": (
            paging.make_paged_decode_fn(
                TINY, BLOCK, PER_SEQ, WIDTH, kv_quant="int8",
                flat_pages=slots * PER_SEQ // 2,
            ),
            (tiny_abstract, cache_q, cache_q, scales, scales, vec,
             jax.ShapeDtypeStruct((len(paging.STEP_ROWS), slots), i32),
             tables),
        ),
        "prefill": (
            paging.make_chunk_prefill_fn(TINY, 8, BLOCK, PER_SEQ, WIDTH),
            (tiny_abstract, cache, cache, chunk, scalar, scalar,
             jax.ShapeDtypeStruct((WIDTH,), i32)),
        ),
        "slab_prefill": (
            slab.make_prefill_fn(TINY, 8, slots),
            (tiny_abstract, slab_cache, slab_cache, chunk, scalar,
             scalar),
        ),
        "slab_decode": (
            slab.make_decode_fn(TINY, 48),
            (tiny_abstract, slab_cache, slab_cache, vec, vec),
        ),
        "spec_draft": (
            spec.make_spec_draft_fn(TINY, k, BLOCK, PER_SEQ, WIDTH),
            (tiny_abstract, cache, cache, vec, vec, tables, vec, vec,
             vec, fvec, fvec),
        ),
        "spec_verify": (
            spec.make_spec_verify_fn(
                TINY, k, BLOCK, PER_SEQ, WIDTH, onehot_q=True
            ),
            (tiny_abstract, cache, cache,
             jax.ShapeDtypeStruct((slots, k + 1), i32), vec, tables,
             vec, vec, vec, fvec, fvec),
        ),
        "train": (
            trainer_mod.make_step_fn(forward, tx, seed=0),
            (state, (tokens, tokens)),
        ),
    }


@pytest.fixture(scope="module")
def program_scopes(programs):
    return {
        name: _scopes_in(jax.jit(fn).lower(*args))
        for name, (fn, args) in programs.items()
    }


SERVE_PROGRAMS = (
    "decode", "decode_flat", "prefill", "slab_prefill", "slab_decode",
    "spec_draft", "spec_verify",
)


@pytest.mark.parametrize("program,scope", [
    *((p, s) for p in SERVE_PROGRAMS for s in SERVE_SCOPES
      if (p, s) != ("slab_prefill", "kv_read")),
    *(("train", s) for s in TRAIN_SCOPES),
])
def test_program_carries_scope(program_scopes, program, scope):
    assert scope in program_scopes[program]


def test_slab_prefill_reads_nothing_back(program_scopes):
    """A slab prompt attends over the K/V it has just computed: the
    one serving program with no ``kv_read``."""
    assert "kv_read" not in program_scopes["slab_prefill"]


def test_training_has_no_kv_scopes(program_scopes):
    assert not {"kv_write", "kv_read"} & program_scopes["train"]


def _flash_jaxpr():
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return blockwise_attention(
            q, k, v, causal=True, impl="pallas_interpret", block_q=64,
            block_k=64,
        )[0].sum()

    return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))


def _serving_jaxpr(kernel):
    """The jaxpr of one of the kernels every serve cell runs:
    interpreted, so the name on its ``pallas_call`` is in the text."""
    slots, heads, d, mb, layers = 2, 2, 16, 4, 2
    tables = jnp.zeros((slots, mb + 2), jnp.int32)
    pos, active = jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), jnp.int32)
    if kernel == "sparse_paged_decode":
        pool = jnp.zeros((layers, 8, heads, BLOCK, d), jnp.float32)
        return str(jax.make_jaxpr(
            lambda q, k, v, *a: sparse_paged_decode(
                q, k, v, *a, scale=d ** -0.5, interpret=True
            )
        )(
            jnp.zeros((slots, heads, 1, d)), pool, pool, jnp.int32(0),
            tables, pos, active,
        ))
    if kernel == "latent_paged_decode":
        rank, rope, pack = 16, 8, 2
        return str(jax.make_jaxpr(
            lambda *a: latent_paged_decode(
                *a, scale=d ** -0.5, interpret=True
            )
        )(
            jnp.zeros((slots, heads, rank)),
            jnp.zeros((slots, pack, heads, pack * rope)),
            jnp.zeros((layers, 8, BLOCK // pack, pack * rank)),
            jnp.zeros((layers, 8, BLOCK // pack, pack * rope)),
            jnp.int32(0), tables, pos, active,
        ))
    experts, dim, hidden = 4, 16, 32
    w = jnp.zeros((experts, dim, hidden))
    return str(jax.make_jaxpr(
        lambda *a: grouped_expert_ffn(*a, interpret=True)
    )(
        jnp.zeros((slots, dim)), jnp.zeros((slots, experts)),
        jnp.arange(experts), jnp.int32(experts), w, w,
        jnp.zeros((experts, hidden, dim)),
    ))


@pytest.mark.parametrize("kernel", [
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "sparse_paged_decode",
    "latent_paged_decode", "grouped_experts",
])
def test_pallas_call_carries_its_name(kernel):
    text = _flash_jaxpr() if kernel.startswith("flash") \
        else _serving_jaxpr(kernel)
    assert f"name={kernel}" in text


@pytest.fixture
def ring():
    """A fresh bus with no sink: spans land in its ring only."""
    bus = EventBus(path="", ring_size=4096)
    prev = set_bus(bus)
    yield bus
    set_bus(prev)


def _spans(bus, since=0):
    return [r for r in bus.ring()[since:] if r["event"] == "span"]


def test_span_annotates_under_the_programs_prefix(tmp_path, ring):
    """One bracket, two names: the profiler sees ``tpu_hpc:<name>``,
    the JSONL record keeps ``<name>``."""
    from jax.profiler import ProfileData

    sink = str(tmp_path / "run.jsonl")
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with obs.span("decode", sink=sink):
            with obs.span("decode.prep", sink=sink):
                pass
    finally:
        jax.profiler.stop_trace()
    with open(sink) as f:
        records = [json.loads(line) for line in f]
    assert [r["name"] for r in records] == ["decode.prep", "decode"]
    assert records[0]["parent"] == "decode" and records[0]["depth"] == 1
    [path] = (tmp_path / "trace").rglob("*.xplane.pb")
    names = {
        ev.name
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines for ev in line.events
    }
    assert {"tpu_hpc:decode", "tpu_hpc:decode.prep"} <= names
    assert "decode" not in names and "decode.prep" not in names


def test_ring_only_spans_are_stamped_when_read(ring):
    with obs.span("tick"):
        pass
    [rec] = _spans(ring)
    assert rec["name"] == "tick" and rec["run_id"] == ring.run_id
    schema_mod.validate_record(rec)


def test_stage_spans_are_no_phases():
    """Phase accounting looks through the stage spans: a phase under
    nothing but stages is top-level, a stage span never is."""
    span = {"event": "span", "dur_s": 1.0}
    assert schema_mod.phase_depth(
        {**span, "name": "decode", "parent": "tick", "depth": 1}
    ) == 0
    assert schema_mod.phase_depth(
        {**span, "name": "ckpt", "parent": "chunk.host", "depth": 1}
    ) == 0
    assert schema_mod.phase_depth(
        {**span, "name": "kv_transfer", "parent": "prefill", "depth": 3}
    ) == 3
    assert schema_mod.phase_depth({**span, "name": "tick"}) == 1
    assert schema_mod.phase_depth({**span, "name": "compute"}) == 0


@pytest.fixture(scope="module")
def warm_engine(devices):
    """A paged engine on one device, warmed up, with the registry's
    compile counter read against its own."""
    obs.get_registry().reset()
    mesh = build_mesh(MeshSpec(axes={"data": 1}), jax.devices()[:1])
    engine = PagedEngine(
        llama2.init_llama(jax.random.key(0), TINY), TINY, SERVE, mesh,
        PagedConfig(block_size=BLOCK, num_blocks=48, prefill_chunk=8),
    )
    engine.warmup()
    return engine


def _compiles():
    return obs.get_registry().snapshot()["counters"].get(
        "serve_compiles_total"
    )


def _requests(n, new=6):
    return [
        Request(rid=f"r{i}", prompt=list(range(1, 10 + i)),
                max_new_tokens=new)
        for i in range(n)
    ]


def test_tick_emits_its_stages_nested_and_in_order(warm_engine, ring):
    batcher = ContinuousBatcher(warm_engine)
    for req in _requests(2):
        batcher.submit(req)
    # Two 9- and 10-token prompts at chunk 8: two prefill ticks with
    # nobody decoding yet (the early return), then decode ticks.
    batcher.step()
    early = _spans(ring)
    assert early[-1]["name"] == "tick" and early[-1]["depth"] == 0
    assert not any(s["name"] == "tick.emit" for s in early)
    while not any(s.decoding for s in batcher.slots):
        batcher.step()
    since = len(ring.ring())
    batcher.step()
    tick = _spans(ring, since)
    children = [s["name"] for s in tick if s.get("parent") == "tick"]
    assert children == [
        "tick.admission", "tick.admit", "tick.prefill", "decode",
        "tick.emit",
    ]
    assert [
        s["name"] for s in tick if s.get("parent") == "decode"
    ] == ["decode.prep", "decode.dispatch", "decode.fetch"]
    assert tick[-1]["name"] == "tick" and "parent" not in tick[-1]
    by_name = {s["name"]: s for s in tick}
    inside = sum(
        by_name[n]["dur_s"] for n in children
    )
    assert inside <= by_name["tick"]["dur_s"]
    # Phase accounting still sees the engine's decode at the top.
    assert schema_mod.phase_depth(by_name["decode"]) == 0
    batcher.run()


def test_prefill_step_emits_its_stages(warm_engine, ring):
    batcher = ContinuousBatcher(warm_engine)
    # A prompt no earlier test left in the prefix trie: two chunks.
    batcher.submit(Request(
        rid="fresh", prompt=list(range(50, 62)), max_new_tokens=2
    ))
    batcher.step()
    under = [
        s["name"] for s in _spans(ring) if s.get("parent") == "prefill"
    ]
    assert under == ["prefill.prep", "prefill.dispatch"]
    batcher.step()  # the prompt's last chunk: the first token's fetch
    under = [
        s["name"] for s in _spans(ring) if s.get("parent") == "prefill"
    ]
    assert under[2:] == ["prefill.prep", "prefill.dispatch", "prefill.fetch"]
    batcher.run()


def test_compile_counter_is_flat_after_warmup(warm_engine, ring):
    assert _compiles() == warm_engine.compile_count_total > 0
    batcher = ContinuousBatcher(warm_engine)
    for req in _requests(3, new=12):
        batcher.submit(req)
    before = _compiles()
    for _ in range(10):
        batcher.step()
    assert _compiles() == before == warm_engine.compile_count_total
    batcher.run()


def test_train_chunk_emits_its_stages(mesh8, ring):
    cfg = TrainingConfig(
        epochs=2, steps_per_epoch=2, global_batch_size=8,
        metrics_path="",
    )
    small = llama2.LlamaConfig(
        dim=32, n_layers=1, n_heads=2, n_kv_heads=2, vocab_size=64,
        multiple_of=16, max_seq_len=16, dtype=jnp.float32,
    )
    trainer = Trainer(
        cfg, mesh8, llama2.make_forward(small),
        llama2.init_llama(jax.random.key(0), small),
        batch_pspec=P("data"),
    )
    trainer.fit(datasets.TokenStream(vocab_size=64, seq_len=16))
    stages = [
        s["name"] for s in _spans(ring) if s["name"].startswith("chunk.")
    ]
    # chunk.host is the fit outside dispatch and fetch: its start,
    # between chunks, its end.
    assert stages == ["chunk.host"] + [
        "chunk.dispatch", "chunk.fetch", "chunk.host",
    ] * 2
    compute = [s for s in _spans(ring) if s["name"] == "compute"]
    assert len(compute) == 2
    assert all(s["parent"] == "chunk.host" for s in compute)
    assert all(schema_mod.phase_depth(s) == 0 for s in compute)
    assert np.isfinite(trainer.fit(
        datasets.TokenStream(vocab_size=64, seq_len=16), epochs=1
    )["epochs"][-1]["total_s"])


# -- a sparse-expert configuration's stages and counts (PR 27) ---------
SPARSE_SCOPES = ("indexer", "router", "experts")


@pytest.fixture(scope="module")
def sparse_programs(devices):
    """The decode and chunk programs of a tiny sparse-expert
    configuration (``models/sparse_moe.py``): ``(function, abstract
    arguments)`` by name."""
    from tpu_hpc.models import sparse_moe

    cfg = sparse_moe.SparseMoEConfig(
        name="tiny-sparse", dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=32, vocab_size=128, max_seq_len=64, n_experts=8,
        experts_per_token=2, expert_hidden=48, indexer_heads=2,
        indexer_head_dim=16, indexer_rope_dim=8, indexer_topk=8,
        dtype=jnp.float32,
    )
    i32 = jnp.int32
    weights = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        sparse_moe.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )
    cache = jax.ShapeDtypeStruct(
        (2, 48, cfg.kv_heads, BLOCK, cfg.head_dim), jnp.float32
    )
    keys = jax.ShapeDtypeStruct((2, 48, BLOCK, 16), jnp.float32)
    vec = jax.ShapeDtypeStruct((SERVE.slots,), i32)
    scalar = jax.ShapeDtypeStruct((), i32)
    return {
        "decode": (
            paging.make_paged_decode_fn(cfg, BLOCK, PER_SEQ, WIDTH),
            (weights, cache, cache, keys,
             jax.ShapeDtypeStruct(
                 (SERVE.slots + len(paging.SPARSE_COUNTERS),), i32
             ),
             jax.ShapeDtypeStruct(
                 (len(paging.STEP_ROWS), SERVE.slots), i32
             ),
             jax.ShapeDtypeStruct((SERVE.slots, WIDTH), i32)),
        ),
        "prefill": (
            paging.make_chunk_prefill_fn(cfg, 8, BLOCK, PER_SEQ, WIDTH),
            (weights, cache, cache, keys,
             jax.ShapeDtypeStruct((1, 8), i32), scalar, scalar,
             jax.ShapeDtypeStruct((WIDTH,), i32)),
        ),
    }


@pytest.fixture(scope="module")
def sparse_program_scopes(sparse_programs):
    return {
        name: _scopes_in(jax.jit(fn).lower(*args))
        for name, (fn, args) in sparse_programs.items()
    }


@pytest.mark.parametrize("program,scope", [
    (p, s) for p in ("decode", "prefill")
    for s in SPARSE_SCOPES + tuple(x for x in SERVE_SCOPES if x != "mlp")
])
def test_sparse_program_carries_scope(sparse_program_scopes, program, scope):
    assert scope in sparse_program_scopes[program]


def test_mlp_stays_the_dense_ffns(sparse_program_scopes, program_scopes):
    """``mlp`` names the dense SwiGLU only: a sparse-expert program has
    ``router`` and ``experts`` in its place, a dense one neither."""
    for program in ("decode", "prefill"):
        assert "mlp" not in sparse_program_scopes[program]
        assert not set(SPARSE_SCOPES) & program_scopes[program]


def _ops_by_scope(fn, args) -> dict:
    """The program's operations (the equations that survive dead-code
    elimination, those of nested calls counted in their place) by the
    LAST stage name on their path, which is how
    ``benchmark/program_trace.py`` reads a trace; ``None`` holds those
    under no stage."""
    from jax.interpreters import partial_eval as pe

    closed = jax.make_jaxpr(fn)(*args)
    jaxpr, _ = pe.dce_jaxpr(
        closed.jaxpr, [True] * len(closed.jaxpr.outvars)
    )
    stages = set(SERVE_SCOPES + SPARSE_SCOPES)
    counts = {}

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            path = outer + str(eqn.source_info.name_stack).split("/")
            inner = eqn.params.get("jaxpr")
            if eqn.primitive.name == "jit" and inner is not None:
                walk(inner.jaxpr, path)
                continue
            stage = next(
                (p for p in reversed(path) if p in stages), None
            )
            counts[stage] = counts.get(stage, 0) + 1

    walk(jaxpr, [])
    return counts


# Taken on the parent commit of the PR that gave every serving program
# one layer loop (PR 29, on 2a3e19e's code). No digest sees a scope:
# one that comes to swallow a neighbour's operations moves
# ``kv_read_ms`` or ``attention_ms.serve`` while the program stays the
# same.
OPS_BY_SCOPE = {
    # Re-taken when a dense row step came to read through the kernel
    # that walks the page tables: all of it under ``kv_read`` (the
    # query's grouping and the call, one operation), ``attention`` the
    # reshape of its result (44 before, ``kv_read`` 36; the rectangle's
    # mask no longer counts among the unscoped, 61 before).
    "dense-decode": {
        None: 56, "embed": 8, "qkv": 98, "kv_write": 116, "kv_read": 12,
        "attention": 2, "attn_out": 6, "mlp": 32, "head": 13,
    },
    # A flat rung of an int8 pool, the one that keeps a ladder,
    # as the parent lowered it: the list of live pages is derived under
    # ``kv_read`` once a step, beside the dequantized gathers, the
    # page requantize under ``kv_write``, the owner products and the
    # combine under ``attention``.
    "dense-decode_flat": {
        None: 60, "embed": 8, "qkv": 98, "kv_write": 268, "kv_read": 144,
        "attention": 80, "attn_out": 6, "mlp": 32, "head": 13,
    },
    "dense-prefill": {
        None: 33, "embed": 5, "qkv": 114, "kv_write": 52, "kv_read": 48,
        "attention": 40, "attn_out": 6, "mlp": 32, "head": 16,
    },
    # Re-taken at PR 38: a sparse-selection row step reads through the
    # kernel that walks the page tables, all of it under ``kv_read``
    # (the query's grouping, the mask's row a block and the call, which
    # counts as one operation); ``attention`` keeps the rounding and
    # the reshape of its result (44 before, ``kv_read`` 36).
    "sparse-decode": {
        None: 83, "embed": 9, "qkv": 134, "kv_write": 116,
        "indexer": 276, "kv_read": 24, "attention": 2, "attn_out": 6,
        "router": 48, "experts": 94, "head": 21,
    },
    "sparse-prefill": {
        None: 48, "embed": 5, "qkv": 150, "kv_write": 52,
        "indexer": 242, "kv_read": 48, "attention": 40, "attn_out": 6,
        "router": 48, "experts": 42, "head": 16,
    },
}


@pytest.mark.parametrize("name", sorted(OPS_BY_SCOPE))
def test_each_stage_holds_the_operations_it_held(
    programs, sparse_programs, name
):
    kind, program = name.split("-")
    fn, args = (programs if kind == "dense" else sparse_programs)[program]
    assert _ops_by_scope(fn, args) == OPS_BY_SCOPE[name]


def _table_of_record():
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "guide", "observability.md",
    )
    with open(path) as f:
        return f.read()


@pytest.mark.parametrize(
    "name", [n for _, n, _ in paging.SPARSE_COUNTERS]
    + [paging.MOE_EXPERTS_READ[0]],
)
def test_sparse_counter_is_described_and_in_the_table_of_record(name):
    """Each count a sparse-expert decode step returns with its tokens,
    and the one the host keeps from them (``MOE_EXPERTS_READ``), has
    HELP text in the registry (set when such an engine is built:
    tests/test_sparse_moe.py drives them) and a row in the guide."""
    assert f"`{name}`" in _table_of_record()
    help_ = dict(
        [paging.MOE_EXPERTS_READ] + [c[1:] for c in paging.SPARSE_COUNTERS]
    )[name]
    assert help_ and name.startswith(("serve_moe_", "serve_sparse_"))


@pytest.mark.parametrize("scope", SPARSE_SCOPES)
def test_sparse_scope_is_in_the_table_of_record(scope):
    assert f"| `{scope}` |" in _table_of_record()


# -- a latent configuration's decode step (PR 36) -----------------------
@pytest.fixture(scope="module")
def latent_decode(devices):
    """The decode program of a tiny latent configuration
    (``models/latent_moe.py``): ``(function, abstract arguments)``."""
    from tpu_hpc.models import latent_moe

    cfg = latent_moe.LatentMoEConfig(
        name="tiny-latent", dim=64, n_layers=2, n_heads=4, vocab_size=128,
        max_seq_len=48, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        dense_hidden=96, first_dense_layers=1, n_experts=16,
        experts_per_token=4, expert_hidden=24,
        held_experts=(0, 1, 2, 3, 8, 9),
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    i32 = jnp.int32
    weights = jax.eval_shape(
        lambda: latent_moe.init_latent_moe(jax.random.key(0), cfg)
    )
    pack = paging.rope_pack(cfg, BLOCK)
    pools = [
        jax.ShapeDtypeStruct(
            (2, 48, BLOCK // pack, pack * width), jnp.float32
        ) for width in (cfg.kv_lora_rank, cfg.rope_dim)
    ]
    return (
        paging.make_paged_decode_fn(cfg, BLOCK, PER_SEQ, WIDTH),
        (weights, *pools,
         jax.ShapeDtypeStruct(
             (SERVE.slots + len(paging.LATENT_COUNTERS),), i32
         ),
         jax.ShapeDtypeStruct((len(paging.STEP_ROWS), SERVE.slots), i32),
         jax.ShapeDtypeStruct((SERVE.slots, WIDTH), i32)),
    )


def test_the_latent_walk_is_named_and_filed_under_kv_read(latent_decode):
    """The kernel that walks the tables carries its name into the trace
    (``latent_paged_decode``, one call a layer) and runs under
    ``kv_read`` (the rule for a table-walking kernel); what is left of
    the read under ``attention`` is the value's way out of the latent
    space (``W_UV``). ``latent_attention_roofline`` divides by the time
    under the two: both keep operations."""
    fn, args = latent_decode
    closed = jax.make_jaxpr(fn)(*args)
    assert "name=latent_paged_decode" in str(closed)
    ops = _ops_by_scope(fn, args)
    assert ops["kv_read"] >= 2 and ops["attention"] >= 2
    # The kernel is traced once and called a layer (``_walk``).
    walks = [
        str(e.source_info.name_stack).split("/")
        for e in closed.jaxpr.eqns
        if e.primitive.name == "jit" and e.params["name"] == "_walk"
    ]
    assert len(walks) == 2
    for path in walks:
        # the LAST stage name on the path is the operation's stage
        assert next(p for p in reversed(path) if p in SERVE_SCOPES) \
            == "kv_read"
