"""The 7B north-star must demonstrably shard and fit (VERDICT round-1
missing item #2): exact static accounting at the true 7B config, and the
real train step must AOT-lower + XLA-compile under the hybrid plan."""
import fractions
import re

import jax
import numpy as np
import pytest

from tpu_hpc.checks import fit
from tpu_hpc.models import llama2
from tpu_hpc.parallel import hybrid, tp


GIB = 1024 ** 3


@pytest.fixture(scope="module")
def full_7b():
    cfg = llama2.LlamaConfig(max_seq_len=4096, remat=True)
    return fit.analyze(
        cfg=cfg, dp=4, tp_size=8, global_batch=8, seq_len=4096,
        do_compile=False,
    )


def test_7b_param_count(full_7b):
    # The true 7B defaults (reference llama2_model.py:13-16).
    assert 6.5e9 < full_7b.n_params < 7.0e9


def test_7b_static_accounting_exact(full_7b):
    # fp32 params + grads + 2x Adam moments = 16 bytes/param, sharded
    # over 32 chips; per-chip padding can only round up slightly.
    ideal = 16 * full_7b.n_params / 32
    assert ideal <= full_7b.static_bytes < ideal * 1.05


def test_7b_fits_v4_hbm(full_7b):
    assert full_7b.fits
    # And with real headroom, not by a whisker.
    assert full_7b.total_bytes < 0.5 * 32 * GIB


@pytest.mark.parametrize("keeping", [0, 1, 32])
def test_activation_model_counts_keeping_blocks(full_7b, keeping):
    """``keeping`` blocks hold their six matmul outputs beside the
    block input: (4096 + 2 x 4096 + 4096 + 2 x 11008) bf16 numbers a
    token, split 8 ways by the model axis; the other terms do not
    move, and 0 is the model the reports were written from."""
    args = (full_7b.cfg, 4, 8, 8, 4096)
    base = fit.activation_model(*args)
    assert base == full_7b.act_bytes
    got = fit.activation_model(*args, keeping=keeping)
    block = 2 * 4096 * (4 * 4096 + 2 * 11008) * 2 // 8
    assert fit.kept_block_bytes(full_7b.cfg, 2 * 4096, 8) == block
    assert got.pop("kept_matmul_outputs", 0) == keeping * block
    assert got == base
    # Halving the microbatch halves them, like every other term.
    assert fit.activation_model(
        *args, grad_accum=2, keeping=32
    )["kept_matmul_outputs"] == 16 * block


def test_7b_every_large_param_is_sharded():
    """No big tensor may stay replicated under the hybrid plan."""
    cfg = llama2.LlamaConfig(max_seq_len=4096, remat=True)
    abstract = jax.eval_shape(
        lambda: llama2.init_llama(jax.random.key(0), cfg)
    )
    specs = hybrid.hybrid_pspecs(abstract, tp.llama_rules(), data_size=4)
    import numpy as np

    for leaf, spec in zip(
        jax.tree.leaves(abstract),
        jax.tree.leaves(
            specs, is_leaf=lambda x: hasattr(x, "index")
        ),
    ):
        if int(np.prod(leaf.shape)) >= 100_000:
            assert any(e is not None for e in spec), (
                f"large param {leaf.shape} left replicated"
            )


def test_hybrid_step_compiles_on_mesh(mesh_2d):
    """The real Trainer step AOT-compiles under the hybrid plan on the
    (data=2, model=4) sim mesh at a reduced-depth 7B-wide config, and
    the partitioned module contains collectives (GSPMD accepted the
    plan end-to-end)."""
    cfg = llama2.LlamaConfig(
        n_layers=2, max_seq_len=512, remat=True
    )
    r = fit.analyze(
        cfg=cfg, dp=2, tp_size=4, global_batch=4, seq_len=512,
        do_compile=True,
    )
    assert r.compiled
    assert r.collectives["all-gather"] > 0
    assert (
        r.collectives["all-reduce"] + r.collectives["reduce-scatter"] > 0
    )
    # XLA's own per-chip argument accounting must agree with the
    # analytic static accounting (params + opt state; batch is noise).
    analytic = r.param_bytes + r.opt_bytes
    assert abs(r.xla_argument_bytes - analytic) / analytic < 0.05


def test_model_presets():
    """Llama-2 family shapes land on the public parameter counts."""
    import numpy as np

    def count(name):
        cfg = llama2.PRESETS[name]
        abstract = jax.eval_shape(
            lambda: llama2.init_llama(jax.random.key(0), cfg)
        )
        return sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(abstract)
        )

    assert abs(count("7b") / 6.74e9 - 1) < 0.01
    assert abs(count("13b") / 13.0e9 - 1) < 0.01
    # 70B: GQA shape (8 KV heads), ffn_hidden 28672.
    assert llama2.PRESETS["70b"].ffn_hidden == 28672
    assert abs(count("70b") / 69.0e9 - 1) < 0.01


def test_sizing_table_rows_fit():
    """Every published ladder row must actually fit -- the docs table
    is generated from this exact computation."""
    table = fit.sizing_table()
    assert "NO" not in table
    assert table.count("| yes |") == len(fit._TABLE_ROWS)


def test_sizing_table_catches_overflow():
    """The analyzer is not a rubber stamp: 70B on 8 chips must not fit."""
    import dataclasses as dc

    cfg = dc.replace(llama2.PRESETS["70b"], max_seq_len=4096)
    r = fit.analyze(
        cfg=cfg, dp=2, tp_size=4, global_batch=16, seq_len=4096,
        do_compile=False,
    )
    assert not r.fits


def test_count_collectives_backend_spellings():
    """The counter must see all three backend spellings: plain ops,
    the TPU async start/done pairs, and the v5e fused reduce-scatter
    (a kCustom fusion calls=%all-reduce-scatter) -- counting only
    'reduce-scatter(' reported 0 on real TPU lowerings."""
    hlo = "\n".join([
        '%ag = f32[8] all-gather(%x), dimensions={0}',
        '%ags = f32[8] all-gather-start(%x)',
        '%ar = f32[8] all-reduce(%x)',
        # Two fused reduce-scatters: computation def + body all-reduce
        # + kCustom call site each. The body all-reduces implement the
        # reduce-scatters and must not inflate the all-reduce row.
        '%all-reduce-scatter (input: f32[8]) -> f32[2] {',
        '  %body-ar = f32[8] all-reduce(%input)',
        '}',
        '%all-reduce-scatter.1 (input: f32[8]) -> f32[2] {',
        '  %body-ar.1 = f32[8] all-reduce(%input)',
        '}',
        '%rs = f32[2] reduce-scatter(%x)',
        '%f = f32[2] fusion(%x), kind=kCustom, calls=%all-reduce-scatter',
        '%f2 = f32[2] fusion(%y), kind=kCustom, calls=%all-reduce-scatter.1',
        '%cp = f32[8] collective-permute-start(%x)',
    ])
    c = fit._count_collectives(hlo)
    assert c["all-gather"] == 2          # plain + async start
    assert c["all-reduce"] == 1          # top-level only; bodies excluded
    assert c["reduce-scatter"] == 3      # plain + 2 fused
    assert c["collective-permute"] == 1  # async start
    assert c["all-to-all"] == 0


@pytest.mark.slow
def test_topology_compile_emits_reduce_scatter():
    """AOT compile of the real step against a virtual TPU topology
    (libtpu, no chips): the real lowering must evidence the
    reduce-scatter form the FSDP plan promises -- the CPU-sim
    backend legalizes it away, which is exactly why this path exists.
    Slow (~2 min: real TPU compiler on 1 core); skipped where libtpu
    or the topologies API is unavailable (e.g. bare CI runners)."""
    pytest.importorskip("libtpu")
    from jax.experimental import topologies

    try:
        topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x4"
        )
    except Exception as e:  # pragma: no cover
        pytest.skip(f"topology descriptor unavailable: {e}")
    cfg = llama2.LlamaConfig(
        n_layers=2, max_seq_len=512, remat=True
    )
    r = fit.analyze(
        cfg=cfg, dp=4, tp_size=2, global_batch=8, seq_len=512,
        do_compile=True, tpu_topology="v5e:2x4",
    )
    assert r.compiled
    assert r.compile_backend == "tpu-topology:v5e:2x4"
    assert r.collectives["reduce-scatter"] > 0, r.collectives
    assert r.xla_temp_bytes > 0


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described (not attached) v5e 2x2 host. Built inside a fixture
    so that only the worker that runs this file loads libtpu."""
    pytest.importorskip("libtpu")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover
        pytest.skip(f"topology descriptor unavailable: {e}")


@pytest.mark.slow
def test_every_pallas_kernel_lowers_for_v5e(v5e_2x2):
    """Mosaic (libtpu's real compiler, no chip) must accept every
    Pallas kernel on the train and serve paths at the 7B head shape:
    flash forward + dq + dkv, both paged kernels on bf16 and int8
    pools, and the whole paged decode program on the four-chip serving
    mesh (KV heads over ``model``, kernels under shard_map). PR 20's
    paged kernels only ever ran interpreted and were refused outright
    by this lowering; this is the test that would have said so."""
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_hpc.kernels import paged_attention as pa
    from tpu_hpc.kernels.attention import blockwise_attention
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.serve import paging
    from tpu_hpc.serve.weights import serving_pspecs

    devices = list(v5e_2x2.devices)
    one = NamedSharding(Mesh(devices[:1], ("x",)), P())

    def sds(shape, dtype, sharding=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def mosaic_calls(fn, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        return text.count("tpu_custom_call")

    # Flash at (B2, S2048, H32, D128) bf16: forward, dq, dkv.
    qkv = sds((2, 2048, 32, 128), jnp.bfloat16)

    def flash_grads(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: blockwise_attention(
                q, k, v, causal=True, impl="pallas", block_q=512,
                block_k=1024,
            )[0],
            q, k, v,
        )
        return (out, *vjp(g))

    assert mosaic_calls(flash_grads, qkv, qkv, qkv, qkv) == 3

    # Paged kernels at 32 KV heads x 128.
    slots, hkv, d, bucket, cap = 4, 32, 128, 256, 1024
    i32 = jnp.int32
    for dtype, bs in ((jnp.bfloat16, 16), (jnp.int8, 32)):
        mb, nb = cap // bs, 64
        pool = sds((nb, hkv, bs, d), dtype)
        scales = (
            dict(k_scale=sds((nb,), jnp.float32),
                 v_scale=sds((nb,), jnp.float32))
            if dtype == jnp.int8 else {}
        )

        def decode(q, k, v, tables, pos, active, scales):
            return pa.paged_decode_attention(
                q, k, v, tables, pos, active, block_size=bs,
                max_blocks=mb, **scales,
            )

        assert mosaic_calls(
            decode, sds((slots, hkv, 1, d), jnp.bfloat16), pool, pool,
            sds((slots, mb + 4), i32), sds((slots,), i32),
            sds((slots,), i32), scales,
        ) == 1

        def prefill(q, k, v, table, start, scales):
            return pa.paged_prefill_attention(
                q, k, v, table, start, block_size=bs, max_blocks=mb,
                **scales,
            )

        assert mosaic_calls(
            prefill, sds((hkv, bucket, 1, d), jnp.bfloat16), pool, pool,
            sds((mb + 4,), i32), sds((), i32), scales,
        ) == 1

    # The grouped expert product at Keye's decode shape: 12 rows, a
    # list of 96 of 128 experts of 2048 x 768 (two whole experts in
    # fast memory: more than Mosaic's default budget, which the call
    # raises for itself).
    from tpu_hpc.kernels import grouped_experts

    stack = sds((128, 2048, 768), jnp.bfloat16)
    assert mosaic_calls(
        grouped_experts.grouped_expert_ffn,
        sds((12, 2048), jnp.bfloat16), sds((12, 128), jnp.float32),
        sds((96,), i32), sds((), i32), stack, stack,
        sds((128, 768, 2048), jnp.bfloat16),
    ) == 1

    # The paged decode program, 7B width x 2 layers, on the serving
    # mesh the engine would build on a four-chip host.
    cfg = dataclasses.replace(llama2.PRESETS["7b"], n_layers=2)
    mesh = build_mesh(
        MeshSpec(axes=tp.auto_mesh_axes(4, cfg.n_heads, cfg.kv_heads)),
        devices=devices,
    )
    assert dict(mesh.shape) == {"data": 1, "model": 4}
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(
        lambda: llama2.init_llama(jax.random.key(0), cfg)
    )
    params = jax.tree.map(
        lambda a, spec: sds(a.shape, a.dtype, NamedSharding(mesh, spec)),
        params, serving_pspecs(params, mesh),
    )
    bs, mb, nb = 16, cap // 16, 257
    width = mb + bucket // bs
    pool = sds(
        (cfg.n_layers, nb, cfg.kv_heads, bs, cfg.head_dim), cfg.dtype,
        NamedSharding(
            mesh, paging.paged_kv_cache_pspec(mesh, cfg.kv_heads)
        ),
    )
    program = paging.make_paged_decode_fn(
        cfg, bs, mb, width, kernel="pallas", mesh=mesh
    )
    vec = sds((slots,), i32, rep)
    assert mosaic_calls(
        program, params, pool, pool, vec,
        sds((len(paging.STEP_ROWS), slots), i32, rep),
        sds((slots, width), i32, rep),
    ) == cfg.n_layers


# The two serve cells' widths (benchmark/configs/*.json) with the
# slots and per-slot capacity their engines run (benchmark/workloads/
# serve-*.json); 2 layers is enough to show every per-layer operation.
_SERVE_CELL_SHAPES = {
    "deepseek-32kvh-32x1536": (
        dict(n_kv_heads=None, vocab_size=102400, norm_eps=1e-6), 32, 1536,
    ),
    "mistral-8kvh-96x2304": (
        dict(n_kv_heads=8, ffn_dim_multiplier=1.3, vocab_size=32000), 96,
        2304,
    ),
}
# "%name = <shape> <opcode>(" of one optimized-HLO instruction.
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s([\w\-]+)\(", re.M
)


@pytest.mark.parametrize(
    "program", ["decode", "prefill", "flat-3/8", "flat-1/2"],
)
@pytest.mark.parametrize("cell", sorted(_SERVE_CELL_SHAPES))
def test_serve_programs_address_the_pool_in_place(
    v5e_2x2, monkeypatch, cell, program
):
    """The compiled decode and chunk-prefill programs move only the
    pages they name: no whole-pool relayout round the token write, no
    per-layer slice of the stacked pool materialised for the view
    read, and in the decode program no transposed copy of the gathered
    views. Until PR 26 the decode program paid four pool-shaped copies
    and both paid a slice a layer a pool -- 80 % of the decode step on
    the chip, whatever was live -- and no test compiled them for the
    chip. Not ``slow``: 2-6 s a case with libtpu's own compiler.

    ``decode`` reads K and V through the kernel that walks the page
    tables (``sparse_paged_decode``, one Mosaic call a layer, compiled
    as the chip would: the test answers for the described chip) and
    holds no gathered view. ``flat-<share>`` is the decode
    program of an int8 pool's flat rung at that share of ``slots x
    pages a slot`` (such a pool's read gathers, and keeps the ladder):
    every K / V gather moves the rung's pages and none moves every
    slot's capacity, and what carries rows between pages and slots
    under ``attention`` is products and reductions, never a
    scatter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpu_hpc.serve import paging

    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    widths, slots, capacity = _SERVE_CELL_SHAPES[cell]
    cfg = llama2.LlamaConfig(
        dim=4096, n_heads=32, multiple_of=256, n_layers=2,
        max_seq_len=capacity, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, **widths,
    )
    bs, bucket = 16, 512
    mb = capacity // bs
    width = mb + bucket // bs
    num_blocks = slots * mb + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: llama2.init_llama(jax.random.key(0), cfg)),
    )
    layer_shape = (num_blocks, cfg.kv_heads, bs, cfg.head_dim)
    pool_shape = (cfg.n_layers, *layer_shape)
    i32 = jnp.int32
    flat = None
    quant = "none"
    if program.startswith("flat-"):
        share = float(fractions.Fraction(program[5:]))
        assert share in paging.FLAT_RUNGS
        flat = int(slots * mb * share)
        quant = "int8"
    dtype, spelling = (jnp.int8, "s8") if flat else (jnp.bfloat16, "bf16")
    pool = (sds(pool_shape, dtype),) * 2
    if flat:
        pool += (sds((cfg.n_layers, num_blocks), jnp.float32),) * 2
    if program != "prefill":
        fn = paging.make_paged_decode_fn(
            cfg, bs, mb, width, kv_quant=quant, flat_pages=flat
        )
        vec = sds((slots,), i32)
        args = (vec, sds((len(paging.STEP_ROWS), slots), i32),
                sds((slots, width), i32))
        view_pages = flat or slots * mb
    else:
        fn = paging.make_chunk_prefill_fn(cfg, bucket, bs, mb, width)
        args = (sds((1, bucket), i32), sds((), i32), sds((), i32),
                sds((width,), i32))
        view_pages = mb
    compiled = jax.jit(
        fn, donate_argnums=tuple(range(1, 1 + len(pool)))
    ).lower(params, *pool, *args).compile()

    def spelled(shape):
        return spelling + "[" + ",".join(map(str, shape)) + "]"

    rectangle = (slots, mb, *layer_shape[1:])
    view_shape = (flat, *layer_shape[1:]) if flat else rectangle
    pool_results = 0
    # The ENTRY computation's instructions are the buffers in HBM; a
    # ``copy`` inside a fusion's body is a relayout on the fly.
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    if flat:
        # Each layer gathers the rung's K and V pages and nothing of
        # the rectangle's size; no scatter anywhere under attention.
        tail = ",".join(map(str, layer_shape[1:]))
        moved = [
            int(n) for line in entry.splitlines()
            if "/kv_read/gather" in line
            for n in re.findall(rf"= {spelling}\[(\d+),{tail}\]", line)
        ]
        assert moved == [flat] * (2 * cfg.n_layers), moved
        assert spelled(rectangle) not in entry
        assert not [
            line for line in entry.splitlines()
            if "/attention/" in line and "scatter" in line
        ]
    for result, opcode in _HLO_INSTRUCTION.findall(entry):
        if program != "prefill" and opcode == "copy":
            # Attention contracts over the gathered pages as they lie;
            # a token-major transpose of every slot's view cost the
            # decode step a third of its time (PR 26).
            assert spelled(view_shape) not in result, (
                f"copy of a gathered view: {result}"
            )
        if spelled(layer_shape) in result:
            assert opcode == "parameter", (
                f"{opcode} materialises a per-layer slice: {result}"
            )
        if spelled(pool_shape) in result:
            pool_results += 1
            assert opcode != "copy", f"whole-pool copy: {result}"
            layouts = re.findall(
                re.escape(spelled(pool_shape)) + r"\{([0-9,]+)", result
            )
            assert layouts and set(layouts) == {"4,3,2,1,0"}, result
    # Not vacuous: both pools come in and go out under that spelling.
    assert pool_results >= 4
    # The two gathered views are the only large temporaries (the
    # faulty programs held a pool-sized copy, or the slices, besides).
    page_bytes = 2 * cfg.kv_heads * bs * cfg.head_dim
    pool_bytes = cfg.n_layers * num_blocks * page_bytes
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * view_pages * page_bytes + pool_bytes // 2, temp
    if program == "decode":
        _walks_the_pool(
            text, entry, cfg, slots, mb, bs, cfg.n_layers, temp
        )


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_programs_address_the_pool_in_place(
    v5e_2x2, monkeypatch, program
):
    """``serve-docqa-joyai-flash``'s decode and chunk programs,
    compiled for the chip at the cell's shape (2 of its 7 layers):
    every pool goes in and out in its own layout with no copy of it
    and no per-layer slice is materialised. The decode program reads
    its pages through the kernel that walks the tables
    (``latent_paged_decode``, a Mosaic call a layer, over the pools as
    they lie): it holds NO tensor of a gathered view's size (16 x 30720
    x 512 latents, in any grouping of its pages and rows), no float32
    score over the 30,720 columns of a slot, and no per-head key or
    value of the cached tokens. Its ONE expert layer is the grouped
    kernel; both kernels are compiled by Mosaic as the chip would (the
    program asks the backend whether to interpret: here the test
    answers for the described chip). The chunk keeps its gathered view
    and the whole-stack product."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpu_hpc.models import latent_moe
    from tpu_hpc.serve import paging

    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    slots, capacity, bs, bucket = 16, 30720, 16, 512
    cfg = dataclasses.replace(
        latent_moe.JOYAI_LLM_FLASH, n_layers=2, max_seq_len=capacity,
        held_experts=tuple(range(64)), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
    )
    mb = capacity // bs
    width = mb + bucket // bs
    num_blocks = slots * mb + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: latent_moe.init_latent_moe(jax.random.key(0), cfg)
        ),
    )
    # Two tokens a row of either array: the rotary keys fill the 128
    # lanes so (a 64-wide row is laid out pages-minor by the runtime,
    # and the program then copies the whole pool in and out: this test
    # failed on exactly that), and the latents lie as the keys do.
    pack = paging.rope_pack(cfg, bs)
    assert pack * cfg.rope_dim == 128
    pool_shapes = [
        (cfg.n_layers, num_blocks, bs // pack, pack * cfg.kv_lora_rank),
        (cfg.n_layers, num_blocks, bs // pack, pack * cfg.rope_dim),
    ]
    pools = [sds(shape, jnp.bfloat16) for shape in pool_shapes]
    i32 = jnp.int32
    if program == "decode":
        fn = paging.make_paged_decode_fn(cfg, bs, mb, width)
        args = (sds((slots + len(paging.LATENT_COUNTERS),), i32),
                sds((len(paging.STEP_ROWS), slots), i32),
                sds((slots, width), i32))
        view_tokens = slots * capacity
    else:
        fn = paging.make_chunk_prefill_fn(cfg, bucket, bs, mb, width)
        args = (sds((1, bucket), i32), sds((), i32), sds((), i32),
                sds((width,), i32))
        view_tokens = capacity
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, *pools, *args
    ).compile()

    def spelled(shape):
        return "bf16[" + ",".join(map(str, shape)) + "]"

    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    # A decode program: the walk a layer, and the grouped experts of
    # its one expert layer (layer 0 is dense).
    kernels = (
        {"latent_paged_decode": cfg.n_layers, "grouped_experts": 1}
        if program == "decode" else {}
    )
    assert text.count("tpu_custom_call") == sum(kernels.values())
    for name, calls in kernels.items():
        assert len(re.findall(
            rf"^\s*%{name}[.\d]* = [^\n]*tpu_custom_call", text, re.M
        )) == calls, name
    for stack in ((64, 2048, 768), (64, 768, 2048)):
        for result, opcode in _HLO_INSTRUCTION.findall(entry):
            if spelled(stack) in result:
                assert opcode == "parameter", (
                    f"{opcode} copies an expert stack: {result}"
                )
    pool_results = 0
    for result, opcode in _HLO_INSTRUCTION.findall(entry):
        for shape in pool_shapes:
            if spelled(shape[1:]) in result:
                assert opcode == "parameter", (
                    f"{opcode} materialises a per-layer slice: {result}"
                )
            if spelled(shape) in result:
                pool_results += 1
                assert opcode != "copy", f"whole-pool copy: {result}"
        if program == "decode":
            # no gathered view of the latents, whatever groups its
            # pages, rows and tokens, and no float32 score a column
            w = cfg.kv_lora_rank
            for view in ((slots, mb, bs // pack, pack * w),
                         (slots, mb, bs, w), (slots, capacity, w),
                         (slots * mb, bs, w),
                         (slots * mb, bs // pack, pack * w)):
                assert spelled(view) not in result, (
                    f"{opcode} holds a gathered latent view: {result}"
                )
            assert not re.search(
                rf"f32\[[\d,]*\b{capacity}\b[\d,]*\]", result
            ), f"{opcode} holds a score a column: {result}"
        if program == "decode":
            # no key or value a head of the cached tokens
            for w in (cfg.qk_head_dim, cfg.qk_nope_head_dim):
                assert f"{capacity},{cfg.n_heads},{w}]" not in result
    assert pool_results >= 4
    # The gathered views (and a chunk's expanded keys and values) are
    # the only large temporaries: no pool-sized copy besides.
    row = 2 * cfg.latent_dim
    pool_bytes = cfg.n_layers * num_blocks * bs * row
    expanded = 0 if program == "decode" else 2 * capacity * cfg.n_heads * (
        cfg.qk_head_dim + cfg.v_head_dim
    )
    temp = compiled.memory_analysis().temp_size_in_bytes
    if program == "decode":
        # nothing the size of one slot's view, let alone sixteen
        assert temp < capacity * row, temp
        return
    assert temp < 2 * view_tokens * row + 2 * expanded + pool_bytes // 2, temp


def test_the_selected_decode_program_walks_the_pool_in_place(
    v5e_2x2, monkeypatch
):
    """``serve-docqa-keye30b``'s decode program, compiled for the chip
    at the cell's shape (2 of its 4 layers): it reads K and V through
    the kernel that walks the tables (``sparse_paged_decode``, a Mosaic
    call a layer over the pools as they lie) and holds NO gathered
    view of either (12 x 1920 pages of 4 x 16 x 128), no score a query
    head and column, no copy of the K or V pool and no per-layer slice
    of one; its temporaries stay under ONE of the two views the
    gathered form wrote a layer (what is left is the indexer's: its
    keys' pool and view, and its scores). Both kernels are compiled by
    Mosaic as the chip would (the program asks the backend whether to
    interpret: here the test answers for the described chip)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpu_hpc.models import sparse_moe
    from tpu_hpc.serve import paging

    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    slots, capacity, bs, bucket = 12, 30720, 16, 512
    cfg = dataclasses.replace(
        sparse_moe.KEYE_VL2_30B_A3B, n_layers=2, max_seq_len=capacity,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    mb = capacity // bs
    width = mb + bucket // bs
    num_blocks = 8 * 1792 + slots * 140 + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: sparse_moe.init_sparse_moe(jax.random.key(0), cfg)
        ),
    )
    page = (cfg.kv_heads, bs, cfg.head_dim)
    pool_shapes = [
        (cfg.n_layers, num_blocks, *page), (cfg.n_layers, num_blocks, *page),
        (cfg.n_layers, num_blocks, bs, cfg.indexer_head_dim),
    ]
    i32 = jnp.int32
    compiled = jax.jit(
        paging.make_paged_decode_fn(cfg, bs, mb, width),
        donate_argnums=(1, 2, 3),
    ).lower(
        params, *(sds(shape, jnp.bfloat16) for shape in pool_shapes),
        sds((slots + len(paging.SPARSE_COUNTERS),), i32),
        sds((len(paging.STEP_ROWS), slots), i32), sds((slots, width), i32),
    ).compile()

    def spelled(shape):
        return "bf16[" + ",".join(map(str, shape)) + "]"

    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    kernels = {
        "sparse_paged_decode": cfg.n_layers, "grouped_experts": cfg.n_layers
    }
    assert text.count("tpu_custom_call") == sum(kernels.values())
    for name, calls in kernels.items():
        assert len(re.findall(
            rf"^\s*%{name}[.\d]* = [^\n]*tpu_custom_call", text, re.M
        )) == calls, name
    pool_results = 0
    for result, opcode in _HLO_INSTRUCTION.findall(entry):
        # K and V. (The indexer's keys, 64 to a row, are laid out
        # pages-minor and copied in and out by this program as by the
        # parent's: ROADMAP A10, the indexer's own.)
        if spelled(pool_shapes[0][1:]) in result:
            assert opcode == "parameter", (
                f"{opcode} materialises a per-layer slice: {result}"
            )
        if spelled(pool_shapes[0]) in result:
            pool_results += 1
            assert opcode != "copy", f"whole-pool copy: {result}"
        for view in ((slots, mb, *page), (slots * mb, *page),
                     (slots, cfg.kv_heads, capacity, cfg.head_dim)):
            assert spelled(view) not in result, (
                f"{opcode} holds a gathered view: {result}"
            )
        # no score a query head and column (the indexer's are a column)
        assert not re.search(
            rf"\[{slots},({cfg.kv_heads},\d+|{cfg.n_heads}),[\d,]*"
            rf"\b{capacity}\b", result
        ), f"{opcode} holds a score a head and column: {result}"
    assert pool_results >= 4
    view_bytes = 2 * slots * mb * cfg.kv_heads * bs * cfg.head_dim
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < view_bytes, temp


def _walks_the_pool(text, entry, cfg, slots, mb, bs, calls, temp):
    """What a state-keeping configuration's decode program, compiled for
    the chip, holds of its K/V read: ``calls`` Mosaic calls of
    ``sparse_paged_decode`` (one an attention layer) and no other,
    no gathered view of K or V (``[slots, max_blocks, kv_heads,
    block_size, head_dim]``, the pages of all slots end to end, or
    token-major), and temporaries (``temp`` bytes) under a quarter of
    ONE layer's two gathered views."""
    assert text.count("tpu_custom_call") == calls
    assert len(re.findall(
        r"^\s*%sparse_paged_decode[.\d]* = [^\n]*tpu_custom_call", text,
        re.M,
    )) == calls
    page = (cfg.kv_heads, bs, cfg.head_dim)
    for result, opcode in _HLO_INSTRUCTION.findall(entry):
        for view in ((slots, mb, *page), (slots * mb, *page),
                     (slots, cfg.kv_heads, mb * bs, cfg.head_dim)):
            assert "[" + ",".join(map(str, view)) + "]" not in result, (
                f"{opcode} holds a gathered view: {result}"
            )
    views = 2 * slots * mb * cfg.kv_heads * bs * cfg.head_dim * 2
    assert temp < views / 4, temp


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_programs_keep_the_state_in_place(
    v5e_2x2, program, monkeypatch
):
    """``serve-docqa-granite4h-small``'s decode and chunk programs,
    compiled for the chip at the cell's shape (layers 3-6 of its ten:
    two state-space layers, the attention layer, one more): the
    recurrent state's two arrays go in and out in their own layout
    with no copy of either (with heads and head_dim apart the chunk
    program's products wanted them in the other order and copied all
    slots' state in and out, 2.3 GB a chunk at ten layers; three
    convolution rows are no tile), the decode program materialises no
    per-layer slice of the state, and the tied table is read as it
    lies: no transposed copy of it for the head. The decode program
    reads K and V through the kernel that walks the tables (one
    ``sparse_paged_decode`` call for the one attention layer, compiled
    by Mosaic as the chip would: the test answers for the described
    chip) and gathers no view; the chunk program calls no kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpu_hpc.models import hybrid_ssm_moe
    from tpu_hpc.serve import paging

    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    slots, capacity, bs, bucket = 16, 30720, 16, 512
    cfg = dataclasses.replace(
        hybrid_ssm_moe.GRANITE_4_0_H_SMALL, n_layers=4,
        layer_types=hybrid_ssm_moe.GRANITE_4_0_H_SMALL.layer_types[3:],
        max_seq_len=capacity, held_experts=tuple(range(18)),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    assert (cfg.n_ssm_layers, cfg.n_attention_layers) == (3, 1)
    mb = capacity // bs
    width = mb + bucket // bs

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: hybrid_ssm_moe.init_hybrid_ssm_moe(
                jax.random.key(0), cfg
            )
        ),
    )
    pool_shape = (1, slots * mb + 1, cfg.kv_heads, bs, cfg.head_dim)
    s_shape, rows_shape = cfg.state_shapes(slots)
    state = [sds(pool_shape, jnp.bfloat16), sds(pool_shape, jnp.bfloat16),
             sds(s_shape, jnp.float32), sds(rows_shape, jnp.bfloat16)]
    i32 = jnp.int32
    if program == "decode":
        fn = paging.make_paged_decode_fn(cfg, bs, mb, width)
        args = (sds((slots + len(paging.LATENT_COUNTERS),), i32),
                sds((len(paging.STEP_ROWS), slots), i32),
                sds((slots, width), i32))
    else:
        fn = paging.make_chunk_prefill_fn(cfg, bucket, bs, mb, width)
        args = (sds((1, bucket), i32), sds((), i32), sds((), i32),
                sds((width,), i32), sds((), i32), sds((), i32))
    compiled = jax.jit(fn, donate_argnums=(1, 2, 3, 4)).lower(
        params, *state, *args
    ).compile()

    def spelled(dtype, shape):
        return dtype + "[" + ",".join(map(str, shape)) + "]"

    whole = [spelled("bf16", pool_shape), spelled("f32", s_shape),
             spelled("bf16", rows_shape)]
    # (the pool has ONE layer here: its "slice" is the pool, bitcast)
    sliced = [spelled("f32", s_shape[1:]), spelled("bf16", rows_shape[1:])]
    table = spelled("bf16", (cfg.vocab_size, cfg.dim))
    flipped = spelled("bf16", (cfg.dim, cfg.vocab_size))
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    seen = 0
    for result, opcode in _HLO_INSTRUCTION.findall(entry):
        for shape in whole:
            if shape in result:
                seen += 1
                assert opcode != "copy", f"whole-state copy: {result}"
        for shape in sliced:
            if shape in result and program == "decode":
                assert opcode in ("parameter", "bitcast"), (
                    f"{opcode} materialises a per-layer slice: {result}"
                )
        assert flipped not in result, f"a transposed table: {result}"
        if table in result:
            assert opcode == "parameter", f"{opcode} of the table: {result}"
    assert seen >= 6
    # a slot's state a layer is 4 MB; nothing state-sized is temporary
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2.5 * 2**30, temp
    if program == "decode":
        _walks_the_pool(text, entry, cfg, slots, mb, bs, 1, temp)
    else:
        assert "tpu_custom_call" not in text


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_parallel_programs_keep_the_pool_and_the_state_in_place(
    v5e_2x2, program, monkeypatch
):
    """``serve-docqa-falcon-h1-34b``'s decode and chunk programs,
    compiled for the chip at the cell's shape (two of its four parallel
    layers: every layer keeps pages AND a state): the pool's two arrays
    and the state's two go in and out in their own layout with no copy
    of any, and neither program materialises a per-layer slice of the
    pool or (decode) of the state. The decode program reads K and V
    through the kernel that walks the tables (a ``sparse_paged_decode``
    call a layer, compiled by Mosaic as the chip would: the test
    answers for the described chip), holds no gathered view (16 slots x
    1920 pages of 4 x 16 x 128, 1 GB for a layer's two) and keeps its
    temporaries under a quarter of that; the chunk program, which
    gathers its one slot's view, keeps them under one layer's two
    views of every slot."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpu_hpc.models import hybrid_ssm_moe
    from tpu_hpc.serve import paging

    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    slots, capacity, bs, bucket = 16, 30720, 16, 512
    cfg = dataclasses.replace(
        hybrid_ssm_moe.FALCON_H1_34B, n_layers=2, vocab_size=65280,
        max_seq_len=capacity, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    mb = capacity // bs
    width = mb + bucket // bs

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: hybrid_ssm_moe.init_hybrid_ssm_moe(
                jax.random.key(0), cfg
            )
        ),
    )
    pool_shape = (2, slots * mb + 1, cfg.kv_heads, bs, cfg.head_dim)
    s_shape, rows_shape = cfg.state_shapes(slots)
    state = [sds(pool_shape, jnp.bfloat16), sds(pool_shape, jnp.bfloat16),
             sds(s_shape, jnp.float32), sds(rows_shape, jnp.float32)]
    i32 = jnp.int32
    if program == "decode":
        fn = paging.make_paged_decode_fn(cfg, bs, mb, width)
        args = (sds((slots,), i32), sds((len(paging.STEP_ROWS), slots), i32),
                sds((slots, width), i32))
    else:
        fn = paging.make_chunk_prefill_fn(cfg, bucket, bs, mb, width)
        args = (sds((1, bucket), i32), sds((), i32), sds((), i32),
                sds((width,), i32), sds((), i32), sds((), i32))
    compiled = jax.jit(fn, donate_argnums=(1, 2, 3, 4)).lower(
        params, *state, *args
    ).compile()

    def spelled(dtype, shape):
        return dtype + "[" + ",".join(map(str, shape)) + "]"

    whole = [spelled("bf16", pool_shape), spelled("f32", s_shape),
             spelled("f32", rows_shape)]
    sliced = [spelled("bf16", pool_shape[1:])]
    if program == "decode":
        sliced += [spelled("f32", s_shape[1:]), spelled("f32", rows_shape[1:])]
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    seen = 0
    for result, opcode in _HLO_INSTRUCTION.findall(entry):
        for shape in whole:
            if shape in result:
                seen += 1
                assert opcode != "copy", f"whole-array copy: {result}"
        for shape in sliced:
            if shape in result:
                assert opcode in ("parameter", "bitcast"), (
                    f"{opcode} materialises a per-layer slice: {result}"
                )
    assert seen >= 8
    views = 2 * slots * mb * cfg.kv_heads * bs * cfg.head_dim * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < views
    if program == "decode":
        _walks_the_pool(text, entry, cfg, slots, mb, bs, cfg.n_layers, temp)
    else:
        assert "tpu_custom_call" not in text


def _most_in_one_body(jaxpr, primitive) -> int:
    """The most ``primitive`` equations in one straight-line stretch of
    a kernel: those of one body (the kernel's own, a branch's, a
    loop's), those of the bodies nested in it not counted."""
    import jax.extend.core as jex

    here, most = 0, 0
    for eqn in jaxpr.eqns:
        here += eqn.primitive.name == primitive
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                if isinstance(sub, jex.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex.Jaxpr):
                    most = max(most, _most_in_one_body(sub, primitive))
    return max(here, most)


# (slots, capacity, KV heads, query rows a KV head) of the serve cells
# whose row step walks K and V pages with no mask, and their pages'
# KiB (16 rows x 128 numbers x KV heads, bf16).
_WALKED_SHAPES = {
    "mistral-32KiB": (96, 2304, 8, 4),
    "granite-32KiB": (16, 30720, 8, 4),
    "deepseek-128KiB": (32, 1536, 32, 1),
    "falcon-16KiB": (16, 30720, 4, 5),
}


@pytest.mark.parametrize("cell", sorted(_WALKED_SHAPES))
def test_a_walk_of_large_pages_issues_its_block_in_rounds(v5e_2x2, cell):
    """The straight-line code ``sparse_paged_decode`` is lowered with at
    every start. Where a page is 32 KiB or more, a whole block's copies
    go ``ROUND_PAGES`` pages at a time in a loop, so the longest
    straight line holds a round's K and V starts, not a block's (32
    pages of 32 KiB in a block, 8 of 128 KiB); 16 KiB pages keep their
    whole block in one line. A block's scores are unrolled over at most
    ``HEAD_ROUND`` KV heads (two products each): deepseek's 32 go in
    a loop over rounds of that many. Mosaic compiles each for the
    v5e."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpu_hpc.kernels import sparse_paged_attention as spa

    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    slots, capacity, kv_heads, groups = _WALKED_SHAPES[cell]
    bs, hd = 16, 128
    mb = capacity // bs
    width = mb + 512 // bs

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((1, slots * mb + 1, kv_heads, bs, hd), jnp.bfloat16)
    args = (sds((slots, kv_heads, groups, hd), jnp.bfloat16), pool, pool,
            sds((), jnp.int32), sds((slots, width), jnp.int32),
            sds((slots,), jnp.int32), sds((slots,), jnp.int32))

    def walk(*args):
        return spa.sparse_paged_decode(*args, scale=0.125)

    page_bytes = kv_heads * bs * hd * 2
    pages = min(spa.PAGES_PER_BLOCK, spa.BUFFER_BYTES // page_bytes)
    line = pages if page_bytes < spa.ROUND_BYTES \
        else min(pages, spa.ROUND_PAGES)
    calls = [
        eqn for eqn in jax.make_jaxpr(walk)(*args).jaxpr.eqns[0]
        .params["jaxpr"].eqns if eqn.primitive.name == "pallas_call"
    ]
    assert len(calls) == 1
    kernel = calls[0].params["jaxpr"]
    assert _most_in_one_body(kernel, "dma_start") == 2 * line
    assert (line < pages) == (page_bytes >= spa.ROUND_BYTES)
    assert _most_in_one_body(kernel, "dot_general") \
        == 2 * min(kv_heads, spa.HEAD_ROUND)
    text = jax.jit(walk).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_the_dense_walk_splits_over_kv_heads_on_a_tensor_axis(v5e_2x2):
    """A dense decode program through ``gather`` on the serving mesh a
    four-chip host builds for 7B (``{data: 1, model: 4}``, KV heads and
    the pool over ``model``): the row step's walk runs under
    ``shard_map`` a KV-head shard a chip, one Mosaic call a layer, and
    no chip gathers another's pool (no all-gather of a pool-sized
    operand)."""
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.serve import paging
    from tpu_hpc.serve.weights import serving_pspecs

    cfg = dataclasses.replace(llama2.PRESETS["7b"], n_layers=2)
    mesh = build_mesh(
        MeshSpec(axes=tp.auto_mesh_axes(4, cfg.n_heads, cfg.kv_heads)),
        devices=list(v5e_2x2.devices),
    )
    assert dict(mesh.shape) == {"data": 1, "model": 4}
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(
        lambda a, spec: sds(a.shape, a.dtype, NamedSharding(mesh, spec)),
        jax.eval_shape(lambda: llama2.init_llama(jax.random.key(0), cfg)),
        serving_pspecs(
            jax.eval_shape(
                lambda: llama2.init_llama(jax.random.key(0), cfg)
            ), mesh,
        ),
    )
    slots, bs, mb = 8, 16, 64
    width = mb + 512 // bs
    pool_shape = (cfg.n_layers, slots * mb + 1, cfg.kv_heads, bs,
                  cfg.head_dim)
    pool = sds(pool_shape, cfg.dtype, NamedSharding(
        mesh, paging.paged_kv_cache_pspec(mesh, cfg.kv_heads)
    ))
    program = paging.make_paged_decode_fn(cfg, bs, mb, width, mesh=mesh)
    text = jax.jit(program).lower(
        params, pool, pool, sds((slots,), jnp.int32),
        sds((len(paging.STEP_ROWS), slots), jnp.int32),
        sds((slots, width), jnp.int32),
    ).compile().as_text()
    assert len(re.findall(
        r"^\s*%sparse_paged_decode[.\d]* = [^\n]*tpu_custom_call", text,
        re.M,
    )) == cfg.n_layers
    whole = "[" + ",".join(map(str, pool_shape)) + "]"
    assert not [
        line for line in text.splitlines()
        if "all-gather" in line and whole in line
    ]


# The decode programs of the cells whose walk this rule leaves as it
# was, at the cells' shapes (2 layers), as the commit before it traced
# them with every kernel in its Mosaic form: the jaxpr, the kernels'
# bodies included. (The lowered text carries the checkout's paths.)
_UNMOVED_DECODE_JAXPRS = {
    "keye": "3e878a6fe45e65bb",
    "joyai": "d616d83a33d86642",
    "falcon": "ab6ce5a3d8af2321",
}


@pytest.mark.parametrize("cell", sorted(_UNMOVED_DECODE_JAXPRS))
def test_small_page_walks_trace_as_they_did(v5e_2x2, monkeypatch, cell):
    """``serve-docqa-keye30b``'s (16 KiB pages under a mask),
    ``serve-docqa-joyai-flash``'s (the latent walk) and
    ``serve-docqa-falcon-h1-34b``'s (16 KiB, no mask) decode programs
    trace to the text they had before a walk of large pages came to
    issue its block in rounds."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses
    import hashlib

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpu_hpc.models import hybrid_ssm_moe, latent_moe, sparse_moe
    from tpu_hpc.serve import paging

    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots, capacity, bs = 12 if cell == "keye" else 16, 30720, 16
    mb = capacity // bs
    width = mb + 512 // bs
    pages = slots * mb + 1
    bf16 = jnp.bfloat16
    if cell == "keye":
        cfg = dataclasses.replace(
            sparse_moe.KEYE_VL2_30B_A3B, n_layers=2, max_seq_len=capacity,
            dtype=bf16, param_dtype=bf16,
        )
        init = sparse_moe.init_sparse_moe
        page = (2, pages, cfg.kv_heads, bs, cfg.head_dim)
        state = [sds(page, bf16)] * 2 + [
            sds((2, pages, bs, cfg.indexer_head_dim), bf16)
        ]
        counted = len(paging.SPARSE_COUNTERS)
    elif cell == "joyai":
        cfg = dataclasses.replace(
            latent_moe.JOYAI_LLM_FLASH, n_layers=2, max_seq_len=capacity,
            held_experts=tuple(range(64)), dtype=bf16, param_dtype=bf16,
        )
        init = latent_moe.init_latent_moe
        pack = paging.rope_pack(cfg, bs)
        state = [
            sds((2, pages, bs // pack, pack * w), bf16)
            for w in (cfg.kv_lora_rank, cfg.rope_dim)
        ]
        counted = len(paging.LATENT_COUNTERS)
    else:
        cfg = dataclasses.replace(
            hybrid_ssm_moe.FALCON_H1_34B, n_layers=2, vocab_size=65280,
            max_seq_len=capacity, dtype=bf16, param_dtype=bf16,
        )
        init = hybrid_ssm_moe.init_hybrid_ssm_moe
        s_shape, rows_shape = cfg.state_shapes(slots)
        page = (2, pages, cfg.kv_heads, bs, cfg.head_dim)
        state = [sds(page, bf16)] * 2 + [
            sds(s_shape, jnp.float32), sds(rows_shape, jnp.float32)
        ]
        counted = 0
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init(jax.random.key(0), cfg)),
    )
    i32 = jnp.int32
    jaxpr = jax.make_jaxpr(paging.make_paged_decode_fn(cfg, bs, mb, width))(
        params, *state, sds((slots + counted,), i32),
        sds((len(paging.STEP_ROWS), slots), i32), sds((slots, width), i32),
    )
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] \
        == _UNMOVED_DECODE_JAXPRS[cell]


def test_trained_expert_layer_and_narrow_flash_lower_for_v5e(
    v5e_2x2, monkeypatch
):
    """``train-lfm2-24b-a2b-1chip``'s two new shapes, compiled for the
    chip forward AND backward: one expert layer at the cell's 32768
    tokens (router, the sort by expert, the row buffer of 131072 rows,
    the ragged products) and the flash kernels at 64-wide heads. The
    ragged products are Mosaic calls (three forward, six backward: a
    tile-skipping grouped kernel, not a dense product over the buffer),
    and no scatter moves a row of the model's width: the dispatch's and
    the combine's transposes are gathers (the one scatter left is the
    router's, 4 gates a token into 64 scores); the products' row tile
    is the ``RAGGED_ROW_TILE`` that ``rows_computed`` is reckoned in.
    ~15 s."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpu_hpc.kernels.attention import blockwise_attention
    from tpu_hpc.models import conv_moe, sparse_moe

    one = SingleDeviceSharding(v5e_2x2.devices[0])
    cfg = dataclasses.replace(
        conv_moe.LFM2_24B_A2B, n_layers=1, first_dense_layers=0,
        held_experts=tuple(range(8)),
    )
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree
    )
    lp = placed(jax.eval_shape(
        lambda: conv_moe.init_conv_moe(jax.random.key(0), cfg)
    )["layers_0"])
    bias = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one)
    u = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.float32, sharding=one)

    def layer(lp, u, b):
        out, counts, _ = conv_moe.expert_layer(u, lp, b, cfg)
        return jnp.sum(out), counts

    text = jax.jit(
        jax.value_and_grad(layer, argnums=(0, 1), has_aux=True)
    ).lower(lp, u, bias).compile().as_text()
    products = [
        line for line in text.splitlines()
        if "custom-call(" in line and "ragged-dot" in line.split("=")[0]
        and "metadata" not in line.split("=")[0]
    ]
    assert len(products) == 9, len(products)
    # ``rows_computed`` is reckoned, not returned by the kernel: the
    # tile it is reckoned in has to be the compiled call's own, whose
    # metadata lists at most rows / tile + groups - 1 visits.
    visits = sparse_moe.ragged_rows(4 * 8192, cfg) \
        // sparse_moe.RAGGED_ROW_TILE + cfg.n_held - 1
    listed = [
        line for line in text.splitlines()
        if "custom-call(" in line
        and "ragged-dot-metadata" in line.split("=")[0]
    ]
    assert listed and all(f"s32[{visits}]" in line.split("custom-call(")[0]
                          for line in listed), visits
    assert not [
        line for line in text.splitlines()
        if " scatter(" in line and ",2048]" in line.split(" scatter(")[0]
    ]

    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16, sharding=one)

    def attend(q, k, v):
        out, _ = blockwise_attention(
            q, k, v, causal=True, impl="pallas", block_q=512, block_k=1024
        )
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(attend, argnums=(0, 1, 2))).lower(
        q, kv, kv
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def test_keeping_blocks_hold_what_the_model_reckons(v5e_2x2):
    """The real train step (``make_step_fn``: forward, backward, AdamW)
    compiled for the chip twice: with no budget open (every block
    recomputes) and under a budget reckoned from the v5e's own limit
    and this model's state, which holds every block's matmul outputs.
    The compiler's temporaries grow by what ``kept_block_bytes``
    reckons, within 25 %, and twelve matmuls a keeping pair fewer are
    recomputed. A peak over the whole step, not a sum, so the
    agreement is for THIS shape (vocabulary large enough that the
    recomputing step peaks at the head, where every kept product is
    live): with 2 layers of the same widths the growth reads 1.4 x the
    reckoning, with 3 and a quarter of the vocabulary 0.58 x (PR 32).
    The CPU compiler cannot show any of it: it drops the barriers that
    make recomputation real. ~45 s."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import remat
    from tpu_hpc.train import trainer

    mesh = Mesh(np.array(v5e_2x2.devices[:1]), ("data",))
    rep = NamedSharding(mesh, P())
    cfg = llama2.LlamaConfig(
        dim=1024, n_layers=4, n_heads=8, n_kv_heads=2, vocab_size=8192,
        multiple_of=256, max_seq_len=1024, remat=True,
    )
    batch, seq = 2, cfg.max_seq_len
    flash = tp.make_tp_flash_attn_fn(
        mesh, "data", None, impl="pallas", block_q=512, block_k=512
    )
    optimizer = trainer.make_optimizer(
        TrainingConfig(epochs=1, steps_per_epoch=1, global_batch_size=batch)
    )
    step = trainer.make_step_fn(
        llama2.make_forward(cfg, attn_fn=flash), optimizer, 0
    )
    params = jax.eval_shape(
        lambda: llama2.init_llama(jax.random.key(0), cfg)
    )
    state = trainer.TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params,
        opt_state=jax.eval_shape(optimizer.init, params), model_state={},
    )
    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree.leaves(tree)
    )
    budget = remat.RematBudget(
        limit_bytes=int(15.75 * GIB), resident_bytes=nbytes(state),
        grad_bytes=nbytes(params),
    )
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        tree,
    )
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    temps, matmuls = [], []
    for open_budget in (None, budget):
        with remat.lowering_under(open_budget):
            compiled = jax.jit(
                lambda s, b: step(s, b), donate_argnums=(0,)
            ).lower(placed(state), placed((tokens, tokens))).compile()
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
        matmuls.append(compiled.as_text().count(" convolution("))
    assert budget.blocks_kept == cfg.n_layers
    block = fit.kept_block_bytes(cfg, batch * seq)
    assert budget.kept_bytes == cfg.n_layers * block == 128 * 2 ** 20
    assert temps[1] - temps[0] == pytest.approx(budget.kept_bytes, rel=0.25)
    assert matmuls[0] - matmuls[1] == 6 * cfg.n_layers


class TestCPLayout:
    """--layout cp / --cp: the long-context fit model (FSDP over data
    x ring attention over context)."""

    def test_static_shards_over_data_only(self):
        cfg = llama2.LlamaConfig(n_layers=2, max_seq_len=8192, remat=True)
        r = fit.analyze(
            cfg=cfg, dp=2, tp_size=4, global_batch=4, seq_len=8192,
            do_compile=False, layout="cp",
        )
        assert r.layout == "cp"
        # Params shard over dp=2 only (no TP axis): per-chip statics
        # are half the fp32 totals, not an eighth.
        full = 16 * r.n_params  # params+grads+mu+nu fp32 bytes
        assert full / 2 * 0.95 < r.static_bytes < full / 2 * 1.10
        assert set(r.act_bytes) >= {
            "residual_checkpoints", "block_recompute_live",
            "lm_head_and_loss",
        }

    def test_activations_scale_inversely_with_ring(self):
        cfg = llama2.LlamaConfig(n_layers=2, max_seq_len=8192, remat=True)

        def act_total(cp):
            r = fit.analyze(
                cfg=cfg, dp=2, tp_size=cp, global_batch=4,
                seq_len=8192, do_compile=False, layout="cp",
            )
            return sum(r.act_bytes.values())

        # Doubling the ring roughly halves per-chip activations (the
        # whole point of context parallelism).
        assert act_total(8) < 0.6 * act_total(4)

    def test_indivisible_seq_rejected(self):
        cfg = llama2.LlamaConfig(n_layers=2, max_seq_len=100, remat=True)
        with pytest.raises(ValueError, match="divisible"):
            fit.analyze(
                cfg=cfg, dp=2, tp_size=3, global_batch=4, seq_len=100,
                do_compile=False, layout="cp",
            )

    def test_cp_step_compiles_on_sim_mesh(self, mesh_2d):
        """The real Trainer step under the CP layout compiles end-to-end
        on the sim mesh and shows the ring (collective-permute) +
        FSDP (all-gather) signature."""
        cfg = llama2.LlamaConfig(n_layers=2, max_seq_len=512, remat=True)
        r = fit.analyze(
            cfg=cfg, dp=2, tp_size=4, global_batch=4, seq_len=512,
            do_compile=True, layout="cp",
        )
        assert r.compiled
        assert r.collectives["collective-permute"] > 0, r.collectives
        assert r.collectives["all-gather"] > 0, r.collectives


class TestPPLayout:
    """Analytic pipeline fit: stage-sharded statics, 1F1B activations."""

    def test_statics_shard_over_stages_only(self):
        from tpu_hpc.models import llama2 as l2

        cfg = l2.PRESETS["7b"]
        r4 = fit.analyze(
            cfg, dp=2, tp_size=4, global_batch=64, seq_len=4096,
            do_compile=False, grad_accum=8, layout="pp",
        )
        r8 = fit.analyze(
            cfg, dp=2, tp_size=8, global_batch=64, seq_len=4096,
            do_compile=False, grad_accum=8, layout="pp",
        )
        # Twice the stages -> roughly half the per-chip layer params
        # (the worst stage keeps its embed/head share, so not exactly).
        assert r8.param_bytes < r4.param_bytes
        parts = l2.count_params_by_part(cfg)
        expect4 = (
            parts["per_layer"] * (cfg.n_layers // 4)
            + max(parts["embed"], parts["head"]) + parts["other"]
        ) * 4
        assert r4.param_bytes == expect4
        # dp does NOT shard pp statics (stage_pspecs replicates them).
        r_dp8 = fit.analyze(
            cfg, dp=8, tp_size=4, global_batch=64, seq_len=4096,
            do_compile=False, grad_accum=8, layout="pp",
        )
        assert r_dp8.param_bytes == r4.param_bytes

    def test_more_microbatches_shrink_activations(self):
        from tpu_hpc.models import llama2 as l2

        cfg = l2.PRESETS["7b"]
        r8 = fit.analyze(
            cfg, dp=1, tp_size=4, global_batch=64, seq_len=4096,
            do_compile=False, grad_accum=8, layout="pp",
        )
        r32 = fit.analyze(
            cfg, dp=1, tp_size=4, global_batch=64, seq_len=4096,
            do_compile=False, grad_accum=32, layout="pp",
        )
        # Past M >= S the in-flight count saturates at S while the
        # microbatch shrinks -> strictly less activation memory.
        assert sum(r32.act_bytes.values()) < sum(r8.act_bytes.values())

    def test_compile_pass_runs_real_stage_program(self):
        """layout='pp' + do_compile AOT-compiles the real stage-split
        Llama 1F1B step (models/llama_pp.py) -- the collective table
        must show the pipeline's ring ppermutes."""
        from tpu_hpc.models import llama2 as l2

        cfg = l2.LlamaConfig(
            dim=64, n_layers=4, n_heads=4, vocab_size=97,
            multiple_of=32, max_seq_len=32,
        )
        r = fit.analyze(
            cfg, dp=2, tp_size=4, global_batch=8,
            seq_len=32, do_compile=True, grad_accum=4,
            layout="pp",
        )
        assert r.compiled
        assert r.collectives.get("collective-permute", 0) >= 2
        # DP grad reduction across the data axis must appear too.
        assert r.collectives.get("all-reduce", 0) >= 1

    def test_layers_divisibility_enforced(self):
        from tpu_hpc.models import llama2 as l2

        with pytest.raises(ValueError, match="divisible by"):
            fit.analyze(
                l2.PRESETS["7b"], dp=1, tp_size=5, global_batch=10,
                seq_len=4096, do_compile=False, grad_accum=5,
                layout="pp",
            )

    def test_stash_backward_costs_memory(self):
        from tpu_hpc.models import llama2 as l2

        cfg = l2.PRESETS["7b"]
        remat = fit.analyze(
            cfg, dp=2, tp_size=4, global_batch=64, seq_len=4096,
            do_compile=False, grad_accum=8, layout="pp",
        )
        stash = fit.analyze(
            cfg, dp=2, tp_size=4, global_batch=64, seq_len=4096,
            do_compile=False, grad_accum=8, layout="pp",
            pp_backward="stash",
        )
        # Stash buffers full residuals (incl. a bf16 param copy per
        # in-flight microbatch) instead of input checkpoints only.
        assert sum(stash.act_bytes.values()) > \
            sum(remat.act_bytes.values())
        assert stash.static_bytes == remat.static_bytes


class TestKVCacheTerm:
    """Memory fit with a co-resident decode config: the serving
    engine's preallocated KV cache is real HBM the training-only
    analysis used to ignore."""

    def test_formula_exact(self):
        cfg = llama2.LlamaConfig(
            dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
            vocab_size=128, multiple_of=16, max_seq_len=32,
        )
        # slots x seq x layers x kv_heads x head_dim x 2 (K,V) x bf16
        want = 8 * 32 * 3 * 2 * 16 * 2 * 2
        assert fit.kv_cache_bytes(cfg, 8) == want
        # explicit capacity overrides the model's max_seq_len
        assert fit.kv_cache_bytes(cfg, 8, max_seq_len=16) == want // 2
        # fp32 cache doubles it
        assert fit.kv_cache_bytes(
            cfg, 8, cache_dtype="float32"
        ) == 2 * want

    @pytest.fixture(scope="class")
    def with_kv(self, full_7b):
        # Same mesh/batch as the module's full_7b fixture, plus a
        # 64-slot decode config -- the pair the deltas below compare.
        return fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False, kv_slots=64,
        )

    def test_analyze_adds_sharded_term_to_total(
        self, full_7b, with_kv
    ):
        assert full_7b.kv_cache_bytes == 0
        full = fit.kv_cache_bytes(full_7b.cfg, 64)
        # 7B MHA: 32 kv heads shard over tp=8, 64 slots over dp=4.
        assert with_kv.kv_cache_bytes == full // (4 * 8)
        assert with_kv.total_bytes == \
            full_7b.total_bytes + with_kv.kv_cache_bytes
        assert with_kv.to_json()["kv_cache_bytes"] == \
            with_kv.kv_cache_bytes

    def test_indivisible_slots_stay_replicated(self):
        cfg = llama2.LlamaConfig(
            dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
            vocab_size=256, multiple_of=16, max_seq_len=64,
        )
        r = fit.analyze(
            cfg, dp=4, tp_size=8, global_batch=8, seq_len=64,
            do_compile=False, kv_slots=6,  # 6 % dp(4) != 0
        )
        # slots don't divide dp -> only the kv-head split applies.
        assert r.kv_cache_bytes == fit.kv_cache_bytes(cfg, 6) // 8

    def test_markdown_reports_the_row(self, full_7b, with_kv):
        md = fit.to_markdown(with_kv)
        assert "KV cache (decode, 64 slots)" in md
        assert "KV cache" not in fit.to_markdown(full_7b)


class TestPagedKVTerm:
    """The paged-pool HBM model (--kv-blocks/--kv-block-size) and the
    slab-vs-paged fragmentation-headroom comparison."""

    def test_formula_exact(self):
        cfg = llama2.LlamaConfig(
            dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
            vocab_size=128, multiple_of=16, max_seq_len=32,
        )
        # blocks x block_size x layers x kv_heads x head_dim x 2 x bf16
        want = 100 * 16 * 3 * 2 * 16 * 2 * 2
        assert fit.kv_paged_bytes(cfg, 100, 16) == want
        assert fit.kv_paged_bytes(
            cfg, 100, 16, cache_dtype="float32"
        ) == 2 * want

    @pytest.mark.parametrize("what", [
        "weights", "active", "pool", "token", "fits", "int8", "dense",
    ])
    def test_sparse_expert_cell_bytes(self, what):
        """``serve-docqa-keye30b``'s widths, slots and capacity
        (benchmark/workloads/): 4 of 48 layers hold 3.124B parameters =
        5.82 GiB in bf16 while a token passes 0.55B of them; a cached
        token is K and V and the indexer's 128 B a layer; 12 x 30720 /
        16 + 1 pages are 2.99 GiB; weights twice (the engine's
        construction) and the pool fit a 15.75 GiB chip."""
        import dataclasses

        from tpu_hpc.models import sparse_moe

        cfg = dataclasses.replace(
            sparse_moe.KEYE_VL2_30B_A3B, n_layers=4, max_seq_len=30720
        )
        counts = fit.param_counts(cfg)
        pages = 12 * 30720 // 16 + 1
        pool = fit.kv_paged_bytes(cfg, pages, 16)
        if what == "weights":
            assert counts["total"] == 3_123_858_944
            assert round(2 * counts["total"] / 2**30, 2) == 5.82
        elif what == "active":
            outside = 625_381_760 - 128 * 4_718_592
            assert counts["active"] == 4 * (outside + 8 * 4_718_592) \
                + 311_164_928 + 2048
            assert counts["total"] / counts["active"] > 5
        elif what == "pool":
            assert pages == 23041 and round(pool / 2**30, 2) == 2.99
        elif what == "token":
            assert pool == pages * 16 * 8704
            assert 8704 == 4 * (2 * 4 * 128 * 2 + 64 * 2)
        elif what == "fits":
            assert (2 * 2 * counts["total"] + pool) / 2**30 < 15.75
            five = fit.param_counts(dataclasses.replace(cfg, n_layers=5))
            assert (2 * 2 * five["total"] + pool) / 2**30 > 15.75
        elif what == "int8":
            with pytest.raises(NotImplementedError, match="keye-vl2"):
                fit.kv_paged_bytes(cfg, pages, 16, kv_quant="int8")
        else:
            dense = llama2.LlamaConfig(
                dim=64, n_layers=3, n_heads=4, vocab_size=128,
                multiple_of=16, max_seq_len=32,
            )
            both = fit.param_counts(dense)
            assert both["total"] == both["active"] \
                == llama2.count_params(dense)

    @pytest.mark.parametrize("what", [
        "weights", "by_kind", "active", "pool", "token", "fits", "all_256",
        "int8", "slab",
    ])
    def test_latent_cell_bytes(self, what):
        """``serve-docqa-joyai-flash``'s widths, slots and capacity
        (benchmark/workloads/): 1 dense + 6 expert layers with 64 of
        each layer's 256 routed experts hold 2.601B parameters = 4.85
        GiB in bf16; a cached token is ONE row of 512 + 64 numbers a
        layer, 1152 B, nothing per head; 16 x 30720 / 16 + 1 pages are
        3.69 GiB; weights twice (the engine's construction) and the
        pool fit a 15.75 GiB chip, which every expert at 1 + 4 layers
        would not. What the check cannot size it refuses by name."""
        import dataclasses

        from tpu_hpc.models import latent_moe

        cfg = dataclasses.replace(
            latent_moe.JOYAI_LLM_FLASH, n_layers=7, max_seq_len=30720,
            held_experts=tuple(range(64)),
        )
        counts = fit.param_counts(cfg)
        shapes = jax.tree.leaves(
            latent_moe.param_shapes(cfg),
            is_leaf=lambda s: isinstance(s, tuple),
        )
        pages = 16 * 30720 // 16 + 1
        pool = fit.kv_paged_bytes(cfg, pages, 16)
        one = 3 * 2048 * 768
        if what == "weights":
            assert counts["total"] == sum(
                int(np.prod(s)) for s in shapes
            ) == 2_601_432_576
            assert round(2 * counts["total"] / 2**30, 2) == 4.85
        elif what == "by_kind":
            kinds = latent_moe.count_params(cfg)
            assert kinds["dense_layer"] == 70_391_808       # 70.4M
            assert kinds["expert_layer"] == 333_584_640     # 333.6M
            assert counts["total"] == kinds["dense_layer"] \
                + 6 * kinds["expert_layer"] + 2 * 129280 * 2048 + 2048
        elif what == "active":
            outside = 333_584_640 - 64 * one
            assert counts["active"] == 70_391_808 \
                + 6 * (outside + 8 * one) + 129280 * 2048 + 2048
            assert counts["total"] / counts["active"] > 3
        elif what == "pool":
            assert pages == 30721 and round(pool / 2**30, 2) == 3.69
        elif what == "token":
            assert pool == pages * 16 * 8064
            assert 8064 == 7 * (512 + 64) * 2
            assert fit.kv_paged_bytes(
                cfg, pages, 16, cache_dtype="float32"
            ) == 2 * pool
        elif what == "fits":
            assert (2 * 2 * counts["total"] + pool) / 2**30 < 15.75
        elif what == "all_256":
            whole = fit.param_counts(dataclasses.replace(
                cfg, n_layers=5, held_experts=None
            ))
            assert round(whole["total"] / 1e9, 2) == 5.56
            assert 2 * 2 * whole["total"] / 2**30 > 15.75
        elif what == "int8":
            with pytest.raises(NotImplementedError, match="joyai-llm-flash"):
                fit.kv_paged_bytes(cfg, pages, 16, kv_quant="int8")
        else:
            with pytest.raises(NotImplementedError, match="joyai-llm-flash"):
                fit.kv_cache_bytes(cfg, 16)

    @pytest.fixture(scope="class")
    def with_paged(self, full_7b):
        # Slab 64 slots x 4096 worst-case vs a pool provisioned for
        # the tokens the mix actually occupies (half the worst case).
        return fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False, kv_slots=64,
            kv_blocks=8192, kv_block_size=16,
        )

    def test_paged_term_replaces_slab_in_total(
        self, full_7b, with_paged
    ):
        full = fit.kv_paged_bytes(full_7b.cfg, 8192, 16)
        # KV heads shard over tp=8; the pool replicates over data.
        assert with_paged.kv_block_bytes == full // 8
        assert with_paged.total_bytes == \
            full_7b.total_bytes + with_paged.kv_block_bytes
        d = with_paged.to_json()
        assert d["kv_block_bytes"] == with_paged.kv_block_bytes
        assert d["kv_blocks"] == 8192
        assert d["kv_block_size"] == 16

    def test_markdown_headroom_line(self, with_paged):
        md = fit.to_markdown(with_paged)
        assert "KV cache (paged, 8192 pages x 16 tok)" in md
        assert "Fragmentation headroom (per data replica" in md
        # Per REPLICA (the only sharding-honest comparison): the
        # slab's share is 64/4 slots x 4096 = 65536 tokens; the pool
        # is 8192 x 16 = 131072 tokens -- over-provisioned 2x, and
        # the line must say so rather than flatter the config.
        assert "MORE** than the slab share" in md

    def test_cli_flags_reach_analyze(self, capsys):
        rc = fit.main([
            "--no-compile", "--kv-slots", "64",
            "--kv-blocks", "4096", "--kv-block-size", "16", "--json",
        ])
        import json as _json

        out = _json.loads(capsys.readouterr().out)
        assert out["kv_blocks"] == 4096
        assert out["kv_block_bytes"] > 0
        assert rc in (0, 1)


class TestQuantizedKVTerm:
    """The int8 page-storage budget (--kv-quant int8,
    tpu_hpc.kernels.paged_attention): 1-byte pages + per-page fp32
    scales, about half the bf16 pool -- and the report must print
    the capacity multiplier the flag exists for."""

    def test_formula_exact(self):
        cfg = llama2.LlamaConfig(
            dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
            vocab_size=128, multiple_of=16, max_seq_len=32,
        )
        # pages at 1 byte/elem + one fp32 scale per page per layer
        # for K and V each.
        want = 100 * 16 * 3 * 2 * 16 * 2 * 1 + 100 * 3 * 2 * 4
        assert fit.kv_paged_bytes(cfg, 100, 16, kv_quant="int8") == want
        # Just under half the bf16 pool (the scale side array is the
        # difference from exactly half).
        bf16 = fit.kv_paged_bytes(cfg, 100, 16)
        assert want < bf16 * 0.51

    @pytest.fixture(scope="class")
    def with_quant(self, full_7b):
        return fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False,
            kv_blocks=8192, kv_block_size=16, kv_quant="int8",
        )

    def test_halves_the_pool_term(self, full_7b, with_quant):
        full = fit.kv_paged_bytes(
            full_7b.cfg, 8192, 16, kv_quant="int8"
        )
        assert with_quant.kv_block_bytes == -(-full // 8)
        bf16 = fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False,
            kv_blocks=8192, kv_block_size=16,
        )
        assert with_quant.kv_block_bytes < bf16.kv_block_bytes * 0.51
        assert with_quant.to_json()["kv_quant"] == "int8"

    def test_draft_mirror_quantizes_too(self, full_7b):
        from tpu_hpc.serve.spec import default_draft_config

        draft = default_draft_config(full_7b.cfg)
        r = fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False,
            kv_blocks=8192, kv_block_size=16, kv_quant="int8",
            draft_cfg=draft,
        )
        assert r.draft_kv_block_bytes == -(-fit.kv_paged_bytes(
            draft, 8192, 16, kv_quant="int8"
        ) // 8)

    def test_markdown_capacity_multiplier(self, with_quant):
        md = fit.to_markdown(with_quant)
        assert "int8 + fp32 scales" in md
        assert "Quantized KV capacity" in md
        assert "2.0x the resident context at equal HBM" in md

    def test_quant_requires_paged_pool(self, full_7b):
        with pytest.raises(ValueError, match="kv_blocks"):
            fit.analyze(
                cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
                seq_len=4096, do_compile=False, kv_quant="int8",
            )
        with pytest.raises(ValueError, match="kv_quant"):
            fit.analyze(
                cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
                seq_len=4096, do_compile=False,
                kv_blocks=64, kv_quant="fp8",
            )

    def test_cli_requires_kv_blocks(self, capsys):
        with pytest.raises(SystemExit):
            fit.main(["--no-compile", "--kv-quant", "int8"])
        assert "--kv-blocks" in capsys.readouterr().err

    def test_cli_flag_reaches_analyze(self, capsys):
        rc = fit.main([
            "--no-compile", "--kv-blocks", "4096",
            "--kv-quant", "int8", "--json",
        ])
        import json as _json

        out = _json.loads(capsys.readouterr().out)
        assert out["kv_quant"] == "int8"
        assert rc in (0, 1)


class TestSpecDraftTerm:
    """The speculative-draft HBM budget (serve/spec.py via
    --spec-draft): draft params + the mirrored paged pool must land
    in the total, and an oversized draft must flip the verdict --
    fail the fit report, not OOM at serving bring-up."""

    def test_draft_terms_add_to_total(self, full_7b):
        from tpu_hpc.serve.spec import default_draft_config

        draft = default_draft_config(full_7b.cfg)
        r = fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False,
            kv_blocks=8192, kv_block_size=16, draft_cfg=draft,
        )
        base = fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False,
            kv_blocks=8192, kv_block_size=16,
        )
        assert r.draft_n_params == llama2.count_params(draft)
        # fp32 serving params, TP-sharded over model=8.
        assert r.draft_param_bytes == -(-r.draft_n_params * 4 // 8)
        assert r.draft_kv_block_bytes == \
            fit.kv_paged_bytes(draft, 8192, 16) // 8
        assert r.total_bytes == (
            base.total_bytes + r.draft_param_bytes
            + r.draft_kv_block_bytes
        )
        md = fit.to_markdown(r)
        assert "spec draft params" in md
        assert "spec draft KV pool (mirrored 8192 pages)" in md

    def test_oversized_draft_fails_the_verdict(self, full_7b):
        # A "draft" as big as the target on an HBM budget that held
        # exactly the target: must flip to DOES NOT FIT.
        fits_alone = fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False,
            kv_blocks=4096, kv_block_size=16,
        )
        gib = fits_alone.total_bytes / (1 << 30) + 0.5
        r = fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, hbm_gib=gib, do_compile=False,
            kv_blocks=4096, kv_block_size=16,
            draft_cfg=full_7b.cfg,
        )
        assert not r.fits

    def test_draft_requires_paged_pool(self, full_7b):
        with pytest.raises(ValueError, match="kv_blocks"):
            fit.analyze(
                cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
                seq_len=4096, do_compile=False,
                draft_cfg=full_7b.cfg,
            )


class TestHostTierTerm:
    """The host-DRAM KV page-tier budget (serve/tier.py via
    --kv-host-tier): host bytes are DRAM, never HBM -- they must be
    reported for sizing without moving the fits verdict, and the
    markdown must carry the resident-sessions multiplier the tier
    exists to buy."""

    @pytest.fixture(scope="class")
    def with_tier(self, full_7b):
        return fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False,
            kv_blocks=1024, kv_block_size=16, kv_host_blocks=9216,
        )

    def test_host_bytes_never_in_hbm_total(self, full_7b, with_tier):
        base = fit.analyze(
            cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
            seq_len=4096, do_compile=False,
            kv_blocks=1024, kv_block_size=16,
        )
        # Full-width per host (device_get assembles the sharded rows
        # before the numpy store): no tp/dp division.
        assert with_tier.kv_host_bytes == \
            fit.kv_paged_bytes(full_7b.cfg, 9216, 16)
        # DRAM, not HBM: the total and the verdict must not move.
        assert with_tier.total_bytes == base.total_bytes
        assert with_tier.fits == base.fits
        d = with_tier.to_json()
        assert d["kv_host_blocks"] == 9216
        assert d["kv_host_bytes"] == with_tier.kv_host_bytes

    def test_markdown_resident_sessions_multiplier(self, with_tier):
        md = fit.to_markdown(with_tier)
        assert "Host KV tier (serve/tier.py)" in md
        assert "NOT in the HBM total" in md
        # 1023 device pages + 9215 host pages over 1023: the ~10x
        # headline resident-sessions claim, computed not asserted by
        # hand-wave.
        assert "**10.0x the resident sessions**" in md

    def test_tier_requires_paged_pool(self, full_7b):
        with pytest.raises(ValueError, match="kv_blocks"):
            fit.analyze(
                cfg=full_7b.cfg, dp=4, tp_size=8, global_batch=8,
                seq_len=4096, do_compile=False, kv_host_blocks=64,
            )

    def test_cli_requires_kv_blocks(self, capsys):
        with pytest.raises(SystemExit) as e:
            fit.main([
                "--no-compile", "--kv-host-tier", "64", "--json",
            ])
        assert e.value.code == 2
        assert "--kv-blocks" in capsys.readouterr().err

    def test_cli_flag_reaches_analyze(self, capsys):
        rc = fit.main([
            "--no-compile", "--kv-blocks", "1024",
            "--kv-host-tier", "9216", "--json",
        ])
        import json as _json

        out = _json.loads(capsys.readouterr().out)
        assert out["kv_host_blocks"] == 9216
        assert out["kv_host_bytes"] > 0
        assert rc in (0, 1)
