"""The timed path's logits against the plain reference at the timed
sizes of ``serve-docqa-falcon-h1-34b``, on the chip:

    python benchmark/tests/parallel_arch_logits.py <seed> [bf16_state]

Builds the cell's engine (its configuration, weights from ``<seed>``,
16 slots x 30720), prefills one 28800-token prompt through the chunk
program (57 chunks of 512 rows: pages and state carried from chunk to
chunk) with the logits of its last row as the result, then decodes 32
steps through the decode program's layer loop (the page pool and the
recurrent state of slot 0 among 16, the other slots inactive) with the
logits as the result, each step fed the arg-max of the step before.
The reference (``benchmark/reference/parallel_hybrid_decoder.py``,
float32, precision "highest", in blocks of 256 rows) runs once over
the same 28832 tokens. Prints, for the prefill's row and each decode
row, the largest difference of a logit in standard deviations of the
reference's row, and the arg-max agreement; and, from the reference's
own layers over the prompt's first 2048 tokens, the share each half of
every layer moves the residual stream by (RMS of what it adds over the
RMS of the stream it is added to). One JSON line last. With
``bf16_state`` the program keeps its recurrent state in bfloat16, not
float32: how far that moves the logits, against the rounding of the
bf16 products alone."""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CELL = "serve-docqa-falcon-h1-34b"
PROMPT, STEPS = 28800, 32
# Query rows a block of the reference, and the prompt rows the shares
# are measured over.
BLOCK, SHARE_ROWS = 256, 2048


def main(seed, fault="none"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.reference import parallel_hybrid_decoder as reference
    from tpu_hpc.serve import decoder, paging
    from tpu_hpc.serve.engine import ServeConfig

    spec = harness.cell_spec(harness.load_manifest(), CELL)
    if fault == "bf16_state":
        spec["config"]["program"]["config_kwargs"]["ssm_state_dtype"] = \
            "bfloat16"
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")
    job = harness.load_module("jobs", "serve_arch.py")
    eng = spec["cell"]["engine"]
    cap, bs, slots, bucket = eng["capacity"], eng["block_size"], \
        eng["slots"], eng["prefill_chunk"]
    cfg, arch = job.build(spec["config"], spec["cell"], cap)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    t = time.perf_counter()
    params = job.init_params(
        spec["config"], cfg, seed, NamedSharding(mesh, P())
    )
    engine = paging.PagedEngine(
        params, cfg,
        ServeConfig(slots=slots, max_seq_len=cap,
                    prefill_buckets=tuple(eng["buckets"])),
        mesh, paging.PagedConfig(
            block_size=bs, num_blocks=slots * cap // bs + 1,
            prefill_chunk=bucket,
        ),
    )
    del params
    params = engine.params
    mb = cap // bs
    width = engine.table_width
    print(f"built in {time.perf_counter() - t:.1f} s", flush=True)

    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32)
    table = np.zeros(width, np.int32)
    table[:mb] = np.arange(1, mb + 1)
    table = jnp.asarray(table)
    chunk = jax.jit(paging.make_chunk_logits_fn(
        cfg, bucket, bs, mb, width
    ), donate_argnums=(1, 2, 3, 4))
    state = engine._state()
    engine.ks = engine.vs = engine.ssm_s = engine.ssm_conv = None
    for start in range(0, PROMPT, bucket):
        run = min(bucket, PROMPT - start)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :run] = prompt[start:start + run]
        *state, logits, _, _ = chunk(
            params, *state, jnp.asarray(tokens), jnp.int32(start),
            jnp.int32(run), table, jnp.int32(0), jnp.int32(0),
        )
    rows = [np.asarray(logits, np.float32)]

    def step(params, state, tokens, pos, active, tables):
        pool = paging.PagedAttention(cfg, bs, mb).on(*state[:2])
        recur = paging.RecurrentState(cfg).on(*state[2:])
        x = decoder._embed(params, tokens[:, None], cfg)
        cos, sin = decoder._rope_tables(cfg, 1, pos)
        mask = (jnp.arange(cap)[None, :] <= pos[:, None])[
            :, None, None, None, :
        ]
        pb = jnp.where(
            active > 0, tables[jnp.arange(slots), pos // bs],
            paging.SCRATCH_BLOCK,
        )
        pool.view(tables, pos, active)
        pool.rows(pb, pos % bs, mask, slot=jnp.arange(slots))
        recur.rows(active)
        x, _ = decoder.decoder_layers(
            params, cfg, x, cos[:, None, :], sin[:, None, :], pool,
            weight=active, recur=recur,
        )
        return decoder._logits_head(x, params, cfg)[0, 0], \
            [*pool.state()[:2], *recur.state()]

    step = jax.jit(step, donate_argnums=(1,))
    tables = jnp.zeros((slots, width), jnp.int32).at[0].set(table)
    active = jnp.zeros((slots,), jnp.int32).at[0].set(1)
    emitted = [int(rows[0].argmax())]
    for j in range(STEPS):
        pos = PROMPT + j
        logits, state = step(
            params, state, jnp.full((slots,), emitted[-1], jnp.int32),
            jnp.full((slots,), pos, jnp.int32), active, tables,
        )
        rows.append(np.asarray(logits, np.float32))
        emitted.append(int(rows[-1].argmax()))
    del state
    got = np.stack(rows)

    pad = spec["cell"]["check"]["pad_to"]
    tokens = np.zeros(pad, np.int32)
    tokens[:PROMPT] = prompt
    tokens[PROMPT:PROMPT + STEPS] = emitted[:STEPS]
    positions = PROMPT - 1 + np.arange(STEPS + 1)
    t = time.perf_counter()
    want = np.asarray(jax.jit(lambda p, tk: reference.logits(
        p, reference.forward(p, tk, arch, q_block=BLOCK)[0][positions], arch
    ))(params, jnp.asarray(tokens)))
    ref_s = time.perf_counter() - t
    sigma = want.std(-1)
    diff = np.abs(got - want).max(-1) / sigma
    agree = got.argmax(-1) == want.argmax(-1)
    for i, (d, a) in enumerate(zip(diff, agree)):
        print(f"row {positions[i]}: max |diff| {d:.5f} sigma, "
              f"arg-max {'agrees' if a else 'differs'}", flush=True)

    shares = _shares(params, prompt[:SHARE_ROWS], arch)
    for layer, s in enumerate(shares):
        print(f"layer {layer}: {s}", flush=True)
    print(json.dumps({
        "seed": seed, "fault": fault, "rows": int(len(diff)),
        "prefill_diff_sigma": float(diff[0]),
        "decode_diff_sigma_max": float(diff[1:].max()),
        "decode_diff_sigma_median": float(np.median(diff[1:])),
        "argmax_agree": float(agree.mean()), "reference_s": ref_s,
        "shares": shares,
    }), flush=True)


def _shares(params, prompt, arch):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import parallel_hybrid_decoder as reference

    def rms(a):
        return jnp.sqrt(jnp.mean(a * a))

    @jax.jit
    def layers(p, tokens):
        eps = arch["norm_eps"]
        out = []
        with jax.default_matmul_precision("highest"):
            x = p["tok_embeddings"]["embedding"][tokens].astype(jnp.float32) \
                * arch["embedding_multiplier"]
            for i in range(arch["n_layers"]):
                lp = p[f"layers_{i}"]
                h = reference.rmsnorm(x, lp["attention_norm"]["scale"], eps)
                a = reference.attention(h, lp["attention"], arch, BLOCK)
                m = reference.ssm_mixer(h, lp["ssm"], arch)
                x1 = x + a + m
                g = reference.rmsnorm(x1, lp["ffn_norm"]["scale"], eps)
                f = reference.feed_forward(g, lp["feed_forward"], arch, BLOCK)
                out.append((rms(x), rms(a) / rms(x), rms(m) / rms(x),
                            rms(f) / rms(x1)))
                x = x1 + f
        return out

    return [
        {"stream_rms": round(float(s), 4), "attention": round(float(a), 4),
         "mixer": round(float(m), 4), "feed_forward": round(float(f), 4)}
        for s, a, m, f in layers(params, jnp.asarray(prompt))
    ]


if __name__ == "__main__":
    main(int(sys.argv[1]), *sys.argv[2:3])
