"""Job kind ``serve_arch``: what ``serve`` does (the paged server under
an open loop: ``jobs/serve.py``'s ``Driver``, window and request
records), for a decoder that ``LlamaConfig`` cannot express.

The configuration file names what the program needs, so the next
architecture adds a file and no job:

    program.config, .config_kwargs   the program's configuration class
                                     and the sizes it is built with
    program.init                     seeded weights, jitted here
    program.reference                the plain reference under
                                     ``benchmark/reference/``
    arch, assumed_sizes              the reference's sizes, by the
                                     PUBLISHED keys they are read from

and every size the program's configuration carries is held to the
published one (``arch``) before anything runs.

Three things of its own:

* the ``serve_moe_*`` / ``serve_sparse_*`` counters and the count of
  decode steps (``engine.paged_stats``) are snapshotted at the
  window's start; ``obs["serve"]["stats"]`` holds their growth over
  the window (the gauge ``serve_moe_max_tokens_per_expert`` is reset
  there and holds the window's largest);
* a mix that shares prefixes has each group's prefix prefilled once
  during set-up (one request a group, one new token), and ``checks``
  reports the window's prefix hit rate, prompt tokens served from the
  trie over prompt tokens admitted, which must be at least
  ``PREFIX_HIT_MIN`` for ``correct``;
* the check: two sampled requests at the timed sizes, one on a warmed
  prefix (a trie hit, one suffix chunk, decode at the prefix's full
  context) and one unshared (every chunk), scored by the reference's
  regret as ``jobs/serve.py`` does; and on sampled decode rows the
  program's own selection (``engine.probe_selection``: the decode
  program with its masks as a result) is held to a band round the
  reference's, see ``SELECTION_EPS_SIGMA`` and ``SELECTION_MEDIAN_SIGMA``.
"""
import importlib
import os
import time

import numpy as np

from benchmark import harness, program_trace, trace_reduce

_serve = harness.load_module("jobs", "serve.py")
Driver, _drain, _warm = _serve.Driver, _serve._drain, _serve._warm

# The stages this job's programs name beside ``program_trace.SCOPES``
# (docs/guide/observability.md, "Stage names"). ``program_trace`` may
# not be edited by the PR that brings them, so the job that runs such a
# program extends the list for its own process: every reader then files
# their operations under their own names, and ``unscoped_pct.serve``
# keeps its meaning. (A ``benchmark`` PR should move the three names
# into ``program_trace.SCOPES``: PERF.md, section 7.)
ARCH_SCOPES = ("indexer", "router", "experts")
program_trace.SCOPES = tuple(
    dict.fromkeys(program_trace.SCOPES + ARCH_SCOPES)
)

# Tolerances of the regret check (``jobs/serve.py`` has the argument:
# the engine computes in bf16 and emits the arg-max of bf16 logits, so
# a flipped near-tie costs a regret of the size of the logits' rounding
# error). Measured on the v5e at ``serve-docqa-keye30b``'s sizes
# (PERF.md PR 27; fourteen runs, fourteen seeds, 64 positions each):
# the largest regret of a run 0 to 0.0300 sigma (one flipped near-tie
# sets it: a heavy tail, 0.0201 the next), the mean 0 to 4.7e-4, the
# arg-max agreed at 95-100 % of positions. With every matmul operand
# rounded through ``float8_e4m3fn`` (the nearest precision below) the
# same check read max 0.0758, mean 2.35e-3. The limits lie between,
# twice the largest seen: the mean's is the one that tells 8-bit
# products apart (2.3 x above it). A stale page or the wrong rows read
# 0.27-0.35 / 0.022-0.031.
REGRET_MAX_SIGMA = 0.06
REGRET_MEAN_SIGMA = 0.001

# The selection's band. The program scores in bf16 products with
# float32 accumulation on a bf16 residual stream, the reference in
# float32, so near the 2048th value their choices differ: for each
# probed row, how far below the reference's k-th largest score the
# worst column the program selected lies, and how far above it the
# worst column it left out, in standard deviations of that row's valid
# scores. Measured on the v5e (PERF.md PR 27; ten runs on ten seeds,
# five decode rows x four layers each): a row reads 0.003-0.05 as a
# rule; about one token in eight has a token-expert choice that bf16
# makes otherwise than float32 (its eighth expert against its ninth),
# and the rows of ITS deeper layers read 0.08-0.13, as every token's
# do when one expert of the eight is left out on purpose. A selection
# made on scores one page out of step, or of the k LOWEST, reads
# 4.4-6.8 on every row. So two limits: SELECTION_EPS_SIGMA on each
# row, far above any flip and far below any wrong mask; and
# SELECTION_MEDIAN_SIGMA on the MEDIAN over the rows of the layers
# past the first (layer 0 sees the embedding alone and reads ~0.01
# whatever the layers do), which a minority of flipped tokens cannot
# move: 0.024-0.046 over the ten runs at 15 such rows, and at the 90
# the cell probes (30 tokens) 0.0312, 0.0334, 0.0338 on three seeds
# more, the worst row 0.15; against 0.109 (90 rows; 0.116 at 9) with
# ONE expert of a token's eight left out -- a fault the regret sees on
# some seeds only (an expert is a few percent of a unit-normal
# residual stream) but that shifts every token's next hidden state and
# with it the indexer's scores. 8-bit products read 0.030 here and are
# caught by the regret's mean.
SELECTION_EPS_SIGMA = 1.0
SELECTION_MEDIAN_SIGMA = 0.06

PREFIX_HIT_MIN = 0.95


def _resolve(path):
    module, _, name = path.partition(":")
    return getattr(importlib.import_module(module), name)


def _published(config, key):
    value = config["published"]
    for part in key.split("."):
        value = value[part]
    return value


def build(config, cell, max_seq_len):
    """-> (the program's configuration, the reference's sizes), every
    size of the first held to the published one."""
    import jax.numpy as jnp

    program = config["program"]
    cfg = _resolve(program["config"])(
        **program["config_kwargs"],
        n_layers=cell["n_layers"], max_seq_len=max_seq_len,
        dtype=jnp.dtype(cell["compute_dtype"]),
        param_dtype=jnp.dtype(cell["param_dtype"]),
    )
    arch = {k: _published(config, v) for k, v in config["arch"].items()}
    arch.update(config.get("assumed_sizes", {}))
    got = {k: getattr(cfg, "kv_heads" if k == "n_kv_heads" else k)
           for k in arch}
    bad = {k: (got[k], v) for k, v in arch.items() if got[k] != v}
    if bad:
        raise SystemExit(
            f"benchmark: {config['name']}: the program's configuration "
            f"differs from the published sizes (got, published): {bad}"
        )
    if max_seq_len > config["published"]["max_position_embeddings"]:
        raise SystemExit(
            f"benchmark: context {max_seq_len} exceeds what "
            f"{config['name']} declares"
        )
    arch["n_layers"] = cell["n_layers"]
    return cfg, arch


def init_params(config, cfg, seed, sharding):
    """``harness.init_params`` with the configuration's own init."""
    import jax
    import jax.numpy as jnp

    init = _resolve(config["program"]["init"])

    def make(lo, hi):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(0), lo), hi
        )
        return init(key, cfg)

    return jax.jit(make, out_shardings=sharding)(
        jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF)
    )


def _prefixes(requests, shared):
    """One prompt prefix a group, in order of first appearance."""
    seen = {}
    for req in requests:
        seen.setdefault(req["prompt"][:shared].tobytes(),
                        req["prompt"][:shared])
    return list(seen.values())


def _warm_prefixes(engine, prefixes, log):
    """Each group's prefix through the normal path once, so the trie
    holds it before the window (a deployment's documents are asked
    about again and again; the first question is not what the cell
    measures)."""
    driver = Driver(engine)
    for k, prefix in enumerate(prefixes):
        driver.submit(
            {"rid": f"doc{k}", "prompt": prefix, "max_new": 1},
            time.perf_counter(),
        )
    _drain(driver)
    log(f"prefixes | {len(prefixes)} of {len(prefixes[0])} tokens, "
        f"{len(driver.calls['prefill'])} chunks")


def _band(picked, scores, row, k):
    """One probed row against the reference's scores of that row:
    -> (count ok, worst selected column below the k-th value, worst
    unselected column above it, both in sigmas of the row's valid
    scores, share of the reference's own selection that was picked)."""
    valid = np.arange(scores.shape[0]) <= row
    s = scores[valid].astype(np.float64)
    p = picked[:row + 1]
    want = min(k, row + 1)
    if picked[row + 1:].any() or int(p.sum()) != want:
        return False, np.inf, np.inf, 0.0
    if want == row + 1:
        return True, 0.0, 0.0, 1.0
    order = np.argsort(-s, kind="stable")
    kth, sigma = s[order[want - 1]], s.std()
    under = max(0.0, float((kth - s[p]).max())) / sigma
    over = max(0.0, float((s[~p] - kth).max())) / sigma
    ref = np.zeros_like(p)
    ref[order[:want]] = True
    return True, under, over, float((p & ref).sum() / want)


def _check(engine, requests, shared, reference, arch, cell, vocab_size, log):
    """Two sampled requests through the normal path (batcher, trie,
    chunked prefill, paged decode), scored by the reference."""
    import jax
    import jax.numpy as jnp

    spec = cell["check"]
    n_new, pad = spec["new_tokens"], spec["pad_to"]
    head = sorted(requests[:64], key=lambda r: -len(r["prompt"]))
    # On a warmed prefix: the prefix as it is, other own tokens ((t + 1)
    # mod vocab), so the window's request of this rid finds the prefix
    # in the trie and nothing of the sample's. Unshared: every token
    # other, so the trie holds nothing of it.
    hit = head[-1]["prompt"].copy()
    hit[shared:] = (hit[shared:] + 1) % vocab_size
    sample = [hit, (head[0]["prompt"] + 1) % vocab_size]
    if not shared:
        sample = sample[1:]
    lens = [len(p) for p in sample]

    driver = Driver(engine)
    probes, steps = [], set(spec.get("probe_steps", ()))
    decode = engine.decode

    def probing(tokens, positions, active=None):
        if len(driver.calls["decode"]) in steps:
            probes.append((
                [int(p) for p in positions],
                [s.rid if a else None
                 for s, a in zip(driver.batcher.slots, active)],
                engine.probe_selection(tokens, positions, active),
            ))
        return decode(tokens, positions, active=active)

    if getattr(engine, "xs", None) is not None:
        engine.decode = probing
    before = dict(engine.paged_stats)
    for k, prompt in enumerate(sample):
        driver.submit(
            {"rid": f"check{k}", "prompt": prompt, "max_new": n_new},
            time.perf_counter(),
        )
    _drain(driver)
    results = driver.batcher.results

    # The rows each request was probed at, by the slot it sat in.
    rows = [[] for _ in sample]
    for positions, rids, _ in probes:
        for pos, rid in zip(positions, rids):
            if rid is not None:
                rows[int(rid[len("check"):])].append(pos)
    ref = jax.jit(
        lambda p, t, pos, e, r: reference.regret(
            p, t, pos, e, arch, probe_rows=r, q_block=spec["q_block"]
        )
    )
    regrets, bands, short = [], [], 0
    for k, prompt in enumerate(sample):
        emitted = results.get(f"check{k}", [])
        if len(emitted) != n_new:
            short += 1
            continue
        plen = len(prompt)
        tokens = np.zeros((pad,), np.int32)
        tokens[:plen] = prompt
        tokens[plen:plen + n_new - 1] = emitted[:-1]
        positions = plen - 1 + np.arange(n_new, dtype=np.int32)
        probe_rows = np.asarray(
            (rows[k] + [plen] * len(steps))[:max(len(steps), 1)], np.int32
        )
        reg, std, probed = ref(
            engine.params, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(np.asarray(emitted, np.int32)),
            jnp.asarray(probe_rows),
        )
        regrets.append(np.asarray(reg / std))
        scores = np.asarray(probed["scores"])      # [layers, rows, pad]
        for positions_, rids, picked in probes:
            for slot, (pos, rid) in enumerate(zip(positions_, rids)):
                if rid != f"check{k}":
                    continue
                j = rows[k].index(pos)
                for layer in range(scores.shape[0]):
                    bands.append(_band(
                        picked[layer, slot], scores[layer, j], pos,
                        arch["indexer_topk"],
                    ) + (layer,))
    engine.decode = decode
    flat = np.concatenate(regrets) if regrets else np.array([np.inf])
    grown = {k: engine.paged_stats[k] - before.get(k, 0)
             for k in ("prefix_hit_blocks", "prefill_chunks")}
    out = {
        "requests": len(sample),
        "prompt_lens": lens,
        "positions": int(flat.size),
        "short": short,
        "regret_max_sigma": float(flat.max()),
        "regret_mean_sigma": float(flat.mean()),
        "argmax_agree": float((flat == 0).mean()),
        "prefill_chunks": grown["prefill_chunks"],
        "prefix_hit_blocks": grown["prefix_hit_blocks"],
        "decode_steps": len(driver.calls["decode"]),
        "selection_rows": len(bands),
        "selection_counts_ok": all(b[0] for b in bands),
        "selection_under_sigma": max((b[1] for b in bands), default=0.0),
        "selection_over_sigma": max((b[2] for b in bands), default=0.0),
        "selection_overlap_min": min((b[3] for b in bands), default=1.0),
        # layer: median and worst shortfall in sigmas, least overlap
        "selection_by_layer": {
            layer: [
                round(float(np.median([max(b[1], b[2]) for b in rows_])), 4),
                round(max(max(b[1], b[2]) for b in rows_), 4),
                round(min(b[3] for b in rows_), 4),
            ]
            for layer in sorted({b[4] for b in bands})
            for rows_ in [[b for b in bands if b[4] == layer]]
        },
    }
    deep = [max(b[1], b[2]) for b in bands
            if b[4] > 0 or arch["n_layers"] == 1]
    out["selection_median_sigma"] = float(np.median(deep)) if deep else 0.0
    probed_ok = not steps or getattr(engine, "xs", None) is None or (
        bands and out["selection_counts_ok"]
        and out["selection_under_sigma"] < SELECTION_EPS_SIGMA
        and out["selection_over_sigma"] < SELECTION_EPS_SIGMA
        and out["selection_median_sigma"] < SELECTION_MEDIAN_SIGMA
    )
    out["ok"] = bool(
        short == 0 and np.isfinite(flat).all() and probed_ok
        and out["regret_max_sigma"] < REGRET_MAX_SIGMA
        and out["regret_mean_sigma"] < REGRET_MEAN_SIGMA
        and (not shared or out["prefix_hit_blocks"] > 0)
    )
    log(f"check | {out} (tolerances: regret max {REGRET_MAX_SIGMA}, mean "
        f"{REGRET_MEAN_SIGMA} sigma; selection a row {SELECTION_EPS_SIGMA}, "
        f"median {SELECTION_MEDIAN_SIGMA} sigma)")
    return out


def run(ctx):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.serve.engine import ServeConfig
    from tpu_hpc.serve.paging import PagedConfig, PagedEngine

    spec, log = ctx["spec"], ctx["log"]
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    eng_spec = cell["engine"]
    capacity = eng_spec["capacity"]
    cfg, arch = build(config, cell, max_seq_len=capacity)
    reference = importlib.import_module(
        f"benchmark.reference.{config['program']['reference']}"
    )
    gen = harness.load_module("traffic", f"{traffic['kind']}.py")
    requests = gen.generate(
        traffic, ctx["seed"], cfg.vocab_size, ctx["seconds"]
    )
    worst = max(len(r["prompt"]) + r["max_new"] for r in requests)
    if worst > capacity:
        raise SystemExit(
            f"benchmark: a request of {worst} tokens exceeds the "
            f"capacity {capacity}: choose traffic on which nothing fails"
        )
    backlog = traffic["arrivals"]["process"] == "backlog"
    shared = int(traffic.get("shared_prefix_tokens", 0))

    devices = ctx["devices"]
    mesh = build_mesh(
        MeshSpec(axes=dict(cell["mesh"])),
        devices if len(devices) != jax.device_count() else None,
    )
    phases = {}
    t = time.perf_counter()
    params = init_params(config, cfg, ctx["seed"], NamedSharding(mesh, P()))
    jax.block_until_ready(params)
    phases["init_s"] = time.perf_counter() - t

    block = eng_spec["block_size"]
    paged = PagedConfig(
        block_size=block,
        num_blocks=eng_spec["slots"] * capacity // block + 1,
        prefill_chunk=eng_spec["prefill_chunk"],
        prefix_cache=eng_spec.get("prefix_cache", True),
        kernel=eng_spec.get("kernel", "gather"),
        kv_quant=eng_spec.get("kv_quant", "none"),
    )
    t = time.perf_counter()
    engine = PagedEngine(
        params, cfg,
        ServeConfig(
            slots=eng_spec["slots"], max_seq_len=capacity,
            prefill_buckets=tuple(eng_spec["buckets"]),
        ),
        mesh, paged,
    )
    del params  # the engine holds its own placed copy
    phases["engine_s"] = time.perf_counter() - t
    t = time.perf_counter()
    n_programs = engine.warmup()
    phases["compile_or_load_s"] = time.perf_counter() - t

    t = time.perf_counter()
    _warm(engine, eng_spec["buckets"], cfg.vocab_size, ctx["seed"])
    if shared:
        _warm_prefixes(engine, _prefixes(requests, shared), log)
    phases["warmup_s"] = time.perf_counter() - t
    t = time.perf_counter()
    check = _check(
        engine, requests, shared, reference, arch, cell, cfg.vocab_size, log
    )
    phases["reference_s"] = time.perf_counter() - t
    programs_before = engine.compile_count_total
    log(f"warm | {phases} | {n_programs} programs | pool "
        f"{engine.cache_bytes / 2**30:.2f} GiB, {paged.num_blocks} pages")

    # ---- the window ------------------------------------------------
    driver = Driver(engine)
    counter = ctx["counter"]
    counter.mark()
    trace_state = {}
    trace_dir = os.path.join(ctx["out_dir"], "trace")
    trace_s = min(cell.get("trace_seconds", 4.0), ctx["seconds"])

    def open_trace():
        harness.start_trace(trace_dir)
        trace_state["span"] = jax.profiler.TraceAnnotation(
            trace_reduce.WINDOW_SPAN
        )
        trace_state["span"].__enter__()
        trace_state["t_open"] = time.perf_counter()

    def close_span():
        if trace_state:
            trace_state["span"].__exit__(None, None, None)

    gauges = [k for k in engine.paged_stats if k.startswith("serve_moe_max")]
    for name in gauges:
        engine.paged_stats[name] = 0
    stats_before = dict(engine.paged_stats)
    t_window = time.perf_counter()
    t0, t_close = driver.run(
        requests, ctx["seconds"], drain=not backlog,
        trace_at=(ctx["seconds"] - trace_s) if ctx["trace"] else None,
        on_trace=open_trace, on_close=close_span,
    )
    t_end = time.perf_counter()
    trace = None
    if trace_state:
        jax.profiler.stop_trace()
        trace = trace_reduce.reduce(
            trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        )
        if trace is not None:
            trace["t_open"] = trace_state["t_open"] - t0
            trace["t_shut"] = t_close - t0
    compiles_in_window = counter.since_mark()
    recompiles = engine.compile_count_total - programs_before
    grown = {
        k: v if k in gauges else v - stats_before[k]
        for k, v in engine.paged_stats.items()
    }

    # ---- what happened, request by request --------------------------
    window_end = (t_close if backlog else t_end) - t0
    records, finished_ok, failed = [], 0, len(driver.errors)
    errored = {rid for rid, _ in driver.errors}
    results = driver.batcher.results
    admitted_prompt_tokens = 0
    for req in requests:
        rid = req["rid"]
        if rid not in driver.submits or rid in errored:
            continue
        due, submitted = driver.submits[rid]
        tr = driver.meter.traces.get(rid)
        rec = {
            "rid": rid, "due": due - t0, "submit": submitted - t0,
            "prompt_len": len(req["prompt"]), "max_new": req["max_new"],
            "admit": None, "first": None, "done": None, "token_times": [],
            "shed": tr is None,
        }
        if tr is not None:
            rec["admit"] = None if tr.t_admit is None else tr.t_admit - t0
            rec["first"] = None if tr.t_first is None else tr.t_first - t0
            rec["done"] = None if tr.t_done is None else tr.t_done - t0
            rec["token_times"] = [x - t0 for x in tr.token_times]
        if rec["admit"] is not None:
            admitted_prompt_tokens += rec["prompt_len"]
        whole = (
            rec["done"] is not None
            and len(results.get(rid, [])) == req["max_new"]
        )
        rec["ok"] = whole
        if backlog:
            ended = rec["shed"] or (
                rec["done"] is not None and rec["done"] <= window_end
            )
            if ended:
                finished_ok += whole
                failed += not whole
        else:
            finished_ok += whole
            failed += not whole
        records.append(rec)
    attempted = finished_ok + failed
    hit_rate = (
        grown["prefix_hit_blocks"] * block / admitted_prompt_tokens
        if admitted_prompt_tokens else 0.0
    )
    dropped = grown.get("serve_moe_dropped_total", 0)
    correct = bool(
        check["ok"] and compiles_in_window == 0 and recompiles == 0
        and attempted > 0 and dropped == 0
        and (not shared or hit_rate >= PREFIX_HIT_MIN)
        and all(r["ok"] for r in records if r["done"] is not None)
    )
    stats = dict(driver.batcher.stats)
    stats.update(grown)
    return {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "window_s": window_end,
        "t_window": t_window,
        "checks": {
            "reference": check,
            "compiles_in_window": compiles_in_window,
            "engine_recompiles": recompiles,
            "prefix_hit_rate": hit_rate,
            "moe_dropped": dropped,
            "errors": driver.errors[:5],
            "unfinished_at_close": sum(
                1 for r in records if r["done"] is None and not r["shed"]
            ),
            "drain_s": t_end - t_close,
            "ticks": len(driver.ticks),
            "tick_ms": [round(1e3 * w, 2) for _, w in driver.ticks],
            "stats": stats,
        },
        "phases": phases,
        "arch": arch,
        "flops_bytes": config["program"].get("flops_bytes"),
        "mesh": dict(cell["mesh"]),
        "serve": {
            "backlog": backlog,
            "requests": records,
            "ticks": [(a - t0, b) for a, b in driver.ticks],
            "calls": {
                k: [(a - t0, b) for a, b in v]
                for k, v in driver.calls.items()
            },
            "decode_live": driver.decode_live,
            "drain_s": t_end - t_close,
            "stats": stats,
            "slots": eng_spec["slots"],
            "pool_bytes": engine.cache_bytes,
        },
        "trace": trace,
    }
