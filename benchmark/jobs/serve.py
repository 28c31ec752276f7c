"""Job kind ``serve``: the paged server under an open loop.

``PagedEngine`` + ``ContinuousBatcher`` + ``ServeMeter``, built as
``serve.server.run_replay`` builds them, and driven here on the wall
clock by one thread: before each ``batcher.step()`` every request whose
due time has passed is submitted. Time to first token counts from the
DUE time, so a stall's cost to the requests behind it shows; how late
the generator itself ran (submit minus due) is recorded beside it.

Two ways a window ends, by the mix's arrival process:

* a rate (``poisson``, ``onoff``): arrivals stop at ``--seconds`` and
  the loop drains, for at most ``DRAIN_LIMIT_S`` more. ``attempted`` =
  requests due in the window; ``failed`` = those shed, errored, short
  of their token count, or unfinished at the limit.
* a ``backlog``: the run stops at ``--seconds`` without draining.
  ``attempted`` = requests that finished (or were shed, or errored) in
  the window, ``failed`` = those shed, errored or short; requests still
  in a slot or the queue at the close count only through the tokens
  they emitted.

Weights are bf16 (both published checkpoints are), the pool bf16, greedy
decoding, chunked prefill and the prefix trie on, read path
``kernel="gather"`` unless the cell says otherwise.
"""
import os
import time

import numpy as np

from benchmark import harness, trace_reduce
from benchmark.reference import dense_decoder

# How long past ``--seconds`` a rate cell may drain. Below its knee a
# cell drains in the time of its longest answer (256 tokens at the chat
# cell's ~121 ms a tick: 28-31 s measured, PERF.md PR 23); one that needs
# twice that is above its knee.
DRAIN_LIMIT_S = 60.0

# Tolerances of the correctness check: a seeded sample of requests goes
# through the normal path (batcher, chunked prefill, paged decode) and
# the float32 reference scores the tokens the engine emitted. With
# random weights the arg-max flips on rounding, so tokens cannot be
# compared; the REGRET can: max(reference logits) - reference
# logit[emitted token], over that row's logit standard deviation.
#
# The engine computes in bf16 (8 significand bits) with float32
# accumulation and emits the arg-max of bf16 logits, so a flipped
# near-tie costs a regret of the size of the logits' rounding error.
# Measured on the v5e (PERF.md PR 23, seven seeds x 128 positions a
# cell): the engine agrees with the reference's arg-max at 94-98 % of
# positions; the largest regret of a run was 0.009 to 0.048 sigma, the
# mean 1.6e-4 to 6.3e-4 sigma. The bounds are 2.5x and 3x the largest
# seen. A pool or weights in an 8-bit format (2-6 % a rounding, logit
# errors of 0.1-0.3 sigma and a mean regret ten times this), a wrong
# position, page or mask (regrets of whole sigmas: the top of 32000+
# logits sits ~4 sigma up) miss them by far.
REGRET_MAX_SIGMA = 0.12
REGRET_MEAN_SIGMA = 0.002


def _timed(fn, sink, annotation):
    """Wrap one engine call in a host span: wall time into ``sink``,
    and a TraceAnnotation so the profiler's trace can name the idle
    gap it covers."""
    import jax

    def call(*args, **kwargs):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(annotation):
            out = fn(*args, **kwargs)
        sink.append((t0, time.perf_counter() - t0))
        return out

    return call


class Driver:
    """The open loop: submit what is due, step, repeat."""

    def __init__(self, engine):
        from tpu_hpc.serve.metrics import ServeMeter
        from tpu_hpc.serve.scheduler import ContinuousBatcher

        self.engine = engine
        self.meter = ServeMeter()
        self.batcher = ContinuousBatcher(engine, meter=self.meter)
        self.calls = {"decode": [], "prefill": [], "admit": [], "release": []}
        self.decode_live = []   # (live KV tokens, active slots) a decode call
        self.ticks = []         # (t0, wall) of each batcher.step()
        self.submits = {}       # rid -> (due, submitted), perf_counter
        self.errors = []
        self._wrap()

    def _wrap(self):
        eng, calls = self.engine, self.calls

        def orig(name):
            # The class's own method, so a second Driver on the same
            # engine (the check's, then the window's) wraps it once.
            return getattr(type(eng), name).__get__(eng)

        decode = _timed(orig("decode"), calls["decode"], "bench:decode")

        def decode_counted(tokens, positions, active=None):
            on = [bool(a) for a in (active or [True] * len(positions))]
            self.decode_live.append((
                sum(int(p) + 1 for p, a in zip(positions, on) if a),
                sum(on),
            ))
            return decode(tokens, positions, active=active)

        eng.decode = decode_counted
        eng.prefill_step = _timed(
            orig("prefill_step"), calls["prefill"], "bench:prefill_chunk"
        )
        eng.admit = _timed(orig("admit"), calls["admit"], "bench:admit")
        eng.release = _timed(
            orig("release"), calls["release"], "bench:release"
        )

    def submit(self, req, t_due):
        from tpu_hpc.serve.scheduler import Request

        try:
            self.batcher.submit(Request(
                rid=req["rid"], prompt=req["prompt"].tolist(),
                max_new_tokens=req["max_new"],
            ))
        except Exception as exc:  # an unservable request is a failure
            self.errors.append((req["rid"], repr(exc)))
        self.submits[req["rid"]] = (t_due, time.perf_counter())

    def step(self):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:tick"):
            self.batcher.step()
        self.ticks.append((t0, time.perf_counter() - t0))

    def run(self, requests, seconds, drain, trace_at=None, on_trace=None,
            on_close=None):
        """Drive ``requests`` (sorted by due time) for ``seconds``;
        returns (t0, t_close): window start and the instant the window
        closed (``--seconds`` on, or the end of the tick that crossed
        it for a backlog). ``on_trace()`` is called once when the
        window reaches ``trace_at`` seconds, ``on_close()`` once when
        it closes."""
        batcher = self.batcher
        t0 = time.perf_counter()
        i, n = 0, len(requests)
        t_close = None
        while True:
            now = time.perf_counter() - t0
            if trace_at is not None and now >= trace_at:
                on_trace()
                trace_at = None
            if now < seconds:
                while i < n and requests[i]["due_s"] <= now:
                    self.submit(requests[i], t0 + requests[i]["due_s"])
                    i += 1
            else:
                if t_close is None:
                    t_close = time.perf_counter()
                    if on_close is not None:
                        on_close()
                if not drain or batcher.done \
                        or now > seconds + DRAIN_LIMIT_S:
                    break
            if batcher.done:
                # Idle: nothing in a slot or the queue. Wait for the
                # next arrival (or the close) without spinning hot.
                nxt = requests[i]["due_s"] if i < n else seconds
                time.sleep(max(0.0, min(nxt, seconds) - now, 0.0002))
                continue
            self.step()
        return t0, t_close


def _drain(driver, limit=20000):
    steps = 0
    while not driver.batcher.done and steps < limit:
        driver.step()
        steps += 1


def _warm(engine, buckets, vocab_size, seed):
    """Run every compiled shape once before anything is timed: one
    short request through each prefill bucket, and so the decode step."""
    rng = np.random.default_rng(seed)
    driver = Driver(engine)
    for k, bucket in enumerate(buckets):
        driver.submit({
            "rid": f"warm{k}", "max_new": 4,
            "prompt": rng.integers(0, vocab_size, bucket - 3, dtype=np.int32),
        }, time.perf_counter())
    _drain(driver)


def _check(engine, requests, arch, cell, vocab_size, log):
    """A seeded sample through the normal path, scored by the
    reference (see the tolerances above). The sample's requests are the
    head of the seeded traffic with their answers cut to
    ``check.new_tokens``; the reference takes one request a call, padded
    to one fixed length, so it compiles once."""
    import jax
    import jax.numpy as jnp

    spec = cell["check"]
    n_new = spec["new_tokens"]
    # Longest prompts first among the head of the traffic: the sample
    # should cross chunk and bucket edges, not sit in the smallest.
    head = sorted(
        requests[:8 * spec["requests"]], key=lambda r: -len(r["prompt"])
    )
    # Same lengths, other tokens ((t + 1) mod vocab): the window's own
    # prompts must not find the sample's pages in the prefix trie.
    sample = [
        {"prompt": (r["prompt"] + 1) % vocab_size}
        for r in head[::8][:spec["requests"]]
    ]
    driver = Driver(engine)
    for k, req in enumerate(sample):
        driver.submit(
            {"rid": f"check{k}", "prompt": req["prompt"], "max_new": n_new},
            time.perf_counter(),
        )
    _drain(driver)
    results = driver.batcher.results

    pad = spec["pad_to"]
    ref = jax.jit(
        lambda p, t, pos, e: dense_decoder.regret(
            p, t, pos, e, **harness.reference_kwargs(arch)
        )
    )
    regrets, short = [], 0
    for k, req in enumerate(sample):
        emitted = results.get(f"check{k}", [])
        if len(emitted) != n_new:
            short += 1
            continue
        plen = len(req["prompt"])
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :plen] = req["prompt"]
        tokens[0, plen:plen + n_new - 1] = emitted[:-1]
        positions = (plen - 1 + np.arange(n_new, dtype=np.int32))[None]
        reg, std = ref(
            engine.params, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(np.asarray(emitted, np.int32)[None]),
        )
        regrets.append(np.asarray(reg / std)[0])
    flat = np.concatenate(regrets) if regrets else np.array([np.inf])
    out = {
        "requests": len(sample),
        "prompt_lens": [len(r["prompt"]) for r in sample],
        "positions": int(flat.size),
        "short": short,
        "regret_max_sigma": float(flat.max()),
        "regret_mean_sigma": float(flat.mean()),
        "argmax_agree": float((flat == 0).mean()),
        "prefill_chunks": len(driver.calls["prefill"]),
        "decode_steps": len(driver.calls["decode"]),
    }
    out["ok"] = bool(
        short == 0 and np.isfinite(flat).all()
        and out["regret_max_sigma"] < REGRET_MAX_SIGMA
        and out["regret_mean_sigma"] < REGRET_MEAN_SIGMA
    )
    log(f"check | {out} (tolerances: max {REGRET_MAX_SIGMA}, mean "
        f"{REGRET_MEAN_SIGMA} sigma)")
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
    cfg, arch = harness.llama_config(config, cell, max_seq_len=capacity)
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

    devices = ctx["devices"]
    mesh = build_mesh(
        MeshSpec(axes=dict(cell["mesh"])),
        devices if len(devices) != jax.device_count() else None,
    )
    phases = {}
    t = time.perf_counter()
    params = harness.init_params(
        cfg, ctx["seed"], NamedSharding(mesh, P())
    )
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
    phases["warmup_s"] = time.perf_counter() - t
    t = time.perf_counter()
    check = _check(engine, requests, arch, cell, cfg.vocab_size, log)
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
        # The window span closes where the window does; the profiler
        # itself stops only after any drain, so its stop (seconds of
        # collection) stalls nothing that is measured.
        if trace_state:
            trace_state["span"].__exit__(None, None, None)

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

    # ---- what happened, request by request --------------------------
    window_end = (t_close if backlog else t_end) - t0
    records, finished_ok, failed = [], 0, len(driver.errors)
    errored = {rid for rid, _ in driver.errors}
    results = driver.batcher.results
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
        whole = (
            rec["done"] is not None
            and len(results.get(rid, [])) == req["max_new"]
        )
        rec["ok"] = whole
        if backlog:
            # Only what came to an end inside the window is attempted.
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
    correct = bool(
        check["ok"] and compiles_in_window == 0 and recompiles == 0
        and attempted > 0
        and all(r["ok"] for r in records if r["done"] is not None)
    )
    stats = dict(driver.batcher.stats)
    stats.update(getattr(engine, "paged_stats", {}))
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
