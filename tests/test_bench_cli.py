"""The benchmark CLI's batch/accum default policy.

bench.py is the driver-facing artifact entry point; its CLI policy
(resolve_batch_accum) decides what configuration every recorded number
describes. The invariants pinned here are the lever-table protocol
from docs/guide/xla_performance_notes.md (measured case study,
ceiling-budget subsection): sweeping
--grad-accum-steps alone holds the microbatch constant, and an
explicit --batch alone reproduces the unaccumulated config.
"""
import importlib.util
import pathlib

import pytest

_BENCH = pathlib.Path(__file__).resolve().parent.parent / "bench.py"


@pytest.fixture(scope="module")
def bench():
    # Import by path: bench.py is a repo-root script, not a package
    # module, and importing it must not initialize a backend.
    spec = importlib.util.spec_from_file_location("bench_cli", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_default_is_microbatch_times_accum8(bench):
    assert bench.resolve_batch_accum(None, None, microbatch=4) == (32, 8)
    assert bench.resolve_batch_accum(None, None, microbatch=1) == (8, 8)


def test_accum_sweep_holds_microbatch_constant(bench):
    # The lever-table protocol: batch scales with accum so every
    # sweep point runs the measured-best microbatch.
    for accum in (1, 2, 4, 8, 16):
        batch, got = bench.resolve_batch_accum(None, accum, microbatch=4)
        assert got == accum
        assert batch // accum == 4
    batch, got = bench.resolve_batch_accum(None, 8, microbatch=1)
    assert (batch, got) == (8, 8)


def test_explicit_batch_runs_unaccumulated(bench):
    # --batch 4 alone must reproduce the round-2 headline config.
    assert bench.resolve_batch_accum(4, None, microbatch=4) == (4, 1)
    assert bench.resolve_batch_accum(16, None, microbatch=1) == (16, 1)


def test_explicit_batch_and_accum_pass_through(bench):
    assert bench.resolve_batch_accum(16, 4, microbatch=4) == (16, 4)


def test_invalid_accum_reaches_trainer_validation(bench):
    # 0 is not silently replaced: it flows to the Trainer, whose
    # config validation rejects it loudly (trainer.py grad_accum >= 1).
    _, accum = bench.resolve_batch_accum(None, 0, microbatch=4)
    assert accum == 0
    _, accum = bench.resolve_batch_accum(8, 0, microbatch=4)
    assert accum == 0


def test_llama_long_threads_block_flags(bench, monkeypatch):
    """--block-q/--block-k(-bwd) must reach the long-context workload
    too, so autotuned tilings apply to the seq-8192 family (the
    harness is shared with bench_llama)."""
    seen = {}

    def fake_bench_llama(steps, remat, batch, attn, block_q=512,
                         block_k=512, **kw):
        seen.update(block_q=block_q, block_k=block_k,
                    block_q_bwd=kw.get("block_q_bwd"),
                    block_k_bwd=kw.get("block_k_bwd"))
        return {"metric": "m", "value": 1, "unit": "u",
                "vs_baseline": 1}

    monkeypatch.setattr(bench, "bench_llama", fake_bench_llama)
    rc = bench.main([
        "--workload", "llama-long", "--block-q", "256",
        "--block-k", "1024", "--block-q-bwd", "128",
        "--block-k-bwd", "512",
    ])
    assert rc == 0
    assert seen == {
        "block_q": 256, "block_k": 1024,
        "block_q_bwd": 128, "block_k_bwd": 512,
    }


def test_pp_accum_divisibility_validated(bench):
    # The PP workload validates --grad-accum-steps against the
    # pipeline microbatch size up front (a non-divisor would otherwise
    # raise deep inside tracing, bench.py round-4 parity levers).
    with pytest.raises(ValueError, match="must divide"):
        bench.bench_llama_pp(grad_accum_steps=3, microbatch_size=4)
    with pytest.raises(ValueError, match="must divide"):
        bench.bench_llama_pp(grad_accum_steps=8, microbatch_size=4)


def test_pp_model_llama_validation(bench):
    with pytest.raises(ValueError, match="stack|llama"):
        bench.bench_llama_pp(model="no-such-model")


def test_bench_model_cfg_is_single_source(bench):
    # The comparability claim of the flagship pp row rests on every
    # llama-family workload building THE same architecture from one
    # factory; a second hardcoded config literal would let them drift.
    cfg = bench.bench_model_cfg()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.vocab_size) == (
        1024, 8, 8, 32000
    )
    assert bench.bench_model_cfg(seq_len=8192).max_seq_len == 8192
    import pathlib
    src = pathlib.Path(bench.__file__).read_text()
    # Exactly one dim=1024 Llama literal: the factory's own.
    assert src.count("dim=1024, n_layers=8") == 1


def test_block_defaults_reconciled_cli_vs_functions(bench):
    """The CLI's --block-q/--block-k defaults and the bench_* function
    defaults must agree (they drifted in round 5: CLI 1024 vs function
    512, so the two entry points silently measured different flash
    tilings -- ADVICE r5). Pinned via introspection so the next retune
    must move both."""
    import inspect

    ap_defaults = {}
    for fn_name in ("bench_llama", "bench_llama_long", "bench_llama_pp"):
        sig = inspect.signature(getattr(bench, fn_name))
        ap_defaults[fn_name] = (
            sig.parameters["block_q"].default,
            sig.parameters["block_k"].default,
        )
    assert set(ap_defaults.values()) == {(512, 1024)}, ap_defaults
    src = pathlib.Path(bench.__file__).read_text()
    assert '"--block-q", type=int, default=512' in src
    assert '"--block-k", type=int, default=1024' in src


def test_records_carry_effective_flash_blocks(bench):
    """Every flash-attention artifact row must be self-describing
    about its tiling, with bwd defaults resolved; xla rows carry no
    block fields (there is no tiling to describe)."""
    rec = bench.flash_blocks_record("flash", 512, 1024, None, None)
    assert rec == {
        "flash_blocks": {"q": 512, "k": 1024, "q_bwd": 512, "k_bwd": 1024}
    }
    rec = bench.flash_blocks_record("flash", 256, 512, 128, 256)
    assert rec["flash_blocks"] == {
        "q": 256, "k": 512, "q_bwd": 128, "k_bwd": 256
    }
    assert bench.flash_blocks_record("xla", 512, 1024, None, None) == {}


def test_comm_mode_routes_to_bench_llama(bench, monkeypatch):
    """--comm-mode must reach the workload (and through it the
    Trainer's gradient-sync layer); defaulting silently to flat would
    make every comm-mode sweep measure the same thing."""
    seen = {}

    def fake_bench_llama(steps, remat, batch, attn, block_q=512,
                         block_k=1024, **kw):
        seen.update(comm_mode=kw.get("comm_mode"))
        return {"metric": "m", "value": 1, "unit": "u",
                "vs_baseline": 1}

    monkeypatch.setattr(bench, "bench_llama", fake_bench_llama)
    rc = bench.main(["--comm-mode", "bucketed_overlap"])
    assert rc == 0
    assert seen == {"comm_mode": "bucketed_overlap"}


def test_guard_mode_routes_to_bench_llama(bench, monkeypatch):
    """--guard-mode must reach the workload (and through it the
    Trainer's numeric-health guard); a row labeled guarded that
    silently ran unguarded would misprice the guard's cost."""
    seen = {}

    def fake_bench_llama(steps, remat, batch, attn, block_q=512,
                         block_k=1024, **kw):
        seen.update(guard_mode=kw.get("guard_mode"))
        return {"metric": "m", "value": 1, "unit": "u",
                "vs_baseline": 1}

    monkeypatch.setattr(bench, "bench_llama", fake_bench_llama)
    rc = bench.main(["--guard-mode", "skip"])
    assert rc == 0
    assert seen == {"guard_mode": "skip"}


def test_guard_mode_on_nonconsuming_workload_is_cli_error(bench):
    """The --comm-mode misplaced-flag discipline applies to the guard
    flag too."""
    with pytest.raises(SystemExit) as ei:
        bench.main(["--workload", "serve", "--guard-mode", "skip"])
    assert ei.value.code == 2


def test_llama_records_carry_comm_mode(bench):
    """Training records must be attributable to their gradient-sync
    strategy: bench_llama (and llama-long through it) records
    comm_mode in every JSON row, defaulting to the flat GSPMD path."""
    import inspect

    sig = inspect.signature(bench.bench_llama)
    assert sig.parameters["comm_mode"].default == "flat"
    assert (
        inspect.signature(bench.bench_llama_long)
        .parameters["comm_mode"].default == "flat"
    )
    src = pathlib.Path(bench.__file__).read_text()
    # The record literally carries the effective mode (not a constant).
    assert '"comm_mode": comm_mode' in src


def test_serve_record_schema_matches_training_benches(bench):
    """--serve artifacts must land in the same record schema every
    training workload emits (metric/value/unit/vs_baseline), with the
    serving-native latency quantiles riding along."""
    summary = {
        "tokens_per_s_per_chip": 123.456, "serve_mfu": 0.10,
        "ttft_ms_p50": 25.0, "ttft_ms_p95": 40.0,
        "itl_ms_p50": 8.0, "itl_ms_p95": 12.0,
        "requests": 32, "slots": 8, "prefill_buckets": [128, 256],
        "recompiles": 0,
    }
    rec = bench.serve_record(summary)
    assert set(rec) >= {"metric", "value", "unit", "vs_baseline"}
    assert rec["metric"] == "serve_tokens_per_s_per_chip"
    assert rec["value"] == 123.5
    assert rec["unit"] == "tokens/s/chip"
    assert rec["vs_baseline"] == 0.25  # 0.10 MFU / 0.40 target
    assert rec["ttft_ms_p50"] == 25.0 and rec["itl_ms_p95"] == 12.0
    assert rec["serve"]["recompiles"] == 0
    # No published peak (CPU sim) -> honest None, not a fake ratio.
    no_mfu = bench.serve_record({**summary, "serve_mfu": None})
    assert no_mfu["vs_baseline"] is None


def test_emitted_record_is_schema_stamped(bench, monkeypatch, capsys):
    """PR 4: the one JSON line bench prints is a ``bench`` event in
    the unified telemetry schema -- same validator as the train and
    serve JSONL sinks."""
    from tpu_hpc.obs import validate_record

    monkeypatch.setattr(
        bench, "bench_serve",
        lambda **kw: {"metric": "serve_tokens_per_s_per_chip",
                      "value": 1, "unit": "tokens/s/chip",
                      "vs_baseline": None},
    )
    assert bench.main(["--workload", "serve"]) == 0
    import json

    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    validate_record(rec)
    assert rec["event"] == "bench"
    assert rec["schema_version"] == 1
    assert rec["run_id"] and rec["host"]


def test_serve_mode_routes_flags(bench, monkeypatch):
    """Both spellings (--serve and --workload serve) reach bench_serve
    with the serve-specific knobs."""
    seen = {}

    def fake_bench_serve(requests, slots, max_new, disagg=False,
                         paged=False, block_size=None, kv_blocks=None,
                         prefill_chunk=None, spec="off", spec_k=None,
                         draft_ckpt=None, host_blocks=None,
                         kv_quant=None):
        seen.update(requests=requests, slots=slots, max_new=max_new,
                    disagg=disagg, paged=paged,
                    block_size=block_size, kv_blocks=kv_blocks,
                    prefill_chunk=prefill_chunk, spec=spec,
                    spec_k=spec_k, draft_ckpt=draft_ckpt,
                    host_blocks=host_blocks, kv_quant=kv_quant)
        return {"metric": "serve_tokens_per_s_per_chip", "value": 1,
                "unit": "tokens/s/chip", "vs_baseline": None}

    monkeypatch.setattr(bench, "bench_serve", fake_bench_serve)
    rc = bench.main([
        "--serve", "--serve-requests", "12", "--serve-slots", "4",
        "--serve-max-new", "7",
    ])
    assert rc == 0
    assert seen == {"requests": 12, "slots": 4, "max_new": 7,
                    "disagg": False, "paged": False,
                    "block_size": None, "kv_blocks": None,
                    "prefill_chunk": None, "spec": "off",
                    "spec_k": None, "draft_ckpt": None,
                    "host_blocks": None, "kv_quant": None}
    seen.clear()
    assert bench.main(["--workload", "serve"]) == 0
    assert seen["requests"] == 32 and seen["slots"] == 8
    assert seen["max_new"] == 64 and seen["disagg"] is False
    seen.clear()
    assert bench.main(["--workload", "serve", "--serve-disagg"]) == 0
    assert seen["disagg"] is True
    seen.clear()
    assert bench.main([
        "--workload", "serve", "--serve-paged",
        "--serve-block-size", "32", "--serve-kv-blocks", "512",
        "--serve-prefill-chunk", "128",
    ]) == 0
    assert seen["paged"] is True and seen["block_size"] == 32
    assert seen["kv_blocks"] == 512 and seen["prefill_chunk"] == 128
    seen.clear()
    assert bench.main([
        "--workload", "serve", "--serve-paged",
        "--serve-kv-quant", "int8",
    ]) == 0
    assert seen["kv_quant"] == "int8"
    seen.clear()
    assert bench.main([
        "--workload", "serve", "--serve-paged",
        "--serve-spec", "ngram", "--spec-k", "3",
    ]) == 0
    assert seen["spec"] == "ngram" and seen["spec_k"] == 3
    seen.clear()
    assert bench.main([
        "--workload", "serve", "--serve-paged",
        "--serve-host-blocks", "4096",
    ]) == 0
    assert seen["paged"] is True and seen["host_blocks"] == 4096


def test_serve_alias_conflicts_with_explicit_workload(bench):
    with pytest.raises(SystemExit):
        bench.main(["--workload", "llama", "--serve"])


def test_loadgen_mode_routes_flags(bench, monkeypatch):
    """--workload loadgen reaches bench_loadgen with the scenario and
    sizing knobs (requests doubled vs serve: the harness measures
    queueing, which needs backlog)."""
    seen = {}

    def fake_bench_loadgen(scenario, requests, slots, max_new,
                           paged=False, block_size=None,
                           kv_blocks=None, prefill_chunk=None,
                           model="bench", spec="off", spec_k=None,
                           draft_ckpt=None, fleet=0, fleet_min=1,
                           fleet_swap_at=None,
                           fleet_router="affinity", host_blocks=None,
                           kv_quant=None):
        seen.update(scenario=scenario, requests=requests, slots=slots,
                    max_new=max_new, paged=paged, spec=spec,
                    host_blocks=host_blocks)
        return {"metric": "loadgen_x_ttft_ms_p95", "value": 1.0,
                "unit": "virtual_ms", "vs_baseline": None}

    monkeypatch.setattr(bench, "bench_loadgen", fake_bench_loadgen)
    rc = bench.main([
        "--workload", "loadgen", "--loadgen-scenario", "bursty",
        "--serve-requests", "16", "--serve-slots", "4",
        "--serve-max-new", "16",
    ])
    assert rc == 0
    assert seen == {"scenario": "bursty", "requests": 32, "slots": 4,
                    "max_new": 16, "paged": False, "spec": "off",
                    "host_blocks": None}
    seen.clear()
    assert bench.main([
        "--workload", "loadgen", "--loadgen-scenario",
        "shared_prefix", "--serve-paged",
    ]) == 0
    assert seen["scenario"] == "shared_prefix"
    assert seen["paged"] is True
    seen.clear()
    assert bench.main([
        "--workload", "loadgen", "--loadgen-scenario",
        "long_idle_sessions", "--serve-paged",
        "--serve-host-blocks", "512",
    ]) == 0
    assert seen["scenario"] == "long_idle_sessions"
    assert seen["host_blocks"] == 512
    # Misplaced scenario flag = CLI error (the --comm-mode
    # discipline), never a silently-plain run recorded as the
    # scenario.
    with pytest.raises(SystemExit):
        bench.main(["--loadgen-scenario", "colocate"])
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve",
                    "--loadgen-scenario", "colocate"])


def test_paged_flags_guarded_like_comm_mode(bench):
    """--serve-paged on a workload that never consumes it is a CLI
    error (a slab row labeled paged would poison the bank), and the
    paged sizing flags require --serve-paged."""
    with pytest.raises(SystemExit):
        bench.main(["--workload", "llama", "--serve-paged"])
    for flag, val in (
        ("--serve-block-size", "16"),
        ("--serve-kv-blocks", "64"),
        ("--serve-host-blocks", "4096"),
        ("--serve-prefill-chunk", "128"),
    ):
        with pytest.raises(SystemExit):
            bench.main(["--workload", "serve", flag, val])
    # A 1-slot host tier could never hold a page (slot 0 is scratch):
    # a parse error, not a row labeled tiered that never spilled.
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve", "--serve-paged",
                    "--serve-host-blocks", "1"])
    # The tiny dev model is only legal where quantiles are
    # virtual-clock (loadgen); a wall-clock serve row on it would
    # wear the bench label while measuring a different machine.
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve", "--serve-model", "tiny"])


def test_spec_flags_guarded_like_comm_mode(bench):
    """The speculative flags follow the misplaced-flag discipline: a
    spec flag on a workload (or cache layout) that cannot consume it
    is a CLI error, not a greedy row wearing a spec label."""
    # Non-consuming workload.
    with pytest.raises(SystemExit):
        bench.main(["--workload", "llama", "--serve-spec", "ngram"])
    # Spec rides the paged engine only.
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve", "--serve-spec", "ngram"])
    # Disagg cannot consume the verify program.
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve", "--serve-paged",
                    "--serve-spec", "ngram", "--serve-disagg"])
    # Spec knobs require --serve-spec (and the ckpt requires draft
    # mode specifically).
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve", "--serve-paged",
                    "--spec-k", "4"])
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve", "--serve-paged",
                    "--serve-draft-ckpt", "/tmp/x"])
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve", "--serve-paged",
                    "--serve-spec", "ngram",
                    "--serve-draft-ckpt", "/tmp/x"])
    # k=0 must error loudly, not coerce to the default 4 (server.py's
    # guard, mirrored).
    with pytest.raises(SystemExit):
        bench.main(["--workload", "serve", "--serve-paged",
                    "--serve-spec", "ngram", "--spec-k", "0"])


def test_serve_record_carries_spec_identity(bench):
    """Speculative rows are labeled with mode/k and carry the two
    judged signals (acceptance rate, draft cost)."""
    base = {
        "requests": 8, "slots": 4, "prefill_buckets": [8],
        "recompiles": 0, "tokens_per_s_per_chip": 10.0,
        "ttft_ms_p50": 1.0, "ttft_ms_p95": 2.0,
        "itl_ms_p50": 1.0, "itl_ms_p95": 2.0,
        "kv_layout": "paged", "kv_block_size": 16, "kv_blocks": 64,
        "prefix_hit_rate": 0.0, "prefix_hit_blocks": 0,
        "spec_mode": "ngram", "spec_k": 4,
        "acceptance_rate": 0.875, "verify_steps": 10,
        "draft_ms": 1.5,
    }
    rec = bench.serve_record(base)
    # Spec rows bank under their own per-mode metric family: the
    # --bank reduction reads only top-level value + side keys, so a
    # spec row under the greedy family would set itl/ttft marks the
    # next greedy row gets judged against.
    assert rec["metric"] == "serve_spec_ngram_tokens_per_s_per_chip"
    assert rec["acceptance_rate"] == 0.875  # top level: gate-visible
    assert rec["serve"]["spec_mode"] == "ngram"
    assert rec["serve"]["spec_k"] == 4
    assert rec["serve"]["acceptance_rate"] == 0.875
    assert rec["serve"]["draft_ms"] == 1.5
    # Loadgen rows bank under their own spec metric family.
    lg = bench.loadgen_record({
        "scenario": "heavy_tail", "seed": 0, "shed": 0, "queued": 1,
        "occupancy_mean": 0.5, "stall_events": 0,
        "slo_violations": [], "recompiles": 0, "tenants": {},
        "kv_layout": "paged", "kv_block_size": 16, "kv_blocks": 64,
        "prefix_hit_rate": 0.1, "spec_mode": "ngram", "spec_k": 4,
        "acceptance_rate": 0.9, "verify_steps": 5, "draft_ms": 0.0,
        "ttft_ms_p50": 1.0, "ttft_ms_p95": 2.0, "ttft_ms_p99": 3.0,
        "itl_ms_p50": 1.0, "itl_ms_p95": 2.0,
    })
    assert lg["metric"] == \
        "loadgen_heavy_tail_paged_spec_ngram_ttft_ms_p95"
    assert lg["loadgen"]["spec_mode"] == "ngram"
    assert lg["loadgen"]["acceptance_rate"] == 0.9
    assert lg["acceptance_rate"] == 0.9  # top level: gate-visible


def test_serve_record_carries_kv_layout(bench):
    """Serve records are labeled with their cache layout; paged rows
    add block size + prefix-hit evidence."""
    base = {
        "requests": 8, "slots": 4, "prefill_buckets": [8],
        "recompiles": 0, "tokens_per_s_per_chip": 10.0,
        "ttft_ms_p50": 1.0, "ttft_ms_p95": 2.0,
        "itl_ms_p50": 1.0, "itl_ms_p95": 2.0,
    }
    rec = bench.serve_record(dict(base, kv_layout="slab"))
    assert rec["serve"]["kv_layout"] == "slab"
    assert "prefix_hit_rate" not in rec["serve"]
    rec = bench.serve_record(dict(
        base, kv_layout="paged", kv_block_size=16, kv_blocks=64,
        prefix_hit_rate=0.25, prefix_hit_blocks=12,
        batcher={"block_stalls": 3},
    ))
    assert rec["serve"]["kv_layout"] == "paged"
    assert rec["serve"]["kv_block_size"] == 16
    assert rec["serve"]["prefix_hit_rate"] == 0.25
    assert rec["serve"]["block_stalls"] == 3


def test_loadgen_record_schema_matches_training_benches(bench):
    """Loadgen rows land in the same record schema as every other
    workload, with the shed/queued admission evidence riding along."""
    summary = {
        "scenario": "multi_tenant", "seed": 0,
        "ttft_ms_p50": 5.0, "ttft_ms_p95": 20.0, "ttft_ms_p99": 30.0,
        "itl_ms_p50": 8.0, "itl_ms_p95": 12.0,
        "shed": 3, "queued": 7, "occupancy_mean": 0.8,
        "stall_events": 1, "slo_violations": [], "recompiles": 0,
        "tenants": {
            "background": {"shed": 3, "queued": 2,
                           "ttft_ms_p95": 40.0},
        },
    }
    rec = bench.loadgen_record(summary)
    assert set(rec) >= {"metric", "value", "unit", "vs_baseline"}
    assert rec["metric"] == "loadgen_multi_tenant_ttft_ms_p95"
    assert rec["value"] == 20.0 and rec["unit"] == "virtual_ms"
    assert rec["loadgen"]["shed"] == 3
    assert rec["loadgen"]["tenants"]["background"]["shed"] == 3
    from tpu_hpc.obs import stamp, validate_record

    validate_record(stamp({"event": "bench", **rec}))
