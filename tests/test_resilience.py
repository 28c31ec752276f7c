"""The resilience subsystem, end to end on CPU.

Every claim the subsystem makes is driven through the deterministic
fault injector (tpu_hpc/resilience/faults.py) against the REAL
Trainer, the REAL Orbax checkpoints, and the REAL supervisor in
subprocesses -- the acceptance run for the package is
``TestSupervisedTraining::test_kill_restart_resume``: kill-at-step
under the supervisor, restart, resume from the latest checkpoint at a
step <= the kill point, complete, and report goodput/restart
accounting in the metrics JSONL.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from tpu_hpc.resilience import (
    EXIT_HANG,
    EXIT_RESUMABLE,
    FaultPlan,
    HangWatchdog,
    Heartbeat,
    PreemptionGuard,
    backoff_delays,
    fault_plan_from_env,
    retry_call,
)
from tpu_hpc.resilience import faults
from tpu_hpc.resilience.supervisor import (
    Supervisor,
    run_supervised,
    unique_attempt_path,
)
from tpu_hpc.train.metrics import GoodputMeter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------
# retry/backoff
# ---------------------------------------------------------------------
class TestRetry:
    def test_jitter_bounds(self):
        """Delay k lies in [d_k, d_k*(1+jitter)] with
        d_k = min(base*2^k, max) -- the documented, testable bound."""
        base, mx, jit = 0.25, 2.0, 0.5
        delays = list(backoff_delays(6, base, mx, jit, seed=7))
        assert len(delays) == 6
        for k, d in enumerate(delays):
            dk = min(base * 2 ** k, mx)
            assert dk <= d <= dk * (1 + jit), (k, d)

    def test_deterministic_given_seed(self):
        a = list(backoff_delays(5, seed=3))
        b = list(backoff_delays(5, seed=3))
        c = list(backoff_delays(5, seed=4))
        assert a == b
        assert a != c

    def test_retry_call_recovers(self):
        calls, slept = [], []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        out = retry_call(
            flaky, retries=3, base_delay=0.1, jitter=0.0,
            sleep=slept.append, seed=0,
        )
        assert out == "ok"
        assert len(calls) == 3
        assert slept == [0.1, 0.2]  # jitter 0: exact exponential

    def test_budget_exhaustion_reraises_last(self):
        def always():
            raise ValueError("perma")

        with pytest.raises(ValueError, match="perma"):
            retry_call(
                always, retries=2, base_delay=0.0, jitter=0.0,
                sleep=lambda _: None,
            )

    def test_retry_on_filters(self):
        def boom():
            raise KeyError("not transient")

        with pytest.raises(KeyError):
            retry_call(
                boom, retries=5, retry_on=(OSError,),
                sleep=lambda _: None,
            )


# ---------------------------------------------------------------------
# fault plan
# ---------------------------------------------------------------------
class TestFaultPlan:
    def test_env_parse(self):
        env = {
            "TPU_HPC_FAULTS":
                "kill_at_step=6, stall_at_step=3, stall_s=12.5,"
                "on_attempt=1",
            "TPU_HPC_ATTEMPT": "1",
        }
        plan = fault_plan_from_env(env)
        assert plan.kill_at_step == 6
        assert plan.stall_at_step == 3
        assert plan.stall_s == 12.5
        assert plan.on_attempt == 1 and plan.attempt == 1
        assert plan.active

    def test_unset_is_none(self):
        assert fault_plan_from_env({}) is None

    def test_unknown_key_rejected(self):
        """A typo'd fault spec must not let a resilience test pass
        vacuously by injecting nothing."""
        with pytest.raises(ValueError, match="unknown fault key"):
            fault_plan_from_env({"TPU_HPC_FAULTS": "kil_at_step=3"})

    def test_attempt_scoping(self):
        plan = fault_plan_from_env({
            "TPU_HPC_FAULTS": "kill_at_step=2",
            "TPU_HPC_ATTEMPT": "1",
        })
        assert not plan.active
        plan.on_step(10)  # inactive: must be a no-op (we survive)

    def test_corrupt_checkpoint_walks_files(self, tmp_path):
        d = tmp_path / "step"
        (d / "sub").mkdir(parents=True)
        (d / "a.bin").write_bytes(b"x" * 100)
        (d / "sub" / "b.json").write_text("{}")
        plan = FaultPlan(corrupt_ckpt_at_step=5)
        assert plan.wants_ckpt_corruption(5)
        assert not plan.wants_ckpt_corruption(4)
        assert plan.corrupt_checkpoint(str(d)) == 2
        assert b"CORRUPTED" in (d / "a.bin").read_bytes()


# ---------------------------------------------------------------------
# heartbeat + watchdog
# ---------------------------------------------------------------------
class TestHeartbeat:
    def test_tick_read_roundtrip(self, tmp_path):
        path = str(tmp_path / "hb.json")
        hb = Heartbeat(path, attempt=2)
        hb.tick(17, loss=0.5)
        rec = Heartbeat.read(path)
        assert rec["step"] == 17
        assert rec["attempt"] == 2
        assert rec["pid"] == os.getpid()
        assert rec["loss"] == 0.5
        # Atomic: no tmp-file debris next to the heartbeat.
        assert os.listdir(tmp_path) == ["hb.json"]

    def test_read_torn_file_is_none(self, tmp_path):
        path = tmp_path / "hb.json"
        path.write_text('{"step": 1')  # torn mid-write
        assert Heartbeat.read(str(path)) is None
        assert Heartbeat.read(str(tmp_path / "absent")) is None

    def test_from_env_contract(self, tmp_path):
        assert Heartbeat.from_env({}) is None
        hb = Heartbeat.from_env({
            "TPU_HPC_HEARTBEAT": str(tmp_path / "h.json")
        })
        assert hb is not None


class TestHangWatchdog:
    def test_fires_without_ticks(self, tmp_path):
        fired = []
        wd = HangWatchdog(
            0.25, poll_s=0.05,
            dump_path=str(tmp_path / "hang.dump"),
            on_hang=fired.append,
        ).start()
        try:
            deadline = time.monotonic() + 5.0
            while not fired and time.monotonic() < deadline:
                time.sleep(0.05)
        finally:
            wd.stop()
        assert fired and fired[0] >= 0.25
        dump = (tmp_path / "hang.dump").read_text()
        assert "hang watchdog" in dump
        # The diagnostic must carry stacks (faulthandler output).
        assert "Thread" in dump or "File" in dump

    def test_ticks_keep_it_quiet(self):
        wd = HangWatchdog(
            0.4, poll_s=0.05, on_hang=lambda s: None
        ).start()
        try:
            for _ in range(10):
                time.sleep(0.05)
                wd.tick()
            assert not wd.fired
        finally:
            wd.stop()

    def test_dump_path_never_overwritten(self, tmp_path):
        base = tmp_path / "hang.dump"
        base.write_text("previous failure evidence")
        wd = HangWatchdog(
            0.1, poll_s=0.02, dump_path=str(base),
            on_hang=lambda s: None,
        ).start()
        try:
            deadline = time.monotonic() + 5.0
            while not wd.fired and time.monotonic() < deadline:
                time.sleep(0.02)
        finally:
            wd.stop()
        assert base.read_text() == "previous failure evidence"
        assert (tmp_path / "hang.dump.1").exists()

    def test_rejects_nonpositive_timeout(self):
        with pytest.raises(ValueError):
            HangWatchdog(0)


# ---------------------------------------------------------------------
# preemption guard + goodput
# ---------------------------------------------------------------------
class TestPreemptionGuard:
    def test_flag_and_restore(self):
        before = signal.getsignal(signal.SIGTERM)
        with PreemptionGuard() as guard:
            assert not guard.triggered
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 2.0
            while not guard.triggered and time.monotonic() < deadline:
                time.sleep(0.01)
            assert guard.triggered
        assert signal.getsignal(signal.SIGTERM) == before


class TestGoodputMeter:
    def test_buckets_and_fraction(self):
        g = GoodputMeter()
        g.add("productive", 3.0)
        g.add("ckpt", 0.5)
        with g.measure("restore"):
            time.sleep(0.01)
        s = g.summary()
        assert s["productive_s"] == 3.0
        assert s["ckpt_s"] == 0.5
        assert s["restore_s"] >= 0.01
        assert 0.0 <= s["goodput"]
        assert s["other_s"] >= 0.0

    def test_unknown_bucket_rejected(self):
        with pytest.raises(ValueError, match="unknown goodput"):
            GoodputMeter().add("coffee", 1.0)


# ---------------------------------------------------------------------
# supervisor (subprocess children, in-process supervisor loop)
# ---------------------------------------------------------------------
def _attempt_gated_cmd(threshold: int):
    """A child that fails until TPU_HPC_ATTEMPT >= threshold."""
    return [
        sys.executable, "-c",
        "import os, sys; "
        f"sys.exit(0 if int(os.environ['TPU_HPC_ATTEMPT']) >= "
        f"{threshold} else 1)",
    ]


class TestSupervisor:
    def test_restart_until_success(self, tmp_path):
        d = str(tmp_path)
        rc = run_supervised(
            _attempt_gated_cmd(2), max_restarts=3, log_dir=d,
            backoff=0.01,
        )
        assert rc == 0
        logs = sorted(
            f for f in os.listdir(d) if f.startswith("run.attempt")
        )
        assert logs == [
            "run.attempt0.log", "run.attempt1.log", "run.attempt2.log"
        ]
        events = [
            json.loads(x)
            for x in open(os.path.join(d, "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == [1, 1, 0]

    def test_budget_exhaustion_propagates_rc(self, tmp_path):
        rc = run_supervised(
            [sys.executable, "-c", "import sys; sys.exit(3)"],
            max_restarts=1, log_dir=str(tmp_path), backoff=0.01,
        )
        assert rc == 3
        logs = [
            f for f in os.listdir(tmp_path)
            if f.startswith("run.attempt")
        ]
        assert len(logs) == 2  # initial + 1 restart, then gave up

    def test_no_restart_on_marked_codes(self, tmp_path):
        rc = run_supervised(
            [sys.executable, "-c", "import sys; sys.exit(2)"],
            max_restarts=5, log_dir=str(tmp_path), backoff=0.01,
            no_restart_on=(2,),
        )
        assert rc == 2
        logs = [
            f for f in os.listdir(tmp_path)
            if f.startswith("run.attempt")
        ]
        assert len(logs) == 1  # usage errors don't burn the budget

    def test_attempt_logs_never_overwritten(self, tmp_path):
        """VERDICT item 9: a previous supervision's failure dump in
        the same directory survives the next one."""
        d = str(tmp_path)
        prev = os.path.join(d, "run.attempt0.log")
        with open(prev, "w") as f:
            f.write("evidence from an earlier run")
        assert unique_attempt_path(d, 0) == prev + ".1"
        rc = run_supervised(
            _attempt_gated_cmd(0), max_restarts=0, log_dir=d,
        )
        assert rc == 0
        assert open(prev).read() == "evidence from an earlier run"
        assert os.path.exists(prev + ".1")

    def test_heartbeat_stall_kills_and_restarts(self, tmp_path):
        """A child wedged past the heartbeat timeout is killed and
        restarted; the stall is recorded as EXIT_HANG policy-wise."""
        hb = str(tmp_path / "hb.json")
        child = (
            "import os, sys, time\n"
            "if int(os.environ['TPU_HPC_ATTEMPT']) >= 1:\n"
            "    sys.exit(0)\n"
            "time.sleep(60)\n"  # never ticks the heartbeat: wedged
        )
        t0 = time.monotonic()
        rc = run_supervised(
            [sys.executable, "-c", child],
            max_restarts=2, log_dir=str(tmp_path), heartbeat=hb,
            heartbeat_timeout=1.5, backoff=0.01, kill_grace_s=2.0,
        )
        assert rc == 0
        assert time.monotonic() - t0 < 30  # killed, not waited out
        events = [
            json.loads(x)
            for x in open(tmp_path / "supervisor.jsonl")
        ]
        assert any(e["event"] == "heartbeat_stall" for e in events)
        ends = [
            e for e in events if e["event"] == "attempt_end"
        ]
        assert ends[0]["rc"] == EXIT_HANG
        assert ends[0]["reason"] == "heartbeat-stall"
        assert ends[-1]["rc"] == 0

    def test_stale_heartbeat_cleared_between_attempts(self, tmp_path):
        """A child that TICKED and then wedged must not poison the
        restart: the stale heartbeat file is cleared at attempt start,
        or every restarted child would be insta-killed as stalled and
        one hang would burn the whole budget."""
        hb = str(tmp_path / "hb.json")
        child = (
            "import json, os, sys, time\n"
            "if int(os.environ['TPU_HPC_ATTEMPT']) >= 1:\n"
            # Runs LONGER than several polls but SHORTER than the
            # timeout: only the stale file from attempt 0 (whose
            # mtime is already past the timeout) could get it killed.
            "    time.sleep(1.0)\n"
            "    sys.exit(0)\n"
            "json.dump({'step': 1}, open(os.environ"
            "['TPU_HPC_HEARTBEAT'], 'w'))\n"
            "time.sleep(60)\n"  # wedged after ticking
        )
        rc = run_supervised(
            [sys.executable, "-c", child],
            max_restarts=1, log_dir=str(tmp_path), heartbeat=hb,
            heartbeat_timeout=1.5, backoff=0.01, kill_grace_s=2.0,
        )
        assert rc == 0  # attempt 1 survived past the stale-file age

    def test_cli_requires_separator(self):
        from tpu_hpc.resilience.supervisor import _split_argv

        with pytest.raises(SystemExit):
            _split_argv(["python", "x.py"])
        opts, cmd = _split_argv(["--max-restarts", "2", "--", "x"])
        assert opts == ["--max-restarts", "2"]
        assert cmd == ["x"]


# ---------------------------------------------------------------------
# the real Trainer under injected faults (subprocess workers)
# ---------------------------------------------------------------------
WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("TPU_VISIBLE_DEVICES", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                "TPU_WORKER_HOSTNAMES"):
        os.environ.pop(var, None)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from tpu_hpc import resilience
    from tpu_hpc.ckpt import CheckpointManager
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.train import Trainer

    class DS:
        # Deterministic per-step batches: resume replays the exact
        # stream (host-fed path -- per-step loop inside each chunk).
        def batch_at(self, step, bs):
            k = jax.random.key(int(step) % 97)
            x = jax.random.normal(k, (bs, 4), jnp.float32)
            return x, x @ jnp.arange(4.0)

    def forward(params, model_state, batch, step_rng):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2), model_state, {}

    ckpt_dir = os.environ["WORK_CKPT"]
    cfg = TrainingConfig(
        epochs=int(os.environ.get("WORK_EPOCHS", "3")),
        steps_per_epoch=2, global_batch_size=8, learning_rate=1e-2,
        save_every=1, checkpoint_dir=ckpt_dir,
        metrics_path=os.environ.get("WORK_METRICS", ""),
    )
    mesh = build_mesh(
        MeshSpec(axes={"data": 1}), devices=jax.devices()[:1]
    )
    mgr = CheckpointManager(ckpt_dir, async_save=False)
    trainer = Trainer(
        cfg, mesh, forward, {"w": jnp.zeros((4,), jnp.float32)},
        checkpoint_manager=mgr,
    )
    result = trainer.fit(DS())
    print("FINAL_STEP", int(jax.device_get(trainer.state.step)),
          flush=True)
    sys.exit(resilience.exit_code_for(result["preempted"]))
""")


@pytest.fixture()
def worker(tmp_path):
    path = tmp_path / "worker.py"
    path.write_text(WORKER)

    def run(env_extra, timeout=240, argv_prefix=()):
        env = dict(os.environ)
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = REPO + (os.pathsep + prev if prev else "")
        env["WORK_CKPT"] = str(tmp_path / "ckpts")
        env["WORK_METRICS"] = str(tmp_path / "run.jsonl")
        env.update({k: str(v) for k, v in env_extra.items()})
        return subprocess.run(
            [*argv_prefix, sys.executable, str(path)],
            capture_output=True, text=True, timeout=timeout,
            env=env, cwd=REPO,
        )

    return run


def _metrics(tmp_path):
    path = tmp_path / "run.jsonl"
    if not path.exists():
        return []
    return [json.loads(x) for x in open(path)]


class TestSupervisedTraining:
    def test_kill_restart_resume(self, worker, tmp_path):
        """THE acceptance run: kill-at-step-4 under the supervisor.
        Attempt 0 checkpoints step 2, is SIGKILLed at step 4 BEFORE
        the step-4 save; attempt 1 resumes from step 2 (= N' <= N),
        re-trains the killed span, completes to step 6, and the
        metrics JSONL carries per-attempt goodput/restart accounting.
        """
        sup_dir = str(tmp_path / "sup")
        proc = worker(
            {"TPU_HPC_FAULTS": "kill_at_step=4"},
            argv_prefix=(
                sys.executable, "-m", "tpu_hpc.resilience.supervisor",
                "--max-restarts", "2", "--log-dir", sup_dir,
                "--heartbeat", str(tmp_path / "hb.json"),
                "--backoff", "0.1", "--",
            ),
        )
        assert proc.returncode == 0, proc.stderr[-3000:]

        # Supervisor accounting: SIGKILL (137) then success.
        events = [
            json.loads(x)
            for x in open(os.path.join(sup_dir, "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == [137, 0]

        # Attempt-unique child logs; the resumed attempt completed.
        a1 = open(os.path.join(sup_dir, "run.attempt1.log")).read()
        assert "FINAL_STEP 6" in a1

        # Trainer-side restart accounting in the metrics JSONL.
        recs = _metrics(tmp_path)
        starts = [r for r in recs if r["event"] == "run_start"]
        assert len(starts) == 2
        assert starts[0]["start_step"] == 0
        # Resumed from the newest checkpoint <= the kill step: the
        # step-4 save had not happened when the kill fired.
        assert starts[1]["start_step"] == 2
        run_ends = [r for r in recs if r["event"] == "run_end"]
        assert len(run_ends) == 1  # attempt 0 died before its epilogue
        end = run_ends[0]
        assert end["attempt"] == 1
        assert end["resumed_from_step"] == 2
        assert end["step"] == 6
        assert end["preempted"] is False
        g = end["goodput"]
        assert g["goodput"] >= 0.0
        assert g["productive_s"] > 0.0
        assert g["restore_s"] > 0.0  # the resume really restored

        # The heartbeat contract was exercised under the supervisor.
        hb = Heartbeat.read(str(tmp_path / "hb.json"))
        assert hb is not None and hb["step"] == 6

    def test_preempt_emergency_save_resumable_exit(
        self, worker, tmp_path
    ):
        """SIGTERM (injected preemption notice) -> snapshot at the
        current step -> EXIT_RESUMABLE; the bare relaunch resumes and
        completes with exit 0."""
        proc = worker({"TPU_HPC_FAULTS": "preempt_at_step=2"})
        assert proc.returncode == EXIT_RESUMABLE, proc.stderr[-3000:]
        recs = _metrics(tmp_path)
        end = [r for r in recs if r["event"] == "run_end"][-1]
        assert end["preempted"] is True
        assert end["step"] == 2
        assert os.path.isdir(tmp_path / "ckpts" / "2")

        # Relaunch clean (fault scoped to attempt 0 via env ordinal).
        proc2 = worker({"TPU_HPC_ATTEMPT": "1"})
        assert proc2.returncode == 0, proc2.stderr[-3000:]
        assert "FINAL_STEP 6" in proc2.stdout
        starts = [
            r for r in _metrics(tmp_path) if r["event"] == "run_start"
        ]
        assert starts[-1]["start_step"] == 2

    def test_hang_watchdog_aborts_with_diagnostics(
        self, worker, tmp_path
    ):
        """A stalled step (wedged-collective stand-in) is aborted by
        the in-process watchdog with EXIT_HANG and a stack dump,
        instead of hanging the allocation."""
        proc = worker({
            "TPU_HPC_FAULTS": "stall_at_step=2,stall_s=120",
            "TPU_HPC_HANG_TIMEOUT": "4",
        })
        assert proc.returncode == EXIT_HANG, (
            proc.returncode, proc.stderr[-3000:]
        )
        dump = tmp_path / "ckpts" / "hang.attempt0.dump"
        assert dump.exists()
        assert "hang watchdog" in dump.read_text()

    def test_corrupt_ckpt_falls_back_to_previous(
        self, worker, tmp_path
    ):
        """corrupt_ckpt_at_step=6 garbles the FINAL snapshot of run 1
        (a torn write); run 2's restore retries, falls back to step 4,
        and still completes -- the self-healing restore path."""
        proc = worker({"TPU_HPC_FAULTS": "corrupt_ckpt_at_step=6"})
        assert proc.returncode == 0, proc.stderr[-3000:]

        proc2 = worker(
            {"TPU_HPC_ATTEMPT": "1", "WORK_EPOCHS": "4"}
        )
        assert proc2.returncode == 0, proc2.stderr[-3000:]
        assert "FINAL_STEP 8" in proc2.stdout
        starts = [
            r for r in _metrics(tmp_path) if r["event"] == "run_start"
        ]
        # Step 6 was unreadable: resumed from 4, not 6.
        assert starts[-1]["start_step"] == 4


class TestCheckpointReplay:
    def test_replay_save_below_latest_preserves_old_step(
        self, tmp_path
    ):
        """A replay save at a step BELOW the newest surviving snapshot
        (possible after restore(step) or a restore fallback): orbax
        declines the save (should_save is False when a later step
        exists), and the stashed-aside old copy must be put back, not
        deleted -- it is the only copy of that step."""
        import jax.numpy as jnp

        from tpu_hpc.ckpt import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
        state = {"w": jnp.ones((4,))}
        for s in (2, 3, 4):
            assert mgr.save(state, step=s)
        assert not mgr.save({"w": jnp.full((4,), 9.0)}, step=3)
        assert 3 in mgr.all_steps()
        restored = mgr.restore(3, state)
        assert float(restored["w"][0]) == 1.0  # the ORIGINAL copy
        mgr.close()


class TestFaultHelpers:
    def test_corrupt_file(self, tmp_path):
        p = tmp_path / "data.bin"
        p.write_bytes(b"A" * 1000)
        faults.corrupt_file(str(p))
        data = p.read_bytes()
        assert data == b"\x00TPU_HPC_FAULT_CORRUPTED\x00"


def _preempt_gated_cmd(threshold: int):
    """A child that takes a clean preemption snapshot (EXIT_RESUMABLE)
    until TPU_HPC_ATTEMPT >= threshold."""
    return [
        sys.executable, "-c",
        "import os, sys; "
        f"sys.exit(0 if int(os.environ['TPU_HPC_ATTEMPT']) >= "
        f"{threshold} else 75)",
    ]


class TestResumableBudgetCarveOut:
    def test_preemptions_do_not_burn_the_failure_budget(self, tmp_path):
        """signals.py contract: EXIT_RESUMABLE means 'nothing is
        wrong, relaunch me' -- three preemptions must ride through a
        max_restarts=1 supervisor and still reach success."""
        rc = run_supervised(
            _preempt_gated_cmd(3), max_restarts=1,
            log_dir=str(tmp_path), backoff=0.01,
        )
        assert rc == 0
        events = [
            json.loads(x)
            for x in open(os.path.join(str(tmp_path), "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == [75, 75, 75, 0]
        restarts = [e for e in events if e["event"] == "restarting"]
        assert all(
            e.get("why") == "resumable preemption snapshot"
            for e in restarts
        )

    def test_crashes_still_bounded(self, tmp_path):
        rc = run_supervised(
            [sys.executable, "-c", "import sys; sys.exit(3)"],
            max_restarts=1, log_dir=str(tmp_path), backoff=0.01,
        )
        assert rc == 3
        events = [
            json.loads(x)
            for x in open(os.path.join(str(tmp_path), "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == [3, 3]  # 1 restart, then stop

    def test_preemption_cap_bounds_the_loop(self, tmp_path):
        """The carve-out is generous, not infinite: a preemption
        cadence outpacing checkpoints must eventually give up."""
        rc = run_supervised(
            [sys.executable, "-c", "import sys; sys.exit(75)"],
            max_restarts=5, max_preemptions=2,
            log_dir=str(tmp_path), backoff=0.01,
        )
        assert rc == 75
        events = [
            json.loads(x)
            for x in open(os.path.join(str(tmp_path), "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == [75, 75, 75]  # cap + 1 runs
        give = [e for e in events if e["event"] == "giving_up"]
        assert "preemption budget" in give[0]["why"]
