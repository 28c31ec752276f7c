"""REAL multi-process distributed init: two OS processes rendezvous
through ``init_distributed`` and reduce across the process boundary.

This is the no-hardware equivalent of the reference's multi-node
smoke tests (tests/test_torchrun.py, tests/check_environment.py): the
coordinator bootstrap, launcher-env detection, global device view and
a cross-process collective are all exercised for real -- each worker
is a separate interpreter with one local CPU device, and the psum
result must contain the other process's contribution. The unit tests
in test_runtime.py only check env *parsing*; this checks the wire.
"""
import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    """Ephemeral coordinator port: a fixed number collides with prior
    leaked workers or parallel jobs on the same host."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]

WORKER = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    # The CPU backend has no cross-process collective implementation
    # by default ("Multiprocess computations aren't implemented on
    # the CPU backend"); jaxlib ships Gloo for exactly this -- opt in
    # BEFORE jax.distributed.initialize or the cross-process psum
    # below cannot run.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from tpu_hpc.runtime import init_distributed

    info = init_distributed(verbose=False)
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 2, jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()).reshape(2), ("data",))
    local = jnp.full((1,), float(jax.process_index() + 1))
    arr = jax.make_array_from_single_device_arrays(
        (2,), NamedSharding(mesh, P("data")),
        [jax.device_put(local, jax.local_devices()[0])],
    )
    total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(arr)
    print("RESULT", info.launcher, jax.process_index(),
          float(total.addressable_shards[0].data))
    """
).format(repo=REPO)


def _launch(rank_env) -> "list[subprocess.Popen]":
    procs = []
    for pid in (0, 1):
        env = dict(os.environ)
        # A clean slate: the host env may carry launcher vars that
        # would win the detection cascade.
        for v in (
            "JAX_PROCESS_ID", "JAX_NUM_PROCESSES",
            "JAX_COORDINATOR_ADDRESS", "JAX_COORDINATOR_PORT",
            "OMPI_COMM_WORLD_RANK",
            "OMPI_COMM_WORLD_SIZE", "MASTER_ADDR", "MASTER_PORT",
            "TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "SLURM_PROCID",
            "SLURM_NTASKS", "TPU_HPC_SIM_DEVICES", "XLA_FLAGS",
        ):
            env.pop(v, None)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(rank_env(pid))
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
        )
    return procs


def _collect(procs, expect_launcher: str):
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, f"worker failed:\n{err[-1500:]}"
            line = [
                l for l in out.splitlines() if l.startswith("RESULT")
            ][-1]
            outs.append(line.split())
    finally:
        # One worker failing/timing out must not leak the other at the
        # rendezvous barrier (it would hold the coordinator port for
        # every later test on this host).
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for _, launcher, _, total in outs:
        assert launcher == expect_launcher
        # 1.0 (process 0) + 2.0 (process 1): the reduction crossed
        # the process boundary.
        assert float(total) == 3.0
    assert {o[2] for o in outs} == {"0", "1"}


def test_explicit_launcher_two_processes():
    """JAX_PROCESS_ID/JAX_NUM_PROCESSES/JAX_COORDINATOR_ADDRESS: the
    'explicit' rung of the detection cascade, end-to-end."""
    port = _free_port()
    procs = _launch(
        lambda pid: {
            "JAX_PROCESS_ID": str(pid),
            "JAX_NUM_PROCESSES": "2",
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        }
    )
    _collect(procs, "explicit")


def test_openmpi_launcher_two_processes():
    """OMPI_COMM_WORLD_* + MASTER_ADDR (the mpiexec contract the
    reference rides, utils/distributed.py:49-60 + :103-121): detection,
    MASTER_ADDR->coordinator shim, and the actual rendezvous."""
    port = _free_port()
    procs = _launch(
        lambda pid: {
            "OMPI_COMM_WORLD_RANK": str(pid),
            "OMPI_COMM_WORLD_SIZE": "2",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
        }
    )
    _collect(procs, "openmpi")


HYBRID_WORKER = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    # Gloo CPU collectives: see WORKER above -- the FSDP gathers in
    # this test cross the process boundary.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from tpu_hpc.runtime import init_distributed

    info = init_distributed(verbose=False)
    import jax.numpy as jnp
    from tpu_hpc.ckpt import CheckpointManager
    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.models import datasets, llama2
    from tpu_hpc.parallel import hybrid, tp
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.train import Trainer

    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4, jax.local_device_count()

    mode = os.environ["TEST_MODE"]          # a: 2 steps + ckpt
    ckpt_dir = os.environ["TEST_CKPT_DIR"]  # b: resume 2 more
                                            # c: 4 straight, no ckpt
    # data axis rows = device pairs -> rows 0-1 live on process 0,
    # rows 2-3 on process 1: FSDP param gathers MUST cross the
    # process boundary; the model axis pairs devices within a host.
    mesh = build_mesh(MeshSpec(axes={{"data": 4, "model": 2}}))
    model_cfg = llama2.LlamaConfig(
        dim=64, n_layers=2, n_heads=4, vocab_size=256,
        multiple_of=32, max_seq_len=32,
    )
    params = llama2.init_llama(jax.random.key(0), model_cfg)
    specs = hybrid.hybrid_pspecs(
        params, tp.llama_rules(), data_size=4, min_size=1000
    )
    constrain = tp.sp_constrain(mesh, dp_axis="data", sp_axis="model")
    cfg = TrainingConfig(
        global_batch_size=8, steps_per_epoch=2,
        epochs=1 if mode == "a" else 2,
        save_every=1, resume=(mode == "b"), learning_rate=1e-2,
    )
    mgr = (
        CheckpointManager(ckpt_dir, async_save=False)
        if mode in ("a", "b") else None
    )
    trainer = Trainer(
        cfg, mesh, llama2.make_forward(model_cfg, constrain), params,
        param_pspecs=specs, checkpoint_manager=mgr,
    )
    # Prove the process-spanning layout: at least one param is laid
    # out over all 8 devices (4 of them non-addressable from here).
    span = any(
        len(l.sharding.device_set) == 8
        for l in jax.tree.leaves(trainer.state.params)
    )
    ds = datasets.TokenStream(vocab_size=256, seq_len=32)
    res = trainer.fit(ds)
    if mgr is not None:
        mgr.close()
    print("RESULT", mode, jax.process_index(),
          repr(float(res["final_loss"])), int(span))
    """
).format(repo=REPO)


def _run_hybrid_pair(mode: str, ckpt_dir: str):
    """Launch one 2-process x 4-sim-device hybrid run; return the
    per-rank (loss_repr, span) results."""
    port = _free_port()
    procs = []
    for pid in (0, 1):
        env = dict(os.environ)
        for v in (
            "JAX_PROCESS_ID", "JAX_NUM_PROCESSES",
            "JAX_COORDINATOR_ADDRESS", "JAX_COORDINATOR_PORT",
            "OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE",
            "MASTER_ADDR", "MASTER_PORT", "TPU_WORKER_ID",
            "TPU_WORKER_HOSTNAMES", "SLURM_PROCID", "SLURM_NTASKS",
            "TPU_HPC_SIM_DEVICES",
        ):
            env.pop(v, None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_PROCESS_ID": str(pid),
            "JAX_NUM_PROCESSES": "2",
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "TEST_MODE": mode,
            "TEST_CKPT_DIR": ckpt_dir,
        })
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", HYBRID_WORKER],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=420)
            assert p.returncode == 0, (
                f"hybrid worker ({mode}) failed:\n{err[-2000:]}"
            )
            line = [
                l for l in out.splitlines() if l.startswith("RESULT")
            ][-1]
            outs.append(line.split())
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert {o[2] for o in outs} == {"0", "1"}
    # Both ranks computed the identical global loss.
    assert outs[0][3] == outs[1][3], outs
    assert all(o[4] == "1" for o in outs), (
        "no param spanned both processes -- the mesh did not cross "
        "the host boundary"
    )
    return outs[0][3]


@pytest.mark.slow
def test_hybrid_fsdp_tp_trainer_across_two_processes(tmp_path):
    """The multi-node rehearsal (reference utils/distributed.py:124-158
    + fsdp_tp/fsdp_tp_example.py:80-97, without hardware): 2 processes
    x 4 sim devices run the hybrid FSDPxTP Trainer over a
    process-spanning {data:4, model:2} mesh -- FSDP all-gathers cross
    the process boundary -- checkpoint at step 2 across both
    processes, and a fresh process pair resumes bit-exact: its step-4
    loss equals a never-interrupted 4-step run's."""
    ckpt = str(tmp_path / "ckpt")
    loss_a = _run_hybrid_pair("a", ckpt)          # steps 1-2 + save
    loss_b = _run_hybrid_pair("b", ckpt)          # restore, steps 3-4
    loss_c = _run_hybrid_pair("c", str(tmp_path / "unused"))  # 1-4
    assert loss_b == loss_c, (
        f"resumed run diverged: resumed {loss_b} vs continuous {loss_c}"
    )
    assert loss_a != loss_b  # sanity: training actually progressed
