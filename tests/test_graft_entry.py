"""The driver-facing entry points must stay green.

``dryrun_multichip`` is the external evidence that the full hybrid
FSDPxTP(+SP) train step compiles and executes over a multi-device mesh
(SURVEY.md section 3.2); ``entry`` is the single-chip compile check.
"""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    loss = jax.jit(fn)(*args)
    assert loss.shape == ()
    assert float(loss) > 0


def test_dryrun_multichip_in_process(devices):
    # The suite asked for 8 simulated devices by name (conftest), so
    # the dry run has them in this process.
    graft.dryrun_multichip(8)


def test_dryrun_multichip_too_few_devices_fails(devices):
    # More devices than this process has is a failure naming what it
    # found -- never a re-exec onto a virtual CPU mesh.
    with pytest.raises(RuntimeError, match="8 cpu device"):
        graft.dryrun_multichip(16)
