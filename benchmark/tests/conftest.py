"""The benchmark's own tests: run by hand and in the rehearsal
(``python -m pytest benchmark/tests -q``), on the CPU with four virtual
devices. They check counts, control flow and arithmetic; a time, a rate
or a share of the device comes only from the chip."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
))))
