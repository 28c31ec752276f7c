"""Profiling: jax.profiler traces with schedule windows.

Capability parity with the reference's ``utils/profiling.py``:
``training_profiler`` context manager with wait/warmup/active windowing
(:25-66 -- here ``start_step``/``num_steps``, the same
schedule(wait, warmup, active) idea collapsed to one window), rank-0
(host-0) only trace output (:44-49), TensorBoard-consumable artifacts,
and a memory/summary printer (:69-86).

TPU-native: ``jax.profiler.start_trace`` captures XLA device traces +
HLO cost analysis viewable in TensorBoard/XProf or Perfetto -- the
comm-vs-compute diagnosis workflow the reference docs prescribe
(docs/guide/troubleshooting.md:230-239) works identically: look for
all-reduce/all-gather ops overlapping (good) or serializing (bad) with
the matmul stream. Step boundaries and the stages inside a chunk are
the program's own ``tpu_hpc:chunk.*`` annotations (obs/spans.py), which
land in any trace this opens.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax

from tpu_hpc.logging_ import get_logger


class TrainingProfiler:
    """Step-windowed trace: profile steps [start_step, start_step +
    num_steps) on host 0, skipping warmup/compilation steps (the
    reference's schedule(wait=1, warmup=1, active=3) -- :36-43)."""

    def __init__(
        self,
        log_dir: str = "profiles",
        start_step: int = 3,
        num_steps: int = 5,
        host0_only: bool = True,
    ):
        self.log_dir = log_dir
        self.start_step = start_step
        self.num_steps = num_steps
        self.enabled = not host0_only or jax.process_index() == 0
        self.active = False
        self.logger = get_logger()

    def step(self, step: int) -> None:
        """Call once per training step with the global step index.
        Threshold (not equality) triggered, so chunked loops that
        advance many steps per host iteration still hit the window."""
        if not self.enabled:
            return
        # Open on threshold, not window membership: chunked loops call
        # this only at chunk boundaries, which may skip past the window
        # entirely (e.g. start_step=3 with 20-step epochs -> calls at
        # 0, 20, 40...).
        if not self.active and step >= self.start_step:
            jax.profiler.start_trace(self.log_dir)
            self.active = True
            self.logger.info(
                "profiler: tracing steps %d..%d -> %s",
                step, step + self.num_steps - 1, self.log_dir,
            )
        elif self.active and step >= self.start_step + self.num_steps:
            self.stop()

    def stop(self) -> None:
        """Close an open trace. ``active`` is cleared even when
        ``stop_trace`` itself raises (a full disk mid-write): a stop
        that failed must not make every later stop re-raise on an
        already-dead trace, which is what leaked the open trace the
        finally-guarantee exists for."""
        if self.active:
            try:
                jax.profiler.stop_trace()
            finally:
                self.active = False
            self.logger.info(
                "profiler: trace written to %s (open with TensorBoard "
                "or xprof)", self.log_dir,
            )

    # Context-manager form: ``with TrainingProfiler(...) as prof``
    # guarantees the trace is closed when the loop exhausts inside the
    # window or an exception unwinds through it -- an open
    # jax.profiler.start_trace otherwise leaks for the life of the
    # process (and blocks any later trace from starting).
    def __enter__(self) -> "TrainingProfiler":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


@contextlib.contextmanager
def training_profiler(
    log_dir: str = "profiles",
    start_step: int = 3,
    num_steps: int = 5,
    host0_only: bool = True,
) -> Iterator[TrainingProfiler]:
    """Context-manager form (parity: utils/profiling.py:25-66); always
    stops the trace on exit, even on error."""
    prof = TrainingProfiler(log_dir, start_step, num_steps, host0_only)
    try:
        yield prof
    finally:
        prof.stop()


def device_memory_summary(
    logger=None,
    devices=None,
    emit: bool = True,
    sink: Optional[str] = None,
) -> Optional[dict]:
    """Per-device HBM usage (the reference's profiler summary table
    analogue, :69-86; here sourced from the runtime's live allocator
    stats rather than a trace).

    Beyond the log lines, the summary lands as telemetry (``emit=True``
    and any device reporting stats): one schema-stamped
    ``device_memory`` event (per-device in_use/peak/limit plus the
    fleet-wide maxima) and an ``hbm_peak_bytes`` registry gauge -- so
    the obs report's memory section and the regress gate see HBM
    high-water marks instead of them scrolling past in a log.
    ``devices`` is injectable for tests (and for summarizing a tier
    subset, e.g. one disagg mesh)."""
    logger = logger or get_logger()
    if devices is None:
        devices = jax.local_devices()
    stats = {}
    for d in devices:
        s = d.memory_stats()
        if not s:
            continue
        in_use = s.get("bytes_in_use", 0)
        limit = s.get("bytes_limit", 0)
        peak = s.get("peak_bytes_in_use", 0)
        stats[str(d)] = {"in_use": in_use, "limit": limit, "peak": peak}
        logger.info(
            "%s | in use %.2f GiB | peak %.2f GiB | limit %.2f GiB",
            d, in_use / 2**30, peak / 2**30, limit / 2**30,
        )
    if not stats:
        return None
    if emit:
        from tpu_hpc.obs import get_bus, get_registry

        peak = max(s["peak"] for s in stats.values())
        get_bus().emit(
            "device_memory",
            sink=sink,
            n_devices=len(stats),
            hbm_peak_bytes=int(peak),
            hbm_in_use_bytes=int(
                max(s["in_use"] for s in stats.values())
            ),
            hbm_limit_bytes=int(
                max(s["limit"] for s in stats.values())
            ),
            per_device=stats,
        )
        get_registry().set_gauge(
            "hbm_peak_bytes", float(peak),
            help="Largest per-device HBM high-water mark (bytes) "
            "reported by the live allocator",
        )
    return stats
