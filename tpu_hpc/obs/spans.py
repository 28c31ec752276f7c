"""Nestable span timers: one timing truth for JSONL and XProf.

``with span("ckpt"): ...`` measures a wall-clock duration, emits a
``span`` event through the bus, and (by default) opens a
``jax.profiler.TraceAnnotation`` named ``tpu_hpc:ckpt`` -- so the
phase boundaries in a run's JSONL and the named regions in an XProf
trace are the SAME brackets, not two instrumentation layers that
drift. The ``tpu_hpc:`` prefix is the program's namespace in a trace:
a reader keeps the events whose name starts with it and thereby tells
the program's stages from the runtime's own host events (the JSONL
name stays bare; ``obs/trace.py`` reads ``decode`` and ``prefill``).
Tracing has no switch of its own: an annotation is recorded while a
profiler session is on and costs a fraction of a microsecond while
none is. Spans nest: each event carries its ``parent`` span name and
depth, so the report can attribute child time without double counting.
The stage names in use are listed in docs/guide/observability.md.

Clock contract (pinned in tests/test_trace.py): **durations come from
the monotonic clock** (``time.perf_counter``), never wall time -- an
NTP step mid-span must not corrupt a phase share. Every span event
also carries ``t_mono`` (the monotonic timestamp at span end, same
clock as the duration) next to the bus-stamped wall ``time``: a
cross-host trace merge (obs/trace.py) orders and measures each host
on its own monotonic axis and uses wall time only for coarse
alignment between hosts.

For phases whose duration is measured some other way (the Trainer's
chunk timer already brackets dispatch-to-fetch), :func:`emit_span`
records a pre-aggregated duration without re-timing it.

Cost: a span with no JSONL sink is a few microseconds (measured in
PERF.md): the record goes to the flight ring with its wall time and
gets its provenance stamp when the ring is read
(``EventBus.emit_ring``), because the hot paths open several a tick.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from tpu_hpc.obs.events import EventBus, get_bus

TRACE_PREFIX = "tpu_hpc:"

_stack = threading.local()


def _current_stack() -> list:
    st = getattr(_stack, "names", None)
    if st is None:
        st = _stack.names = []
    return st


def _record(name: str, dur_s: float, step, fields: dict) -> dict:
    st = _current_stack()
    rec = {
        "event": "span",
        "name": name,
        "dur_s": dur_s,
        "t_mono": time.perf_counter(),
        "depth": len(st),
    }
    if step is not None:
        rec["step"] = step
    if st:
        rec["parent"] = st[-1]
    for key, value in fields.items():
        if value is not None:
            rec[key] = value
    return rec


def _emit(rec: dict, bus: Optional[EventBus], sink, hist) -> dict:
    if hist is not None:
        from tpu_hpc.obs.registry import get_registry

        get_registry().observe(hist, rec["dur_s"])
    bus = bus or get_bus()
    if sink or bus.path:
        return bus.emit_record(rec, sink=sink)
    return bus.emit_ring(rec)


def emit_span(
    name: str,
    dur_s: float,
    *,
    bus: Optional[EventBus] = None,
    sink: Optional[str] = None,
    step: Optional[int] = None,
    hist: Optional[str] = None,
    **fields,
) -> dict:
    """Emit one ``span`` record for an already-measured duration.
    ``hist`` additionally observes the duration into the global
    metrics registry under that histogram name."""
    return _emit(_record(name, dur_s, step, fields), bus, sink, hist)


# jax.profiler.TraceAnnotation, resolved on first use (importing jax
# here would make ``import tpu_hpc.obs`` pull it in); False where jax
# or its profiler cannot be had.
_TraceAnnotation = None


def _annotation(name: str):
    global _TraceAnnotation
    if _TraceAnnotation is None:
        try:
            import jax

            _TraceAnnotation = jax.profiler.TraceAnnotation
        except Exception:  # pragma: no cover - profiler unavailable
            _TraceAnnotation = False
    if _TraceAnnotation is False:  # pragma: no cover
        return None
    return _TraceAnnotation(TRACE_PREFIX + name)


class span:
    """Time a block as a named span (a context manager).

    Emits the ``span`` event on the way out whether or not the block
    raised (an exception inside still records the phase and its
    duration -- the flight recorder wants exactly the event that
    preceded the crash). ``annotate=False`` skips the profiler
    annotation for spans on paths where jax may not be initialized
    yet. ``with span(...) as s`` hands back the span, so that what is
    known only at the block's end can go into ``s.fields`` and reach
    the record.
    """

    __slots__ = (
        "name", "bus", "sink", "step", "hist", "fields", "_ann", "_t0",
    )

    def __init__(
        self,
        name: str,
        *,
        bus: Optional[EventBus] = None,
        sink: Optional[str] = None,
        step: Optional[int] = None,
        annotate: bool = True,
        hist: Optional[str] = None,
        **fields,
    ):
        self.name, self.bus, self.sink = name, bus, sink
        self.step, self.hist, self.fields = step, hist, fields
        self._ann = _annotation(name) if annotate else None

    def __enter__(self) -> "span":
        _current_stack().append(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _current_stack().pop()
        _emit(
            _record(self.name, dur, self.step, self.fields),
            self.bus, self.sink, self.hist,
        )
        return False
