"""From a profiler trace (``.xplane.pb``) to numbers.

Two steps, so the arithmetic can be checked on a small recorded trace
without a chip: :func:`load_xplane` reads the file with
``jax.profiler.ProfileData`` into plain lists of events (all times in
seconds on the trace's one clock), and :func:`reduce` turns those into
busy and idle time, time by kind of operation, the operations that took
most time, and the longest idle gaps, each named by the benchmark's own
host span that covers it.

What the trace looks like on a TPU v5e (read by hand before this was
written, PERF.md PR 23): one plane per chip, ``/device:TPU:<n>``, whose
line ``XLA Ops`` holds one event per executed HLO operation, named by
the instruction's whole text (the core runs one at a time, but a
``while`` spans its body's operations) and whose line ``XLA Modules``
holds one event per program execution (``jit_decode(<hash>)``); the
events carry no category. Host threads are lines of the plane
``/host:CPU`` on the same clock, and a ``jax.profiler.TraceAnnotation``
is an event on its thread's line (``python3``).
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"

# An event of the ``XLA Ops`` line is named by its HLO instruction's
# whole text: ``%fusion.761 = bf16[2,2048,4096]{...} fusion(f32[...]
# %remat2.296, ...), kind=kOutput, calls=...``. Only the instruction's
# own name, opcode and fusion kind say what it is; its operands' names
# say what OTHER operations were.
_INSTRUCTION = re.compile(r"^%?(?P<name>\S+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"(?:^|\s)(?P<op>[a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"\bkind=(k\w+)")

# ``while`` / ``conditional`` / ``call`` events span their bodies'
# operations, which have events of their own on the same line: they are
# containers, and counting them would count their children twice.
CONTAINERS = {"while", "conditional", "call"}

# Kinds of operation, by what the trace itself calls them. First match
# wins; the pattern is searched in "<opcode> <instruction name>".
BUCKETS = (
    ("collective", re.compile(
        r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")),
    ("copy", re.compile(r"copy")),
    ("gather_scatter", re.compile(r"gather|scatter|slice")),
)


def parse_op(text):
    """-> (short name without its number, opcode, fusion kind) of an
    ``XLA Ops`` event's text."""
    match = _INSTRUCTION.match(text)
    if not match:  # not HLO text: a plain name
        return re.sub(r"\.\d+$", "", text), text, ""
    rest = match.group("rest")
    opcode = _OPCODE.search(rest)
    kind = _KIND.search(rest)
    return (
        re.sub(r"\.\d+$", "", match.group("name")),
        opcode.group("op") if opcode else "",
        kind.group(1) if kind else "",
    )


def bucket_of(text):
    name, opcode, kind = parse_op(text)
    if opcode in CONTAINERS:
        return None
    if opcode == "custom-call":
        # Mosaic (Pallas) kernels are the custom calls whose target is
        # ``tpu_custom_call``; the others (AllocateBuffer, ConcatBitcast)
        # are the compiler's own bookkeeping and take no time.
        return "custom_call" if 'custom_call_target="tpu_custom_call"' \
            in text else "other"
    for bucket, pattern in BUCKETS:
        if pattern.search(f"{opcode} {name}"):
            return bucket
    if opcode == "fusion" or name.endswith("fusion"):
        # A fusion rooted at a convolution or dot is an output fusion
        # (``kind=kOutput``): the matmuls, with what was fused behind.
        return "matmul_fusion" if kind == "kOutput" or re.search(
            r"convolution|dot", name) else "other_fusion"
    if opcode in ("convolution", "dot"):
        return "matmul_fusion"
    return "other"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path):
    """-> {"devices": {"<n>": {"ops": [[text, start_s, dur_s], ...],
    "modules": [[name, start_s, dur_s], ...]}},
    "spans": [[name, start_s, dur_s], ...]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "spans": []}
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        dev["ops"].append([
                            ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9,
                        ])
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        dev["modules"].append([
                            ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9,
                        ])
            out["devices"][match.group(1)] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out["spans"].append([
                            ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9,
                        ])
    out["spans"].sort(key=lambda s: s[1])
    return out


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(start, end, lo, hi):
    return max(start, lo), min(end, hi)


def _overlap(start, end, merged):
    """Seconds of [start, end) covered by merged intervals."""
    total = 0.0
    for a, b in merged:
        if b <= start:
            continue
        if a >= end:
            break
        total += min(b, end) - max(a, start)
    return total


def _cover(spans, start, end):
    """The benchmark's host span that says what the host was doing
    over [start, end): the SHORTEST span that covers the gap's
    midpoint (the innermost one), the window span last."""
    mid = 0.5 * (start + end)
    best = None
    for name, s, d in spans:
        if s <= mid < s + d and name != WINDOW_SPAN:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0][len(SPAN_PREFIX):] if best else "between_spans"


def reduce(events, top=10):
    """Busy and idle time inside the window span, by device.

    The window is the host span ``bench:window`` (the steady part of
    the run that the trace covers); without one it is first operation
    to last. Every operation is clipped to it."""
    spans = events["spans"]
    window = next(((s, s + d) for n, s, d in spans if n == WINDOW_SPAN), None)
    all_ops = [op for dev in events["devices"].values() for op in dev["ops"]]
    if not all_ops:
        return None
    if window is None:
        window = (
            min(op[1] for op in all_ops),
            max(op[1] + op[2] for op in all_ops),
        )
    lo, hi = window
    devices = {}
    op_totals, gaps = {}, []
    for dev_id, dev in events["devices"].items():
        buckets, counts, intervals, by_bucket = {}, {}, [], {}
        for text, start, dur in dev["ops"]:
            s, e = _clip(start, start + dur, lo, hi)
            bucket = bucket_of(text)
            if e <= s or bucket is None:
                continue
            buckets[bucket] = buckets.get(bucket, 0.0) + (e - s)
            counts[bucket] = counts.get(bucket, 0) + 1
            by_bucket.setdefault(bucket, []).append((s, e))
            intervals.append((s, e))
            tot = op_totals.setdefault((bucket, parse_op(text)[0]), [0.0, 0])
            tot[0] += e - s
            tot[1] += 1
        busy_iv = _union(intervals)
        busy = sum(b - a for a, b in busy_iv)
        # A collective's exposed time: the part of it during which no
        # other operation runs on this device.
        others = _union([
            iv for b, ivs in by_bucket.items() if b != "collective"
            for iv in ivs
        ])
        exposed = sum(
            (e - s) - _overlap(s, e, others)
            for s, e in _union(by_bucket.get("collective", []))
        )
        edges = [lo] + [t for iv in busy_iv for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a > 0:
                gaps.append((b - a, a, b, dev_id))
        modules = {}
        for name, start, dur in dev["modules"]:
            s, e = _clip(start, start + dur, lo, hi)
            if e > s and s >= lo and start + dur <= hi:
                m = modules.setdefault(name, [])
                m.append(dur)
        devices[dev_id] = {
            "busy_s": busy,
            "idle_s": (hi - lo) - busy,
            "buckets_s": buckets,
            "bucket_counts": counts,
            "collective_exposed_s": exposed,
            "n_ops": len(intervals),
            "modules": {
                name: {"n": len(d), "total_s": sum(d)}
                for name, d in modules.items()
            },
        }
    n_dev = len(devices)
    gap_totals = {}
    for dur, a, b, _dev in gaps:
        name = _cover(spans, a, b)
        gap_totals[name] = gap_totals.get(name, 0.0) + dur / n_dev
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n_dev,
        "devices": devices,
        "device_ops": [
            [f"{bucket}:{key} x{n}", secs / n_dev]
            for (bucket, key), (secs, n) in sorted(
                op_totals.items(), key=lambda kv: -kv[1][0]
            )[:top]
        ],
        # Idle time by what the host was doing, summed over the gaps
        # each span covers (mean over devices), longest first.
        "idle_gaps": [
            [name, secs] for name, secs in sorted(
                gap_totals.items(), key=lambda kv: -kv[1]
            )[:top]
        ],
        "longest_gap_s": max((g[0] for g in gaps), default=0.0),
    }


def describe(path, per_line=4):
    """The trace as it is, for reading by hand: every plane and line
    with its event count and its first events with their stats."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                stats = {k: str(v)[:60] for k, v in ev.stats}
                lines.append(
                    f"    {ev.name[:80]!r} start {ev.start_ns:.0f} ns "
                    f"dur {ev.duration_ns:.0f} ns {stats}"
                )
    return "\n".join(lines)


def main(argv=None):
    """``python benchmark/trace_reduce.py <trace_dir> [--events out.json
    --seconds S]``: describe a trace, reduce it, and optionally save its
    first S seconds of events as JSON (what ``tests/recorded`` holds)."""
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--events")
    ap.add_argument("--seconds", type=float, default=0.05)
    args = ap.parse_args(argv)
    path = find_xplane(args.trace_dir)
    print(describe(path))
    events = load_xplane(path)
    print(json.dumps(reduce(events), indent=1)[:6000])
    if args.events:
        start = min(
            op[1] for dev in events["devices"].values() for op in dev["ops"]
        )
        end = start + args.seconds
        cut = {
            "devices": {
                k: {
                    "ops": [o for o in dev["ops"] if start <= o[1] and o[1] + o[2] <= end],
                    "modules": [m for m in dev["modules"] if start <= m[1] and m[1] + m[2] <= end],
                } for k, dev in events["devices"].items()
            },
            "spans": [s for s in events["spans"] if s[1] + s[2] >= start and s[1] <= end],
        }
        with open(args.events, "w") as f:
            json.dump(cut, f)


if __name__ == "__main__":
    main()
