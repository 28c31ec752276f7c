"""The program's own names in this run's trace.

``tpu_hpc`` names its stages itself: host spans are
``jax.profiler.TraceAnnotation``s called ``tpu_hpc:<stage>``
(``obs/spans.py``), device operations carry the ``jax.named_scope``
they were traced under in their HLO ``op_name``, and every Pallas
kernel has a ``name``. :func:`load` reads the ``.xplane.pb`` this
process wrote (the newest under ``benchmark/out/*/trace``) into plain
lists, clipped to ``bench:window``, once; the per-layer readers that
want a scope's device time or a span's wall time share the result.

Where the names are (read by hand from a v5e trace, PERF.md PR 25):
``jax.profiler.ProfileData`` gives every event's name, start and
duration but none of its metadata, and an ``XLA Ops`` event's name is
its HLO text WITHOUT the ``metadata={op_name=...}`` part. The
``op_name`` is the stat ``tf_op`` of the event's ``XEventMetadata``
(``jit(decode)/kv_write/scatter:``), which only the raw protobuf
holds; :func:`event_op_names` reads just those tables with a small
wire-format reader (no protobuf schema is installed apart from
TensorFlow's) and joins them to the events by name.

A trace of a program without these names (the parent of the PR that
brought them, or executables a compile cache kept from before) has
no ``tpu_hpc:`` span and no scope: :func:`load` then returns what it
found, readers find nothing to read and return ``None``.

Nothing here imports ``tpu_hpc``.
"""
import bisect
import glob
import os
import re
import sys

if __package__ in (None, ""):  # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))

from benchmark import harness, trace_reduce  # noqa: E402

PREFIX = "tpu_hpc:"

# The program's scope names (docs/guide/observability.md, "Stage
# names"). An operation's scope is the LAST of these on its op_name
# path: ``.../layers_3/attention/qkv/wq/dot_general`` is ``qkv`` (the
# flax module called ``attention`` holds three of the stages).
SCOPES = (
    "embed", "qkv", "kv_write", "kv_read", "attention", "attn_out",
    "mlp", "head", "optimizer", "sp_constrain",
)
# Opcodes the compiler adds to move or re-lay data, on a chip or
# between chips. One with no scope of its own inherits that of its
# producers or consumers when they agree on one (``inherited`` in the
# reduction).
MOVES = {
    "copy", "copy-start", "copy-done", "bitcast", "reshape",
    "transpose", "slice-start", "slice-done", "get-tuple-element",
    "all-to-all", "all-gather", "all-reduce", "reduce-scatter",
    "collective-permute-start", "collective-permute-done",
    "async-collective-start", "async-collective-done",
}

_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


# -- the raw protobuf's event metadata --------------------------------
def _varint(buf, pos):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message;
    length-delimited values are memoryview slices (not copied)."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield number, wire, value


def _map_value(entry):
    """The value message of one ``map<int64, Message>`` entry."""
    for number, _, value in _fields(entry):
        if number == 2:
            return value
    return b""


def event_op_names(path):
    """-> {plane name: {event name: op_name}} from the ``tf_op`` stat
    of each ``XEventMetadata`` of each device plane. Field numbers are
    those of ``tsl/profiler/protobuf/xplane.proto``: XSpace.planes=1;
    XPlane.name=2, .event_metadata=4, .stat_metadata=5;
    XEventMetadata.name=2, .stats=5; XStat.metadata_id=1,
    .str_value=5, .ref_value=7; XStatMetadata.id=1, .name=2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, _, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                events.append(_map_value(value))
            elif field == 5:
                sid, sname = 0, ""
                for f2, _, v2 in _fields(_map_value(value)):
                    if f2 == 1:
                        sid = v2
                    elif f2 == 2:
                        sname = bytes(v2).decode()
                stat_names[sid] = sname
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        table = out[name] = {}
        for meta in events:
            ev_name, op_name = "", None
            for f2, _, v2 in _fields(meta):
                if f2 == 2:
                    ev_name = bytes(v2).decode()
                elif f2 == 5:
                    sid, text = 0, None
                    for f3, _, v3 in _fields(v2):
                        if f3 == 1:
                            sid = v3
                        elif f3 == 5:
                            text = bytes(v3).decode()
                        elif f3 == 7:
                            text = stat_names.get(v3)
                    if stat_names.get(sid) == "tf_op":
                        op_name = text
            if op_name and ev_name not in table:
                table[ev_name] = op_name
    return out


def path_of(op_name):
    """``jit(f)/transpose(jvp(Llama))/layers_0/qkv/wq/dot_general:`` ->
    the path's bare names: transform wrappers peeled, the op type
    after the colon dropped."""
    parts = []
    for part in (op_name or "").split(":")[0].split("/"):
        match = _WRAPPED.match(part)
        while match:
            part = match.group(1)
            match = _WRAPPED.match(part)
        parts.append(part)
    return parts


def scope_of(parts):
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return None


# -- one run's trace ---------------------------------------------------
_CACHE = {}


def find_trace():
    """The newest ``.xplane.pb`` under ``benchmark/out/*/trace`` (this
    process wrote it: ``harness.start_trace`` clears the cell's
    directory first), or None."""
    paths = glob.glob(os.path.join(
        harness.BENCH_DIR, "out", "*", "trace", "plugins", "profile",
        "*", "*.xplane.pb",
    ))
    return max(paths, key=os.path.getmtime) if paths else None


def load(obs):
    """The traced window by the program's names, or None when this
    run traced nothing (never looks for a file then: a stale trace of
    an earlier run may lie there). Cached by path."""
    if not obs.get("trace"):
        return None
    path = find_trace()
    if path is None:
        return None
    if path not in _CACHE:
        try:
            _CACHE[path] = reduce(read(path))
        except Exception as e:  # a metric less, never a run less
            harness.log(f"program_trace: cannot read {path}: {e!r}")
            _CACHE[path] = None
    return _CACHE[path]


def read(path):
    """-> events as ``trace_reduce.load_xplane`` gives them, with the
    program's spans beside the benchmark's and each operation's
    op_name: {"devices": {id: {"ops": [[text, start_s, dur_s,
    op_name], ...], "modules": [...]}}, "spans": [[name, start_s,
    dur_s], ...]}."""
    from jax.profiler import ProfileData

    op_names = event_op_names(path)
    out = {"devices": {}, "spans": []}
    for plane in ProfileData.from_file(path).planes:
        match = trace_reduce.DEVICE_PLANE.match(plane.name)
        if match:
            table = op_names.get(plane.name, {})
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    dev["ops"] = [
                        [ev.name, ev.start_ns * 1e-9,
                         ev.duration_ns * 1e-9, table.get(ev.name)]
                        for ev in line.events
                    ]
                elif line.name == trace_reduce.MODULES_LINE:
                    dev["modules"] = [
                        [ev.name, ev.start_ns * 1e-9,
                         ev.duration_ns * 1e-9]
                        for ev in line.events
                    ]
            out["devices"][match.group(1)] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX) \
                            or ev.name == trace_reduce.WINDOW_SPAN:
                        out["spans"].append([
                            ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9,
                        ])
    out["spans"].sort(key=lambda s: s[1])
    return out


def _program(module_name):
    """``jit_decode(123)`` -> ``decode``."""
    return re.sub(r"^jit_|\(\d+\)$", "", module_name)


def _resolve(ops):
    """-> {event text: {"scope", "inherited", "kernels", "short",
    "counted"}} for one device's distinct operations (an event's text
    is parsed once, however often it ran)."""
    info, by_name = {}, {}
    for text, _, _, op_name in ops:
        if text in info:
            continue
        short, opcode, _ = trace_reduce.parse_op(text)
        head, _, rest = text.partition(" = ")
        parts = path_of(op_name)
        mosaic = "tpu_custom_call" in text
        info[text] = {
            "scope": scope_of(parts), "inherited": False,
            "kernels": [
                p for p in parts
                if mosaic and p.startswith(("flash_", "paged_"))
            ],
            "short": short, "opcode": opcode,
            "counted": trace_reduce.bucket_of(text) is not None,
            "full": head.lstrip("%"),
            "operands": _OPERAND.findall(rest.partition("(")[2]),
        }
        by_name[info[text]["full"]] = text
    consumers = {}
    for text, entry in info.items():
        for operand in entry["operands"]:
            consumers.setdefault(operand, []).append(text)
    # A chain of moves (copy -> all-to-all -> copy) takes its scope
    # from its ends: repeat until nothing more is claimed.
    changed, passes = True, 0
    while changed and passes < 8:
        changed, passes = False, passes + 1
        for entry in info.values():
            if entry["scope"] is not None or entry["opcode"] not in MOVES:
                continue
            near = {
                info[by_name[o]]["scope"]
                for o in entry["operands"] if o in by_name
            } | {
                info[c]["scope"]
                for c in consumers.get(entry["full"], [])
            }
            near.discard(None)
            if len(near) == 1:
                entry["scope"], entry["inherited"] = near.pop(), True
                changed = True
    return info


def reduce(events):
    """Clip to ``bench:window`` and add up by the program's names.

    -> {"window": (lo, hi), "spans": {stage: [[start, dur], ...]}
    (inside the window, prefix dropped), "leaf_spans": [[start, end],
    ...] merged, "devices": {id: {"programs": {program: {"n", "total_s",
    "scopes_s": {scope: s}, "inherited_s": {scope: s}, "unscoped_s",
    "unscoped_ops": {short name: s}}}, "kernels_s": {kernel: s},
    "scopes_s": {scope: s}, "idle_s", "idle_unnamed_s"}}}"""
    spans = events["spans"]
    window = next(
        ((s, s + d) for n, s, d in spans if n == trace_reduce.WINDOW_SPAN),
        None,
    )
    if window is None:
        return None
    lo, hi = window
    mine = [s for s in spans if s[0].startswith(PREFIX)]
    by_stage = {}
    for name, start, dur in mine:
        if start >= lo and start + dur <= hi:
            by_stage.setdefault(name[len(PREFIX):], []).append([start, dur])
    # Leaf spans: those that hold no other span of the program.
    # Spans of one thread nest, so sorted by (start, longest first) a
    # span has a child exactly if the next one starts inside it.
    inside = sorted(
        ((s, s + d) for _, s, d in mine if s + d > lo and s < hi),
        key=lambda iv: (iv[0], -iv[1]),
    )
    # (A nanosecond of slack: the trace's clock, and abutting spans
    # must not read as nested through float rounding.)
    leaves = [
        [s, e] for i, (s, e) in enumerate(inside)
        if i + 1 == len(inside) or inside[i + 1][0] >= e - 1e-9
    ]
    leaf_iv = trace_reduce._union(leaves)
    leaf_ends = [b for _, b in leaf_iv]

    def named(a, b):
        """Seconds of [a, b] that lie inside a leaf span."""
        total = 0.0
        for s, e in leaf_iv[bisect.bisect_right(leaf_ends, a):]:
            if s >= b:
                break
            total += min(e, b) - max(s, a)
        return total

    devices = {}
    for dev_id, dev in events["devices"].items():
        info = _resolve(dev["ops"])
        modules = sorted(
            (s, s + d, _program(n)) for n, s, d in dev["modules"]
        )
        mod_starts = [m[0] for m in modules]
        programs, kernels, scopes, intervals = {}, {}, {}, []
        for n, s, d in dev["modules"]:
            if s >= lo and s + d <= hi:
                prog = programs.setdefault(_program(n), _new_program())
                prog["n"] += 1
                prog["total_s"] += d
        for text, start, dur, _ in dev["ops"]:
            s, e = max(start, lo), min(start + dur, hi)
            entry = info[text]
            if e <= s or not entry["counted"]:
                continue
            intervals.append((s, e))
            scope = entry["scope"]
            for kernel in entry["kernels"]:
                kernels[kernel] = kernels.get(kernel, 0.0) + (e - s)
            if scope is not None:
                scopes[scope] = scopes.get(scope, 0.0) + (e - s)
            i = bisect.bisect_right(mod_starts, start) - 1
            if i < 0 or start >= modules[i][1]:
                continue
            prog = programs.get(modules[i][2])
            if prog is None:
                continue
            if scope is None:
                prog["unscoped_s"] += e - s
                short = entry["short"]
                prog["unscoped_ops"][short] = \
                    prog["unscoped_ops"].get(short, 0.0) + (e - s)
            else:
                key = "inherited_s" if entry["inherited"] else "scopes_s"
                prog[key][scope] = prog[key].get(scope, 0.0) + (e - s)
        busy = trace_reduce._union(intervals)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle = unnamed = 0.0
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle += b - a
                unnamed += b - a - named(a, b)
        devices[dev_id] = {
            "programs": programs, "kernels_s": kernels,
            "scopes_s": scopes, "idle_s": idle,
            "idle_unnamed_s": unnamed,
        }
    return {
        "window": [lo, hi], "spans": by_stage, "leaf_spans": leaf_iv,
        "devices": devices,
    }


def _new_program():
    return {
        "n": 0, "total_s": 0.0, "scopes_s": {}, "inherited_s": {},
        "unscoped_s": 0.0, "unscoped_ops": {},
    }


# -- what the readers share --------------------------------------------
def scope_ms_per_run(obs, program, scope):
    """Device time under ``scope`` (its own and inherited) in one
    execution of ``program``, mean over the window's executions, on
    the device that spent most; None where the trace has no scope."""
    trace = load(obs)
    if not trace:
        return None
    worst = None
    for dev in trace["devices"].values():
        prog = dev["programs"].get(program)
        if not prog or not prog["n"] or not prog["scopes_s"]:
            continue
        value = (
            prog["scopes_s"].get(scope, 0.0)
            + prog["inherited_s"].get(scope, 0.0)
        ) / prog["n"]
        worst = value if worst is None else max(worst, value)
    return None if worst is None else 1e3 * worst


def scope_ms_per_step(obs, scope):
    """Device time under ``scope`` per training step (all programs of
    the window), on the device that spent most."""
    trace = load(obs)
    if not trace or not obs["trace"].get("steps"):
        return None
    values = [
        dev["scopes_s"][scope] for dev in trace["devices"].values()
        if scope in dev["scopes_s"]
    ]
    if not values:
        return None
    return 1e3 * max(values) / obs["trace"]["steps"]


def kernel_ms_per_step(obs, *kernels):
    """Device time of the Mosaic calls named ``kernels`` per training
    step, on the device that spent most."""
    trace = load(obs)
    if not trace or not obs["trace"].get("steps"):
        return None
    values = [
        sum(dev["kernels_s"].get(k, 0.0) for k in kernels)
        for dev in trace["devices"].values()
    ]
    worst = max(values, default=0.0)
    return 1e3 * worst / obs["trace"]["steps"] if worst else None


def span_walls(obs, stage):
    """Wall seconds of each ``tpu_hpc:<stage>`` span inside the
    window; [] where there is none, None where nothing was traced."""
    trace = load(obs)
    if not trace:
        return None
    return [d for _, d in trace["spans"].get(stage, [])]


def tick_own_ms(obs):
    """Wall time of ``tpu_hpc:tick`` outside ``tick.admit``,
    ``tick.prefill`` and the engine's ``decode``, mean over the ticks
    inside the window (children counted under the tick that holds
    their start)."""
    trace = load(obs)
    if not trace or not trace["spans"].get("tick"):
        return None
    ticks = trace["spans"]["tick"]
    starts = [s for s, _ in ticks]
    own = sum(d for _, d in ticks)
    for stage in ("tick.admit", "tick.prefill", "decode"):
        for start, dur in trace["spans"].get(stage, []):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < ticks[i][0] + ticks[i][1]:
                own -= dur
    return 1e3 * own / len(ticks)


def idle_unnamed_pct(obs):
    """Share of the devices' idle time in the window that lies in no
    leaf ``tpu_hpc:`` span; None without such spans. (By overlap, not
    by each gap's midpoint: a 5 ms gap runs through eight spans, and
    its midpoint falls between two of them by chance.)"""
    trace = load(obs)
    if not trace or not trace["spans"]:
        return None
    idle = sum(d["idle_s"] for d in trace["devices"].values())
    unnamed = sum(d["idle_unnamed_s"] for d in trace["devices"].values())
    return 100.0 * unnamed / idle if idle > 0 else 0.0


def main(argv=None):
    """``python benchmark/program_trace.py <trace_dir | file.xplane.pb>
    [--events out.json --seconds S]``: reduce a trace by the program's
    names and print it; ``--events`` saves its first S seconds of
    events as JSON (what ``tests/recorded`` holds)."""
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--events")
    ap.add_argument("--seconds", type=float, default=0.25)
    ap.add_argument("--offset", type=float, default=0.0,
                    help="seconds into the window where the cut starts")
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") \
        else trace_reduce.find_xplane(args.trace)
    events = read(path)
    print(json.dumps(reduce(events), indent=1)[:20000])
    if args.events:
        start = args.offset + next(
            s for n, s, _ in events["spans"]
            if n == trace_reduce.WINDOW_SPAN
        )
        end = start + args.seconds
        cut = {
            "devices": {
                k: {
                    key: [o for o in dev[key]
                          if start <= o[1] and o[1] + o[2] <= end]
                    for key in ("ops", "modules")
                } for k, dev in events["devices"].items()
            },
            # The cut is its own window; spans that reach out of it go.
            "spans": [[trace_reduce.WINDOW_SPAN, start, args.seconds]] + [
                sp for sp in events["spans"]
                if sp[0] != trace_reduce.WINDOW_SPAN
                and start <= sp[1] and sp[1] + sp[2] <= end
            ],
        }
        with open(args.events, "w") as f:
            json.dump(cut, f)


if __name__ == "__main__":
    main()
