"""What the readers of the program's own tracing share: the engine's
``serve.*`` host spans with their counters, and each device
operation's ``tf_op`` name stack (the ``jax.named_scope``s of the step
programs), from the traced run's ``*.xplane.pb``.

The harness reduces the trace before the readers run and deletes its
directory after them, so this module reads the file again, once a
run, and keeps what it found on the ``Run``.  ``ProfileData`` gives the
host spans and their stats, but not the stats that XLA keeps on each
op's event metadata, ``tf_op`` among them: those come from the file's
protobuf wire format, decoded here as far as ``XSpace -> XPlane ->
event_metadata / stat_metadata``.  A run of a program without these
spans or scopes finds none, and its readers return None.
"""
from __future__ import annotations

from bench import trace_reduce

SPAN_PREFIX = "serve."


def _cache(run) -> dict:
    return run.__dict__.setdefault("_program", {})


def _raw(run):
    """The trace file's bytes; None for an untraced run."""
    c = _cache(run)
    if "raw" not in c:
        c["raw"] = None
        if run.trace is not None:
            d = run.cell["root"] / "bench" / ".out" / "trace"
            found = sorted(d.glob("plugins/profile/*/*.xplane.pb"))
            if found:
                c["raw"] = found[-1].read_bytes()
    return c["raw"]


def spans(run) -> list:
    """``(name, start, end, stats)`` of every ``serve.*`` host span that
    meets the traced window, clipped to it, by start (a span before the
    spans it holds)."""
    c = _cache(run)
    if "spans" not in c:
        raw = _raw(run)
        c["spans"] = [] if raw is None else host_spans(
            raw, run.trace["t0"], run.trace["t1"])
    return c["spans"]


def host_spans(raw: bytes, t0, t1) -> list:
    from jax.profiler import ProfileData
    out = []
    for p in ProfileData.from_serialized_xspace(raw).planes:
        if p.name != trace_reduce.HOST_PLANE:
            continue
        for line in p.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                s = max(ev.start_ns, t0)
                e = min(ev.start_ns + ev.duration_ns, t1)
                if e > s:
                    out.append((ev.name, s, e, dict(ev.stats)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def ticks(run) -> list:
    """Each ``serve.tick`` span wholly inside the traced window, with the
    spans it holds: ``[(tick, [inner, ...]), ...]``."""
    sp = spans(run)
    out = []
    for i, t in enumerate(sp):
        if t[0] != "serve.tick" or not (
                run.trace["t0"] < t[1] and t[2] < run.trace["t1"]):
            continue
        inner = []
        for s in sp[i + 1:]:
            if s[1] >= t[2]:
                break
            inner.append(s)
        out.append((t, inner))
    return out


def tf_ops(run) -> dict:
    """``{chip: {op event name: tf_op name stack}}`` for the device
    planes; ops the compiler adds (its own copies) carry none."""
    c = _cache(run)
    if "tf_ops" not in c:
        raw = _raw(run)
        c["tf_ops"] = {} if raw is None else op_stacks(raw)
    return c["tf_ops"]


# ----------------------------------------------------------------------
# the protobuf wire format, as far as the planes' metadata
# ----------------------------------------------------------------------
# field numbers of tsl/profiler/protobuf/xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
EVENT_METADATA_NAME, EVENT_METADATA_STATS = 2, 5
STAT_METADATA_NAME = 2
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """``(field number, value)`` of one message: an int for varints, a
    memoryview for length-delimited fields, bytes for fixed widths."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            w = 8 if wire == 1 else 4
            v, i = bytes(b[i:i + w]), i + w
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_stacks(raw: bytes) -> dict:
    """``{chip: {event name: tf_op name stack}}`` from an ``XSpace``.
    ``tf_op`` reads ``<name stack>:<op type>``; the type is dropped."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != SPACE_PLANES:
            continue
        name, events, stat_names = None, [], {}
        for pf, pv in _fields(plane):
            if pf == PLANE_NAME:
                name = _str(pv)
            elif pf == PLANE_EVENT_METADATA:
                events.append(dict(_fields(pv)).get(MAP_VALUE, b""))
            elif pf == PLANE_STAT_METADATA:
                ent = dict(_fields(pv))
                md = dict(_fields(ent.get(MAP_VALUE, b"")))
                stat_names[ent.get(MAP_KEY, 0)] = _str(
                    md.get(STAT_METADATA_NAME, b""))
        m = trace_reduce.DEVICE_PLANE.match(name or "")
        if not m:
            continue
        tf_id = next((k for k, v in stat_names.items() if v == "tf_op"),
                     None)
        stacks = {}
        for md in events:
            ev_name, stack = None, None
            for ef, ev in _fields(md):
                if ef == EVENT_METADATA_NAME:
                    ev_name = _str(ev)
                elif ef == EVENT_METADATA_STATS:
                    st = dict(_fields(ev))
                    if st.get(STAT_METADATA_ID) != tf_id:
                        continue
                    stack = (_str(st[STAT_STR]) if STAT_STR in st else
                             stat_names.get(st.get(STAT_REF), ""))
            if ev_name is not None and stack:
                stacks[ev_name] = stack.rpartition(":")[0] \
                    if ":" in stack else stack
        out[int(m.group(1))] = stacks
    return out
