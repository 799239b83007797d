"""From a profiler trace (``*.xplane.pb``) to device busy time, device
time per operation and per module, and idle gaps named by what the
host was doing.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the device and their ``XLA Modules`` line
one per program execution (``jit_prefill(..)``, ``jit_step(..)``).
Host spans are the harness's own ``bench.*`` annotations on the host
plane.  All times are nanoseconds on the trace's one clock, clipped to
the window ``[t0, t1)``, which is the ``bench.window`` span.
"""
from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MIN_GAP_NS = 1000             # shorter gaps between ops are not idle time
                              # worth naming (they still count in busy)
WINDOW_SPAN = "bench.window"


def load(path):
    """A trace from ``*.xplane.pb`` (or its gzip)."""
    from jax.profiler import ProfileData
    path = str(path)
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, t0, t1):
    return max(s, t0), min(e, t1)


CONTAINER = re.compile(r"\s(while|conditional|call)\(")


def op_name(name: str) -> str:
    """``%fusion.245 = bf16[8,512,12288]{2,1,0:..} fusion(..)`` ->
    ``fusion.245 bf16[8,512,12288]``: the instruction and its shape."""
    lhs, _, rhs = name.partition(" = ")
    shape = re.search(r"[a-z]+\d*\[[\d,]*\]", rhs)
    return f"{lhs.lstrip('%')} {shape.group(0) if shape else ''}".strip()


def module_name(name: str) -> str:
    """``jit_prefill(123)`` -> ``jit_prefill``."""
    return name.split("(")[0]


def reduce(pd, window=None) -> dict:
    """Reduce one trace.  ``window`` is ``(t0, t1)`` in ns; by default
    the ``bench.window`` host span.  Returns::

        window_ns, chips,
        busy_ns        {chip: ns with >= 1 op running},
        ops            {op: device ns summed over chips} (loops and
                       calls, which hold other ops, left out),
        op_events      [(chip, name, start, end)],
        modules        [(chip, module, start, end)],
        spans          [(name, start, end)]   host bench.* spans,
        gaps           [(ns, host span name)] idle gaps on chip 0 of
                       MIN_GAP_NS or more
    """
    planes = list(pd.planes)
    spans = []
    for p in planes:
        if p.name == HOST_PLANE:
            for line in p.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    if window is None:
        wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"trace holds no {WINDOW_SPAN} span")
        window = wins[0]
    t0, t1 = window
    busy, ops, op_events, modules = {}, defaultdict(float), [], []
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        if not m:
            continue
        chip = int(m.group(1))
        ivs = []
        for line in p.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                             t0, t1)
                if e <= s:
                    continue
                if line.name == OPS_LINE:
                    ivs.append((s, e))
                    op_events.append((chip, ev.name, s, e))
                    if not CONTAINER.search(ev.name):
                        ops[op_name(ev.name)] += e - s
                else:
                    modules.append((chip, module_name(ev.name), s, e))
        busy[chip] = sum(e - s for s, e in _union(ivs))
    chips = sorted(busy)
    gaps = []
    if chips:
        first = [(s, e) for c, _, s, e in op_events if c == chips[0]]
        cur = t0
        for s, e in _union(first) + [[t1, t1]]:
            if s - cur >= MIN_GAP_NS:
                gaps.append((s - cur, _host_during(spans, cur, s)))
            cur = max(cur, e)
    return {"window_ns": t1 - t0, "t0": t0, "t1": t1, "chips": chips,
            "busy_ns": busy, "ops": dict(ops), "op_events": op_events,
            "modules": modules, "spans": spans, "gaps": gaps}


def _host_during(spans, s, e) -> str:
    """The ``bench.*`` span (other than the window) that covers most of
    ``[s, e)``; ``host:other`` where none does."""
    best, best_cov = "host:other", 0
    for name, a, b in spans:
        cov = min(b, e) - max(a, s)
        if name != WINDOW_SPAN and cov > best_cov:
            best, best_cov = name, cov
    return best


def busy_share(red) -> float:
    """Busy time over the window, averaged over the chips."""
    if not red["chips"]:
        return 0.0
    return (sum(red["busy_ns"].values()) / len(red["chips"])
            / red["window_ns"])


def module_times(red, name: str) -> list:
    """Device durations (ns) of each execution of module ``name``,
    averaged over the chips that ran it."""
    per = defaultdict(list)
    for chip, mod, s, e in red["modules"]:
        if mod == name:
            per[chip].append(e - s)
    if not per:
        return []
    n = min(len(v) for v in per.values())
    return [sum(v[i] for v in per.values()) / len(per) for i in range(n)]


def op_time(red, pattern: str) -> float:
    """Device ns of the ops whose name matches ``pattern``, averaged
    over the chips."""
    rx = re.compile(pattern)
    tot = sum(e - s for _, n, s, e in red["op_events"] if rx.search(n))
    return tot / max(len(red["chips"]), 1)


def breakdown(red, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps by what the host was doing, in seconds (per chip)."""
    nchip = max(len(red["chips"]), 1)
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"], key=lambda g: -g[0])[:top]
    return {"device_ops": [[n, v / nchip / 1e9] for n, v in ops],
            "idle_gaps": [[n, v / 1e9] for v, n in gaps]}
