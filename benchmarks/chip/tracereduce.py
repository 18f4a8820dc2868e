"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain interval lists; everything after that is arithmetic on intervals,
tested on a small recorded trace (``tests/``):

* busy time: the union of the intervals in which an operation ran on a
  chip, and the idle share 1 - busy / window;
* time by operation name, summed over a chip's events;
* idle gaps, each named by the host span of the benchmark that covered
  most of it (what the host was doing while the chip waited).

Times are in seconds.
"""
from __future__ import annotations

import collections
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPAN_PREFIX = "bench."
# control flow that encloses other operations on the same line
CONTAINER_OPS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """An operation's short name: a TPU trace names an XLA op by its
    whole HLO instruction (``%fusion.12 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path: str, plane_prefix: str = DEVICE_PLANE_PREFIX,
            line_pred=lambda name: name == DEVICE_OP_LINE,
            op_pred=lambda name: True,
            host_plane: str = HOST_PLANE,
            span_prefix: str = HOST_SPAN_PREFIX) -> dict:
    """Device operations per chip and the benchmark's host spans.

    Returns ``{"ops": {plane: [(name, start, end), ...]},
    "spans": [(name, start, end), ...], "stats": {plane: {op: {...}}}}``
    with times in seconds on the trace's clock. Planes are matched by
    prefix, their lines by ``line_pred`` and events by ``op_pred``.
    """
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in prof.planes:
        if plane.name.startswith(plane_prefix):
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if not line_pred(line.name):
                    continue
                for ev in line.events:
                    if op_pred(ev.name):
                        s = ev.start_ns * 1e-9
                        evs.append((op_name(ev.name), s,
                                    s + ev.duration_ns * 1e-9))
        if plane.name == host_plane:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    for evs in ops.values():
        evs.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return {"ops": ops, "spans": spans}


def merge(intervals) -> list:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy(events, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran."""
    return total(merge(clip([(s, e) for _, s, e in events], lo, hi)))


def time_by_name(events, lo: float, hi: float) -> dict:
    """Summed duration of each operation name's events within [lo, hi]."""
    out = collections.Counter()
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[name] += e - s
    return dict(out)


def subtract(a, b) -> list:
    """Parts of disjoint sorted intervals ``a`` not covered by ``b``."""
    b = merge(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def idle_gaps(events, spans, lo: float, hi: float, top: int = 10) -> list:
    """The ``top`` longest gaps in [lo, hi] with no operation running,
    as ``[name, seconds]``: the name is that of the innermost host span
    that covers most of the gap, or ``"no host span"`` (the host was
    between the benchmark's own spans)."""
    busy_iv = merge(clip([(s, e) for _, s, e in events], lo, hi))
    gaps = subtract([(lo, hi)], busy_iv)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:top]:
        covering = [(min(e, ge) - max(s, gs), -(e - s), name)
                    for name, s, e in spans if min(e, ge) > max(s, gs)]
        # most of the gap covered; among equals the innermost span
        out.append([max(covering)[2] if covering else "no host span",
                    ge - gs])
    return out


def summarize(trace: dict, window_span: str, top: int = 10) -> dict:
    """The per-chip reduction of one traced window.

    ``window_span`` names the host span that encloses the measured
    window; busy, op times and gaps are taken within it and averaged
    over the chips (planes) present."""
    win = [sp for sp in trace["spans"] if sp[0] == window_span]
    if not win:
        raise ValueError(f"no host span {window_span!r} in the trace")
    _, lo, hi = win[0]
    planes = sorted(trace["ops"])
    if not planes:
        raise ValueError("no device plane in the trace")
    n = len(planes)
    by_name = collections.Counter()
    busy_s = 0.0
    gaps = []
    for p in planes:
        evs = trace["ops"][p]
        busy_s += busy(evs, lo, hi) / n
        for k, v in time_by_name(evs, lo, hi).items():
            by_name[k] += v / n
        gaps += idle_gaps(evs, [sp for sp in trace["spans"]
                                if sp[0] != window_span], lo, hi, top)
    gaps.sort(key=lambda g: -g[1])
    leaf_ops = collections.Counter({
        k: v for k, v in by_name.items()
        if not k.split(".")[0] in CONTAINER_OPS})
    return {"window_s": hi - lo, "busy_s": busy_s, "chips": n,
            "op_time": dict(by_name),
            "device_ops": [[k, v] for k, v in leaf_ops.most_common(top)],
            "idle_gaps": gaps[:top]}
