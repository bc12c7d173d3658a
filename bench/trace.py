"""Reduce a profiler trace (``.xplane.pb``) to device time: busy union, idle
share, time of named operations, collective time that no compute hides,
and idle gaps named by what the host was doing.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. On the v5e their name is the HLO instruction's
text (``%sign_reduce.11 = f32[...] custom-call(...), custom_call_target=
"tpu_custom_call", ...``) and they nest: a ``while`` op spans the loop whose
body ops run inside it. An op is kept under its instruction name
(``%sign_reduce.11``) with the whole text as its metadata; busy time is the
union of all ops, and time by name, exposed collective time and the top ops
count leaf ops alone (those that hold no other op), so that nothing counts
twice. Host spans are the events of the host plane's threads; the
benchmark's own are named ``bench.<phase>``. All times are in seconds on the
trace's clock, and every reduction is clipped to a window, the span
``bench.window`` unless another is given.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"


class Op(NamedTuple):
    name: str           # the HLO instruction name, e.g. %sign_reduce.11
    start: float
    end: float
    meta: str           # the event's whole name and its metadata values
    leaf: bool = True   # holds no other op


class Trace(NamedTuple):
    #: device plane name -> its operations, sorted by start
    ops: dict
    #: (name, start, end) of host spans, sorted by start
    host: list


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops[plane.name] = nest(
                    Op(e.name.split(" = ")[0], e.start_ns * 1e-9,
                       e.end_ns * 1e-9, " ".join(
                           [e.name] + [str(v) for _, v in e.stats]))
                    for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events)
    host.sort(key=lambda s: s[1])
    return Trace(ops, host)


def nest(ops) -> list:
    """Ops sorted by start (outer before inner), each marked a leaf unless
    the op after it lies inside it."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    return [o._replace(leaf=i + 1 == len(ops) or ops[i + 1].start >= o.end
                       or ops[i + 1].end > o.end)
            for i, o in enumerate(ops)]


def window(tr: Trace, name: str = WINDOW) -> tuple:
    spans = [(s, e) for n, s, e in tr.host if n == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def union(intervals, lo: float, hi: float) -> list:
    """Merged (start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: list, b: list) -> list:
    """Parts of merged intervals ``a`` that merged intervals ``b`` miss."""
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


def busy(tr: Trace, win: tuple) -> dict:
    """Device plane -> seconds in which an operation ran, within win."""
    return {d: length(union(((o.start, o.end) for o in ops), *win))
            for d, ops in tr.ops.items()}


def op_seconds(tr: Trace, match, win: tuple) -> dict:
    """Device plane -> summed duration of the leaf operations ``match(op)``
    selects, within win."""
    return {d: float(sum(min(o.end, win[1]) - max(o.start, win[0])
                         for o in ops if o.leaf and match(o)
                         and o.end > win[0] and o.start < win[1]))
            for d, ops in tr.ops.items()}


def exposed(tr: Trace, is_collective, win: tuple) -> dict:
    """Device plane -> seconds in which a collective ran and no other leaf
    operation did, within win."""
    out = {}
    for d, ops in tr.ops.items():
        coll = union(((o.start, o.end) for o in ops
                      if o.leaf and is_collective(o)), *win)
        rest = union(((o.start, o.end) for o in ops
                      if o.leaf and not is_collective(o)), *win)
        out[d] = length(subtract(coll, rest))
    return out


def top_ops(tr: Trace, win: tuple, n: int = 10) -> list:
    """[[name, seconds]] of the n leaf operations that took most device
    time, averaged over the device planes."""
    total = {}
    for ops in tr.ops.values():
        for o in ops:
            if not o.leaf:
                continue
            t = min(o.end, win[1]) - max(o.start, win[0])
            if t > 0:
                total[o.name] = total.get(o.name, 0.0) + t
    k = max(len(tr.ops), 1)
    return [[name, t / k] for name, t in
            sorted(total.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(tr: Trace, win: tuple, n: int = 10,
              prefix: str = "bench.") -> list:
    """[[host span, seconds]] of the n longest idle gaps of the first
    device plane, each named by the host span (``prefix``...) that covers
    most of it, or ``other``."""
    if not tr.ops:
        return []
    first = sorted(tr.ops)[0]
    busy_iv = union(((o.start, o.end) for o in tr.ops[first]), *win)
    gaps = subtract([win], busy_iv)
    spans = [(nm, s, e) for nm, s, e in tr.host
             if nm.startswith(prefix) and nm != WINDOW]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        cover = {}
        for nm, hs, he in spans:
            c = min(e, he) - max(s, hs)
            if c > 0:
                cover[nm] = cover.get(nm, 0.0) + c
        name = max(cover, key=cover.get) if cover else "other"
        out.append([name, e - s])
    return out


def mean(values) -> float:
    values = list(values)
    return float(np.mean(values)) if values else 0.0
