"""Reduction of a profiler trace to device busy time, kernel time,
exposed collective time and labelled idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``, into a plain structure that the reductions
take (and that a test can build by hand):

    {"devices": {plane name: [[op name, start_ns, duration_ns], ...]},
     "spans": [[span name, start_ns, duration_ns], ...]}

``devices`` holds the ops of each device plane's ``XLA Ops`` line;
``spans`` the host annotations whose names start with ``bench.`` (the
harness's own ``TraceAnnotation``s). Both run on the trace's one clock.
All reductions are clipped to a window ``(lo, hi)`` in that clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"(\.\d+)+$")
CONTAINERS = ("while", "conditional", "call")


def load(logdir):
    """The trace under ``logdir`` (the newest ``.xplane.pb`` in it)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, float(e.start_ns),
                              float(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def base_name(op):
    """``%fusion.12 = f32[8]{0} fusion(...)`` or ``fusion.12`` ->
    ``fusion``: the HLO instruction's name (a TPU trace names each op by
    its whole instruction text) without its numeric suffixes."""
    return _SUFFIX.sub("", op.split(" = ", 1)[0].strip().lstrip("%"))


def span(tr, name):
    """(start, end) of the first host span called ``name``."""
    for n, s, d in tr["spans"]:
        if n == name:
            return s, s + d
    raise KeyError(f"no host span {name!r} in the trace")


def is_op(base):
    """Whether an op of this base name does work of its own: control flow
    (``while``, ``conditional``, ``call``) spans the ops it runs, idle
    time between them included."""
    return base not in CONTAINERS


def intervals(events, lo, hi, keep=is_op):
    """Sorted, merged ``[start, end)`` intervals of the events whose base
    name ``keep`` accepts, clipped to ``[lo, hi)``."""
    iv = sorted((max(s, lo), min(s + d, hi)) for n, s, d in events
                if keep(base_name(n)) and s < hi and s + d > lo)
    merged = []
    for a, b in iv:
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def length(iv):
    return sum(b - a for a, b in iv)


def subtract(a, b):
    """Length of the union ``a`` less its overlap with the union ``b``
    (both merged and sorted)."""
    out, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def busy_ns(tr, lo, hi):
    """{device: ns in which at least one op ran}; control flow does not
    count."""
    return {d: length(intervals(ev, lo, hi))
            for d, ev in tr["devices"].items()}


def op_time_ns(tr, lo, hi, match):
    """{device: (summed duration, count) of the ops whose base name
    ``match`` accepts}; durations summed, not merged."""
    out = {}
    for d, ev in tr["devices"].items():
        tot = cnt = 0
        for n, s, dur in ev:
            if lo <= s < hi and match(base_name(n)):
                tot += dur
                cnt += 1
        out[d] = (tot, cnt)
    return out


def exposed_ns(tr, lo, hi, is_collective):
    """{device: ns in which a collective op ran and no other op did}."""
    out = {}
    for d, ev in tr["devices"].items():
        coll = intervals(ev, lo, hi, is_collective)
        comp = intervals(ev, lo, hi,
                         lambda b: is_op(b) and not is_collective(b))
        out[d] = subtract(coll, comp)
    return out


def top_ops(tr, lo, hi, k=10):
    """The ``k`` op base names with most device time, in seconds averaged
    over the devices; control flow left out."""
    tot = {}
    for ev in tr["devices"].values():
        for n, s, d in ev:
            b = base_name(n)
            if lo <= s < hi and is_op(b):
                tot[b] = tot.get(b, 0.0) + d
    nd = max(len(tr["devices"]), 1)
    return [[n, t / nd / 1e9]
            for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr, lo, hi, k=10):
    """The ``k`` longest gaps between ops on the first device, in seconds,
    each named by the innermost ``bench.`` host span open at its middle
    (``host.none`` when none is)."""
    if not tr["devices"]:
        return []
    first = sorted(tr["devices"])[0]
    busy = intervals(tr["devices"][first], lo, hi)
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        mid = (a + b) / 2
        open_ = [(s, n) for n, s, d in tr["spans"]
                 if s <= mid < s + d and n != "bench.window"]
        label = max(open_)[1] if open_ else "host.none"
        out.append([label, (b - a) / 1e9])
    return out
