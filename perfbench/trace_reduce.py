"""From a profiler trace to device busy time, idle share, the heaviest
device operations and the longest idle gaps with what the host was doing.

A trace is a list of planes, each ``{"name": str, "lines": [{"name": str,
"events": [[name, start_ns, duration_ns], ...]}]}``: what
:func:`load_xplane` makes of an ``.xplane.pb`` and what the tests keep as
JSON. Device planes are named ``/device:TPU:<n>``; the host's threads are
the lines of ``/host:CPU``. The harness wraps the traced queries in a
``TraceAnnotation`` named :data:`WINDOW`, which fixes the window on the
trace's own clock; an event that crosses its edge counts for the part
inside."""

import numpy as np

WINDOW = "perfbench_window"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
#: the device line that holds one event per executed XLA op
OPS_LINE = "XLA Ops"
TOP = 10
NAME_CHARS = 120
#: only the longest gaps are attributed to a host span; the rest are summed
MAX_ATTRIBUTED_GAPS = 400


def load_xplane(path):
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _host_events(planes):
    return [e for p in planes if p["name"] == HOST_PLANE
            for line in p["lines"] for e in line["events"]]


def _device_op_events(plane):
    return [e for line in plane["lines"] if line["name"] == OPS_LINE
            for e in line["events"]]


def _clip(events, t0, t1):
    """(name, start, end) of each event's part inside [t0, t1]."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    """Merged, sorted [start, end] pairs of possibly overlapping ones."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _gaps(busy, t0, t1):
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _name_gaps(gaps, host):
    """Seconds of idle gap per host span: a gap goes to the span that
    overlaps most of it, the shortest such span where several cover it
    whole (the innermost one open then)."""
    if not gaps:
        return {}
    totals = {}
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    rest = sum(b - a for a, b in gaps[MAX_ATTRIBUTED_GAPS:])
    if rest:
        totals["(shorter gaps, not attributed)"] = rest
    host = [e for e in host if e[0] != WINDOW and e[2] > 0]
    starts = np.array([e[1] for e in host])
    ends = np.array([e[1] + e[2] for e in host])
    for a, b in gaps[:MAX_ATTRIBUTED_GAPS]:
        name = "(no host span)"
        if len(host):
            overlap = np.minimum(ends, b) - np.maximum(starts, a)
            best = overlap.max()
            if best > 0:
                tied = np.flatnonzero(overlap >= best * (1 - 1e-9))
                name = host[tied[np.argmin((ends - starts)[tied])]][0]
        totals[name] = totals.get(name, 0.0) + (b - a)
    return totals


def _top(totals):
    """The ten largest, names cut to what a result line can carry (an XLA
    op's name is its whole HLO line)."""
    return [[name[:NAME_CHARS], ns / 1e9] for name, ns in
            sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce_trace(planes):
    """``None`` where the window's annotation is missing or no device plane
    holds an event of its ops line inside the window;
    else ``window_s``, ``busy_s`` (mean over the device planes of the union
    of their op intervals), ``devices``, ``device_ops`` and ``idle_gaps``
    (each at most ten ``[name, seconds]``, largest first; gaps are those of
    the first device)."""
    host = _host_events(planes)
    devices = sorted((p for p in planes
                      if p["name"].startswith(DEVICE_PREFIX)
                      and p["name"][len(DEVICE_PREFIX):].isdigit()),
                     key=lambda p: int(p["name"][len(DEVICE_PREFIX):]))
    window = [e for e in host if e[0] == WINDOW]
    if not window:
        return None
    t0, t1 = window[0][1], window[0][1] + window[0][2]
    busy_ns, op_totals, first_gaps = [], {}, None
    for plane in devices:
        ops = _clip(_device_op_events(plane), t0, t1)
        busy = _union([(a, b) for _, a, b in ops])
        busy_ns.append(sum(b - a for a, b in busy))
        for name, a, b in ops:
            op_totals[name] = op_totals.get(name, 0.0) + (b - a)
        if first_gaps is None:
            first_gaps = _gaps(busy, t0, t1)
    if not busy_ns or not sum(busy_ns):
        return None
    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "devices": len(devices),
            "device_ops": _top(op_totals),
            "idle_gaps": _top(_name_gaps(first_gaps, host))}
