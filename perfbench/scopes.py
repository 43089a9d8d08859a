"""Device time by what the program says it was doing: seconds per
``<module>/<operator>/<stage>`` from a profiler trace.

    python3 perfbench/scopes.py <file.xplane.pb>

The program compiles every device program under the name of its kernel
family (the device line ``XLA Modules`` shows ``jit_<family>(<fingerprint>)``)
and divides it with ``jax.named_scope``: the plan operator outermost, then a
kernel stage. XLA keeps that path as each op's ``op_name`` metadata, and the
profiler stores it as the ``tf_op`` stat of an op's EVENT METADATA:
``jit(agg_update_sort)/TpuHashAggregateExec/lexsort/while/body/sort:``.
``jax.profiler.ProfileData`` shows an event's own stats only, so
:func:`load` reads the ``.xplane.pb`` itself — the few fields of the XSpace
protobuf it needs, by their wire format, with nothing to install.

A trace is a list of planes as ``perfbench/trace_reduce.py`` has them, with
one more item per event of the ``XLA Ops`` line: ``[name, start_ns,
duration_ns, op_name]``. Not called by ``run.py`` yet: its ``breakdown``
still lists HLO lines (PERF.md section 7, "For the next benchmark issue").
"""

import sys

WINDOW = "perfbench_window"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNSCOPED = "(unscoped)"
#: path components that jax puts between scopes for its own transforms
_STRUCTURAL = frozenset(("while", "body", "cond", "closed_call", "core_call",
                         "checkpoint", "custom_jvp_call", "custom_vjp_call",
                         "pjit", "remat"))


# -- the XSpace protobuf, by its wire format ---------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def _text(buf):
    return bytes(buf).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _event_metadata(buf, stat_names):
    """(name, op_name) of one XEventMetadata: ``name`` = 2, ``stats`` = 5;
    of an XStat ``metadata_id`` = 1, ``str_value`` = 5, ``ref_value`` = 7 (a
    string kept once, as the NAME of a stat metadata)."""
    name, op_name = "", ""
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 5:
            stat = dict(_fields(v))
            if stat_names.get(stat.get(1)) == "tf_op":
                if 5 in stat:
                    op_name = _text(stat[5])
                elif 7 in stat:
                    op_name = stat_names.get(stat[7], "")
    return name, op_name


def _plane(buf):
    name, lines, event_meta, stat_meta = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif f == 5:
            key, value = _map_entry(v)
            stat_meta[key] = value
    stat_names = {}
    for key, value in stat_meta.items():
        for f, v in _fields(value):
            if f == 2:
                stat_names[key] = _text(v)
    names = {key: _event_metadata(value, stat_names)
             for key, value in event_meta.items()}
    out = []
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for f, v in _fields(line):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        with_op = name.startswith(DEVICE_PREFIX) and line_name == OPS_LINE
        rows = []
        for ev in events:
            e = dict(_fields(ev))     # metadata_id, offset_ps, duration_ps
            ev_name, op_name = names.get(e.get(1), ("", ""))
            row = [ev_name, t0_ns + e.get(2, 0) / 1e3, e.get(3, 0) / 1e3]
            if with_op:
                row.append(op_name)
            rows.append(row)
        out.append({"name": line_name, "events": rows})
    return {"name": name, "lines": out}


def load(path):
    """The planes of an ``.xplane.pb`` (``XSpace.planes`` = 1)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v) for f, v in _fields(buf) if f == 1]


# -- from op names to scopes ---------------------------------------------------

def scope_of(op_name):
    """``<operator>/<stage>`` of an op's name, or ``None`` where it lies in
    fewer than two scopes. The first component is the program
    (``jit(<family>)``) and the last the primitive; of those between, the
    ones jax adds for a transform (``jit(_where)``, ``while``, ``body``)
    are no scope of the program's."""
    parts = op_name.rstrip(":").split("/")[1:-1]
    scopes = [p for p in parts if "(" not in p and p not in _STRUCTURAL
              and not p.startswith("branch_")]
    return "/".join(scopes[:2]) if len(scopes) >= 2 else None


def _module_name(event_name):
    """``jit_agg_update_sort(15237067454702470764)`` without the
    fingerprint."""
    return event_name.split("(", 1)[0]


def _window(planes):
    for p in planes:
        if p["name"] == HOST_PLANE:
            for line in p["lines"]:
                for e in line["events"]:
                    if e[0] == WINDOW:
                        return e[1], e[1] + e[2]
    return None


def _device_planes(planes):
    return [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
            and p["name"][len(DEVICE_PREFIX):].isdigit()]


def _events(plane, line_name):
    return [e for line in plane["lines"] if line["name"] == line_name
            for e in line["events"]]


def modules(planes):
    """Seconds per program of the ``XLA Modules`` line inside the window
    (the whole trace where the window's annotation is missing)."""
    t0, t1 = _window(planes) or (float("-inf"), float("inf"))
    totals = {}
    for plane in _device_planes(planes):
        for name, start, dur in _events(plane, MODULES_LINE):
            inside = min(start + dur, t1) - max(start, t0)
            if inside > 0:
                key = _module_name(name)
                totals[key] = totals.get(key, 0.0) + inside / 1e9
    return totals


def _self_times(ops, t0, t1):
    """(start, end, self ns, op_name) of each op's part inside [t0, t1]. A
    ``while`` or ``conditional`` is an event round those of its body: an
    instant belongs to the innermost op running, so that the self times
    add up to the union of the intervals."""
    clipped = sorted(((max(start, t0), min(start + dur, t1), op_name)
                      for _name, start, dur, op_name in ops
                      if min(start + dur, t1) > max(start, t0)),
                     key=lambda e: (e[0], -e[1]))
    out, open_ops = [], []          # open_ops: indexes into out, a stack
    for a, b, op_name in clipped:
        while open_ops and out[open_ops[-1]][1] <= a:
            open_ops.pop()
        if open_ops:
            parent = out[open_ops[-1]]
            parent[2] -= min(b, parent[1]) - a
        out.append([a, b, b - a, op_name])
        open_ops.append(len(out) - 1)
    return out


def by_scope(planes):
    """Seconds per ``<module>/<operator>/<stage>`` of the ``XLA Ops`` inside
    the window, summed over the device planes: each op's SELF time (an
    op that encloses others keeps what they leave), so the seconds add up
    to the device's busy time. An op in fewer than two scopes counts under
    ``<module>/(unscoped)``. The module is the program running at the op's
    midpoint (``XLA Modules``), else the program its name starts with.
    ``None`` where the window's annotation is missing."""
    window = _window(planes)
    if window is None:
        return None
    t0, t1 = window
    totals = {}
    for plane in _device_planes(planes):
        spans = sorted((s, s + d, _module_name(n))
                       for n, s, d in _events(plane, MODULES_LINE))
        for a, b, self_ns, op_name in _self_times(
                _events(plane, OPS_LINE), t0, t1):
            if self_ns <= 0:
                continue
            mid = (a + b) / 2
            module = next((m for lo, hi, m in spans if lo <= mid <= hi), None)
            if module is None:
                head = op_name.split("/", 1)[0]
                module = "jit_" + head[4:-1] if head.startswith("jit(") \
                    else (head or "(no module)")
            key = f"{module}/{scope_of(op_name) or UNSCOPED}"
            totals[key] = totals.get(key, 0.0) + self_ns / 1e9
    return totals


def scoped_share(totals):
    """The share of the seconds of :func:`by_scope` that lie under a named
    ``<module>/<operator>/<stage>``."""
    whole = sum(totals.values())
    named = sum(s for k, s in totals.items() if not k.endswith(UNSCOPED))
    return named / whole if whole else 0.0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    planes = load(argv[0])
    print("XLA Modules inside the window, seconds:")
    for name, s in sorted(modules(planes).items(), key=lambda kv: -kv[1]):
        print(f"  {s:12.6f}  {name}")
    totals = by_scope(planes)
    if totals is None:
        print(f"no {WINDOW!r} annotation in this trace", file=sys.stderr)
        return 1
    print("XLA Ops inside the window by <module>/<operator>/<stage>, "
          "seconds of self time:")
    for name, s in sorted(totals.items(), key=lambda kv: -kv[1]):
        print(f"  {s:12.6f}  {name}")
    print(f"  {sum(totals.values()):12.6f}  total; "
          f"{100 * scoped_share(totals):.2f}% under a named scope")
    return 0


if __name__ == "__main__":
    sys.exit(main())
