"""From a profiler trace to numbers: device busy time, per-operation time,
device time by ``jax.named_scope`` path, collective time that no compute
hides, and the longest idle gaps with what the host's threads were doing in
them.

``load`` turns an ``.xplane.pb`` into plain data: ``[{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}], "origins":
{event name: op_name}}]``.  Planes, lines and events are read with
``jax.profiler.ProfileData``.  Where a device operation came from is in the
trace too (a v5e trace keeps the HLO ``op_name`` of every operation, e.g.
``jit(train_step)/jit(main)/transpose(jvp(TransformerLM))/block_0/Dense_0/
dot_general``, as a stat of the event's *metadata*), but ``ProfileData`` does
not show an event metadata's stats, so ``origins`` reads those few fields
from the file's protobuf wire format itself, with no other dependency.
``reduce`` works on the plain data alone, so it is tested on hand-made and
recorded traces without a chip.
"""

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINES = ("XLA Ops",)
FALLBACK_LINES = ("XLA Modules",)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
SPAN_PREFIX = "perfbench/"
#: host spans that label an idle gap: the benchmark's own and the program's
SPAN_PREFIXES = (SPAN_PREFIX, "tfos/")
#: the stat of a device operation's metadata that holds its HLO ``op_name``
ORIGIN_STATS = ("tf_op", "op_name")
NO_SCOPE = "(none)"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under " + trace_dir)
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData

    with open(path, "rb") as f:     # hundreds of MiB for a host-bound cell:
        raw = f.read()              # read once, for both readers
    data = ProfileData.from_serialized_xspace(raw)
    where = origins(raw)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines,
                       "origins": where.get(plane.name, {})})
    return planes


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of the protobuf message in ``buf[lo:hi]``: an
    int for a varint, a ``(lo, hi)`` slice for a length-delimited field,
    None for the fixed-width ones (not read here)."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError("not a protobuf message (wire type %d)" % wire)
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def origins(raw):
    """{plane name: {event name: op_name}} of an ``.xplane.pb``'s bytes, for
    the device planes: XSpace.planes(1) -> XPlane{name(2), event_metadata(4):
    map -> XEventMetadata{name(2), stats(5): XStat{metadata_id(1),
    str_value(5), ref_value(7)}}, stat_metadata(5): map ->
    XStatMetadata{id(1), name(2)}}.  Lines and events are skipped over, not
    decoded.  {} for a plane whose operations carry no such stat."""
    buf = memoryview(raw)
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, stat_names, events = "", {}, []
        for field, value in _fields(buf, *plane):
            if field == 2:
                name = _text(buf, value)
            elif field in (4, 5):
                entry = dict(_fields(buf, *value)).get(2)
                if entry is None:
                    continue
                if field == 4:
                    events.append(entry)
                else:
                    meta = dict(_fields(buf, *entry))
                    if 1 in meta and 2 in meta:
                        stat_names[meta[1]] = _text(buf, meta[2])
        if not name.startswith("/device:"):
            continue
        wanted = {i for i, n in stat_names.items() if n in ORIGIN_STATS}
        found = {}
        for entry in events:
            event_name, origin = None, None
            for field, value in _fields(buf, *entry):
                if field == 2:
                    event_name = _text(buf, value)
                elif field == 5:
                    stat = dict(_fields(buf, *value))
                    if stat.get(1) in wanted:
                        if 5 in stat:
                            origin = _text(buf, stat[5])
                        elif 7 in stat:     # a reference to a stat's name
                            origin = stat_names.get(stat[7])
            if event_name and origin:
                found[event_name] = origin
        out[name] = found
    return out


_WRAPPED = re.compile(r"\b[A-Za-z_]\w*\(([^()]*)\)")
_JITTED = re.compile(r"\bp?jit\([^()]*\)")


def scope_of(op_name):
    """The ``jax.named_scope`` path of an HLO ``op_name``:
    ``jit(step)/jit(main)/transpose(jvp(Model/block_0))/Dense_0/dot_general``
    -> ``Model/block_0/Dense_0``.  The names of jitted functions and the
    transformations' wrappers (``jvp(...)``, ``transpose(...)``, ...) go, and
    so does the last part, the primitive; "" where nothing is left."""
    text = _JITTED.sub("", op_name or "")
    before = None
    while before != text:
        before, text = text, _WRAPPED.sub(r"\1", text)
    parts = [p for p in text.split("/") if p]
    return "/".join(parts[:-1])


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name, width=96, scope=""):
    """The trace prints a device operation as its whole HLO line; keep the
    operation's name, the scope it came from where the trace says
    (``fusion.7 @Model/block_0/Dense_0 = ...``), its kind and the head of
    its result shapes."""
    head, _, rest = name.partition(" = ")
    if scope and " @" not in head:
        head += " @" + scope
    if not rest:
        return head[:width]
    return (head.lstrip("%") + " = " + _LAYOUT.sub("", rest))[:width]


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def is_collective(name):
    base = name.lstrip("%")
    return base.startswith(COLLECTIVES)


def _device_ops(plane):
    for wanted in (OPS_LINES, FALLBACK_LINES):
        events = [ev for line in plane["lines"] if line["name"] in wanted
                  for ev in line["events"]]
        if events:
            return events
    return []


def host_spans(planes):
    """The benchmark's own spans (``perfbench/...`` TraceAnnotations) and
    the program's (``tfos/...``), from every host thread: [(name, start_ns,
    end_ns, thread)], a thread being one line of a host plane."""
    spans = []
    for p, plane in enumerate(planes):
        if plane["name"].startswith("/device:"):
            continue
        for t, line in enumerate(plane["lines"]):
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIXES):
                    spans.append((name, start, start + dur, (p, t)))
    return spans


def _label(gap, spans):
    """What the host's threads were doing in the gap: on each thread the
    innermost (shortest) span, the benchmark's or the program's, that covers
    at least half of the gap; the threads that have one, the one that covers
    most first, three at most, joined by " | ".  Failing that, the one span
    that covers most of it."""
    length = gap[1] - gap[0]
    half, most = {}, None
    for name, s, e, thread in spans:
        if name == SPAN_PREFIX + "window":
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        if 2 * overlap >= length and (thread not in half
                                      or e - s < half[thread][0]):
            half[thread] = (e - s, -overlap, name)
        if most is None or overlap > most[0]:
            most = (overlap, name)
    if half:
        names = []
        for _, _, name in sorted(half.values(), key=lambda h: (h[1], h[0])):
            if name not in names:
                names.append(name)
        return " | ".join(names[:3])
    return most[1] if most else "no span"


def _scope_paths(scope, depth):
    """A scope and its ancestors, to ``depth`` parts: ``a/b/c`` -> ``a``,
    ``a/b``, ``a/b/c``."""
    parts = scope.split("/")[:depth]
    return ["/".join(parts[:i + 1]) for i in range(len(parts))]


def reduce(planes, top_ops=10, top_gaps=5, scope_depth=4):
    """Numbers of one traced window.

    The window is the ``perfbench/window`` span where the trace has one, else
    from the first to the last device operation.  Busy time, idle gaps and
    exposed collective time are taken per device and averaged over the
    devices; operation times are summed over the devices and divided by
    their number.

    ``by_scope`` (only where the trace says where its operations came from,
    ``origins``): device-busy seconds in the window under each
    ``jax.named_scope`` path, to ``scope_depth`` parts: the union of the
    intervals of the operations whose scope is that path or below it,
    averaged over the devices; an operation with no scope counts under
    ``"(none)"``.  A loop's own event lies over its body's, so scopes can sum
    to more than the busy time; each alone never exceeds it."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
               and _device_ops(p)]
    if not devices:
        return None
    spans = host_spans(planes)
    per_dev = [_device_ops(p) for p in devices]
    window = [(s, e) for name, s, e, _ in spans
              if name == SPAN_PREFIX + "window"]
    if window:
        lo, hi = window[0]
    else:
        lo = min(s for evs in per_dev for _, s, _ in evs)
        hi = max(s + d for evs in per_dev for _, s, d in evs)
    busy = exposed = collective = 0
    op_time, gaps, scope_time = {}, [], {}
    for plane, evs in zip(devices, per_dev):
        all_iv = _clip(_union([(s, s + d) for _, s, d in evs]), lo, hi)
        coll_iv = _clip(_union([(s, s + d) for n, s, d in evs
                                if is_collective(n)]), lo, hi)
        comp_iv = _clip(_union([(s, s + d) for n, s, d in evs
                                if not is_collective(n)]), lo, hi)
        busy += _length(all_iv)
        collective += _length(coll_iv)
        exposed += _length(_subtract(coll_iv, comp_iv))
        where = plane.get("origins") or {}
        named, under = {}, {}   # a step's operations come again every step
        for n, s, d in evs:
            inside = min(s + d, hi) - max(s, lo)
            if inside <= 0:
                continue
            if n not in named:
                scope = scope_of(where.get(n))
                named[n] = (short_name(n, scope=scope), _scope_paths(
                    scope, scope_depth) if scope else [NO_SCOPE])
            short, paths = named[n]
            op_time[short] = op_time.get(short, 0) + inside
            if where:
                for path in paths:
                    under.setdefault(path, []).append((s, s + d))
        for path, intervals in under.items():
            scope_time[path] = scope_time.get(path, 0) + _length(
                _clip(_union(intervals), lo, hi))
        gaps += _subtract([[lo, hi]], all_iv)
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "collective_s": collective / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "device_ops": [[name, t / n / 1e9] for name, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top_ops]],
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) / 1e9]
                      for g in gaps[:top_gaps]],
        "spans": sorted({name for name, _, _, _ in spans}),
    }
    if scope_time:
        out["by_scope"] = {path: t / n / 1e9
                           for path, t in sorted(scope_time.items())}
    return out
