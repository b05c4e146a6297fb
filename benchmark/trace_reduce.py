"""From a profiler trace to numbers: device busy time, per-operation time,
collective time that no compute hides, and the longest idle gaps with what
the host was doing in them.

``load`` turns an ``.xplane.pb`` (read with ``jax.profiler.ProfileData``, no
other dependency) into plain data: ``[{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]``.  ``reduce`` works on that plain
data alone, so it is tested on hand-made and recorded traces without a chip.
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


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under " + trace_dir)
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name, width=96):
    """The trace prints a device operation as its whole HLO line; keep the
    operation's name, its kind and the head of its result shapes."""
    if " = " not in name:
        return name[:width]
    head, rest = name.split(" = ", 1)
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
    """The benchmark's own spans (``perfbench/...`` TraceAnnotations), from
    every host thread: [(name, start_ns, end_ns)]."""
    spans = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, start, start + dur))
    return spans


def _label(gap, spans):
    """The innermost (shortest) benchmark span that covers at least half of
    the gap; failing that, the one that covers most of it."""
    length = gap[1] - gap[0]
    half, most = None, None
    for name, s, e in spans:
        if name == SPAN_PREFIX + "window":
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        if 2 * overlap >= length and (half is None or e - s < half[0]):
            half = (e - s, name)
        if most is None or overlap > most[0]:
            most = (overlap, name)
    if half:
        return half[1]
    return most[1] if most else "no benchmark span"


def reduce(planes, top_ops=10, top_gaps=5):
    """Numbers of one traced window.

    The window is the ``perfbench/window`` span where the trace has one, else
    from the first to the last device operation.  Busy time, idle gaps and
    exposed collective time are taken per device and averaged over the
    devices; operation times are summed over the devices and divided by
    their number."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
               and _device_ops(p)]
    if not devices:
        return None
    spans = host_spans(planes)
    per_dev = [_device_ops(p) for p in devices]
    window = [(s, e) for name, s, e in spans if name == SPAN_PREFIX + "window"]
    if window:
        lo, hi = window[0]
    else:
        lo = min(s for evs in per_dev for _, s, _ in evs)
        hi = max(s + d for evs in per_dev for _, s, d in evs)
    busy = exposed = collective = 0
    op_time, gaps = {}, []
    for evs in per_dev:
        all_iv = _clip(_union([(s, s + d) for _, s, d in evs]), lo, hi)
        coll_iv = _clip(_union([(s, s + d) for n, s, d in evs
                                if is_collective(n)]), lo, hi)
        comp_iv = _clip(_union([(s, s + d) for n, s, d in evs
                                if not is_collective(n)]), lo, hi)
        busy += _length(all_iv)
        collective += _length(coll_iv)
        exposed += _length(_subtract(coll_iv, comp_iv))
        for n, s, d in evs:
            d = min(s + d, hi) - max(s, lo)
            if d > 0:
                n = short_name(n)
                op_time[n] = op_time.get(n, 0) + d
        gaps += _subtract([[lo, hi]], all_iv)
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "collective_s": collective / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "device_ops": [[name, t / n / 1e9] for name, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top_ops]],
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) / 1e9]
                      for g in gaps[:top_gaps]],
        "spans": sorted({name for name, _, _ in spans}),
    }
