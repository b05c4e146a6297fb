#!/usr/bin/env python
"""Merge a profile capture into ONE Perfetto timeline.

Input: a ``profiles/<capture_id>/`` directory produced by the observatory's
``GET /profile`` trigger (see ``tensorflowonspark_tpu/profiling.py``) —
per-node ``node-<executor>/.../*.xplane.pb`` device traces plus the
``capture.json`` manifest — and optionally the telemetry dir holding the
per-process ``trace-<host>-<pid>.json`` host traces.

Output: one Chrome-trace JSON loadable in Perfetto / chrome://tracing with
the device planes and the host spans on the same wall-clock-µs timeline
(both sides already share the convention: XPlane lines stamp nanoseconds
since the UNIX epoch, telemetry stamps ``time.time() * 1e6`` — see
``telemetry.wall_time_us``).

The ``.xplane.pb`` decoder is a minimal pure-Python protobuf wire-format
reader (varint / length-delimited), dependency-free by design: this repo
must not require a protobuf install to explain its own captures.  Field
numbers follow tensorflow/tsl ``xplane.proto`` (stable since 2020):

    XSpace         { repeated XPlane planes = 1; }
    XPlane         { int64 id = 1; string name = 2; repeated XLine lines = 3;
                     map<int64, XEventMetadata> event_metadata = 4; }
    XLine          { int64 id = 1; string name = 2; int64 timestamp_ns = 3;
                     repeated XEvent events = 4; string display_name = 11; }
    XEvent         { int64 metadata_id = 1; int64 offset_ps = 2;
                     int64 duration_ps = 3; }
    XEventMetadata { int64 id = 1; string name = 2; string display_name = 4; }

Usage:
    python scripts/analyze_profile.py profiles/<capture_id> \
        [--telemetry-dir DIR] [--out merged_timeline.json]
"""

import argparse
import glob
import json
import os
import sys

# -- protobuf wire-format primitives ---------------------------------------


def _read_varint(buf, pos):
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise ValueError("varint too long (corrupt protobuf)")


def parse_fields(buf):
    """Decode one message's wire fields: ``{field_num: [value, ...]}``.
    Varints decode to ints, length-delimited fields to ``bytes`` (the
    caller knows which are strings vs sub-messages); fixed32/64 skip."""
    fields = {}
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _read_varint(buf, pos)
        field_num, wire_type = tag >> 3, tag & 0x7
        if wire_type == 0:          # varint
            value, pos = _read_varint(buf, pos)
        elif wire_type == 2:        # length-delimited
            length, pos = _read_varint(buf, pos)
            value = bytes(buf[pos:pos + length])
            pos += length
        elif wire_type == 1:        # fixed64
            value, pos = None, pos + 8
        elif wire_type == 5:        # fixed32
            value, pos = None, pos + 4
        else:
            raise ValueError("unsupported wire type %d" % wire_type)
        fields.setdefault(field_num, []).append(value)
    return fields


def _first_int(fields, num, default=0):
    for v in fields.get(num, []):
        if isinstance(v, int):
            return v
    return default


def _first_str(fields, num, default=""):
    for v in fields.get(num, []):
        if isinstance(v, bytes):
            return v.decode("utf-8", "replace")
    return default


# -- xplane -> Chrome events -------------------------------------------------


def decode_xplane(data, pid, process_label):
    """One serialized XSpace -> a list of Chrome trace events under ``pid``.
    Event names resolve through the plane's event_metadata map; timestamps
    land in wall-clock µs (line timestamp_ns/1e3 + event offset_ps/1e6)."""
    events = [{"ph": "M", "name": "process_name", "pid": pid, "ts": 0,
               "args": {"name": process_label}}]
    space = parse_fields(data)
    for plane_buf in space.get(1, []):
        plane = parse_fields(plane_buf)
        plane_name = _first_str(plane, 2)
        metadata = {}
        for entry_buf in plane.get(4, []):  # map<int64, XEventMetadata>
            entry = parse_fields(entry_buf)
            key = _first_int(entry, 1)
            meta_bufs = [v for v in entry.get(2, [])
                         if isinstance(v, bytes)]
            if meta_bufs:
                meta = parse_fields(meta_bufs[0])
                metadata[key] = (_first_str(meta, 4)
                                 or _first_str(meta, 2)
                                 or str(key))
        for line_buf in plane.get(3, []):
            line = parse_fields(line_buf)
            line_ns = _first_int(line, 3)
            tid = _first_int(line, 1)
            line_name = _first_str(line, 11) or _first_str(line, 2)
            if line_name:
                events.append({"ph": "M", "name": "thread_name", "pid": pid,
                               "tid": tid, "ts": 0,
                               "args": {"name": "%s/%s" % (plane_name,
                                                           line_name)}})
            for event_buf in line.get(4, []):
                ev = parse_fields(event_buf)
                dur_ps = _first_int(ev, 3)
                events.append({
                    "ph": "X",
                    "name": metadata.get(_first_int(ev, 1),
                                         str(_first_int(ev, 1))),
                    "cat": "device",
                    "pid": pid,
                    "tid": tid,
                    "ts": line_ns / 1e3 + _first_int(ev, 2) / 1e6,
                    "dur": dur_ps / 1e6,
                })
    return events


# -- merge + report ----------------------------------------------------------

#: synthetic pid base for device planes: far above real host pids, so the
#: merged file never aliases a device track onto a host process track
DEVICE_PID_BASE = 1 << 22


def merge_capture(capture_dir, telemetry_dir=None):
    """Returns (merged_payload, manifest, notes): the Chrome-trace dict,
    the parsed capture.json (or {}), and human-readable merge notes."""
    notes = []
    merged = []
    manifest = {}
    manifest_path = os.path.join(capture_dir, "capture.json")
    if os.path.exists(manifest_path):
        with open(manifest_path) as f:
            manifest = json.load(f)
    else:
        notes.append("no capture.json manifest in %s" % capture_dir)

    xplanes = sorted(glob.glob(os.path.join(capture_dir, "node-*", "**",
                                            "*.xplane.pb"), recursive=True))
    for i, path in enumerate(xplanes):
        node_label = os.path.relpath(path, capture_dir).split(os.sep)[0]
        label = "device:%s:%s" % (node_label,
                                  os.path.basename(path)
                                  .replace(".xplane.pb", ""))
        try:
            with open(path, "rb") as f:
                events = decode_xplane(f.read(), DEVICE_PID_BASE + i, label)
            merged.extend(events)
            notes.append("%s: %d device events" % (path, len(events)))
        except Exception as e:
            notes.append("%s: decode failed (%s)" % (path, e))

    host_traces = []
    if telemetry_dir:
        host_traces = sorted(glob.glob(os.path.join(telemetry_dir,
                                                    "trace-*.json")))
    for path in host_traces:
        try:
            with open(path) as f:
                payload = json.load(f)
            events = payload.get("traceEvents", [])
            merged.extend(events)
            notes.append("%s: %d host events" % (path, len(events)))
        except Exception as e:
            notes.append("%s: load failed (%s)" % (path, e))

    flows = request_flow_summary(merged)
    if flows["ids"]:
        notes.append("request flows: %d ids, %d crossing process boundaries"
                     % (flows["ids"], flows["cross_pid"]))

    return ({"traceEvents": merged, "displayTimeUnit": "ms",
             "otherData": {"capture_id": manifest.get("capture_id"),
                           "sources": len(xplanes) + len(host_traces),
                           "request_flows": flows}},
            manifest, notes)


def request_flow_summary(events):
    """Tally ``serving/request_flow`` flow events (cat ``tfos_flow``, the
    gateway's per-request trace flow): distinct flow ids and how many of
    them cross process boundaries — a cross-pid id is one request whose
    client, admission, dispatch and reply legs stitch into a single
    Perfetto track."""
    pids_by_id = {}
    for ev in events:
        if ev.get("cat") != "tfos_flow":
            continue
        if ev.get("name") != "serving/request_flow":
            continue
        fid = ev.get("id")
        if fid is None:
            continue
        pids_by_id.setdefault(fid, set()).add(ev.get("pid"))
    cross = sum(1 for pids in pids_by_id.values() if len(pids) >= 2)
    return {"ids": len(pids_by_id), "cross_pid": cross}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="merge a profile capture into one Perfetto timeline")
    ap.add_argument("capture_dir",
                    help="profiles/<capture_id> directory from GET /profile")
    ap.add_argument("--telemetry-dir", default=None,
                    help="dir holding the host-side trace-*.json files")
    ap.add_argument("--out", default=None,
                    help="merged output path (default: "
                         "<capture_dir>/merged_timeline.json)")
    args = ap.parse_args(argv)

    payload, _, notes = merge_capture(args.capture_dir, args.telemetry_dir)
    out = args.out or os.path.join(args.capture_dir, "merged_timeline.json")
    with open(out, "w") as f:
        json.dump(payload, f)
    for note in notes:
        print(note)
    print("merged timeline: %s (%d events) — load it in ui.perfetto.dev"
          % (out, len(payload["traceEvents"])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
