"""Device-plane profiling tests: the device-memory counters (``metrics``),
the capture coordinator (``profiling``), and the pure-Python xplane decoder (``scripts/analyze_profile.py``).  All CPU, no sockets —
the coordinator is driven through a duck-typed fake reservation server."""

import json
import os
import sys

import pytest

from tensorflowonspark_tpu import metrics as metrics_mod
from tensorflowonspark_tpu import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))
import analyze_profile  # noqa: E402


# -- device-memory counters --------------------------------------------------


def test_device_memory_counters_shape():
    out = metrics_mod.device_memory_counters()
    assert isinstance(out, dict)
    for key, val in out.items():
        assert key.endswith("_hwm") and isinstance(val, int) and val >= 0


def test_device_memory_counters_without_jax_import(monkeypatch):
    """Beat-thread contract: in a process that never imported JAX the read
    returns {} instead of paying the ~0.5s import — which would stall the
    heartbeat past the liveness tolerance and fence a healthy node."""
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    assert metrics_mod.device_memory_counters() == {}


def test_device_memory_counters_without_backend_init(monkeypatch):
    """Same contract, second trap: jax imported but no backend initialized.
    ``jax.local_devices()`` would first-touch-init one (seconds on TPU), so
    the read must bail on an empty ``xla_bridge._backends`` cache."""
    import jax  # noqa: F401 - must be present in sys.modules for this case
    import jax._src.xla_bridge as xb

    monkeypatch.setattr(xb, "_backends", {})
    assert metrics_mod.device_memory_counters() == {}


# -- capture plumbing --------------------------------------------------------


class TestSafeRelpath:
    def test_preserves_nested_layout(self):
        assert (profiling._safe_relpath("plugins/profile/run/h.xplane.pb")
                == os.path.join("plugins", "profile", "run", "h.xplane.pb"))

    @pytest.mark.parametrize("bad", ["", None, "/etc/passwd", "../x",
                                     "a/../../b", "a/.."])
    def test_rejects_escapes(self, bad):
        with pytest.raises(ValueError):
            profiling._safe_relpath(bad)


def test_collect_artifacts_caps_and_prioritizes_xplane(tmp_path):
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"x" * 100)
    (run / "aux.trace.json.gz").write_bytes(b"y" * 10_000)
    files, total, dropped = profiling._collect_artifacts(
        str(tmp_path), max_bytes=200)
    # the cap clips the big auxiliary file, never the device timeline
    assert [f["name"] for f in files] == ["plugins/profile/run1/host.xplane.pb"]
    assert total == 100 and dropped == 1


def test_await_steps_watches_registered_counter():
    ticks = [0]

    def counter():
        ticks[0] += 1
        return ticks[0]

    profiling.register_step_counter(counter)
    try:
        assert profiling._await_steps(2, timeout=5.0) is True
    finally:
        profiling.register_step_counter(None)


def test_handle_capture_request_produces_artifacts():
    result = profiling.handle_capture_request(
        {"capture_id": "cap-1", "duration_ms": 100})
    assert result["capture_id"] == "cap-1"
    assert "error" not in result, result
    assert result["files"] and result["artifact_bytes"] > 0
    assert any(f["name"].endswith(".xplane.pb") for f in result["files"])


class _FakeServer:
    """Duck-typed reservation server: the two surfaces the coordinator
    reads (roster metas + metrics snapshot), no sockets."""

    def __init__(self, metas):
        self._metas = metas

        class _R:
            def get(_self):
                return self._metas

        self.reservations = _R()

    def metrics_snapshot(self):
        return {"nodes": {},
                "aggregate": {"dispatch_count": 12,
                              "goodput_dispatch_us": 40_000,
                              "goodput_infeed_starved_us": 10_000}}


def _coordinator(tmp_path, metas=None):
    metas = metas if metas is not None else [
        {"job_name": "chief", "executor_id": 0},
        {"job_name": "worker", "executor_id": 1},
        {"job_name": "ps", "executor_id": 2},  # not a JAX job: never targeted
    ]
    return profiling.CaptureCoordinator(_FakeServer(metas),
                                        str(tmp_path / "profiles"))


class TestCaptureCoordinator:
    def test_trigger_requires_jax_nodes(self, tmp_path):
        coord = _coordinator(tmp_path, metas=[{"job_name": "ps",
                                               "executor_id": 2}])
        with pytest.raises(RuntimeError):
            coord.trigger()

    def test_full_capture_lifecycle(self, tmp_path):
        coord = _coordinator(tmp_path)
        out = coord.trigger(duration_ms=500)
        assert sorted(out["targets"]) == ["0", "1"]
        assert os.path.isdir(out["dir"])
        assert "trace_flow" not in out["request"]

        # fan-out: exactly once per target; non-targets get nothing
        req = coord.poll(0)
        assert req["capture_id"] == out["capture_id"]
        assert req["duration_ms"] == 500
        assert coord.poll(0) is None
        assert coord.poll(2) is None
        assert coord.poll(1) is not None

        # a second trigger is refused while nodes are still out capturing
        with pytest.raises(RuntimeError):
            coord.trigger()
        assert coord.status()["complete"] is False

        import base64
        coord.receive({"capture_id": out["capture_id"], "executor_id": 0,
                       "host": "a", "artifact_bytes": 2, "files": [
                           {"name": "run/a.xplane.pb",
                            "b64": base64.b64encode(b"hi").decode()}]})
        coord.receive({"capture_id": out["capture_id"], "executor_id": 1,
                       "host": "b", "error": "capture failed", "files": []})

        status = coord.status()
        assert status["complete"] is True and status["pending"] == []
        assert status["errors"] == {"1": "capture failed"}
        artifact = os.path.join(out["dir"], "node-0", "run", "a.xplane.pb")
        with open(artifact, "rb") as f:
            assert f.read() == b"hi"
        with open(os.path.join(out["dir"], "capture.json")) as f:
            manifest = json.load(f)
        assert manifest["capture_id"] == out["capture_id"]
        assert manifest["nodes"]["0"]["files"] == ["run/a.xplane.pb"]
        assert manifest["errors"] == {"1": "capture failed"}
        assert manifest["metrics"]["aggregate"]["dispatch_count"] == 12

        # the capture is closed: a new trigger is admitted again
        assert coord.trigger()["capture_id"] != out["capture_id"]

    def test_receive_rejects_unknown_capture_and_bad_paths(self, tmp_path):
        coord = _coordinator(tmp_path)
        with pytest.raises(ValueError):
            coord.receive({"capture_id": "nope", "executor_id": 0})
        out = coord.trigger()
        with pytest.raises(ValueError):
            coord.receive({"capture_id": out["capture_id"], "executor_id": 0,
                           "files": [{"name": "../escape", "b64": ""}]})

    def test_stale_capture_stops_blocking(self, tmp_path):
        coord = _coordinator(tmp_path)
        out = coord.trigger()
        # age the capture past the stale horizon: the next trigger
        # finalizes it as-is instead of wedging captures forever
        coord._capture["started"] -= profiling.STALE_CAPTURE_SECS + 1
        out2 = coord.trigger()
        assert out2["capture_id"] != out["capture_id"]
        with open(os.path.join(out["dir"], "capture.json")) as f:
            manifest = json.load(f)
        assert manifest["stale"] is True
        assert manifest["unreported"] == ["0", "1"]


# -- xplane decoder ----------------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _vi(num, val):
    return _varint(num << 3) + _varint(val)


def _ld(num, data):
    return _varint((num << 3) | 2) + _varint(len(data)) + data


def _tiny_xspace():
    """One plane / one line / one event named via the metadata map: the
    minimal real XSpace shape (field numbers from xplane.proto)."""
    meta = _vi(1, 7) + _ld(2, b"fusion") + _ld(4, b"matmul.1")
    entry = _vi(1, 7) + _ld(2, meta)
    event = _vi(1, 7) + _vi(2, 2_000_000) + _vi(3, 5_000_000)  # ps
    line = (_vi(1, 3) + _ld(2, b"stream#0") + _vi(3, 1_000_000_000)
            + _ld(4, event))
    plane = _vi(1, 1) + _ld(2, b"/device:TPU:0") + _ld(3, line) + _ld(4, entry)
    return _ld(1, plane)


class TestXplaneDecoder:
    def test_parse_fields_wire_types(self):
        buf = (_vi(1, 300) + _ld(2, b"abc")
               + bytes([(3 << 3) | 1]) + b"\0" * 8    # fixed64: skipped
               + bytes([(4 << 3) | 5]) + b"\0" * 4)   # fixed32: skipped
        fields = analyze_profile.parse_fields(buf)
        assert fields[1] == [300]
        assert fields[2] == [b"abc"]
        assert fields[3] == [None] and fields[4] == [None]

    def test_parse_fields_rejects_unknown_wire_type(self):
        with pytest.raises(ValueError):
            analyze_profile.parse_fields(bytes([0x0B]))  # wire type 3

    def test_decode_xplane_events(self):
        events = analyze_profile.decode_xplane(_tiny_xspace(), 42, "dev:n0")
        by_ph = {}
        for ev in events:
            by_ph.setdefault(ev["ph"], []).append(ev)
        assert by_ph["M"][0]["args"]["name"] == "dev:n0"
        names = [ev["args"]["name"] for ev in by_ph["M"]]
        assert "/device:TPU:0/stream#0" in names
        (x,) = by_ph["X"]
        assert x["name"] == "matmul.1"  # display_name wins over name
        assert x["pid"] == 42 and x["tid"] == 3
        # line 1 s epoch + 2e6 ps offset -> 1_000_002 us; 5e6 ps -> 5 us
        assert x["ts"] == pytest.approx(1_000_002.0)
        assert x["dur"] == pytest.approx(5.0)

    def test_merge_capture_into_one_timeline(self, tmp_path):
        cap = tmp_path / "cap-001"
        node = cap / "node-0" / "run"
        node.mkdir(parents=True)
        (node / "host.xplane.pb").write_bytes(_tiny_xspace())
        manifest = {"capture_id": "cap-001",
                    "metrics": _FakeServer([]).metrics_snapshot()}
        (cap / "capture.json").write_text(json.dumps(manifest))
        tdir = tmp_path / "telemetry"
        tdir.mkdir()
        (tdir / "trace-h-1.json").write_text(json.dumps(
            {"traceEvents": [{"ph": "X", "name": "host_span", "pid": 9,
                              "tid": 1, "ts": 1_000_000.0, "dur": 3.0}]}))

        out = tmp_path / "merged.json"
        rc = analyze_profile.main([str(cap), "--telemetry-dir", str(tdir),
                                   "--out", str(out)])
        assert rc == 0
        with open(str(out)) as f:
            merged = json.load(f)
        names = {ev.get("name") for ev in merged["traceEvents"]}
        assert {"matmul.1", "host_span"} <= names  # one merged timeline
        assert merged["otherData"]["capture_id"] == "cap-001"
