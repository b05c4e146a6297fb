"""Cluster-wide on-demand device-trace capture (driver + node halves).

The observability stack's device-plane leg: PR 7's observatory can say *what*
the MFU number is; this module captures *where the step time goes on device*,
from a live cluster, on demand.

How a capture travels (no new connections, no new ports):

1. **Trigger** — ``GET /profile?duration_ms=&steps=`` on the observatory (or
   :meth:`CaptureCoordinator.trigger` directly) creates a capture id and
   resolves the target nodes from the reservation roster (JAX-hosting jobs
   only — the ones that started a ``jax.profiler`` server and published
   ``profiler_port``).
2. **Fan-out** — the pending request rides OUT on each target's next
   heartbeat *reply* (``reservation.Server`` asks :meth:`CaptureCoordinator.poll`;
   exactly-once per node per capture).  Riding the existing control channel
   means capture works wherever heartbeats work — through the same NAT/
   firewall path the cluster already proved at rendezvous — where dialing
   back into per-host profiler ports from the driver often does not.
3. **Capture** — the node's ``HeartbeatSender`` hands the request to
   :func:`handle_capture_request` on a dedicated thread (a capture takes
   seconds; the beat loop must not miss its liveness deadline):
   ``jax.profiler.start_trace`` into a tempdir, wait out the requested
   duration or watch the trainer's dispatch counter for N steps, stop, and
   base64 the artifact files.
4. **Collection** — the node uploads the artifacts as a ``PROF`` control
   message; :meth:`CaptureCoordinator.receive` lands them under
   ``profiles/<capture_id>/node-<executor_id>/`` on the driver and, when the
   last node reports, writes a ``capture.json`` manifest carrying the
   cluster metrics snapshot so ``scripts/analyze_profile.py`` can merge
   from one directory.

A ``profiling/capture_flow`` trace flow links trigger -> per-node capture ->
collection on the merged Perfetto timeline (telemetry wall-clock-µs
convention, :func:`telemetry.wall_time_us`).

Concurrency: ``jax`` allows ONE active trace per process, and LocalBackend
test clusters host several "nodes" in one process — node captures serialize
on a module-level lock rather than racing ``start_trace``.
"""

import base64
import json
import logging
import os
import shutil
import socket
import tempfile
import threading
import time

from tensorflowonspark_tpu import telemetry

logger = logging.getLogger(__name__)

#: duration used when a trigger names neither duration_ms nor steps
DEFAULT_DURATION_MS = 2000
#: hard ceiling on a requested duration — a fat-fingered ?duration_ms=9e9
#: must not pin the capture lock (and the node's capture thread) for hours
MAX_DURATION_MS = 60000
#: per-node cap on base64 artifact payload; biggest-last files are dropped
#: (and the drop recorded) rather than stalling the control channel
MAX_ARTIFACT_BYTES = 32 * 1024 * 1024
#: step-mode poll cadence / give-up horizon (a stalled trainer must not pin
#: the capture lock forever)
STEP_POLL_SECS = 0.05
STEP_TIMEOUT_SECS = 60.0
#: an incomplete capture older than this no longer blocks a new trigger
#: (nodes may have died mid-capture; their slots show in the manifest)
STALE_CAPTURE_SECS = 120.0

#: roster job names that host jax and therefore capture (node._JAX_JOBS;
#: restated here to keep this module importable without the node runtime)
JAX_JOBS = ("chief", "master", "worker")

# One active jax trace per process (see module docstring).
_capture_lock = threading.Lock()

# Latest registered dispatch counter: a zero-arg callable returning a
# cumulative count, registered by Trainer.fit_feed so ?steps=N captures
# know when N more dispatches have happened.
_step_counter = None


def register_step_counter(fn):
    """Register the step-progress source for ``?steps=N`` captures (the
    newest registration wins — one trainer drives a node's step loop)."""
    global _step_counter
    _step_counter = fn


def _await_steps(steps, timeout=STEP_TIMEOUT_SECS):
    """Block until the registered dispatch counter advances by ``steps``
    (or the timeout passes / no counter is registered — then fall back to
    the default duration so the capture still returns *something*)."""
    counter = _step_counter
    if counter is None:
        logger.warning("steps-mode capture without a registered step "
                       "counter; falling back to %d ms", DEFAULT_DURATION_MS)
        time.sleep(DEFAULT_DURATION_MS / 1000.0)
        return False
    try:
        start = counter()
    except Exception:
        logger.warning("step counter failed; falling back to duration",
                       exc_info=True)
        time.sleep(DEFAULT_DURATION_MS / 1000.0)
        return False
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        time.sleep(STEP_POLL_SECS)
        try:
            if counter() - start >= steps:
                return True
        except Exception:
            break
    logger.warning("steps-mode capture timed out waiting for %d steps", steps)
    return False


def _collect_artifacts(tmpdir, max_bytes=MAX_ARTIFACT_BYTES):
    """Walk a stopped trace's output dir into ``[{"name", "b64"}, ...]``.

    Names are tmpdir-relative with forward slashes (the layout jax writes —
    ``plugins/profile/<run>/<host>.xplane.pb`` — is preserved on the driver).
    ``.xplane.pb`` files are packed first: they are the device timeline the
    analyzer needs, so if the size cap clips anything it clips the
    auxiliary files.  Returns (files, total_bytes, dropped_count)."""
    paths = []
    for root, _, names in os.walk(tmpdir):
        for name in names:
            full = os.path.join(root, name)
            rel = os.path.relpath(full, tmpdir).replace(os.sep, "/")
            paths.append((0 if name.endswith(".xplane.pb") else 1,
                          os.path.getsize(full), rel, full))
    paths.sort()
    files, total, dropped = [], 0, 0
    for _, size, rel, full in paths:
        if total + size > max_bytes:
            dropped += 1
            continue
        with open(full, "rb") as f:
            files.append({"name": rel,
                          "b64": base64.b64encode(f.read()).decode("ascii")})
        total += size
    return files, total, dropped


def handle_capture_request(request):
    """Node-side half: run one capture described by a fanned-out request
    dict (``capture_id`` + ``duration_ms`` or ``steps`` [+ ``trace_flow``]);
    returns the PROF payload (artifacts or an error).  Passed to
    ``reservation.HeartbeatSender(on_profile=...)`` by the node runtime;
    runs on the sender's capture thread."""
    capture_id = request.get("capture_id")
    steps = request.get("steps")
    duration_ms = min(int(request.get("duration_ms") or DEFAULT_DURATION_MS),
                      MAX_DURATION_MS)
    tracer = telemetry.get_tracer()
    flow = request.get("trace_flow")
    if flow:
        tracer.flow_step("profiling/capture_flow", flow, leg="node_capture",
                         capture_id=capture_id)
    tmpdir = tempfile.mkdtemp(prefix="tfos-profile-")
    try:
        t0 = time.monotonic()
        with _capture_lock, \
                tracer.span("profiling/capture", capture_id=capture_id,
                            steps=steps, duration_ms=duration_ms):
            import jax

            jax.profiler.start_trace(tmpdir)
            try:
                if steps:
                    _await_steps(int(steps))
                else:
                    time.sleep(duration_ms / 1000.0)
            finally:
                jax.profiler.stop_trace()
        files, total, dropped = _collect_artifacts(tmpdir)
        result = {
            "capture_id": capture_id,
            "host": socket.gethostname(),
            "pid": os.getpid(),
            "elapsed_secs": round(time.monotonic() - t0, 3),
            "files": files,
            "artifact_bytes": total,
        }
        if dropped:
            result["files_dropped"] = dropped
        if not files:
            result["error"] = "capture produced no artifact files"
        return result
    except Exception as e:
        logger.exception("device trace capture failed")
        return {"capture_id": capture_id, "host": socket.gethostname(),
                "error": repr(e)}
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _safe_relpath(name):
    """Validate an uploaded artifact name into a safe relative path — the
    node is trusted but the path still crosses a wire; a capture must never
    be able to write outside its own directory."""
    name = str(name or "").replace("\\", "/")
    parts = [p for p in name.split("/") if p not in ("", ".")]
    if not parts or any(p == ".." for p in parts) or name.startswith("/"):
        raise ValueError("unsafe artifact path {!r}".format(name))
    return os.path.join(*parts)


class CaptureCoordinator(object):
    """Driver-side half: owns capture lifecycle + the ``profiles/`` dir.

    Attached to the reservation server (``server.profile_coordinator``) by
    ``cluster.run`` when the observatory is enabled; the observatory's
    ``/profile`` endpoint calls :meth:`trigger`, the server's HBEAT/PROF
    handlers call :meth:`poll` / :meth:`receive` from the listener thread.
    One capture in flight at a time (a stale incomplete one —
    :data:`STALE_CAPTURE_SECS` — stops blocking and is finalized as-is).
    """

    def __init__(self, server, profiles_dir):
        self.server = server
        self.profiles_dir = profiles_dir
        self._lock = threading.Lock()
        self._seq = 0
        self._capture = None  # latest capture state (also the history head)

    # -- trigger ---------------------------------------------------------

    def trigger(self, duration_ms=None, steps=None):
        """Start a capture against every JAX-hosting roster node; returns
        the ``/profile`` response payload.  Raises ``RuntimeError`` when no
        targets are registered yet or a capture is already in flight."""
        targets = []
        for meta in self.server.reservations.get():
            if (isinstance(meta, dict) and meta.get("job_name") in JAX_JOBS
                    and meta.get("executor_id") is not None):
                targets.append(meta["executor_id"])
        if not targets:
            raise RuntimeError("no JAX-hosting nodes registered yet")
        tracer = telemetry.get_tracer()
        with self._lock:
            cur = self._capture
            if cur and not cur["complete"]:
                if time.time() - cur["started"] < STALE_CAPTURE_SECS:
                    raise RuntimeError(
                        "capture {} still in flight (waiting on nodes {})"
                        .format(cur["id"],
                                sorted(map(str, cur["pending"]))))
                logger.warning("abandoning stale capture %s (nodes %s never "
                               "reported)", cur["id"],
                               sorted(map(str, cur["pending"])))
                self._finalize_locked(cur, stale=True)
            self._seq += 1
            capture_id = "{}-{:03d}".format(
                time.strftime("%Y%m%d-%H%M%S"), self._seq)
            request = {"capture_id": capture_id}
            if steps:
                request["steps"] = int(steps)
            else:
                request["duration_ms"] = min(
                    int(duration_ms or DEFAULT_DURATION_MS), MAX_DURATION_MS)
            flow = tracer.new_flow_id()
            if flow:
                request["trace_flow"] = flow
            capture = {
                "id": capture_id,
                "dir": os.path.join(self.profiles_dir, capture_id),
                "started": time.time(),
                "request": request,
                "targets": list(targets),
                "pending": set(targets),
                "nodes": {},
                "errors": {},
                "complete": False,
            }
            os.makedirs(capture["dir"], exist_ok=True)
            self._capture = capture
        if flow:
            tracer.flow_start("profiling/capture_flow", flow, leg="trigger",
                              capture_id=capture_id, targets=len(targets))
        tracer.instant("profiling/trigger", capture_id=capture_id,
                       targets=len(targets), **{
                           k: v for k, v in request.items()
                           if k in ("duration_ms", "steps")})
        logger.info("profile capture %s triggered for %d node(s) -> %s",
                    capture_id, len(targets), capture["dir"])
        return {"capture_id": capture_id, "dir": capture["dir"],
                "targets": [str(t) for t in targets],
                "request": {k: v for k, v in request.items()
                            if k != "trace_flow"}}

    # -- server hooks (listener thread) ----------------------------------

    def poll(self, executor_id):
        """The pending request for ``executor_id``, exactly once per
        capture (the HBEAT reply piggyback); None when there is nothing
        for this node."""
        with self._lock:
            capture = self._capture
            if (capture is None or capture["complete"]
                    or executor_id not in capture["pending"]):
                return None
            # Delivery == removal from the *poll* set, but completion is
            # tracked by receive(); keep a separate handed-out record.
            handed = capture.setdefault("handed", set())
            if executor_id in handed:
                return None
            handed.add(executor_id)
            return dict(capture["request"])

    def receive(self, data):
        """Land one node's PROF payload under the capture directory; when
        the last pending node reports, finalize (manifest + flow end)."""
        capture_id = data.get("capture_id")
        executor_id = data.get("executor_id")
        with self._lock:
            capture = self._capture
            if capture is None or capture["id"] != capture_id:
                raise ValueError(
                    "unknown capture id {!r}".format(capture_id))
        node_dir = os.path.join(capture["dir"],
                                "node-{}".format(executor_id))
        written = []
        for entry in data.get("files") or []:
            rel = _safe_relpath(entry.get("name"))
            path = os.path.join(node_dir, rel)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "wb") as f:
                f.write(base64.b64decode(entry.get("b64") or ""))
            written.append(rel.replace(os.sep, "/"))
        tracer = telemetry.get_tracer()
        flow = capture["request"].get("trace_flow")
        if flow:
            tracer.flow_step("profiling/capture_flow", flow,
                             leg="collect", capture_id=capture_id,
                             executor_id=executor_id, files=len(written))
        with self._lock:
            capture["pending"].discard(executor_id)
            node_record = {
                "host": data.get("host"),
                "files": written,
                "artifact_bytes": data.get("artifact_bytes", 0),
                "elapsed_secs": data.get("elapsed_secs"),
            }
            if data.get("files_dropped"):
                node_record["files_dropped"] = data["files_dropped"]
            capture["nodes"][str(executor_id)] = node_record
            if data.get("error"):
                capture["errors"][str(executor_id)] = str(data["error"])
            done = not capture["pending"] and not capture["complete"]
            if done:
                self._finalize_locked(capture)
        logger.info("profile capture %s: node %s reported %d file(s)%s",
                    capture_id, executor_id, len(written),
                    "; capture complete" if done else "")

    def _finalize_locked(self, capture, stale=False):
        """Write the ``capture.json`` manifest and end the trace flow
        (caller holds ``self._lock``)."""
        capture["complete"] = True
        manifest = {
            "capture_id": capture["id"],
            "started_utc": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime(capture["started"])),
            "elapsed_secs": round(time.time() - capture["started"], 3),
            "request": {k: v for k, v in capture["request"].items()
                        if k != "trace_flow"},
            "targets": [str(t) for t in capture["targets"]],
            "nodes": capture["nodes"],
            "errors": capture["errors"],
        }
        if stale:
            manifest["stale"] = True
            manifest["unreported"] = sorted(map(str, capture["pending"]))
        # The cluster metrics snapshot rides in the manifest so
        # analyze_profile.py reads the timeline and the counters from one
        # directory.
        try:
            manifest["metrics"] = self.server.metrics_snapshot()
        except Exception:
            logger.debug("metrics snapshot unavailable for manifest",
                         exc_info=True)
        path = os.path.join(capture["dir"], "capture.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
        flow = capture["request"].get("trace_flow")
        if flow:
            telemetry.get_tracer().flow_end(
                "profiling/capture_flow", flow, leg="manifest",
                capture_id=capture["id"], nodes=len(capture["nodes"]),
                stale=stale)

    # -- status ----------------------------------------------------------

    def status(self):
        """Latest capture's state for the observatory ``/status`` JSON
        (None before the first trigger)."""
        with self._lock:
            capture = self._capture
            if capture is None:
                return None
            return {
                "capture_id": capture["id"],
                "dir": capture["dir"],
                "complete": capture["complete"],
                "pending": sorted(map(str, capture["pending"])),
                "nodes": sorted(capture["nodes"]),
                "errors": dict(capture["errors"]),
            }
