"""Profiling lifecycle (reference SURVEY §5.1).

The reference's tracing story is TensorBoard managed by the framework
(launch on the chief, URL via the cluster, kill at shutdown — implemented in
:mod:`~tensorflowonspark_tpu.node`) plus example-level step profiling
(``--profile_steps`` building a Keras profiler callback, reference
``examples/resnet/common.py:192-197,293-300``).  The TPU-native equivalents:

- :func:`start_server` — a per-host ``jax.profiler`` server so TensorBoard's
  profile plugin (or ``xprof``) can capture device traces on demand; the
  node runtime starts one per JAX-hosting node when ``cluster.run(...,
  profiler=True)`` — in the process that runs the user fn, once that
  process has opened the device
  (:func:`start_server_when_backend_is_up`) — and publishes the port in
  the cluster roster.
- :class:`StepProfiler` — programmatic trace capture over a step range,
  the ``--profile_steps start,stop`` behavior: call :meth:`on_step_end`
  once per step and the trace for [start, stop] lands in ``log_dir``.
"""

import logging
import threading
import time

logger = logging.getLogger(__name__)


def start_server(port=None):
    """Start this process's jax.profiler gRPC server; returns the port
    (0 when jax lacks profiler support).  Idempotent per process — jax
    allows one server; subsequent calls return the first port.

    A FAILED start does not latch: ``_server_port`` stays ``None`` so the
    next call retries (a transient bind race / grpc hiccup at bring-up must
    not permanently cost the node its capture capability), while
    ``_server_state`` records the last outcome for the heartbeat counter
    (:func:`server_counters`)."""
    global _server_port, _server_state
    if _server_port is not None:
        return _server_port
    import jax

    if port is None:
        import socket

        sock = socket.socket()
        sock.bind(("", 0))
        port = sock.getsockname()[1]
        sock.close()
    try:
        jax.profiler.start_server(port)
    except Exception:
        logger.warning("jax profiler server unavailable", exc_info=True)
        _server_state = "down"
        return 0
    _server_port = port
    _server_state = "up"
    logger.info("jax profiler server listening on port %d", port)
    return port


def start_server_when_backend_is_up(port, poll_secs=0.5):
    """Start the profiler server on ``port`` once this process has created
    its JAX backend — from a daemon thread, and never before:
    ``jax.profiler.start_server`` creates the backend itself, which would
    open the chip ahead of the user fn (before its ``pin_chips`` or
    ``initialize_distributed``) or, worse, in a shell that is about to fork
    it.  A process that never touches the device gets no server."""
    from tensorflowonspark_tpu import device_info

    def wait_then_start():
        while not device_info.backends_initialized():
            time.sleep(poll_secs)
        start_server(port)

    thread = threading.Thread(target=wait_then_start, daemon=True,
                              name="profiler-server-start")
    thread.start()
    return thread


def server_counters():
    """Heartbeat-counter view of the profiler server: ``{}`` when a start
    was never attempted, else ``profiler_server_up_max`` 1/0 (``_max``
    suffix -> rendered as a Prometheus gauge by the observatory)."""
    if _server_state is None:
        return {}
    return {"profiler_server_up_max": 1 if _server_state == "up" else 0}


_server_port = None
_server_state = None  # None = never attempted, else "up"/"down" (last try)


def parse_profile_steps(spec):
    """``"start,stop"`` -> (start, stop) step numbers (reference flag format,
    ``common.py:293-300``)."""
    if not spec:
        return None
    parts = [p.strip() for p in str(spec).split(",")]
    if len(parts) != 2:
        raise ValueError(
            "profile_steps must be 'start,stop', got {!r}".format(spec))
    start, stop = int(parts[0]), int(parts[1])
    if start < 0 or stop < start:
        raise ValueError(
            "need 0 <= start <= stop in profile_steps, got {!r}".format(spec))
    return start, stop


class StepProfiler(object):
    """Capture a device trace over a global-step range.

    Usage: ``prof = StepProfiler(log_dir, "10,20")`` then call
    ``prof.on_step_begin()`` before and ``prof.on_step_end()`` after every
    step; the trace starts before step ``start`` executes and stops after
    step ``stop``.  Callers that only hook ``on_step_end`` still get a
    trace (it starts lazily, one step late — after step ``start``
    completes) as long as the range spans more than one step.
    """

    def __init__(self, log_dir, profile_steps):
        self.log_dir = log_dir
        self.bounds = parse_profile_steps(profile_steps)
        self.step = 0
        self._active = False

    def _start(self):
        import jax

        jax.profiler.start_trace(self.log_dir)
        self._active = True
        logger.info("profiler trace started at step %d -> %s",
                    self.step, self.log_dir)

    def on_step_begin(self):
        if self.bounds and not self._active and self.step == self.bounds[0]:
            self._start()

    def on_step_end(self):
        self.step += 1
        if not self.bounds:
            return
        if self._active and self.step > self.bounds[1]:
            self.stop()
        elif (not self._active
              and self.bounds[0] <= self.step <= self.bounds[1]):
            # on_step_begin was never called: start late rather than never.
            self._start()

    def stop(self):
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            logger.info("profiler trace stopped at step %d", self.step)

    # Context-manager form: an exception between start/stop would otherwise
    # leak an active jax.profiler trace and poison the next capture attempt
    # (start_trace raises if one is already running).
    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
        return False
