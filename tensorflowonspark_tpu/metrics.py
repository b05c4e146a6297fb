"""Throughput / step-time / MFU instrumentation.

First-class equivalent of the reference's ``TimeHistory`` callback and
``build_stats`` summary (reference ``examples/resnet/common.py:177-245``):
per-N-step wall-clock logging, ``avg_exp_per_second``, and final stats —
plus MFU (model FLOPs utilization) where the model's owner states a step's
FLOPs: that count over the device's published peak over device-synced step
time.  Nothing here derives a count from a compiled program.
"""

import json
import logging
import sys
import time

logger = logging.getLogger(__name__)

# Peak dense (bf16) FLOPs per chip for MFU accounting, keyed on the FULL
# lowercased ``device_kind`` string (exact match, not prefix: "tpu v5"
# must never swallow "tpu v5 lite" — a silent 2.3x MFU error).
PEAK_FLOPS = {
    "tpu v2": 46e12,
    "tpu v3": 123e12,
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,   # v5e: 197 TFLOP/s bf16 (394 is the int8 figure)
    "tpu v5e": 197e12,
    "tpu v5": 459e12,        # v5p reports plain "TPU v5" on some stacks
    "tpu v5p": 459e12,
    "tpu v6 lite": 918e12,   # v6e / trillium
    "tpu v6e": 918e12,
}


# Step-time histogram bucket upper bounds, in milliseconds.  Shared by the
# Trainer's runtime accountant (``step_ms_le_<bound>`` heartbeat counters)
# and the observatory's Prometheus rendering (``tfos_step_ms_bucket{le=}``),
# so the two never disagree on bucket edges.  Roughly log-spaced from a
# sub-millisecond CPU toy step to a multi-second pathological stall.
STEP_MS_BUCKETS = (1, 2, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000)

# Serving latency histogram bucket upper bounds, in microseconds.  Shared by
# the gateway's request-plane accountant (``serving_*_us_le_<bound>``
# heartbeat counters for the queue/coalesce/dispatch/serialize stages plus
# the end-to-end ``serving_latency_us`` family) and the observatory's
# Prometheus rendering, mirroring the STEP_MS_BUCKETS contract above.
# Log-spaced from a 50us in-process hit to a 1s pathological stall.
SERVING_US_BUCKETS = (50, 100, 250, 500, 1000, 2500, 5000, 10000, 25000,
                      50000, 100000, 250000, 500000, 1000000)


def achieved_flops_per_sec(step_flops, step_seconds):
    """Achieved per-device FLOP/s for one dispatch (None when unknowable)."""
    if not step_flops or not step_seconds or step_seconds <= 0:
        return None
    return step_flops / step_seconds


def mfu_from_step_time(step_flops, step_seconds):
    """MFU for one step from per-device FLOPs and wall seconds.

    The exact formula :meth:`TimeHistory.mfu` applies (per-device FLOPs over
    per-device peak over step seconds) — exposed standalone so the runtime
    accountant (``train.Trainer``) computes the same number from the same
    inputs.
    """
    if not step_flops or not step_seconds or step_seconds <= 0:
        return None
    peak = peak_flops_per_device()
    if peak is None:
        return None
    return step_flops / peak / step_seconds


def compression_ratio(raw_bytes, wire_bytes):
    """Wire compression ratio ``raw / wire`` (> 1 when the codec saved
    bytes; 1.0 when nothing compressed or either side is unknown, so
    gauges never divide by zero).  ``ServiceFeed.counters_snapshot``
    publishes it as ``wire_compress_ratio_max``."""
    if not raw_bytes or not wire_bytes or wire_bytes <= 0:
        return 1.0
    return raw_bytes / float(wire_bytes)


def _device_peak(table, table_name):
    """The default device's row of a peak table.  A host CPU has no row and
    gets None — a run without an accelerator reports no utilization at all.
    An accelerator whose ``device_kind`` is missing from the table is an
    error, not a default: a guessed peak is a wrong MFU."""
    import jax

    device = jax.devices()[0]
    kind = device.device_kind.lower()
    if kind in table:
        return table[kind]
    if device.platform == "cpu":
        return None
    raise ValueError(
        "device kind {!r} (platform {}) has no row in metrics.{}; add its "
        "published peak there".format(device.device_kind, device.platform,
                                      table_name))


def peak_flops_per_device():
    return _device_peak(PEAK_FLOPS, "PEAK_FLOPS")


def device_memory_counters():
    """Per-device peak-memory high-water marks as heartbeat counters.

    Reads ``device.memory_stats()`` across local devices; the max over
    devices of ``bytes_in_use`` and ``peak_bytes_in_use`` land as
    ``device_mem_bytes_in_use_hwm`` / ``device_mem_peak_bytes_hwm``
    (``_hwm`` suffix -> merged by max, rendered as gauges).  Backends
    without memory stats (CPU) contribute ``{}`` — callers must not rely
    on the keys existing.

    This runs on the heartbeat thread, so it must never be the thing that
    pays JAX startup: importing jax (~0.5s) or first-touch backend init
    (seconds on TPU) would stall the beat past the liveness tolerance and
    fence a healthy node.  Processes that never initialized JAX contribute
    ``{}``; ones that did (the trainer) get stats for free."""
    out = {}
    try:
        from tensorflowonspark_tpu import device_info

        if not device_info.backends_initialized():
            return out  # no backend up yet; local_devices() would init one
        jax = sys.modules["jax"]

        in_use, peak = 0, 0
        seen = False
        for dev in jax.local_devices():
            stats = dev.memory_stats()
            if stats is None:  # the CPU backend keeps none
                continue
            seen = True
            in_use = max(in_use, int(stats.get("bytes_in_use", 0)))
            peak = max(peak, int(stats.get("peak_bytes_in_use",
                                           stats.get("bytes_in_use", 0))))
        if seen:
            out["device_mem_bytes_in_use_hwm"] = in_use
            out["device_mem_peak_bytes_hwm"] = peak
    except Exception:  # metrics must never cost a heartbeat
        logger.debug("device memory stats unavailable", exc_info=True)
    return out


class TimeHistory(object):
    """Per-N-step timing + throughput recorder (reference ``common.py:177``).

    Call :meth:`on_step_end(value)` once per global step, passing a device
    value data-dependent on that step (the loss).  Timestamps of each
    N-step window land in ``timestamp_log`` exactly like the reference's
    Keras callback, so ``avg_examples_per_second`` is computed the same way
    (reference ``common.py:236-244``).

    Timing discipline: jax dispatch is asynchronous — the host returns from
    a jitted call long before the device finishes, so timestamping the host
    clock alone measures dispatch rate, not step time (it reported >100%
    MFU).  At every window boundary we therefore force a device->host
    readback of ``value`` before reading the clock; steps *within* a window
    still pipeline freely, so the sync cost amortizes over ``log_steps``.
    """

    def __init__(self, batch_size, log_steps=20, step_flops=None,
                 num_devices=None, summary_writer=None):
        import jax

        self.batch_size = batch_size
        self.log_steps = log_steps
        self.step_flops = step_flops  # per-device model FLOPs, as stated
        self.num_devices = num_devices or len(jax.devices())
        # optional tensorflowonspark_tpu.summary.SummaryWriter: window
        # scalars (loss/throughput/MFU) land in TensorBoard (chief-only by
        # caller convention)
        self.summary_writer = summary_writer
        self.global_steps = 0
        self.timestamp_log = []
        self.train_start_time = None
        self.start_time = None
        self.elapsed = 0.0
        # per-step loss vectors from K-step scan groups, buffered as DEVICE
        # arrays (reading them eagerly would sync every group and defeat
        # the async pipeline); drained into the summary writer at window
        # boundaries, where a sync happens anyway
        self._pending_losses = []
        self._loss_curve_end = 0  # last step the per-step curve has covered
        # Host copy of the value the last window boundary synced on (scalar
        # loss, or a per-step loss vector under K-steps-per-dispatch) — the
        # Trainer's training-health counters read it here, so observing the
        # loss costs no sync beyond the one the boundary already forced.
        self.last_synced_value = None

    def on_train_begin(self):
        self.train_start_time = time.time()
        self.start_time = time.time()
        self.timestamp_log.append((0, self.start_time))

    @staticmethod
    def _sync(value):
        """Force a device->host readback so the host clock reflects device
        completion; returns the host value (None when there was nothing to
        sync on).  The value read back is also what the health counters and
        the loss curve consume, so the boundary costs one transfer."""
        if value is None:
            return None
        import jax

        return jax.device_get(jax.block_until_ready(value))

    def on_step_end(self, value=None):
        self.on_steps_end(1, value)

    def on_steps_end(self, n, value=None, window_value=None):
        """Record ``n`` global steps completed by one dispatch (n > 1 when a
        ``lax.scan`` group ran K steps on device, see ``Trainer.multi_step``).
        A window closes whenever the step counter crosses a ``log_steps``
        boundary; window length in steps is tracked exactly, so throughput
        stays honest even when boundaries land mid-group.

        ``value`` may be a length-``n`` PER-STEP loss vector (the scan's
        stacked ys): the TensorBoard loss curve then keeps full per-step
        density under K-steps-per-dispatch — points buffer as device arrays
        and flush at window boundaries, so no extra syncs enter the
        pipeline.

        ``window_value`` may carry an O(1) DEVICE SCALAR summarizing the
        dispatch (e.g. the scan-computed group loss mean): boundaries then
        sync on it instead of the K-element vector, so the per-boundary
        device->host readback stays O(1) no matter how large K grows.
        ``last_synced_value`` becomes that scalar (a mean, not the last
        step's loss — NaN/Inf still propagate through the mean, so
        nonfinite health detection keeps working)."""
        if self.train_start_time is None:
            self.on_train_begin()
        before = self.global_steps
        self.global_steps += n
        vec = value if getattr(value, "ndim", 0) else None
        if vec is not None and self.summary_writer is not None:
            self._pending_losses.append((before, vec))
        if self.global_steps // self.log_steps > before // self.log_steps:
            synced = self._sync(
                window_value if window_value is not None else value)
            if synced is not None:
                self.last_synced_value = synced
            now = time.time()
            window_steps = self.global_steps - self.timestamp_log[-1][0]
            elapsed = now - self.start_time
            eps = self.batch_size * window_steps / elapsed
            msg = ("step %d: %.1f examples/sec (%.1f/sec/chip), "
                   "%.1f ms/step" % (
                       self.global_steps, eps, eps / self.num_devices,
                       1000 * elapsed / window_steps))
            mfu = self.mfu(elapsed / window_steps)
            if mfu is not None:
                msg += ", %.1f%% MFU" % (100 * mfu)
            logger.info(msg)
            if self.summary_writer is not None:
                # drain buffered per-step loss vectors first (their steps
                # completed long ago: device_get here stalls nothing)
                flushed_loss = self._drain_pending_losses()
                scalars = {"examples_per_sec": eps,
                           "ms_per_step": 1000 * elapsed / window_steps}
                if mfu is not None:
                    scalars["mfu"] = mfu
                if value is not None and not flushed_loss:
                    try:
                        scalars["loss"] = float(value)
                    except TypeError:
                        pass  # non-scalar sync value: skip the loss curve
                self.summary_writer.add_scalars(scalars, self.global_steps)
                # flush per window (amortized by log_steps): live dashboards
                # update mid-run and a killed job keeps its curves
                self.summary_writer.flush()
            self.timestamp_log.append((self.global_steps, now))
            self.start_time = now

    def _drain_pending_losses(self):
        """Write buffered per-step loss vectors to the summary writer;
        returns True if any point was written."""
        import jax
        import numpy as np

        for s0, v in self._pending_losses:
            arr = np.asarray(jax.device_get(v))
            for i, l in enumerate(arr):
                self.summary_writer.add_scalars({"loss": float(l)}, s0 + i + 1)
            self._loss_curve_end = max(self._loss_curve_end, s0 + len(arr))
        drained = bool(self._pending_losses)
        self._pending_losses = []
        return drained

    def on_train_end(self, value=None):
        synced = self._sync(value)
        if synced is not None:
            self.last_synced_value = synced
        self.elapsed = time.time() - self.train_start_time
        if self.summary_writer is not None and self._pending_losses:
            # flush the tail of the per-step loss curve (steps since the
            # last window boundary)
            self._drain_pending_losses()
            self.summary_writer.flush()

    def mfu(self, step_seconds):
        # step_flops and peak are both per-device figures, so no num_devices
        # term; delegated so the runtime accountant provably shares the
        # formula.
        return mfu_from_step_time(self.step_flops, step_seconds)

    # -- summary (reference build_stats, common.py:202-245) ---------------

    def avg_examples_per_second(self):
        log = self.timestamp_log
        if len(log) >= 2:
            steps = log[-1][0] - log[0][0]
            elapsed = log[-1][1] - log[0][1]
            return self.batch_size * steps / elapsed if elapsed > 0 else 0.0
        if self.elapsed and self.global_steps:
            # run shorter than one log window: fall back to the (synced)
            # whole-run elapsed from on_train_end
            return self.batch_size * self.global_steps / self.elapsed
        return 0.0

    def build_stats(self, loss=None, eval_loss=None, accuracy=None):
        eps = self.avg_examples_per_second()
        stats = {
            "global_steps": self.global_steps,
            "avg_exp_per_second": eps,
            "exp_per_second_per_chip": eps / self.num_devices,
            "train_finish_time": time.time(),
            "elapsed_seconds": self.elapsed,
        }
        avg_step = (self.elapsed / self.global_steps
                    if self.global_steps and self.elapsed else None)
        if avg_step:
            stats["avg_step_seconds"] = avg_step
            mfu = self.mfu(avg_step)
            if mfu is not None:
                stats["mfu"] = mfu
        if loss is not None:
            stats["loss"] = float(loss)
        if eval_loss is not None:
            stats["eval_loss"] = float(eval_loss)
        if accuracy is not None:
            stats["accuracy_top_1"] = float(accuracy)
        return stats

    def log_stats(self, **kwargs):
        stats = self.build_stats(**kwargs)
        logger.info("train stats: %s", json.dumps(stats, default=float))
        if self.summary_writer is not None:
            keys = ["loss", "avg_exp_per_second", "avg_step_seconds",
                    "mfu", "eval_loss", "accuracy_top_1"]
            if self._loss_curve_end >= self.global_steps:
                keys.remove("loss")  # per-step curve already has this point
            final = {k: float(stats[k]) for k in keys if k in stats}
            self.summary_writer.add_scalars(final, self.global_steps)
            self.summary_writer.flush()
        return stats
