"""What every chip-holding process of the benchmark does the same way: open
the device and refuse anything but the chips the cell asks for, watch for
compiles, read the peak memory, trace a window and reduce the trace.

Imported only in the process that holds the chip; ``run.py`` (the parent)
never imports it, so the parent never touches JAX.
"""

import json
import os
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: set by the tests' rehearsals only: skip the look for a chip
ALLOW_CPU_ENV = "PERFBENCH_REHEARSAL_PLATFORM"


class BenchError(Exception):
    """The run cannot give a result (no chip, a compile inside the window,
    an unknown device): the process exits non-zero and prints none."""


def load_peaks(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks or device_kind.startswith("_"):
        raise BenchError("device_kind {!r} has no row in benchmark/peaks.json"
                         .format(device_kind))
    return peaks[device_kind]


def open_device(chips):
    """First touch of the device.  Refuses a platform other than the TPU, or
    fewer chips than the cell asks for (the tests' rehearsals name the
    platform they run on instead)."""
    import jax

    t0 = time.perf_counter()
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "open_secs": round(time.perf_counter() - t0, 3),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir}
    wanted = os.environ.get(ALLOW_CPU_ENV) or "tpu"
    if info["platform"] != wanted:
        raise BenchError("this cell needs platform {!r} but JAX found {}"
                         .format(wanted, info))
    if wanted == "tpu" and info["count"] != chips:
        raise BenchError("this cell needs {} chips but JAX found {}".format(
            chips, info))
    if wanted == "tpu":
        info["peaks"] = load_peaks(info["kind"])
    return info


class CompileWatch(object):
    """Counts, from jax's own monitoring events: programs compiled by the
    backend (persistent-cache misses that reached the compiler), persistent
    cache hits and misses, and the seconds spent tracing, lowering,
    compiling and reading the cache.  ``mark()`` returns the counts so far;
    the difference of two marks is what happened between them."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration",
                 "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring

        self.counts = {"backend_compiles": 0, "cache_hits": 0,
                       "cache_misses": 0, "compile_secs": 0.0}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event in self.DURATIONS:
            self.counts["compile_secs"] += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def mark(self):
        return dict(self.counts)


def memory_peak_bytes():
    """Peak bytes in use on the fullest local device (None where the backend
    keeps no statistics, as the CPU's)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def memory_report(step_fn, state, example_batch, batch_size):
    """Peak device memory of a training cell.  The TPU client's statistics
    count live buffers only, not the scratch a running program takes (the
    jitted step of ResNet-50 at batch 256 holds 0.3 GB of buffers and 9 GB
    of activations), so the peak is the larger of the statistics' peak and
    the buffers in use now plus the step program's own temporary size, by
    XLA's memory analysis of that program (found in the compile cache)."""
    import jax
    import numpy as np

    stats = jax.local_devices()[0].memory_stats() or {}
    out = {"stats": {k: v for k, v in stats.items()
                     if isinstance(v, (int, float))},
           "stats_peak_bytes": memory_peak_bytes()}
    abstract = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
                for k, v in example_batch.items()}
    mask = jax.ShapeDtypeStruct((batch_size,), np.float32)
    try:
        analysis = step_fn.lower(state, abstract, mask).compile() \
            .memory_analysis()
        out["step_temp_bytes"] = int(analysis.temp_size_in_bytes)
    except Exception as e:  # the CPU rehearsal, or a backend without it
        out["step_temp_bytes"] = 0
        out["analysis_error"] = repr(e)
    in_use = max([(d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in jax.local_devices()] or [0])
    peak = out["stats_peak_bytes"]
    out["peak_bytes"] = (None if peak is None else
                         max(peak, in_use + out["step_temp_bytes"]))
    return out


class WindowTrace(object):
    """jax.profiler around one window; ``stop`` reduces the trace."""

    def __init__(self, directory):
        self.directory = directory
        self._span = None

    def start(self):
        """Start the profiler.  Slow, and it stalls the process for seconds:
        the drivers call it some steps (or seconds of load) before they
        ``open`` the window, so that the window holds none of it.  The Python
        tracer stays off: hooking every Python call slowed the host-bound
        cells two- to threefold (the feed's consumer threads, the gateway);
        TraceAnnotation spans need only the host tracer."""
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def open(self):
        """The traced window begins here."""
        import jax

        self._span = jax.profiler.TraceAnnotation("perfbench/window")
        self._span.__enter__()

    def stop(self):
        import jax

        from benchmark import trace_reduce

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(self.directory)))


def span(name):
    """A host span of the benchmark's own in the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation("perfbench/" + name)


def write_report(path, report):
    tmp = "{}.tmp.{}".format(path, os.getpid())
    with open(tmp, "w") as f:
        json.dump(report, f, default=float)
    os.replace(tmp, path)


class reporting(object):
    """Context manager: whatever happens, the report (with the error's text,
    if any) is left at ``path`` for the parent."""

    def __init__(self, path):
        self.path = path
        self.report = {"ok": False, "pid": os.getpid()}

    def __enter__(self):
        return self.report

    def __exit__(self, kind, exc, tb):
        if exc is None:
            self.report["ok"] = True
        else:
            self.report["error"] = "".join(
                traceback.format_exception(kind, exc, tb))
            self.report["bench_error"] = isinstance(exc, BenchError)
        write_report(self.path, self.report)
        return False
