"""Test harness configuration.

Puts JAX on a virtual 8-device CPU platform *before any backend init*, so
multi-chip sharding logic is exercised without TPU hardware — the TPU-native
equivalent of the reference's local Spark Standalone test rig
(reference ``test/run_tests.sh:15-22``, ``test/README.md:10``): multiple
executor processes on one machine behave like multiple hosts.

The platform is chosen by the environment and by nothing else:
``JAX_PLATFORMS=cpu`` here, inherited by every executor child.
"""

import os
import sys

# pyspark shim (tests/sparkshim): a process-backed test double of the exact
# pyspark API surface the framework's Spark layer consumes.  On the path for
# the WHOLE suite (before any framework import) so import-gated pyspark code
# (pipeline ml-subclassing, SparkBackend, DataFrame dfutil) is active and
# exercised; PYTHONPATH propagates it to spawned executor processes.
# TFOS_REAL_PYSPARK=1 (the CI spark-real leg) skips the shim so the same
# tests run against an installed real pyspark + JVM — the reference's live
# Spark Standalone rig (reference test/run_tests.sh:15-22).
_SHIM = os.path.join(os.path.dirname(os.path.abspath(__file__)), "sparkshim")
_use_shim = True
if os.environ.get("TFOS_REAL_PYSPARK"):
    try:
        import pyspark  # noqa: F401  (probe: is the real package here?)
    except ImportError as e:
        # fail LOUDLY: falling back to the shim here would let a run that
        # claims real-JVM validation silently test the double instead
        raise RuntimeError(
            "TFOS_REAL_PYSPARK=1 but pyspark is not importable — install "
            "pyspark (and a JVM) or unset the variable") from e
    _use_shim = False
if _use_shim:
    if _SHIM not in sys.path:
        sys.path.insert(0, _SHIM)
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (_SHIM, os.environ.get("PYTHONPATH", "")) if p)

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402,F401  (must import after the env staging above)
import pytest  # noqa: E402


@pytest.fixture
def cpu_peaks(monkeypatch):
    """A peak for the CPU test device, passed explicitly.  The table holds
    accelerators only (a CPU run reports no utilization), so a test that
    wants the MFU arithmetic to run puts its own row in."""
    from tensorflowonspark_tpu import metrics

    monkeypatch.setitem(metrics.PEAK_FLOPS, "cpu", 1e11)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Per-test wall-clock limit for ``chaos``-marked tests.

    Fault-injection tests deliberately kill processes mid-protocol; a
    recovery bug there presents as a HANG (a feeder blocked on a dead
    consumer), which would otherwise eat the whole suite's 600s timeout.
    SIGALRM (not pytest-timeout: not installed here) turns that hang into a
    stack-bearing failure.  Armed only on the main thread of the main
    interpreter — SIGALRM can't target worker threads.
    """
    import signal
    import threading

    marker = item.get_closest_marker("chaos")
    if marker is None or threading.current_thread() is not threading.main_thread():
        yield
        return
    limit = int(marker.kwargs.get("timeout", 120))

    def _on_alarm(signum, frame):
        raise TimeoutError(
            "chaos test exceeded its {}s wall-clock limit — a recovery path "
            "is hanging instead of failing".format(limit))

    previous = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(limit)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(params=["xla", "kernels"])
def row_path(request, monkeypatch):
    """Both paths of an expert layer's row movement (``ops/routed_rows``)
    and of the row-wise passes between its products (``ops/expert_gate``,
    which asks the same question): XLA's take and the plain form, which is
    what a CPU process gets, and the kernels a TPU process gets, here in
    interpret mode."""
    import importlib

    if request.param == "kernels":
        monkeypatch.setattr(
            importlib.import_module("tensorflowonspark_tpu.ops.routed_rows"),
            "_default_impl", lambda: ("pallas", True))
    return request.param
