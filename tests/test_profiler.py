"""Profiler lifecycle tests (reference SURVEY §5.1: framework-managed
tracing; ``--profile_steps`` behavior from ``examples/resnet/common.py``)."""

import glob
import os

import pytest

from tensorflowonspark_tpu import profiler


class TestParseProfileSteps:
    def test_parses(self):
        assert profiler.parse_profile_steps("10,20") == (10, 20)
        assert profiler.parse_profile_steps(" 0 , 0 ") == (0, 0)

    def test_empty_means_disabled(self):
        assert profiler.parse_profile_steps("") is None
        assert profiler.parse_profile_steps(None) is None

    @pytest.mark.parametrize("bad", ["5", "1,2,3", "-1,4", "9,3", "a,b"])
    def test_rejects(self, bad):
        with pytest.raises(ValueError):
            profiler.parse_profile_steps(bad)


def test_step_profiler_captures_range(tmp_path):
    import jax
    import jax.numpy as jnp

    log_dir = str(tmp_path / "trace")
    prof = profiler.StepProfiler(log_dir, "1,2")
    f = jax.jit(lambda x: x * 2)
    for _ in range(4):
        prof.on_step_begin()
        f(jnp.ones((8,))).block_until_ready()
        prof.on_step_end()
    prof.stop()  # no-op: already stopped after step 2
    # a trace landed under the log dir (plugins/profile/<run>/...)
    assert glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True), os.listdir(log_dir)


def test_profiler_server_start_idempotent():
    port = profiler.start_server()
    assert profiler.start_server() == port  # same port on second call
    assert profiler.server_counters() == {"profiler_server_up_max": 1}


def test_profiler_server_failure_does_not_latch(monkeypatch):
    """A failed start must leave the next call free to retry (transient
    bind races at bring-up must not permanently cost capture capability),
    while the heartbeat counter records the last outcome."""
    import jax

    monkeypatch.setattr(profiler, "_server_port", None)
    monkeypatch.setattr(profiler, "_server_state", None)
    assert profiler.server_counters() == {}  # never attempted -> no counter

    def boom(port):
        raise RuntimeError("grpc hiccup")

    monkeypatch.setattr(jax.profiler, "start_server", boom)
    assert profiler.start_server() == 0
    assert profiler._server_port is None  # not latched
    assert profiler.server_counters() == {"profiler_server_up_max": 0}

    monkeypatch.setattr(jax.profiler, "start_server", lambda port: None)
    port = profiler.start_server()
    assert port > 0  # the retry succeeded
    assert profiler.server_counters() == {"profiler_server_up_max": 1}


def test_cluster_publishes_profiler_ports():
    from tensorflowonspark_tpu import backend, cluster

    def fn(args, ctx):
        pass

    b = backend.LocalBackend(1)
    try:
        c = cluster.run(b, fn, {}, num_executors=1, profiler=True)
        addrs = c.profiler_addresses()
        assert len(addrs) == 1 and ":" in addrs[0]
        c.shutdown(grace_secs=1)
    finally:
        b.stop()


def test_server_start_waits_for_the_backend(monkeypatch):
    """``jax.profiler.start_server`` creates the backend, so the node
    runtime never calls it first: the start is deferred until the process
    has opened the device itself."""
    import time

    import jax

    from tensorflowonspark_tpu import device_info, profiler

    started = []
    monkeypatch.setattr(profiler, "_server_port", None)
    monkeypatch.setattr(profiler, "_server_state", None)
    monkeypatch.setattr(jax.profiler, "start_server", started.append)
    up = [False]
    monkeypatch.setattr(device_info, "backends_initialized", lambda: up[0])
    thread = profiler.start_server_when_backend_is_up(4242, poll_secs=0.01)
    time.sleep(0.1)
    assert thread.is_alive() and started == []  # no backend yet: no server
    up[0] = True
    thread.join(timeout=5)
    assert not thread.is_alive() and started == [4242]


def test_profiler_does_not_open_the_device_in_the_executor_shell():
    """SPARK mode forks the user fn from the executor shell; with
    ``profiler=True`` the shell used to start the profiler server, which
    created a backend there — on a chip, a second owner.  The fork must see
    no backend, and the user fn's process gets the server."""
    from tensorflowonspark_tpu import backend, cluster

    def fn(args, ctx):
        import time

        from tensorflowonspark_tpu import device_info, profiler

        inherited = device_info.backends_initialized()
        import jax

        jax.devices()
        deadline = time.time() + 10
        while profiler._server_port is None and time.time() < deadline:
            time.sleep(0.05)
        feed = ctx.get_data_feed(train_mode=False)
        while not feed.should_stop():
            rows = feed.next_batch(1)
            feed.batch_results([(inherited, profiler._server_port)
                                for _ in rows])

    b = backend.LocalBackend(1)
    try:
        c = cluster.run(b, fn, {}, num_executors=1, profiler=True,
                        input_mode=cluster.InputMode.SPARK)
        (addr,) = c.profiler_addresses()
        ((inherited, port),) = c.inference(backend.partition([0], 1))
        assert inherited is False
        assert port == int(addr.rsplit(":", 1)[1])
        c.shutdown(grace_secs=1)
    finally:
        b.stop()
