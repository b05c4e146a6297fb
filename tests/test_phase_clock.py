"""``telemetry.PhaseClock`` (every instant in exactly one phase, so the
phases sum to the clock's wall time) and the module-level ``telemetry.span``
(the tracer's span, plus a profiler annotation only where jax is already
imported)."""

import random
import sys
import threading
import time
import types

import pytest

from tensorflowonspark_tpu import telemetry

PHASES = ("idle", "a", "b", "c")


@pytest.fixture(autouse=True)
def _reset_global_tracer():
    yield
    telemetry.configure(False)


def _total(snapshot):
    return sum(snapshot.values())


def test_starts_on_the_first_phase_and_names_every_phase():
    clock = telemetry.PhaseClock(PHASES)
    time.sleep(0.01)
    snap = clock.snapshot("x_")
    assert list(snap) == ["x_idle_us", "x_a_us", "x_b_us", "x_c_us"]
    assert snap["x_idle_us"] >= 10000
    assert snap["x_a_us"] == snap["x_b_us"] == snap["x_c_us"] == 0
    assert all(isinstance(v, int) for v in snap.values())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_phases_sum_to_the_wall_time_after_any_switches(seed):
    rng = random.Random(seed)
    t0 = time.monotonic_ns()
    clock = telemetry.PhaseClock(PHASES)
    for _ in range(2000):
        clock.switch(rng.choice(PHASES))
        if rng.random() < 0.01:
            time.sleep(0.001)
    snap = clock.snapshot()
    wall_us = (time.monotonic_ns() - t0) / 1e3
    assert abs(_total(snap) - wall_us) < 1000, (snap, wall_us)
    assert all(v >= 0 for v in snap.values())


def test_deltas_add_up_to_the_snapshot():
    clock = telemetry.PhaseClock(PHASES)
    total = dict.fromkeys(clock.snapshot("x_"), 0)
    for phase in ("a", "b", "a", "c", "idle"):
        clock.switch(phase)
        time.sleep(0.002)
        part = clock.delta("x_")
        assert list(part) == list(total)
        assert all(isinstance(v, int) and v >= 0 for v in part.values())
        total = {k: total[k] + part[k] for k in total}
    snap = clock.snapshot("x_")
    assert total["x_a_us"] >= 4000 and total["x_c_us"] >= 2000
    # the phases that are over: exactly; the current one: up to this instant
    for key in ("x_a_us", "x_b_us", "x_c_us"):
        assert total[key] == snap[key], key
    assert 0 <= snap["x_idle_us"] - total["x_idle_us"] < 1000
    # a second reader of the same clock is not disturbed by delta()
    assert _total(clock.snapshot()) >= _total(snap)


def test_switch_books_on_the_phase_that_was_current():
    clock = telemetry.PhaseClock(PHASES)
    clock.switch("a")
    time.sleep(0.02)
    t = clock.switch("b")
    assert isinstance(t, int)
    snap = clock.snapshot()
    assert snap["a_us"] >= 20000
    assert snap["b_us"] < 20000 and snap["c_us"] == 0


def test_an_unknown_phase_is_refused():
    clock = telemetry.PhaseClock(PHASES)
    with pytest.raises(KeyError):
        clock.switch("no_such_phase")
    # and the refusal booked nothing anywhere else
    assert set(clock.snapshot()) == {p + "_us" for p in PHASES}


def test_snapshot_from_another_thread_never_raises_nor_goes_backwards():
    clock = telemetry.PhaseClock(PHASES)
    done = threading.Event()
    problems = []

    def reader():
        last = clock.snapshot()
        last_total = _total(last)
        while not done.is_set():
            try:
                snap = clock.snapshot()
            except Exception as e:  # the test's whole point
                problems.append(repr(e))
                return
            total = _total(snap)
            if total < last_total or any(snap[k] < last[k] for k in snap):
                problems.append((last, snap))
                return
            last, last_total = snap, total

    t = threading.Thread(target=reader)
    t.start()
    for i in range(100000):
        clock.switch(PHASES[i % len(PHASES)])
    done.set()
    t.join(30)
    assert not t.is_alive()
    assert problems == []


# ---------------------------------------------------------------------------
# telemetry.span
# ---------------------------------------------------------------------------

class _FakeAnnotation(object):
    entered = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.entered.append("/" + self.name)
        return False


@pytest.fixture
def no_jax(monkeypatch):
    for name in ("jax", "jax.profiler"):
        monkeypatch.delitem(sys.modules, name, raising=False)


@pytest.fixture
def fake_jax(monkeypatch):
    _FakeAnnotation.entered = []
    profiler = types.ModuleType("jax.profiler")
    profiler.TraceAnnotation = _FakeAnnotation
    jax = types.ModuleType("jax")
    jax.profiler = profiler
    monkeypatch.setitem(sys.modules, "jax", jax)
    monkeypatch.setitem(sys.modules, "jax.profiler", profiler)
    return _FakeAnnotation


def test_span_without_jax_is_the_tracers_alone(no_jax, tmp_path):
    assert telemetry.span("train/dispatch") is telemetry._NULL_SPAN
    assert "jax" not in sys.modules     # and looking did not import it
    tracer = telemetry.configure(True, str(tmp_path))
    with telemetry.span("train/dispatch", kind="single") as s:
        assert isinstance(s, telemetry._Span)
    assert "jax" not in sys.modules
    (event,) = [e for e in tracer._events if e["ph"] == "X"]
    assert event["name"] == "train/dispatch"
    assert event["args"] == {"kind": "single"}


def test_span_with_jax_imported_enters_one_annotation(fake_jax, tmp_path):
    with telemetry.span("infeed/assemble"):
        assert fake_jax.entered == ["tfos/infeed/assemble"]
    assert fake_jax.entered == ["tfos/infeed/assemble",
                                "/tfos/infeed/assemble"]
    # telemetry on: both, under the names each had; attributes stay with
    # the Chrome JSON
    tracer = telemetry.configure(True, str(tmp_path))
    fake_jax.entered = []
    with telemetry.span("infeed/device_put", rows=8):
        pass
    assert fake_jax.entered == ["tfos/infeed/device_put",
                                "/tfos/infeed/device_put"]
    (event,) = [e for e in tracer._events if e["ph"] == "X"]
    assert event["name"] == "infeed/device_put"
    assert event["args"] == {"rows": 8}


def test_span_with_a_half_imported_jax_is_the_tracers_alone(monkeypatch):
    """Another thread is still importing jax: ``sys.modules`` has the
    package, the profiler is not there yet."""
    monkeypatch.setitem(sys.modules, "jax", types.ModuleType("jax"))
    monkeypatch.delitem(sys.modules, "jax.profiler", raising=False)
    assert telemetry.span("feed/wait") is telemetry._NULL_SPAN
    monkeypatch.setitem(sys.modules, "jax.profiler",
                        types.ModuleType("jax.profiler"))
    assert telemetry.span("feed/wait") is telemetry._NULL_SPAN


def test_annotation_is_the_profilers_half_alone(fake_jax, tmp_path):
    """A region entered once a chunk: never in the tracer's bounded buffer,
    telemetry on or off."""
    tracer = telemetry.configure(True, str(tmp_path))
    with telemetry.annotation("feed/read"):
        assert fake_jax.entered == ["tfos/feed/read"]
    assert fake_jax.entered == ["tfos/feed/read", "/tfos/feed/read"]
    assert [e for e in tracer._events if e["ph"] == "X"] == []


def test_annotation_without_jax_is_nothing(no_jax, tmp_path):
    telemetry.configure(True, str(tmp_path))
    assert telemetry.annotation("feed/read") is telemetry._NULL_SPAN
    assert "jax" not in sys.modules


def test_span_exception_reaches_both(fake_jax, tmp_path):
    tracer = telemetry.configure(True, str(tmp_path))
    with pytest.raises(ValueError):
        with telemetry.span("train/on_steps"):
            raise ValueError("boom")
    assert fake_jax.entered[-1] == "/tfos/train/on_steps"
    (event,) = [e for e in tracer._events if e["ph"] == "X"]
    assert "boom" in event["args"]["error"]
