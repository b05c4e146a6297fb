"""Unit tests for the telemetry plane: span tracing, counter merges, the
flight recorder, and the zero-cost-off contract.

The cluster-level legs (HBEAT-carried counters, chaos timelines) are covered
by ``scripts/ci_assert_telemetry.py`` and ``test_chaos.py``; this file pins
the process-local core."""

import json
import os
import signal
import threading
import time

import pytest

from tensorflowonspark_tpu import telemetry


@pytest.fixture(autouse=True)
def _reset_global_tracer():
    """Each test owns the process-global tracer; never leak an enabled one."""
    yield
    telemetry.configure(False)


def _load_trace(tracer):
    path = tracer.flush()
    assert path is not None
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# spans + Chrome-JSON output
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_json_validity(tmp_path):
    tracer = telemetry.Tracer(str(tmp_path))
    with tracer.span("outer", executor_id=1):
        with tracer.span("inner"):
            time.sleep(0.01)
        tracer.instant("marker", step=3)
    doc = _load_trace(tracer)  # json.load raises on an invalid file
    events = {e["name"]: e for e in doc["traceEvents"]}
    assert set(events) >= {"outer", "inner", "marker", "process_name"}
    # complete events carry ts+dur in microseconds; the inner span nests
    # strictly inside the outer one on the same track
    outer, inner = events["outer"], events["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert inner["dur"] >= 0.01 * 1e6
    assert outer["args"] == {"executor_id": 1}
    assert events["marker"]["ph"] == "i"
    assert events["marker"]["args"] == {"step": 3}


def test_span_records_exception_and_still_emits(tmp_path):
    tracer = telemetry.Tracer(str(tmp_path))
    with pytest.raises(ValueError):
        with tracer.span("failing"):
            raise ValueError("boom")
    doc = _load_trace(tracer)
    (event,) = [e for e in doc["traceEvents"] if e["name"] == "failing"]
    assert "boom" in event["args"]["error"]


def test_flush_is_idempotent_and_crash_safe(tmp_path):
    tracer = telemetry.Tracer(str(tmp_path))
    tracer.instant("one")
    path1 = tracer.flush()
    tracer.instant("two")
    path2 = tracer.flush()
    assert path1 == path2  # same per-process file, atomically replaced
    names = {e["name"] for e in json.load(open(path2))["traceEvents"]}
    assert {"one", "two"} <= names
    assert not [p for p in os.listdir(tmp_path) if ".tmp" in p]


def test_ring_buffer_truncates_and_counts_drops(tmp_path):
    tracer = telemetry.Tracer(str(tmp_path), capacity=10)
    for i in range(25):
        tracer.instant("e{}".format(i))
    doc = _load_trace(tracer)
    # newest 10 events survive (+ the metadata record); drops are counted
    names = [e["name"] for e in doc["traceEvents"] if e["name"] != "process_name"]
    assert names == ["e{}".format(i) for i in range(15, 25)]
    assert doc["otherData"]["events_dropped"] == 15


# ---------------------------------------------------------------------------
# counter merge semantics
# ---------------------------------------------------------------------------

def test_merge_counters_sums_and_maxes():
    merged = telemetry.merge_counters([
        {"feed_items": 10, "ring_occupancy_hwm": 100, "feed_stall_secs": 0.5},
        {"feed_items": 7, "ring_occupancy_hwm": 40, "feed_stall_secs": 1.25},
    ])
    assert merged == {"feed_items": 17, "ring_occupancy_hwm": 100,
                      "feed_stall_secs": 1.75}


def test_merge_counters_drops_non_numeric_and_tolerates_junk():
    merged = telemetry.merge_counters([
        {"n": 1, "label": "abc", "flag": True, "depth_max": 3},
        None,
        "not-a-dict",
        {"n": 2, "depth_max": 9, "nested": {"x": 1}},
    ])
    assert merged == {"n": 3, "depth_max": 9}


def test_tracer_counter_add_and_max(tmp_path):
    tracer = telemetry.Tracer(str(tmp_path))
    tracer.counter_add("chunks", 3)
    tracer.counter_add("chunks", 2)
    tracer.counter_max("depth_hwm", 5)
    tracer.counter_max("depth_hwm", 2)
    assert tracer.counters_snapshot() == {"chunks": 5, "depth_hwm": 5}


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_dump_has_all_thread_stacks_and_open_spans(tmp_path):
    tracer = telemetry.Tracer(str(tmp_path))
    release = threading.Event()
    started = threading.Event()

    def _stuck():
        with tracer.span("worker/stuck", task=7):
            started.set()
            release.wait(10)

    t = threading.Thread(target=_stuck, name="stuck-worker")
    t.start()
    try:
        assert started.wait(5)
        path = tracer.dump(reason="unit-test", extra={"k": "v"})
        assert path is not None and os.path.basename(path).startswith("flight-")
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "unit-test"
        assert doc["extra"] == {"k": "v"}
        # the stuck thread's stack and its open span are both attributed
        stuck_keys = [k for k in doc["thread_stacks"] if "stuck-worker" in k]
        assert stuck_keys, doc["thread_stacks"].keys()
        assert any("release.wait" in line or "_stuck" in line
                   for line in doc["thread_stacks"][stuck_keys[0]])
        (spans,) = [v for k, v in doc["open_spans"].items()
                    if "stuck-worker" in k]
        assert spans == [{"name": "worker/stuck", "args": {"task": 7}}]
    finally:
        release.set()
        t.join()


def test_stall_watch_fires_once_past_deadline(tmp_path, monkeypatch):
    tracer = telemetry.configure(True, str(tmp_path))
    dumps = []
    monkeypatch.setattr(tracer, "dump",
                        lambda reason="", extra=None: dumps.append((reason, extra)))
    watch = telemetry.StallWatch("await stalled", deadline=0.05,
                                 extra_fn=lambda: {"registered": 1})
    watch.poke()
    assert dumps == []  # before the deadline: nothing
    time.sleep(0.06)
    watch.poke()
    watch.poke()  # one-shot: the second poke past deadline is a no-op
    assert len(dumps) == 1
    reason, extra = dumps[0]
    assert reason == "await stalled"
    assert extra["registered"] == 1
    assert extra["stalled_secs"] >= 0.05


@pytest.mark.skipif(not hasattr(signal, "SIGUSR1"), reason="no SIGUSR1")
def test_sigusr1_triggers_flight_dump(tmp_path):
    telemetry.configure(True, str(tmp_path))
    assert telemetry.install_sigusr1()
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.time() + 5
        flights = []
        while time.time() < deadline and not flights:
            flights = [p for p in os.listdir(tmp_path)
                       if p.startswith("flight-")]
            time.sleep(0.01)
        assert flights, os.listdir(tmp_path)
        with open(os.path.join(str(tmp_path), flights[0])) as f:
            doc = json.load(f)
        assert doc["reason"] == "SIGUSR1"
        assert doc["thread_stacks"]
    finally:
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)


# ---------------------------------------------------------------------------
# configuration + zero-cost-off
# ---------------------------------------------------------------------------

def test_null_tracer_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    tracer = telemetry.configure(False)
    assert tracer is telemetry.NULL
    assert not tracer.enabled
    with tracer.span("anything", x=1):
        tracer.instant("nope")
    tracer.counter_add("n")
    tracer.flush()
    assert tracer.dump(reason="ignored") is None
    assert os.listdir(tmp_path) == []  # no telemetry dir, no files, nothing
    assert telemetry.install_sigusr1() is False


def test_node_metrics_provider_gated_on_telemetry(tmp_path):
    """Heartbeats carry counters only when the plane is on; off means bare
    beats and no tf_status["telemetry"] latch driver-side."""
    from tensorflowonspark_tpu import node

    class _Mgr:
        def get(self, key):
            return None

        def get_queue(self, qname):
            raise RuntimeError("no queue in this test")

    telemetry.configure(False)
    assert node._node_metrics_provider(_Mgr())() is None
    telemetry.configure(True, str(tmp_path))
    snap = node._node_metrics_provider(_Mgr())()
    assert isinstance(snap, dict)


def test_configure_reuses_same_dir_and_meta_roundtrip(tmp_path):
    t1 = telemetry.configure(True, str(tmp_path))
    t2 = telemetry.configure_from_meta(
        {"telemetry": telemetry.meta_spec(True, str(tmp_path))})
    assert t1 is t2  # same dir + pid: one tracer, one file
    assert telemetry.configure_from_meta({}) is t2  # no spec: keep current
    spec = telemetry.meta_spec(False, None)
    assert spec == {"enabled": False, "dir": None}


def test_configure_from_meta_env_fallback(tmp_path, monkeypatch):
    telemetry.configure(False)
    monkeypatch.setenv(telemetry.TELEMETRY_ENV, "1")
    monkeypatch.setenv(telemetry.TELEMETRY_DIR_ENV, str(tmp_path))
    tracer = telemetry.configure_from_meta({})
    assert tracer.enabled and tracer.out_dir == str(tmp_path)


def test_null_tracer_counter_max_is_noop():
    """Regression: the heartbeat/infeed paths call counter_max on whatever
    get_tracer() returns — the NULL tracer must absorb it, not raise."""
    telemetry.NULL.counter_max("depth_hwm", 5)
    telemetry.NULL.counter_add("n", 2)


# ---------------------------------------------------------------------------
# the bring-up's account (telemetry.Bringup)
# ---------------------------------------------------------------------------

def _told(account):
    snap = account.snapshot()
    phases = {p: snap["bringup_%s_us" % p] for p in telemetry.BRINGUP_PHASES}
    return phases, snap["bringup_wall_us"]


def test_bringup_phases_sum_to_last_mark_less_first(monkeypatch):
    """Every instant between the first mark and the closing one belongs to
    the phase marked last: the phases sum to the wall time to the
    microsecond, a phase entered twice adds up, and nothing is told before
    the account is closed."""
    clock = iter([1000, 1250, 1300, 2300, 2301, 9000, 9500])
    monkeypatch.setattr(telemetry, "wall_time_us", lambda: next(clock))
    account = telemetry.Bringup()
    account.begin()                                   # 1000 driver
    account.mark("spawn")                             # 1250
    assert account.mark("user") == "spawn"            # 1300
    assert account.mark("trainer_init") == "user"     # 2300
    account.mark("user")                              # 2301
    account.mark("first_dispatch")                    # 9000
    assert account.snapshot() == {} and account.open
    assert account.close() is False and not account.open   # 9500
    phases, wall = _told(account)
    assert phases == {"driver": 250, "spawn": 50, "node": 0, "rendezvous": 0,
                      "launch": 0, "user": 1000 + 6699, "trainer_init": 1,
                      "first_batch": 0, "first_dispatch": 500}
    assert wall == 8500 == sum(phases.values())


def test_bringup_mark_after_the_first_dispatch_changes_nothing():
    account = telemetry.Bringup()
    account.begin()
    account.mark("user")
    account.close()
    before, marks = account.snapshot(), account.export()
    assert account.mark("trainer_init") is None   # nothing to hand back to
    account.mark(None)
    account.close()
    assert account.snapshot() == before and account.export() == marks


def test_bringup_wall_clock_stepping_back_never_makes_a_phase_negative(
        monkeypatch):
    clock = iter([5000, 4000, 6000])
    monkeypatch.setattr(telemetry, "wall_time_us", lambda: next(clock))
    account = telemetry.Bringup()
    account.begin()
    account.mark("spawn")        # the clock stepped back: held at 5000
    account.close()
    phases, wall = _told(account)
    assert phases["driver"] == 0 and phases["spawn"] == 1000 == wall


def test_bringup_driver_marks_arrive_through_cluster_meta():
    """The driver's marks ride ``cluster_meta`` as plain lists (they pass
    through pickle or JSON), the executor's account goes on from them, and
    the sum still holds across the two."""
    driver = telemetry.Bringup()
    driver.begin()
    time.sleep(0.002)
    driver.mark("spawn")
    meta = json.loads(json.dumps({"bringup": driver.export()}))
    time.sleep(0.002)
    node = telemetry.Bringup()
    node.mark("user")            # a mark of an earlier life of the process
    node.adopt(meta["bringup"])
    node.mark("node")
    node.mark("rendezvous")
    node.close()
    phases, wall = _told(node)
    assert phases["driver"] >= 2000 and phases["spawn"] >= 2000
    assert phases["user"] == 0
    assert sum(phases.values()) == wall
    assert wall == node.marks[-1][0] - driver.marks[0][0]


def test_bringup_two_hosts_clocks_apart_shorten_spawn_and_nothing_else():
    """A driver whose clock is ahead of the executor's: ``spawn`` is floored
    at 0 and the driver's own phase keeps its length."""
    ahead = int(telemetry.wall_time_us()) + 60 * 10 ** 6
    node = telemetry.Bringup()
    node.adopt([[ahead - 700, "driver"], [ahead, "spawn"]])
    node.mark("node")
    node.close()
    phases, wall = _told(node)
    assert phases["driver"] == 700 and 0 <= phases["spawn"] < 10 ** 6
    assert sum(phases.values()) == wall


def test_bringup_marks_made_in_a_forked_child_go_on_with_the_parents(
        monkeypatch):
    """SPARK mode: the executor's start task forks the process that runs
    the user function, and the child inherits the process's account."""
    import multiprocessing

    account = telemetry.Bringup()
    monkeypatch.setattr(telemetry, "bringup", account)
    account.begin()
    account.mark("spawn")
    account.mark("node")
    account.mark("launch")
    parent_marks = account.export()

    def child(conn):
        telemetry.bringup.mark("user")
        telemetry.bringup.mark("first_dispatch")
        telemetry.bringup.close()
        conn.send((telemetry.bringup.snapshot(), telemetry.bringup.export()))

    here, there = multiprocessing.get_context("fork").Pipe()
    p = multiprocessing.get_context("fork").Process(target=child,
                                                    args=(there,))
    p.start()
    snap, marks = here.recv()
    p.join(10)
    assert marks[:len(parent_marks)] == parent_marks
    phases = {k: v for k, v in snap.items() if k != "bringup_wall_us"}
    assert sum(phases.values()) == snap["bringup_wall_us"] \
        == marks[-1][0] - marks[0][0]
    # the parent's own account stays open: it never dispatched
    assert account.snapshot() == {} and account.open


def test_bringup_span_and_instant_mark_and_trace_in_one_call(tmp_path,
                                                             monkeypatch):
    """A phase begins where a span or an instant of the program already
    stands: one call makes the mark and, with telemetry on, the event of
    today's name; a phase of ``None`` (a node in a driver thread) marks
    nothing and still traces."""
    tracer = telemetry.configure(True, str(tmp_path))
    account = telemetry.Bringup()
    account.begin()
    account.instant("node", "node/role_assigned", executor_id=0)
    with account.span("rendezvous", "node/register", executor_id=0):
        pass
    with account.span(None, "node/await"):
        pass
    assert [p for _, p in account.export()] == ["driver", "node",
                                                "rendezvous"]
    names = [e["name"] for e in _load_trace(tracer)["traceEvents"]]
    assert {"node/role_assigned", "node/register", "node/await"} <= set(names)
    # telemetry off: the same calls mark all the same
    telemetry.configure(False)
    with account.span("launch", "node/user_fn"):
        pass
    assert account.current() == "launch"
