"""LocalBackend tests: the built-in stand-in for a Spark cluster."""

import os
import re
import threading
import time
import types

import pytest

from tensorflowonspark_tpu import backend, cluster, fault


def test_partition_even_spread():
    assert backend.partition(range(10), 3) == [[0, 1, 2], [3, 4, 5], [6, 7, 8, 9]]
    assert backend.partition([], 2) == [[], []]
    assert backend.partition([1], 3) == [[], [], [1]]


@pytest.fixture(scope="module")
def local_backend():
    b = backend.LocalBackend(2)
    yield b
    b.stop()


def test_map_partitions(local_backend):
    parts = backend.partition(range(8), 4)
    results = local_backend.map_partitions(parts, lambda it: [x * x for x in it])
    assert results == [[0, 1], [4, 9], [16, 25], [36, 49]]


def test_task_error_propagates(local_backend):
    def boom(it):
        raise ValueError("injected failure")

    with pytest.raises(RuntimeError, match="injected failure"):
        local_backend.foreach_partition([[1]], boom)


def test_executors_persist_across_jobs(local_backend):
    """State written by one job is visible to the next on the same executor —
    the property the executor-id handshake relies on (reference
    ``util.py:66-75``, ``test/README.md:10``)."""

    def write_marker(it):
        import time

        with open("marker.txt", "w") as f:
            f.write(str(os.getpid()))
        # Hold the task slot briefly so the second task must use the other
        # executor (cluster start tasks get this for free from the rendezvous
        # barrier; see node.run).
        time.sleep(1.0)
        return [os.getcwd()]

    def read_marker(it):
        with open("marker.txt") as f:
            return [(os.getcwd(), f.read())]

    cwds = [r[0] for r in
            local_backend.map_partitions([[0], [1]], write_marker)]
    assert len(set(cwds)) == 2  # each executor has its own working dir
    seen = [r[0][0] for r in local_backend.map_partitions([[0], [1]], read_marker)]
    assert sorted(seen) == sorted(cwds)


def test_async_job_handle(local_backend):
    handle = local_backend.foreach_partition_async(
        [[1], [2]], lambda it: [sum(it)])
    results = handle.wait(timeout=30)
    assert sorted(r[0] for r in results) == [1, 2]
    assert handle.done()


def test_more_partitions_than_executors(local_backend):
    parts = backend.partition(range(12), 6)
    results = local_backend.map_partitions(parts, lambda it: [sum(it)])
    assert [r[0] for r in results] == [1, 5, 9, 13, 17, 21]


# ---------------------------------------------------------------------------
# one task of look-ahead for feed jobs: the next partition travels into the
# executor while this one runs
# ---------------------------------------------------------------------------

class _UnpickledAt(object):
    """An item that unpickles as the clock reading of its unpickling (the
    executor's receiver thread does that; ``time.monotonic`` is one clock for
    every process of the machine)."""

    def __reduce__(self):
        return (time.monotonic, ())


def _timed(hold):
    """A task that returns ``[entry, exit, pid, items]`` and holds its
    executor for ``hold`` seconds."""

    def fn(it):
        import os
        import time

        entry = time.monotonic()
        items = list(it)
        time.sleep(hold)
        return [entry, time.monotonic(), os.getpid(), items]

    return fn


def _threads_left(b, within=5.0):
    """Names of the dispatch, task and reader threads of ``b`` that still
    run ``within`` seconds from now (none, if all is well)."""
    deadline = time.monotonic() + within
    while True:
        left = sorted(t.name for t in threading.enumerate()
                      if t.name.startswith(("task-", "job-dispatch"))
                      or t in b._readers)
        if not left or time.monotonic() > deadline:
            return left
        time.sleep(0.05)


@pytest.fixture
def one_executor():
    b = backend.LocalBackend(1)
    yield b
    b.stop()


@pytest.mark.parametrize("look_ahead", [True, False])
def test_look_ahead_unpickles_the_next_task_while_this_one_runs(
        one_executor, look_ahead):
    """(a), (b), (c): with look-ahead task k+1's items are unpickled before
    task k returns; without, after; either way the tasks of one executor run
    one at a time, in dispatch order."""
    parts = [[_UnpickledAt()] for _ in range(4)]
    runs = one_executor.foreach_partition_async(
        parts, _timed(0.15), look_ahead=look_ahead).wait(30)
    for k in range(3):
        (_, exit_k, _, _), (entry_next, _, _, (unpickled_next,)) = (
            runs[k], runs[k + 1])
        assert runs[k][0] < exit_k <= entry_next     # never interleaved
        if look_ahead:
            assert unpickled_next < exit_k, (k, runs)
        else:
            assert unpickled_next > exit_k, (k, runs)
    assert len({pid for _, _, pid, _ in runs}) == 1


def test_a_free_executor_beats_look_ahead(local_backend):
    """(d): two executors free, two tasks: one each (the start job's shape
    would deadlock its rendezvous otherwise), even for a job that asks."""
    runs = local_backend.foreach_partition_async(
        [[0], [1]], _timed(0.3), look_ahead=True).wait(30)
    assert runs[0][2] != runs[1][2]
    assert runs[1][0] < runs[0][1]      # side by side, not one after the other


def test_no_look_ahead_while_another_job_holds_an_executor(local_backend):
    """An executor busy with another job's task (a start task about to
    return) may come free, and a free executor is preferred: the task waits
    for one, as it did before there was look-ahead."""
    other = local_backend.foreach_partition_async([[0]], _timed(0.4))
    time.sleep(0.05)            # the other job holds one executor
    runs = local_backend.foreach_partition_async(
        [[_UnpickledAt()], [_UnpickledAt()]], _timed(0.1),
        look_ahead=True).wait(30)
    (held,) = other.wait(30)
    assert runs[0][2] == runs[1][2] != held[2]   # both on the free executor
    assert runs[1][3][0] > runs[0][1]            # the second was not sent ahead


def _ran(b):
    return sorted(n for n in os.listdir(os.path.join(b.workdir_root,
                                                     "executor-0"))
                  if n.startswith("ran-"))


def test_a_failed_task_skips_the_one_waiting_behind_it(one_executor):
    """(e): task k raises: the waiting k+1 is not run, and says so in the
    words the retry policy knows; the job reports the first error."""

    def fn(it):
        import time

        (item,) = it
        time.sleep(0.2)
        if item == 0:
            raise ValueError("boom in task 0")
        open("ran-{}".format(item), "w").close()

    handle = one_executor.foreach_partition_async(
        [[0], [1], [2]], fn, look_ahead=True)
    handle.wait_settled(30)
    errors = dict(handle.failed_tasks())
    assert "boom in task 0" in errors[0]
    assert fault.RetryPolicy().is_retryable(errors[1])
    assert errors[1] == errors[2] == backend.TASK_SKIPPED
    with pytest.raises(RuntimeError, match="boom in task 0"):
        handle.wait(1)
    assert _ran(one_executor) == []
    # the executor serves the next job as ever
    assert one_executor.map_partitions([[3]], lambda it: list(it)) == [[3]]


def test_a_supervised_feed_refeeds_the_failed_and_the_skipped_once_each(
        one_executor):
    """(e), through ``cluster._dispatch_with_retry``: a retryable failure
    with a task waiting behind it; every partition's rows arrive exactly
    once."""

    def fn(it):
        import os
        import time

        rows = list(it)
        time.sleep(0.1)
        if rows[0] == 0 and not os.path.exists("failed-once"):
            open("failed-once", "w").close()
            raise ConnectionError("connection reset by a test")
        with open("ran-{}".format(rows[0]), "a") as f:
            f.write("".join("{}\n".format(r) for r in rows))

    driver = types.SimpleNamespace(backend=one_executor, tf_status={})
    parts = backend.partition(range(12), 4)
    cluster.TPUCluster._dispatch_with_retry(
        driver, parts, fn, fault.RetryPolicy(max_attempts=3,
                                             initial_backoff=0.05))
    fed = []
    for name in _ran(one_executor):
        with open(os.path.join(one_executor.workdir_root, "executor-0",
                               name)) as f:
            fed += [int(line) for line in f]
    assert sorted(fed) == list(range(12))


def test_a_dead_executor_takes_its_running_and_its_waiting_task(one_executor):
    """(f): SIGKILL mid-task with one waiting: both fail retryably, the job
    settles, and the two partitions complete on a second executor."""

    def fn(it):
        import os
        import signal
        import time

        (item,) = it
        time.sleep(0.3)
        if item == 0:
            os.kill(os.getpid(), signal.SIGKILL)
        return [item]

    handle = one_executor.foreach_partition_async(
        [[0], [1]], fn, look_ahead=True)
    handle.wait_settled(30)
    errors = dict(handle.failed_tasks())
    for task_id in (0, 1):
        assert re.search(r"executor 0 died while running task %d" % task_id,
                         errors[task_id]), errors
        assert fault.RetryPolicy().is_retryable(errors[task_id])
    second = one_executor.provision_replacement()
    assert one_executor.run_on(second, lambda it: list(it), [7]).wait(30) \
        == [[7]]
    retry = one_executor.foreach_partition_async(
        [[1], [2]], fn, look_ahead=True)
    assert retry.wait(30) == [[1], [2]]


def test_a_dead_executor_whose_child_holds_the_pipe_settles_too(one_executor):
    """(f), the case ``_hang_up`` is for: a child of the executor keeps the
    pipe's other end open (every node forks a manager server), so the pipe
    never ends; the task waiting behind is still being sent (nobody reads
    it any more).  Both fail, nothing blocks for good."""

    def fn(it):
        import os
        import signal
        import time

        items = list(it)
        if os.fork() == 0:      # holds every descriptor of the executor
            deadline = time.monotonic() + 30
            while (not os.path.exists("release")
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            os._exit(0)
        if items[0] == 0:
            os.kill(os.getpid(), signal.SIGKILL)
        return [len(items)]

    # the second message outgrows any socket buffer: its sender blocks
    big = [1, b"x" * (32 << 20)]
    handle = one_executor.foreach_partition_async(
        [[0], big], fn, look_ahead=True)
    try:
        handle.wait_settled(10)
        errors = dict(handle.failed_tasks())
        assert sorted(errors) == [0, 1]
        assert all(re.search(r"executor 0 died", e) for e in errors.values())
        assert _threads_left(one_executor) == []
    finally:
        open(os.path.join(one_executor.workdir_root, "executor-0",
                          "release"), "w").close()


class _Unloadable(object):
    """An item the executor cannot unpickle."""

    def __reduce__(self):
        return (int, ("not a number",))


@pytest.mark.parametrize("look_ahead", [True, False])
def test_a_message_that_does_not_unpickle_ends_the_executor_in_its_turn(
        one_executor, look_ahead):
    """As it always did, and not before the task that is running has
    answered: the receiver thread leaves the error to the main thread."""
    handle = one_executor.foreach_partition_async(
        [[0], [_Unloadable()]], _timed(0.2), look_ahead=look_ahead)
    handle.wait_settled(30)
    errors = dict(handle.failed_tasks())
    assert sorted(errors) == [1]
    assert re.search(r"executor 0 died while running task 1", errors[1])
    assert handle.results[0][3] == [0]


def test_nothing_is_sent_ahead_to_a_fenced_executor(one_executor):
    """(g): ``exclude(i)`` while a job runs on ``i``: the task already
    waiting there runs (as one in flight does), the next is not sent."""
    handle = one_executor.foreach_partition_async(
        [[0], [1], [2]], _timed(0.3), look_ahead=True)
    time.sleep(0.1)             # task 0 runs, task 1 waits behind it
    one_executor.exclude(0)
    handle.wait_settled(30)
    errors = dict(handle.failed_tasks())
    assert sorted(errors) == [2]
    assert "unschedulable: no live executors" in errors[2]
    assert [r[3] for r in handle.results[:2]] == [[0], [1]]


def test_stop_with_a_task_waiting(tmp_path):
    """(h): ``stop()`` returns, the waiting task is dropped, no process and
    no thread of the backend is left."""

    def fn(it):
        import time

        (item,) = it
        open("ran-{}".format(item), "w").close()
        time.sleep(0.4)

    b = backend.LocalBackend(1, workdir_root=str(tmp_path))
    try:
        b.foreach_partition_async([[0], [1], [2]], fn, look_ahead=True)
        time.sleep(0.2)         # task 0 runs, task 1 waits behind it
        t0 = time.monotonic()
        b.stop()
        assert time.monotonic() - t0 < 4
        assert not any(p.is_alive() for p in b._procs)
        assert _ran(b) == ["ran-0"]
        assert _threads_left(b) == []
    finally:
        b.stop()


# ---------------------------------------------------------------------------
# a message's large buffers travel beside the pipe, in a shared-memory
# segment that the driver owns
# ---------------------------------------------------------------------------

SEGMENT = "memfd:tfos-handover"


def _family(pid):
    """``pid`` and every live descendant of it."""
    parent_of = {}
    for name in os.listdir("/proc"):
        if name.isdigit():
            try:
                with open("/proc/{}/stat".format(name)) as f:
                    parent_of[int(name)] = int(
                        f.read().rsplit(")", 1)[1].split()[1])
            except OSError:
                pass    # it went meanwhile
    family, grew = {pid}, True
    while grew:
        more = {p for p, parent in parent_of.items() if parent in family}
        grew = not more <= family
        family |= more
    return family


def _segments_held(pid):
    """How many descriptors and mappings of hand-over segments ``pid``
    holds (0 for a process that is gone)."""
    held = 0
    try:
        for fd in os.listdir("/proc/{}/fd".format(pid)):
            try:
                held += SEGMENT in os.readlink(
                    "/proc/{}/fd/{}".format(pid, fd))
            except OSError:
                pass
        with open("/proc/{}/maps".format(pid)) as f:
            held += sum(SEGMENT in line for line in f)
    except OSError:
        pass
    return held


def _segments_left(within=5.0):
    """What this process and its descendants (the executors, their
    children) still hold of hand-over segments ``within`` seconds from now
    at the latest: ``{pid: count}``, empty if all is well."""
    deadline = time.monotonic() + within
    while True:
        left = {pid: n for pid in _family(os.getpid())
                for n in [_segments_held(pid)] if n}
        if not left or time.monotonic() > deadline:
            return left
        time.sleep(0.05)


@pytest.fixture(autouse=True)
def no_segment_is_left():
    """After every test of this file: no shared-memory object that a
    hand-over made is held by this process, an executor or a child of one
    (a memory file has no name: what no process holds is gone)."""
    yield
    assert _segments_left() == {}


def _mixed_rows():
    """Rows as users send them: arrays above the size that travels beside
    the pipe (C and Fortran order, several dtypes, one read-only), small
    ones, ``bytes``, scalars, an array that is not contiguous and one of
    size zero.  Returns ``(rows, bytes_of_the_large_contiguous_arrays)``."""
    import numpy as np

    rng = np.random.default_rng(31)
    image = rng.integers(0, 255, (256, 256, 3), dtype=np.uint8)
    wide = rng.standard_normal((130, 131))               # float64, 136 KB
    fortran = np.asfortranarray(
        rng.standard_normal((96, 200)).astype(np.float32))
    frozen = rng.integers(-5, 5, (40000,), dtype=np.int16)
    frozen.flags.writeable = False
    strided = rng.standard_normal((600, 300))[::2, ::3]  # 240 KB, gaps
    small = np.arange(12, dtype=np.int32).reshape(3, 4)
    empty = np.zeros((0, 7), np.float32)
    rows = [(image, 7, b"label-7"), (wide, 2.5, None), (fortran, "text"),
            (frozen, empty), (strided, small, b"x" * 100000), 41,
            (image[:, :, 1].copy(), [1, 2, {"k": small}])]
    large = [image, wide, fortran, frozen, image[:, :, 1]]
    return rows, sum(a.nbytes for a in large)


def _arrays(x):
    """The numpy arrays in ``x``, in the order of its nesting."""
    if isinstance(x, (list, tuple)):
        return [a for item in x for a in _arrays(item)]
    if isinstance(x, dict):
        return [a for item in x.values() for a in _arrays(item)]
    return [x] if hasattr(x, "flags") else []


def _flags(x):
    return [(a.flags.writeable, a.flags.c_contiguous, a.flags.f_contiguous,
             a.flags.aligned) for a in _arrays(x)]


def _same(got, want):
    """Equal in values, dtypes, shapes, order and nesting."""
    import numpy as np

    if isinstance(want, np.ndarray):
        return (isinstance(got, np.ndarray) and got.dtype == want.dtype
                and got.shape == want.shape and np.array_equal(got, want))
    if isinstance(want, (list, tuple)):
        return (type(got) is type(want) and len(got) == len(want)
                and all(_same(g, w) for g, w in zip(got, want)))
    if isinstance(want, dict):
        return (type(got) is dict and list(got) == list(want)
                and all(_same(got[k], want[k]) for k in want))
    return type(got) is type(want) and got == want


def _echo(it):
    """How the task's rows came in, what kind of arrays they are (whether
    each can be written to, its order, its alignment), and the rows
    themselves (they travel back in the reply)."""
    from tensorflowonspark_tpu import backend

    rows = list(it)
    flags = _flags(rows)
    for a in _arrays(rows):
        if a.flags.writeable and a.size:
            a.flat[0] = a.flat[0]   # its own segment, nobody else's
    return [tuple(backend.task_handover()), flags, rows]


@pytest.mark.parametrize("way", ["beside", "in_band"])
def test_a_partition_of_arrays_arrives_as_it_was_sent(
        one_executor, monkeypatch, way):
    """(a): values, dtypes, shapes, order; ``task_handover`` says how it
    came.  ``in_band`` is the fall-back the code takes by itself where no
    segment can be made (no shared memory, no room): the message then
    travels whole, as it always did."""
    if way == "in_band":
        def no_room(buffers):
            raise OSError(28, "No space left on device")

        monkeypatch.setattr(backend, "_Segment", no_room)
    import pickle

    rows, large = _mixed_rows()
    (handover, flags, got), = one_executor.map_partitions([rows], _echo)
    assert _same(got, rows)
    # each array is what pickle alone would have made of it: read-only if
    # it was, in its order, aligned
    assert flags == _flags(pickle.loads(pickle.dumps(rows, 5)))
    assert [f[0] for f in flags] == [True, True, True, False, True, True,
                                     True, True, True]
    came = backend.Handover(*handover)
    rest = 240000 + 100000      # the array with gaps, the ``bytes``
    everything = large + rest
    if way == "beside":
        assert came.oob_bytes == large
        assert rest < came.inband_bytes < rest + 20000
    else:
        assert came.oob_bytes == 0
        assert everything < came.inband_bytes < everything + 20000
    assert not came.ahead and not came.ready    # nobody asked
    assert 0 < came.us < 30e6


def test_a_message_with_no_large_buffer_crosses_the_pipe_whole(one_executor):
    """(b): what crosses the pipe is the message's own pickle, in one
    piece, and no segment is made: arrays under the size, ``bytes`` of any
    size, scalars, an array that is not contiguous."""
    import pickle

    import numpy as np

    just_under = np.zeros(backend._BESIDE_MIN - 1, np.uint8)
    strided = np.ones((400, 400))[:, ::2]
    items = [just_under, b"y" * (1 << 20), 3, "s", strided]
    msg = (0, 0, False, 1.5, b"fn", items)
    data, segment = backend._pack(msg)
    assert segment is None
    assert _same(pickle.loads(data), msg)
    (handover, _, got), = one_executor.map_partitions([items], _echo)
    assert _same(got, items)
    came = backend.Handover(*handover)
    assert came.oob_bytes == 0
    assert came.inband_bytes > (1 << 20) + just_under.nbytes + strided.nbytes
    # one byte more in one array, and that array alone goes beside
    data, segment = backend._pack(
        (0, 0, False, 1.5, b"fn", [np.zeros(backend._BESIDE_MIN, np.uint8),
                                   just_under]))
    try:
        assert segment.lengths == [backend._BESIDE_MIN]
        assert isinstance(pickle.loads(data), backend._Beside)
    finally:
        segment.close()


def _big(k, hold=0.0, fail=False, kill=False):
    """A partition of two 256 KB arrays filled with ``k``."""
    import numpy as np

    return [np.full((1 << 16,), k, np.int32), np.full((1 << 18,), k, np.uint8),
            {"hold": hold, "fail": fail, "kill": kill}]


def _check_big(it):
    import os
    import signal
    import time

    a, b, todo = it
    time.sleep(todo["hold"])
    if todo["kill"]:
        os.kill(os.getpid(), signal.SIGKILL)
    if todo["fail"]:
        raise ValueError("boom in a big task")
    assert (a == a[0]).all() and (b == a[0]).all()
    return [int(a[0])]


@pytest.mark.parametrize(
    "case", ["answered", "skipped", "killed", "excluded", "stopped"])
def test_no_segment_outlives_its_task(tmp_path, case):
    """(c): the driver owns the segment and lets go of it when the task is
    answered, when a waiting task was skipped after the failure of the one
    before it, when the executor was killed with one task running and one
    waiting, after ``exclude``, and at ``stop()`` with a task waiting.
    While two tasks are in flight the driver holds two (so the probe can
    see one), and a task's mapping goes with its rows."""
    b = backend.LocalBackend(1, workdir_root=str(tmp_path))
    try:
        first = dict(hold=0.5, fail=case == "skipped", kill=case == "killed")
        handle = b.foreach_partition_async(
            [_big(0, **first), _big(1), _big(2)], _check_big,
            look_ahead=True)
        executor = b._procs[0].pid
        deadline = time.monotonic() + 10
        while (not (_segments_held(os.getpid()) == 2
                    and _segments_held(executor))
               and time.monotonic() < deadline):
            time.sleep(0.01)    # task 0 runs, task 1 waits behind it
        assert _segments_held(os.getpid()) == 2
        assert _segments_held(executor) >= 1
        if case == "excluded":
            b.exclude(0)
        if case == "stopped":
            b.stop()
        else:
            handle.wait_settled(30)
        errors = dict(handle.failed_tasks())
        if case == "answered":
            assert handle.results == [[0], [1], [2]]
        elif case == "skipped":
            assert errors[1] == errors[2] == backend.TASK_SKIPPED
        elif case == "killed":
            assert sorted(errors) == [0, 1, 2]
        elif case == "excluded":
            assert handle.results[:2] == [[0], [1]] and sorted(errors) == [2]
        if case == "stopped":
            assert _threads_left(b) == []
        assert _segments_left() == {}
        if case in ("answered", "skipped", "excluded"):
            assert b._procs[0].is_alive()   # and it holds no mapping
    finally:
        b.stop()
    assert _segments_left() == {}


def test_a_kept_row_outlives_the_next_partitions_arrival(one_executor):
    """(d): a segment is a task's own, never reused: a task that keeps a
    row past the arrival of the next partitions still reads its own bytes,
    and each kept row keeps its own mapping alive."""

    def keep(it):
        import builtins
        import time

        a, b, _ = it
        kept = builtins.__dict__.setdefault("_kept_rows", [])
        kept.append(a)
        time.sleep(0.2)         # the next partition arrives meanwhile
        with open("/proc/self/maps") as f:
            mapped = sum("memfd:tfos-handover" in line for line in f)
        return [[(int(r[0]), int(r.min()), int(r.max())) for r in kept],
                mapped]

    def forget(it):
        import builtins

        del builtins.__dict__["_kept_rows"][:]
        return []

    runs = one_executor.foreach_partition_async(
        [_big(k + 5) for k in range(4)], keep, look_ahead=True).wait(30)
    for k, (kept, mapped) in enumerate(runs):
        assert kept == [(j + 5,) * 3 for j in range(k + 1)]
        # the kept rows' segments, this task's, and the next one's if it
        # has arrived
        assert k + 1 <= mapped <= k + 2
    one_executor.map_partitions([[0]], forget)
