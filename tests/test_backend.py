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
