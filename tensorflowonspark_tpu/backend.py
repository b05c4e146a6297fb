"""Cluster execution backends: who runs the long-lived per-host tasks.

The reference framework is welded to Apache Spark: the driver runs "jobs" whose
tasks are scheduled one-per-executor, executors are long-lived OS processes
that persist across jobs, and data reaches tasks as partition iterators
(reference ``TFCluster.py:312-329``, ``TFSparkNode.py:121-135``).  This module
abstracts exactly that contract so the TPU framework can run on:

- :class:`SparkBackend`  — a thin adapter over a live ``SparkContext`` (used
  when ``pyspark`` is installed; API-compatible with the reference deployment).
- :class:`LocalBackend`  — a built-in multi-process standalone cluster: N
  long-lived executor processes on this host, a driver-side scheduler that
  dispatches one task per free executor, per-executor working directories, and
  partition-iterator task semantics.  This is the moral equivalent of the
  reference's test rig (a local Spark Standalone cluster with separate worker
  processes, ``test/run_tests.sh:15-22``, ``test/README.md:10``) promoted to a
  first-class deployment mode — one process per TPU host is the natural
  granularity for JAX/libtpu anyway (SURVEY §7.2).

The backend contract (used by :mod:`~tensorflowonspark_tpu.cluster`):

- ``foreach_partition_async(partitions, fn) -> JobHandle`` — run ``fn(iter)``
  once per partition on some executor; non-blocking ("start job" / "feed job").
- ``map_partitions(partitions, fn) -> list`` — run ``fn(iter)`` per partition,
  collect per-partition result lists (inference results job).
- one task slot per executor: a task occupies its executor until it returns,
  which is what lets the framework co-locate feed tasks with the long-running
  node process via the executor-id working-dir handshake (``util.py:66-75``).
- ``look_ahead`` (``foreach_partition`` / ``foreach_partition_async``): an
  internal argument that :meth:`cluster.TPUCluster.train` alone passes for
  its list-of-partitions feed jobs.  It is not a user's to set; a backend
  that has no use for it (:class:`SparkBackend`) accepts and ignores it.

**LocalBackend's pipe protocol.**  The driver sends an executor
``(job, task_id, ahead, built, pickled_fn, partition_items)`` and gets back
``(job, task_id, ok, result_or_traceback)``; ``None`` shuts the executor
down.  ``job`` is the driver's number for the ``JobHandle`` the task belongs
to: two tasks may be outstanding on one pipe, so one reader a connection
routes each reply to its own task.  ``built`` is the driver's monotonic
clock when it began to build the message (one clock a machine): the
executor times the hand-over from it (:func:`task_handover`).

**What crosses the pipe, and what travels beside it.**  A message is
pickled in protocol 5.  Contiguous buffers of ``_BESIDE_MIN`` bytes (64 KB)
or more, which a numpy array offers and nothing else a partition commonly
holds, are taken out of the pickle stream and laid end to end (each at the
next multiple of 64 bytes) in one shared-memory segment of their size; what
crosses the pipe is then a :class:`_Beside` (the in-band pickle, the
buffers' lengths) and, behind it on the socket, the segment's descriptor.
The executor's receiver thread maps the segment (shared, writable, its pages
populated there and not by the task's first pass) and unpickles with
``buffers=``: the task's arrays are views of the mapping (no copy; a task
that writes to one writes to its own segment, which nobody else reads), and
the mapping lives as long as anything in the executor references one of
them.  So the bulk of a
partition is copied once, by the kernel, into the segment, and never passes
through ``write``/``read`` in socket-buffer pieces or through a pickle
stream.  Smaller buffers, ``bytes``, scalars, arrays that are not
contiguous, and everything a start task or a FILES feed sends (15 KB, 4 KB)
stay in band: such a message crosses the pipe whole, in one send, as it
always did.  **The fall-back is what the code observes**: where no segment
can be made (no ``memfd_create``, no room), that message's buffers stay in
band too.  The replies are not touched.

**Who owns a segment: the driver.**  A segment is an anonymous memory file
(``memfd_create``): it has no name, so nothing of it can be left under
``/dev/shm`` whatever dies, in whatever order; its space is reserved before
the first byte is written, so no room is an ``OSError`` when it is made (the
fall-back above) and never a ``SIGBUS`` in a copy.  It is a task's own and
never reused: a task that keeps a row past the arrival of the next
partition still reads its own bytes.  The driver (``_Task.segment``) makes
it when it builds the message and closes its descriptor when the task is
answered (run, failed or skipped), when the executor died or was hung up on
(running and waiting task alike), and at ``stop()``; the executor closes
the descriptor it received as soon as the segment is mapped, and its
mapping goes with the task's last row.  What no process holds is gone.

**One task of look-ahead for feed jobs.**  An executor runs its tasks
strictly one at a time, in the order they were dispatched, but a job that
asked for look-ahead may have one task *waiting* in an executor while
another of the same job *runs* there: the waiting task's message (for a
feed job, the next partition: 100 MB in the benchmark's cell) is built,
sent, received and unpickled by the executor's receiver thread while the
running task feeds, instead of after it.  A free executor is always
preferred, and one that is busy with another job's task may come free:
nothing is sent ahead until every live executor works for this job.  There
is never more than one waiting task an executor, and never one behind a
task of another job.  The cost is one more partition resident while such a
job runs (the one in hand, the one waiting), in shared memory for as long
as it waits or runs.  What happens to a waiting task, and to its segment,
when things go wrong:

- the task before it fails: the waiting task is not run; the executor
  drops its rows (and with them its mapping) and answers ``task skipped:
  job cancelled after an earlier task failure`` (retryable, so a supervised
  ``train`` re-feeds both partitions once); the answer releases the
  driver's hold like any other;
- the executor dies: running and waiting task are both reported
  ``executor N died ...`` (retryable); each task's thread closes its
  segment as it reports, and the dead process's mappings went with it;
- the executor is fenced (:meth:`LocalBackend.exclude`): nothing more is
  sent ahead to it; a task already waiting runs, as one in flight does, and
  its segment goes when it is answered;
- ``stop()``: the waiting task is dropped with the executor, and ``stop()``
  closes the segment of every task still in flight.
"""

import collections
import errno
import io
import itertools
import logging
import mmap
import os
import pickle
import queue as _queue
import shutil
import socket
import tempfile
import threading
import time
import traceback
import weakref

import cloudpickle
from multiprocessing import get_context
from multiprocessing import reduction as _mp_reduction
from multiprocessing import util as _mp_util

logger = logging.getLogger(__name__)

#: LocalBackends that have not been stop()ped.  A leaked backend would hang
#: interpreter shutdown: multiprocessing's exit hook joins non-daemon
#: children, and an idle executor blocks on its command pipe forever (the
#: executors can't be daemonic — their tasks fork manager-server children).
#: A plain ``atexit`` handler can't help: multiprocessing registers its own
#: lazily at the first spawn, so LIFO ordering would run the join loop
#: first.  ``util.Finalize`` with an exitpriority runs INSIDE that hook,
#: before the join loop, so leaked executors are stopped in time.
_live_backends = weakref.WeakSet()


def _reap_leaked_backends():
    for backend in list(_live_backends):
        if not backend._stopped:
            logger.warning(
                "LocalBackend leaked (never stopped); stopping at exit")
            try:
                backend.stop()
            except Exception:
                pass


_mp_util.Finalize(None, _reap_leaked_backends, exitpriority=100)


def partition(data, num_partitions):
    """Split a list into ``num_partitions`` contiguous partitions.

    The local-mode stand-in for ``sc.parallelize(data, n)``; Spark's formula
    (elements spread as evenly as possible) is used so partition sizes match
    what the reference's feeders would see.
    """
    items = list(data)
    n = len(items)
    out = []
    for i in range(num_partitions):
        start = (i * n) // num_partitions
        stop = ((i + 1) * n) // num_partitions
        out.append(items[start:stop])
    return out


class JobHandle(object):
    """Handle for an asynchronously running backend job."""

    def __init__(self, num_tasks):
        self.num_tasks = num_tasks
        self._done = threading.Event()
        self._lock = threading.Lock()
        self._completed = 0
        self.error = None  # first task error (formatted traceback string)
        self.results = [None] * num_tasks
        self.task_errors = [None] * num_tasks  # per-task error strings

    def _task_done(self, index, ok, payload):
        with self._lock:
            if ok:
                self.results[index] = payload
                self.task_errors[index] = None
            else:
                self.task_errors[index] = payload
                if self.error is None:
                    self.error = payload
            self._completed += 1
            if self._completed >= self.num_tasks or not ok:
                self._done.set()

    def _set_progress(self, completed):
        """Monotonically update the completed-task count from an external
        progress source (Spark statusTracker) without firing completion —
        task results/errors still arrive via ``_task_done``."""
        with self._lock:
            if completed > self._completed:
                self._completed = min(completed, self.num_tasks)

    def _finish_ok(self):
        """Mark the whole job successfully finished (backends that only
        observe job-level completion, e.g. Spark's ``foreachPartition``)."""
        with self._lock:
            self._completed = self.num_tasks
            self._done.set()

    def done(self):
        return self._done.is_set()

    def wait(self, timeout=None):
        """Block until all tasks finished; raises on the first task error."""
        if not self._done.wait(timeout):
            raise TimeoutError("job did not complete within {}s".format(timeout))
        if self.error is not None:
            raise RuntimeError("job failed:\n{}".format(self.error))
        return self.results

    def wait_settled(self, timeout=None):
        """Block until EVERY task reached a terminal state (ok, failed, or
        skipped) — unlike :meth:`wait`, which fires on the *first* failure
        while sibling tasks may still be in flight.  The retry machinery
        needs the settled view: retrying a partition whose original task is
        still running would double-feed its rows.
        """
        deadline = None if timeout is None else time.time() + timeout
        while True:
            with self._lock:
                if self._completed >= self.num_tasks:
                    return
            if deadline is not None and time.time() > deadline:
                raise TimeoutError(
                    "job did not settle within {}s".format(timeout))
            time.sleep(0.05)

    def failed_tasks(self):
        """``[(task_index, error_string), ...]`` for tasks that failed or
        were skipped; call after :meth:`wait_settled`."""
        with self._lock:
            return [(i, e) for i, e in enumerate(self.task_errors)
                    if e is not None]


# ---------------------------------------------------------------------------
# LocalBackend: executor worker process main loop
# ---------------------------------------------------------------------------

#: The reply to a task that waited in an executor while the task before it,
#: of the same job, failed there (matched by ``fault.RETRYABLE_PATTERNS``;
#: the dispatcher answers the job's unsent tasks with the same words).
TASK_SKIPPED = "task skipped: job cancelled after an earlier task failure"

#: How the task that this executor process is running came in, see
#: :func:`task_handover`.
Handover = collections.namedtuple(
    "Handover", "ahead ready oob_bytes inband_bytes us")

#: Written by ``_executor_main`` alone.
_handover = Handover(False, False, 0, 0, 0)


def task_handover():
    """How the task this executor process is running came in, a
    :class:`Handover`: ``ahead`` if its message had begun to arrive before
    the task before it returned (the look-ahead engaged), ``ready`` if it
    was wholly unpickled by then (the hand-over was hidden completely);
    ``oob_bytes``, the bytes of its message that travelled beside the pipe
    in a shared-memory segment, and ``inband_bytes``, the bytes that
    crossed the pipe; ``us``, how long the hand-over took, from the moment
    the driver began to build the message to the message whole in this
    process (microseconds of the machine's monotonic clock).  ``ahead`` and
    ``ready`` are false for an executor's first task; all five are false
    or zero outside a :class:`LocalBackend` executor."""
    return _handover


# ---------------------------------------------------------------------------
# LocalBackend: a message's large buffers travel beside the pipe
# ---------------------------------------------------------------------------

#: A contiguous buffer of this many bytes or more leaves a message's pickle
#: stream and travels in a shared-memory segment.  What a message with no
#: arrays costs today is one ``send`` of a few kilobytes (a start task's is
#: 15 KB, a feed task's closure 4.5 KB, the shutdown task's 4 KB), which the
#: socket pair's buffer (208 KB by default) takes without waiting for the
#: reader; a segment costs eight system calls whatever it holds.  So a
#: buffer leaves the stream only where its bytes cost more than those calls
#: (at the pipe's 117 MB/s, 64 KB is half a millisecond), and a message of
#: small things still crosses the pipe in one piece, as it did.
_BESIDE_MIN = 64 << 10

#: Buffers lie in a segment at multiples of this, so that an array mapped
#: from one is aligned as one that ``pickle`` allocated would be.
_BESIDE_ALIGN = 64

#: What crosses the pipe in place of a message whose large buffers travel
#: beside it: the message's in-band pickle and the buffers' lengths (they
#: lie in the segment in this order, each at the next multiple of
#: ``_BESIDE_ALIGN``); the segment's descriptor follows on the socket.
_Beside = collections.namedtuple("_Beside", "inband lengths")


#: Linux's (Python 3.10 names it); elsewhere a mapping's pages come as they
#: are touched.
_MAP_POPULATE = getattr(mmap, "MAP_POPULATE", 0)


def _layout(lengths):
    """``(offsets, size)`` of buffers of ``lengths`` laid end to end."""
    offsets, end = [], 0
    for n in lengths:
        offsets.append(end)
        end += -(-n // _BESIDE_ALIGN) * _BESIDE_ALIGN
    return offsets, end


class _Segment(object):
    """The driver's hold on one message's shared-memory segment: an
    anonymous memory file (``memfd_create``: it has no name, so nothing of it
    can be left under ``/dev/shm`` whatever dies) with ``buffers`` copied
    into it.  Its space is reserved before the first byte is written, so no
    room is an ``OSError`` here and never a ``SIGBUS`` in somebody's copy.
    The executor gets a descriptor of its own over the socket pair and maps
    it; the memory goes when the driver has closed its descriptor and the
    executor's last row of it is gone."""

    def __init__(self, buffers):
        self.lengths = [raw.nbytes for raw in buffers]
        offsets, self.size = _layout(self.lengths)
        self._lock = threading.Lock()
        if not hasattr(os, "memfd_create"):  # not Linux: the pipe, then
            raise OSError(errno.ENOSYS, "no memfd_create on this platform")
        self._fd = os.memfd_create("tfos-handover", os.MFD_CLOEXEC)
        try:
            os.posix_fallocate(self._fd, 0, self.size)
            for raw, offset in zip(buffers, offsets):
                while raw.nbytes:  # the kernel copies; the GIL is released
                    n = os.pwrite(self._fd, raw, offset)
                    raw, offset = raw[n:], offset + n
        except BaseException:
            self.close()
            raise

    def send(self, conn):
        """Pass a descriptor of the segment over ``conn``'s socket."""
        with self._lock:
            if self._fd is None:
                raise OSError("segment already released")
            _mp_reduction.send_handle(conn, self._fd, None)

    def close(self):
        with self._lock:
            fd, self._fd = self._fd, None
        if fd is not None:
            os.close(fd)


def _dumps(obj, buffer_callback=None):
    """``obj`` as ``Connection.send`` would pickle it, in protocol 5."""
    out = io.BytesIO()
    _mp_reduction.ForkingPickler(out, 5, True, buffer_callback).dump(obj)
    return out.getbuffer()


def _pack(msg):
    """``(data, segment)`` for one message: the bytes that cross the pipe
    and, where ``msg`` offered contiguous buffers of ``_BESIDE_MIN`` bytes
    or more (a numpy array does) and a segment could be made, the segment
    that holds them (the caller's to ``send`` after ``data`` and to
    ``close``).  Else ``segment`` is None and ``data`` is the whole message,
    as it always was: nothing large in it, or no shared memory to be had."""
    buffers = []

    def beside(buf):  # pickle's buffer_callback: true keeps it in band
        try:
            raw = buf.raw()
        except BufferError:  # not contiguous
            return True
        if raw.nbytes < _BESIDE_MIN:
            return True
        buffers.append(raw)
        return False

    inband = _dumps(msg, beside)
    if not buffers:
        return inband, None
    try:
        segment = _Segment(buffers)
    except OSError:  # no room, or no shared memory of this kind here
        return _dumps(msg), None
    return _dumps(_Beside(bytes(inband), segment.lengths)), segment


def _unpack(conn):
    """Read one message off ``conn``; returns ``(msg, oob_bytes,
    inband_bytes)``.  Where a :class:`_Beside` came, the segment is mapped
    and the message's arrays are views of the mapping, which lives as long
    as anything in this process references one of them.  The mapping is
    shared and writable (a task that writes to its rows writes to its own
    segment, which nobody else reads: a private one would copy every page
    it populates) and populated here, on the receiver thread: a fresh
    mapping's page faults, one a 4 KB page, cost the task's first pass over
    its rows more than the hand-over itself (166 ms against 110 for 100 MB
    on the benchmark's host; populated, 6 ms)."""
    data = conn.recv_bytes()
    msg = pickle.loads(data)
    if not isinstance(msg, _Beside):
        return msg, 0, len(data)
    fd = _mp_reduction.recv_handle(conn)
    try:
        offsets, size = _layout(msg.lengths)
        view = memoryview(mmap.mmap(
            fd, size, flags=mmap.MAP_SHARED | _MAP_POPULATE,
            prot=mmap.PROT_READ | mmap.PROT_WRITE))
    except OSError as e:  # not the pipe's end: a message that cannot be had
        raise RuntimeError("cannot map a segment of {} bytes: {}".format(
            size, e))
    finally:
        os.close(fd)
    buffers = [view[o:o + n] for o, n in zip(offsets, msg.lengths)]
    return (pickle.loads(msg.inband, buffers=buffers), sum(msg.lengths),
            len(data))


def _receive_tasks(conn, inbox, closing):
    """Executor's receiver thread: read and unpickle the driver's messages
    while the main thread runs a task; hand each over as ``(msg, began,
    whole, oob_bytes, inband_bytes)``: when it began to arrive, when it was
    whole, how it came.  ``inbox`` holds at most one waiting task; at the
    shutdown (``closing``) a task still waiting there is dropped, not run."""
    while True:
        oob = inband = 0
        try:
            conn.poll(None)  # the first bytes of the next message
            began = time.monotonic()
            msg, oob, inband = _unpack(conn)
        except (EOFError, OSError):
            msg = None
        except Exception as e:
            # a message that does not unpickle ends the executor, as it
            # always did: in its turn, after the task that is running
            msg = e
        if msg is None:  # backend shutdown, or the driver is gone
            closing.set()
            try:
                inbox.put_nowait(None)  # wake a main thread that waits
            except _queue.Full:
                pass  # it will see ``closing`` when it takes what waits
            return
        inbox.put((msg, began, time.monotonic(), oob, inband))
        if isinstance(msg, Exception):
            return
        # the main thread's alone now: a partition (and its segment's
        # mapping) goes with its task, not when the next message arrives
        msg = None


def _executor_main(executor_index, workdir, conn, env_overrides):
    """Long-lived executor process: apply env, chdir, serve tasks over a pipe.

    Tasks arrive as ``(job, task_id, ahead, built, pickled_fn,
    partition_items)``; results return as ``(job, task_id, ok,
    result_or_traceback)``.  A receiver thread reads and unpickles the
    messages, so a task sent ahead arrives while the one before it runs;
    this thread runs them one at a time, in arrival order.  Environment
    overrides are applied *before* any task runs so that e.g.
    ``JAX_PLATFORMS`` is set before the first ``import jax`` in user code.
    """
    global _handover
    os.environ.update(env_overrides or {})
    os.makedirs(workdir, exist_ok=True)
    os.chdir(workdir)
    import threading as _threading

    from tensorflowonspark_tpu import fault

    _threading.current_thread().name = "executor-{}".format(executor_index)
    # Resolved once per executor (counters are per-process).  Note: specs
    # targeted with ``executor_id`` resolve to NULL here — the executor-id
    # file doesn't exist until a node's start task writes it — so target
    # executor-loss faults via ``env_per_executor`` instead.
    injector = fault.from_env()
    inbox = _queue.Queue(maxsize=1)
    closing = _threading.Event()
    _threading.Thread(target=_receive_tasks, args=(conn, inbox, closing),
                      name="executor-{}-recv".format(executor_index),
                      daemon=True).start()
    failed_job = None  # the job whose task last failed here
    returned = None  # when the task before returned
    while True:
        arrival = inbox.get()
        if arrival is None or closing.is_set():  # backend shutdown
            break
        msg, began, whole, oob, inband = arrival
        if isinstance(msg, Exception):
            raise msg
        job, task_id, ahead, built, fn_bytes, items = msg
        del arrival
        if ahead and job == failed_job:
            # it waited here behind a task of its job that failed: not run
            del msg, items  # its segment's mapping goes with them
            returned = time.monotonic()
            conn.send((job, task_id, False, TASK_SKIPPED))
            continue
        _handover = Handover(
            returned is not None and began < returned,
            returned is not None and whole < returned,
            oob, inband, max(0, int((whole - built) * 1e6)))
        try:
            fn = cloudpickle.loads(fn_bytes)
            result = fn(iter(items))
            if result is not None and not isinstance(result, (list, tuple)):
                result = list(result)  # drain generators inside the executor
            returned = time.monotonic()
            conn.send((job, task_id, True, result))
        except Exception:
            returned = time.monotonic()
            failed_job = job
            conn.send((job, task_id, False, traceback.format_exc()))
        # the partition goes now, not when the next one has arrived
        del msg, fn_bytes, items
        fn = result = None
        injector.on_task()  # kill_after_tasks: die AFTER serving N tasks


class _Task(object):
    """Driver-side record of one task in flight on a :class:`LocalBackend`
    executor."""

    def __init__(self, job, task_id, fn_bytes, items, handle):
        self.job = job  # the driver's number for ``handle``'s job
        self.task_id = task_id
        self.fn_bytes = fn_bytes
        self.items = items
        self.handle = handle
        self.ahead = False  # sent to wait behind a running task of its job
        # the shared-memory segment its message's large buffers travel in
        # (none: the message crosses the pipe whole), from the moment it is
        # made to the task's last moment in ``_run_one``
        self.segment = None
        self.after = None  # the ``sent`` of the task before it on the pipe
        self.sent = threading.Event()  # its message has left (or never will)
        # from the connection's reader: True once the reply has reached
        # ``handle``, False if the pipe ended first
        self.answered = _queue.SimpleQueue()

    def release(self):
        """Close the driver's hold on the task's segment, if it has one
        (again does no harm)."""
        if self.segment is not None:
            self.segment.close()


class LocalBackend(object):
    """Built-in standalone cluster: N long-lived executor processes on this host.

    Args:
      num_executors: number of executor processes.
      env: base environment overrides applied in every executor before the
        first task (e.g. ``{"JAX_PLATFORMS": "cpu"}`` for tests).
      env_per_executor: optional list of per-executor override dicts (e.g. to
        give exactly one executor the real TPU and the rest CPU).
      workdir_root: parent directory for per-executor working dirs (a fresh
        temp dir by default); each executor gets ``<root>/executor-<i>``, its
        own cwd, which is what makes the executor-id file handshake work.
    """

    #: Per-task outcomes (JobHandle.task_errors) are real here, so the
    #: driver's supervised feed retry can re-dispatch failed partitions.
    supports_task_retry = True

    #: The driver's elastic recovery can ask this backend to spawn a FRESH
    #: executor process into a dead node's freed roster slot
    #: (:meth:`provision_replacement` + :meth:`run_on`).
    supports_replacement = True

    def __init__(self, num_executors, env=None, env_per_executor=None, workdir_root=None):
        self.num_executors = num_executors
        self._owns_root = workdir_root is None
        self.workdir_root = workdir_root or tempfile.mkdtemp(prefix="tfos_tpu_local_")
        self._ctx = get_context("spawn")
        self._base_env = dict(env or {})
        self._procs = []
        self._conns = []
        self._send_locks = []  # one a connection: a message goes out whole
        self._readers = []  # one reply-routing thread a connection
        # Scheduling state, all under ``_cv``: the executors that are free,
        # oldest first, and each executor's tasks in flight in dispatch
        # order (none: free or parked; one: running; two: one running, one
        # waiting behind it — look-ahead).
        self._cv = threading.Condition()
        self._idle = collections.deque()
        self._inflight = []
        self._job_ids = itertools.count()
        self._stopped = False
        self._excluded = set()  # executor indices fenced off from scheduling
        self._lock = threading.Lock()  # guards _procs/_conns growth
        _live_backends.add(self)
        for i in range(num_executors):
            overrides = dict(env or {})
            if env_per_executor:
                overrides.update(env_per_executor[i] or {})
            self._spawn_executor(i, overrides)
            self._idle.append(i)

    def _spawn_executor(self, i, overrides):
        parent_conn, child_conn = self._ctx.Pipe()
        proc = self._ctx.Process(
            target=_executor_main,
            args=(
                i,
                os.path.join(self.workdir_root, "executor-{}".format(i)),
                child_conn,
                overrides,
            ),
            name="local-executor-{}".format(i),
        )
        proc.start()
        child_conn.close()
        self._procs.append(proc)
        self._conns.append(parent_conn)
        self._send_locks.append(threading.Lock())
        self._inflight.append([])
        reader = threading.Thread(
            target=self._route_replies, args=(i,),
            name="executor-{}-replies".format(i), daemon=True)
        reader.start()
        self._readers.append(reader)

    # -- scheduling -------------------------------------------------------

    def _route_replies(self, executor_index):
        """One reader a connection: two tasks may be outstanding on it, so
        each ``(job, task_id, ok, payload)`` goes to the ``JobHandle`` of the
        task it answers — from this one thread, so that a job hears of a
        failure before it hears of the task skipped behind it.  Ends when
        the pipe does (the executor's exit, or :meth:`_hang_up`), telling
        the tasks still in flight that no reply will come."""
        conn = self._conns[executor_index]
        while True:
            try:
                reply = conn.recv()
            except (EOFError, OSError):
                reply = None
            with self._cv:
                tasks = list(self._inflight[executor_index])
            for task in tasks:
                if reply is None:
                    task.answered.put(False)
                elif (task.job, task.task_id) == reply[:2]:
                    task.handle._task_done(task.task_id, *reply[2:])
                    task.answered.put(True)
                    break
            if reply is None:
                return

    def _hang_up(self, executor_index):
        """Shut the driver's end of a dead (or stopped) executor's pipe.  The
        pipe is a socket pair, and children of the executor may still hold
        its other end: without this, a sender blocked on a message that
        nobody will read and the connection's reader would wait forever."""
        try:
            sock = socket.socket(
                fileno=os.dup(self._conns[executor_index].fileno()))
        except (OSError, ValueError):
            return  # already closed
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        finally:
            sock.close()

    def _place(self, task, executor_index):
        """Put ``task`` behind whatever ``executor_index`` has in flight
        (call with ``_cv`` held)."""
        queue = self._inflight[executor_index]
        task.after = queue[-1].sent if queue else None
        queue.append(task)

    def _assign(self, task, look_ahead):
        """Block until an executor can take ``task``; returns its index, or
        None when none will (backend stopped; every executor dead or
        excluded; a look-ahead job that failed meanwhile).  A free executor
        first.  For a job that asked for look-ahead, else, an executor that
        is running one task of the job and has nothing waiting
        (``task.ahead``) — but only once every live executor works for this
        job: one that is busy with another job's task (a start task about to
        return, say) may come free, and a free executor is preferred, so the
        task waits for one as it always did."""
        with self._cv:
            while not self._stopped:
                if look_ahead and task.handle.error is not None:
                    return None  # cancelled while it waited its turn
                while self._idle:
                    i = self._idle.popleft()
                    if (i not in self._excluded
                            and self._procs[i].is_alive()):
                        self._place(task, i)
                        return i
                    # else: drop the stale slot
                live = self._live_executors()
                if not live:
                    return None  # no executor can ever serve this task
                if look_ahead:
                    queues = [self._inflight[i] for i in live]
                    if all(q and q[0].job == task.job for q in queues):
                        for i, queue in zip(live, queues):
                            if len(queue) == 1:
                                task.ahead = True
                                self._place(task, i)
                                return i
                # Poll instead of waiting forever: a dead or excluded
                # executor never comes back, and nothing notifies of it.
                self._cv.wait(1.0)
        return None

    def _run_one(self, executor_index, task):
        conn = self._conns[executor_index]
        proc = self._procs[executor_index]
        handle, task_id = task.handle, task.task_id
        try:
            try:
                # messages leave in dispatch order
                while task.after is not None and not task.after.wait(1.0):
                    if not proc.is_alive():
                        raise EOFError("executor process died")
                data, task.segment = _pack(
                    (task.job, task_id, task.ahead, time.monotonic(),
                     task.fn_bytes, task.items))
                with self._send_locks[executor_index]:
                    conn.send_bytes(data)
                    if task.segment is not None:
                        task.segment.send(conn)
            finally:
                task.items = data = None
                task.sent.set()
            # wait with a LIVENESS poll, not a bare get: an executor whose
            # task spawned children (every node runtime forks a manager
            # server) leaves those children holding a dup of the pipe fd,
            # so a SIGKILLed executor never EOFs the pipe — the job would
            # wedge forever instead of failing (observed: vanished-executor
            # shutdown hang).
            answered = None
            while answered is None:
                try:
                    answered = task.answered.get(timeout=1.0)
                except _queue.Empty:
                    if not proc.is_alive():
                        try:  # final response raced with process exit
                            answered = task.answered.get(timeout=0.5)
                        except _queue.Empty:
                            answered = False
            if not answered:  # says the poll, or the reader at the pipe's end
                raise EOFError("executor process died")
        except (EOFError, OSError):
            # whoever else still sends to or reads from this executor
            # (a task waiting behind this one) must not block on it
            self._hang_up(executor_index)
            if self._stopped:
                return
            handle._task_done(
                task_id,
                False,
                "executor {} died while running task {} (exitcode={})".format(
                    executor_index, task_id, proc.exitcode
                ),
            )
        finally:
            # answered (run, failed or skipped), or never to be: the
            # executor died, was hung up on, or the backend stopped
            task.release()
            with self._cv:
                queue = self._inflight[executor_index]
                queue.remove(task)
                if (not queue and proc.is_alive()
                        and executor_index not in self._excluded):
                    self._idle.append(executor_index)
                self._cv.notify_all()

    def exclude(self, executor_index):
        """Fence an executor off from future scheduling (liveness monitor:
        its node process died, so tasks landing there would feed a corpse).
        In-flight tasks finish/fail on their own (a task waiting there behind
        a running one too); the slot is simply never returned to the free
        pool, and no task is sent ahead to it."""
        if 0 <= executor_index < self.num_executors:
            self._excluded.add(executor_index)
            logger.warning("executor %d excluded from scheduling", executor_index)
            from tensorflowonspark_tpu import telemetry
            telemetry.get_tracer().instant("backend/executor_excluded",
                                           executor_id=executor_index)

    def provision_replacement(self, env=None):
        """Spawn a FRESH executor process for elastic recovery; returns its
        executor index (a brand-new identity — never a recycled index, so
        the liveness monitor's zombie fence on the dead executor keeps
        holding).  The new executor gets its own working directory and does
        NOT enter the free pool until its first task (the replacement start
        task dispatched via :meth:`run_on`) completes."""
        from tensorflowonspark_tpu import telemetry
        with telemetry.get_tracer().span("backend/provision_replacement"):
            with self._lock:
                if self._stopped:
                    # A liveness monitor racing teardown must not spawn an
                    # executor nobody will ever stop.
                    raise RuntimeError("backend stopped; no replacements")
                i = len(self._procs)
                overrides = dict(self._base_env)
                overrides.update(env or {})
                self._spawn_executor(i, overrides)
                self.num_executors = len(self._procs)
        logger.warning("provisioned replacement executor %d", i)
        return i

    def run_on(self, executor_index, fn, items):
        """Dispatch one task DIRECTLY onto ``executor_index``, bypassing the
        free pool (elastic recovery must land the replacement start task on
        the replacement executor — any other executor's working dir already
        hosts a node).  Returns a single-task :class:`JobHandle`; when the
        task finishes, the executor joins the free pool for ordinary
        scheduling (``_run_one``'s finally)."""
        handle = JobHandle(1)
        task = _Task(next(self._job_ids), 0, cloudpickle.dumps(fn),
                     list(items), handle)
        with self._cv:
            self._place(task, executor_index)
        t = threading.Thread(
            target=self._run_one,
            args=(executor_index, task),
            name="task-on-{}".format(executor_index),
            daemon=True,
        )
        t.start()
        return handle

    def _live_executors(self):
        return [i for i, p in enumerate(self._procs)
                if p.is_alive() and i not in self._excluded]

    def foreach_partition_async(self, partitions, fn, look_ahead=False):
        """Dispatch ``fn(iter(partition))`` per partition onto free executors.

        ``look_ahead`` is internal (``cluster.train``'s list-of-partitions
        feed jobs pass it, nothing else does): with no executor free, the
        job's next task is sent to wait in an executor that is running one
        of its tasks, so that the partition travels and is unpickled while
        the one before it is fed (see the module docstring).  It costs one
        more partition resident (in shared memory, where its arrays travel
        beside the pipe)."""
        handle = JobHandle(len(partitions))
        fn_bytes = cloudpickle.dumps(fn)
        job = next(self._job_ids)

        def _dispatch():
            for task_id, items in enumerate(partitions):
                if handle.error is not None:
                    # Job-level cancel: a sibling task already failed, so
                    # don't keep feeding the failed job's remaining tasks to
                    # executors (wait() has raised; stop() may be imminent).
                    # In-flight tasks finish on their own.
                    handle._task_done(task_id, False, TASK_SKIPPED)
                    continue
                task = _Task(job, task_id, fn_bytes, list(items), handle)
                executor_index = self._assign(task, look_ahead)
                if executor_index is None:
                    if self._stopped:
                        why = "backend stopped"
                    elif look_ahead and handle.error is not None:
                        why = TASK_SKIPPED
                    else:
                        why = ("task {} unschedulable: no live executors "
                               "remain (all died or were excluded)"
                               .format(task_id))
                    handle._task_done(task_id, False, why)
                    continue
                threading.Thread(
                    target=self._run_one,
                    args=(executor_index, task),
                    name="task-{}".format(task_id),
                    daemon=True,
                ).start()

        threading.Thread(target=_dispatch, name="job-dispatch", daemon=True).start()
        return handle

    def foreach_partition(self, partitions, fn, timeout=None,
                          look_ahead=False):
        self.foreach_partition_async(partitions, fn, look_ahead).wait(timeout)

    def map_partitions(self, partitions, fn, timeout=None):
        """Run ``fn`` per partition and return the list of per-partition results."""
        return self.foreach_partition_async(partitions, fn).wait(timeout)

    def stop(self):
        with self._lock:
            if self._stopped:
                return
            self._stopped = True
            conns = list(self._conns)
            procs = list(self._procs)
        _live_backends.discard(self)
        with self._cv:
            self._cv.notify_all()  # a dispatcher waiting for an executor
        for conn, lock in zip(conns, self._send_locks):
            # behind a message that is leaving, never into the middle of it;
            # a sender stuck on a dead executor is not waited for
            if not lock.acquire(timeout=2):
                continue
            try:
                conn.send(None)
            except OSError:
                pass
            finally:
                lock.release()
        for proc in procs:
            proc.join(timeout=5)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=5)
                if proc.is_alive():
                    proc.kill()
                    proc.join(timeout=1)
        # The executors are gone; their children may still hold the pipes'
        # other ends, so end the readers (and any sender) from this side.
        for i in range(len(conns)):
            self._hang_up(i)
        for reader in list(self._readers):
            reader.join(timeout=2)
        # a task that waited in an executor went with it; its thread lets
        # go of its segment in its own time, this does it now
        with self._cv:
            tasks = [task for queue in self._inflight for task in queue]
        for task in tasks:
            task.release()
        if self._owns_root:
            shutil.rmtree(self.workdir_root, ignore_errors=True)


# ---------------------------------------------------------------------------
# SparkBackend: adapter over a live SparkContext (requires pyspark)
# ---------------------------------------------------------------------------

class SparkBackend(object):
    """Adapter over ``pyspark.SparkContext`` matching the backend contract.

    Deployment-equivalent to the reference: the "start job" is
    ``sc.parallelize(range(n), n).foreachPartition(fn)`` on a background thread
    (reference ``TFCluster.py:312-329``) and feed jobs are ``rdd.foreachPartition``
    / ``rdd.mapPartitions`` (reference ``TFCluster.py:92,113``).  Requires one
    task slot per executor, exactly like the reference
    (``TFSparkNode.py:110-115``).

    ``partitions`` arguments may be RDDs (used as-is) or lists (parallelized).

    Elastic recovery on Spark is **Spark's own**: when an executor dies,
    Spark re-runs its failed start/feed tasks on another executor
    (``spark.task.maxFailures``), so a replacement node "re-lands" with the
    task rather than via :meth:`LocalBackend.provision_replacement` — the
    re-run start task registers from its fresh executor and claims the dead
    node's released ``(job_name, task_index)`` slot exactly like a built-in
    replacement would (the reservation server's admission path is backend
    agnostic; only *who spawns the process* differs).  The driver therefore
    does not request replacements here (``supports_replacement = False``).
    """

    #: Spark only reports job-level outcomes to the driver (task retries are
    #: Spark's own); the supervised feed retry therefore skips this backend.
    supports_task_retry = False

    #: Replacement processes come from Spark's task retry (see class doc),
    #: not from a driver-side provisioning call.
    supports_replacement = False

    def __init__(self, sc, num_executors=None):
        import pyspark  # gated: only needed when this backend is chosen

        assert isinstance(sc, pyspark.SparkContext)
        self.sc = sc
        self.num_executors = num_executors or int(
            sc.getConf().get("spark.executor.instances", "1")
        )

    def _to_rdd(self, partitions):
        if hasattr(partitions, "foreachPartition"):  # already an RDD
            return partitions
        flat = [item for part in partitions for item in part]
        return self.sc.parallelize(flat, len(partitions))

    def foreach_partition_async(self, partitions, fn, look_ahead=False):
        # look_ahead: LocalBackend's; here the hand-over of a partition to
        # the Python worker is Spark's own
        rdd = self._to_rdd(partitions)
        handle = JobHandle(rdd.getNumPartitions())
        # uuid, not id(): a freed handle's address can be reused, and a
        # recycled group name would let statusTracker count a PRIOR job's
        # completed tasks into this handle's progress.
        import uuid

        job_group = "tfos-{}".format(uuid.uuid4().hex)

        def _run():
            # Job group scopes the statusTracker queries below to this job
            # (setJobGroup is thread-local, so it must be set in the thread
            # that triggers the action).
            self.sc.setJobGroup(job_group, "tensorflowonspark_tpu job")
            try:
                rdd.foreachPartition(fn)
                handle._finish_ok()
            except Exception:
                handle._task_done(0, False, traceback.format_exc())

        t = threading.Thread(target=_run, name="spark-job", daemon=True)
        t.start()
        threading.Thread(target=self._track_progress,
                         args=(job_group, handle),
                         name="spark-job-progress", daemon=True).start()
        return handle

    def _track_progress(self, job_group, handle):
        """Feed per-task completion counts into the JobHandle while the job
        runs (reference statusTracker active-task polling,
        ``TFCluster.py:152-167``).

        Without this, ``_completed`` would only move when the WHOLE job ends
        — and a job whose ps/evaluator tasks park forever never ends, so
        FILES-mode shutdown (which waits for ``_completed >= num_workers``)
        would spin until the SIGALRM watchdog.
        """
        while not handle.done():
            try:
                st = self.sc.statusTracker()
                completed = 0
                for job_id in st.getJobIdsForGroup(job_group):
                    info = st.getJobInfo(job_id)
                    if info is None:
                        continue
                    for stage_id in info.stageIds:
                        si = st.getStageInfo(stage_id)
                        if si is not None:
                            completed += si.numCompletedTasks
                handle._set_progress(completed)
            except Exception:
                logger.debug("statusTracker poll failed", exc_info=True)
            time.sleep(1)

    def foreach_partition(self, partitions, fn, timeout=None,
                          look_ahead=False):
        self.foreach_partition_async(partitions, fn).wait(timeout)

    def map_partitions(self, partitions, fn, timeout=None):
        rdd = self._to_rdd(partitions)
        return rdd.mapPartitions(lambda it: [fn(it)]).collect()

    def stop(self):
        pass  # the caller owns the SparkContext's lifecycle

    @property
    def default_fs(self):
        """Filesystem defaultFS from the Hadoop conf (reference TFCluster.py:269-272)."""
        return self.sc._jsc.hadoopConfiguration().get("fs.defaultFS")
