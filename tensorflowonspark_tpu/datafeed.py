"""User-side data feed helpers, used inside ``main_fun`` (reference ``TFNode.py``).

:class:`DataFeed` consumes the per-executor input queue as batches and pushes
inference results back — same queue semantics as the reference (end-of-feed
``None``, :class:`~tensorflowonspark_tpu.marker.EndPartition` alignment, the
1:1 inference contract) — but adds TPU-first batch assembly: instead of the
reference's element-at-a-time generator hops into ``tf.data.from_generator``
(the known InputMode.SPARK bottleneck, SURVEY §3.2), :meth:`next_batch` can
return columnar numpy arrays ready for a single per-host ``jax.device_put``
into a sharded global batch (see :mod:`tensorflowonspark_tpu.parallel.infeed`).
"""

import collections
import logging
import queue as _queue
import threading

import numpy as np

from tensorflowonspark_tpu import fault, marker, telemetry

logger = logging.getLogger(__name__)

_INTERRUPTED = object()  # internal next_batch abort marker (see interrupt())
_BUFFERED = object()     # _pull() buffered a chunk; nothing to hand over yet

#: The consumer's phases (see :class:`~tensorflowonspark_tpu.telemetry.PhaseClock`):
#: ``away`` between two ``next_batch*`` calls (the caller's transform, the
#: host-to-device put, a full prefetch queue), ``wait`` blocked on the empty
#: queue, ``read`` the ring read (on the array path with its one copy, from
#: the in-ring views into the batch's buffers; on the row path with its
#: decode) or an in-queue chunk's unpacking, and the chunk's ``task_done``
#: round trip, ``assemble`` what is left of building a batch: the loop's
#: bookkeeping and the copy of in-queue and object chunks' rows into the
#: batch's buffers.
FEED_PHASES = ("away", "wait", "read", "assemble")


class _Batch(object):
    """A batch under construction on the array path: one buffer a column,
    shaped ``[size, ...]`` in the dtype the caller gets, and the rows
    filled so far."""

    __slots__ = ("cols", "asked", "tuple_rows", "size", "count")

    def __init__(self, cols, asked, tuple_rows, size):
        self.cols = cols
        self.asked = asked  # the dtype the caller named for each, or None
        self.tuple_rows = tuple_rows
        self.size = size
        self.count = 0


def _buffers_key(cols):
    """What a batch's buffers hold (batch size, column shapes and dtypes),
    or ``None`` when ``cols`` are not whole buffers of one batch: views,
    read-only or strided arrays, columns of different lengths."""
    key = []
    for c in cols:
        if not (isinstance(c, np.ndarray) and c.size and c.base is None
                and c.flags.c_contiguous and c.flags.writeable
                and c.shape[0] == cols[0].shape[0]):
            return None
        key.append((c.shape, c.dtype.str))
    return tuple(key) or None


def _rows_to_fields(rows):
    """Convert a list of rows into per-field arrays: ``(fields, tuple_rows)``
    (the degraded path for object chunks; columnar chunks skip this).
    Row semantics live in :mod:`~tensorflowonspark_tpu.columnar`; this is
    the strict caller — inconsistent arity raises (truncating would
    silently drop fields — wrong training data) where the feeder-side
    packer soft-falls-back."""
    from tensorflowonspark_tpu import columnar

    return columnar.rows_to_fields(rows, strict=True)


def assemble_columns(parts, tuple_rows, dtypes, input_tensors=None):
    """Concatenate per-part field slices into final per-field arrays and
    shape the result per the input_mapping contract (shared by
    :class:`DataFeed` and the data-service
    :class:`~tensorflowonspark_tpu.dataservice.ServiceFeed`).

    ``parts`` is a list of per-field tuples of array slices; the result is a
    per-tensor dict when ``input_tensors`` is given, a tuple of field arrays
    for tuple rows, else a single array."""
    if not parts:
        if input_tensors is None:
            return np.empty((0,))
        return {t: np.empty((0,)) for t in input_tensors}
    arity = len(parts[0])

    def col(f, dtype):
        arrs = [p[f] for p in parts]
        out = arrs[0] if len(arrs) == 1 else np.concatenate(arrs)
        return out if dtype is None else np.asarray(out, dtype=dtype)

    if input_tensors is not None:
        if arity != len(input_tensors):
            raise ValueError(
                "input_mapping names {} tensors but feed rows have {} "
                "fields".format(len(input_tensors), arity))
        return {
            t: col(f, None if dtypes is None else dtypes.get(t))
            for f, t in enumerate(input_tensors)
        }
    if tuple_rows:
        return tuple(
            col(f, None if dtypes is None else dtypes[f])
            for f in range(arity))
    return col(0, dtypes)


def absolute_path(ctx, path):
    """Convert a user path to an absolute path on shared storage.

    Reference ``TFNode.py:23-58`` (``hdfs_path``); scheme list extended with
    the TPU-era object stores (``gs://``, ``s3://``).

    Rules:
    - recognized scheme prefixes pass through unchanged;
    - absolute paths pass through (prefixed with ``file://`` when default_fs
      is local);
    - relative paths resolve against the default filesystem, or against the
      executor's working dir when default_fs is ``file://`` (reference
      behavior for Spark Standalone).
    """
    schemes = ("file://", "hdfs://", "viewfs://", "gs://", "s3://", "s3a://")
    if path.startswith(schemes):
        return path
    default_fs = getattr(ctx, "default_fs", None) or "file://"
    if path.startswith("/"):
        return path if not default_fs.startswith("file://") else "file://" + path
    if default_fs.startswith("file://"):
        working_dir = getattr(ctx, "working_dir", None) or "."
        return "file://{}/{}".format(working_dir, path)
    if default_fs.startswith("hdfs://") or default_fs.startswith("viewfs://"):
        # hdfs relative paths resolve to the user's home dir (reference
        # TFNode.py:52-53).
        import getpass

        return "{}/user/{}/{}".format(default_fs.rstrip("/"), getpass.getuser(), path)
    return "{}/{}".format(default_fs.rstrip("/"), path)


def strip_scheme(path):
    """Drop a ``file://``/``file:`` prefix for direct POSIX access (shared
    canonical helper — keeps this and the checkpoint/data paths agreeing
    on what counts as a local path)."""
    from tensorflowonspark_tpu import fsio

    return fsio.strip_file_scheme(path)


class DataFeed(object):
    """Queue consumer for InputMode.SPARK nodes (reference ``TFNode.py:86-194``).

    Args:
      mgr: this node's connected manager (from ``ctx.mgr``).
      train_mode: True for training (no result queue), False for inference.
      qname_in / qname_out: queue names.
      input_mapping: optional ``{column_name: tensor_name}`` dict; when given,
        :meth:`next_batch` returns a dict of per-tensor columns, keyed by
        tensor name, with columns ordered by sorted column name — the same
        contract the pipeline API uses to line up DataFrame columns
        (reference ``TFNode.py:96-103``, ``pipeline.py:428-429``).
    """

    def __init__(self, mgr, train_mode=True, qname_in="input",
                 qname_out="output", input_mapping=None):
        self.mgr = mgr
        self.train_mode = train_mode
        self.qname_in = qname_in
        self.qname_out = qname_out
        self.done_feeding = False
        self.input_tensors = (
            [tensor for _, tensor in sorted(input_mapping.items())]
            if input_mapping is not None else None
        )
        # Unpacked-but-unconsumed rows from the last chunk (feeders send
        # chunks to amortize the per-element IPC hop; see marker.Chunk /
        # marker.ColChunk).  ``_buffer`` is either a list of items or a
        # ColChunk (columnar rows); ``_buffer_idx`` indexes rows in both.
        # The chunk's task_done is DEFERRED until its last item is handed
        # out (_chunk_q holds the pending ack): a consumer crashing
        # mid-chunk must leave the queue un-joined so the feeder's
        # error-poll fires, matching the reference's per-item fail-fast
        # semantics (reference TFSparkNode.py:407-418).
        self._buffer = []
        self._buffer_idx = 0
        self._chunk_q = None
        # The array path's batches under construction (see _Batch): during
        # a next_batch_arrays call the head is the batch in hand; between
        # calls the deque holds only what a ring chunk's tail filled beyond
        # it (the ring slot is consumed with the chunk's one copy, so the
        # rows must already be somewhere).  ``_buffer`` is exhausted
        # whenever rows wait here, and the pending ack in ``_chunk_q`` is
        # then of the chunk whose last row lies in the LAST of them.
        self._batches = collections.deque()
        # Batch buffers the reader handed back (see release): the columns
        # of whole batches, all of one kind (``_free_key``).  Empty for a
        # caller that never hands back: every batch is then new memory.
        self._free = []
        self._free_key = None
        self.buffers_reused = 0
        self.buffers_new = 0
        # Transport observability: {format: chunks seen} — wire.WIRE_COLV1
        # for zero-copy framed ring records, wire.WIRE_PICKLE for pickled
        # ring records, "queue" for in-queue chunks: a throughput number
        # can always name the wire format that produced it.
        self.wire_formats = {}
        # More always-on feed-plane tallies (plain numbers; snapshotted into
        # heartbeat payloads by the node runtime — see counters_snapshot):
        # total rows handed to the trainer, and cumulative seconds spent
        # blocked on an empty input queue (the consumer-starved signal that
        # tells an input-bound job from a compute-bound one).
        self.items_consumed = 0
        self.stall_secs = 0.0
        # Where this feed's consumer thread spends its wall time, phase by
        # phase (always on; ``feed_<phase>_us`` in counters_snapshot).  The
        # switches sit in next_batch/next_batch_arrays and the helpers they
        # call, nowhere else: terminate() may run in a signal handler.
        self._clock = telemetry.PhaseClock(FEED_PHASES)
        # Set by interrupt(): unblocks a next_batch blocked on the queue so
        # another thread can take over queue consumption (the queue/ring is
        # single-consumer; see ShardedFeed.terminate).
        self._interrupt = threading.Event()
        # Queue-poll cadence of the interruptible blocking get; a live knob
        # (``feed_poll_secs``) because it trades idle-CPU wakeups against
        # interrupt latency and the right value depends on measured load.
        self._poll_secs = 0.5
        # Chaos hook: consumption-side fault injection ("node dies / fails
        # after N items") — a null object unless TFOS_FAULT_SPEC targets
        # this process (see tensorflowonspark_tpu.fault).
        self._fault = fault.from_env()

    def apply_knob(self, name, value):
        """Live-knob hook — the duck-typed protocol every registered feed
        source shares (see ``node.apply_knobs`` and docs/AUTOPILOT.md):
        claim a ``{knob: value}`` push by returning True, return False for
        names that belong to other planes.  The queue-backed DataFeed owns
        just ``feed_poll_secs``; richer feeds (ShardedFeed, ServiceFeed)
        claim the autopilot's performance knobs."""
        if name == "feed_poll_secs":
            self._poll_secs = min(max(float(value), 0.05), 5.0)
            return True
        return False

    def next_batch(self, batch_size):
        """Get up to ``batch_size`` items from the input queue.

        Blocks until data is available.  Returns fewer than ``batch_size``
        items at end-of-feed (``None`` sentinel) or at a partition boundary
        during inference (``EndPartition``) — reference ``TFNode.py:105-151``.

        Returns a list of items, or a dict of per-tensor lists when
        ``input_mapping`` was provided.
        """
        self._clock.switch("assemble")
        try:
            return self._next_batch(batch_size)
        finally:
            self._clock.switch("away")

    def _next_batch(self, batch_size):
        logger.debug("requesting batch of %d items", batch_size)
        queue = self.mgr.get_queue(self.qname_in)
        tensors = ([] if self.input_tensors is None
                   else {tensor: [] for tensor in self.input_tensors})
        count = 0
        if self._batches:
            self._unbatch()  # rows next_batch_arrays copied ahead come first
        while count < batch_size:
            if self._buffer_idx < self._buflen():
                item = self._bufrow(self._buffer_idx)
                self._buffer_idx += 1
                from_queue = False
            else:
                item = self._pull(queue)
                if item is _INTERRUPTED:
                    logger.info("next_batch: interrupted with %d items", count)
                    break
                if item is _BUFFERED:
                    continue
                from_queue = True
            if item is None:
                # End-of-feed: producers are done for good (reference 129-134).
                logger.info("next_batch: end of feed")
                self.done_feeding = True
                if from_queue:
                    queue.task_done()
                break
            elif isinstance(item, marker.EndPartition):
                # Partition boundary: stop here if we already have items so
                # result batches align with partitions (reference 135-140).
                logger.debug("next_batch: end of partition")
                if from_queue:
                    queue.task_done()
                if count > 0:
                    break
            else:
                if self.input_tensors is None:
                    tensors.append(item)
                else:
                    for i, tensor in enumerate(self.input_tensors):
                        tensors[tensor].append(item[i])
                count += 1
                if from_queue:
                    queue.task_done()
                elif self._buffer_idx >= self._buflen():
                    # Ack only after the chunk's last item is safely batched:
                    # a crash on a malformed item above must leave the queue
                    # un-joined so the feeder's error-poll fires (see ctor).
                    self._ack_read()
        self.items_consumed += count
        self._fault.on_items(count)
        logger.debug("next_batch: returning %d items", count)
        return tensors

    def _buflen(self):
        """Row count of the pending chunk buffer (item list or columnar)."""
        buf = self._buffer
        return buf.count if isinstance(buf, marker.ColChunk) else len(buf)

    def _bufrow(self, i):
        """Row ``i`` of the pending chunk buffer."""
        buf = self._buffer
        return buf.row(i) if isinstance(buf, marker.ColChunk) else buf[i]

    def _get_interruptible(self, queue):
        """Blocking get that aborts (returning ``_INTERRUPTED``) once
        :meth:`interrupt` fires.  Short-timeout polling, not ``block=True``:
        the proxy's blocking get cannot be cancelled from another thread.
        Phase ``wait``; leaves the clock on ``read`` (see :meth:`_pull`)."""
        t0 = self._clock.switch("wait")
        try:
            with telemetry.annotation("feed/wait"):
                while not self._interrupt.is_set():
                    try:
                        return queue.get(block=True, timeout=self._poll_secs)
                    except _queue.Empty:
                        continue
                return _INTERRUPTED
        finally:
            self.stall_secs += (self._clock.switch("read") - t0) / 1e9

    def _pull(self, queue, into=None):
        """The next thing off the queue, accounted: blocked on the empty
        queue (phase ``wait``), then a chunk's payload read (phase ``read``;
        the ack is deferred, see ctor), then back to ``assemble``.  A chunk
        goes into the row buffer; with ``into`` (the array path, see
        :meth:`_ring_read`) a framed ring chunk's rows go straight into the
        batch's buffers instead.  Returns ``_BUFFERED`` for a chunk,
        ``_INTERRUPTED``, or the loose item / ``None`` / ``EndPartition``
        for the caller."""
        item = self._get_interruptible(queue)
        try:
            if item is _INTERRUPTED:
                return item
            with telemetry.annotation("feed/read"):
                if isinstance(item, marker.ShmChunk):
                    # Payload took the native shm-ring fast path; the token
                    # preserves ordering/join semantics (see marker.ShmChunk).
                    item = self._ring_read(item, into=into)
                    if item is None:
                        # Copied into the batches.  Acked now if its last
                        # row is in the batch in hand, else by the call that
                        # takes the batch that holds it.
                        self._chunk_q = queue
                        if len(self._batches) <= 1:
                            self._ack_chunk()
                        return _BUFFERED
                elif isinstance(item, (marker.Chunk, marker.ColChunk)):
                    self._note_transport("queue")
                if not isinstance(item, (marker.Chunk, marker.ColChunk)):
                    return item
                self._buffer = (item.items if isinstance(item, marker.Chunk)
                                else item)
                self._buffer_idx = 0
                self._chunk_q = queue
                if not self._buflen():
                    self._ack_chunk()
                return _BUFFERED
        finally:
            self._clock.switch("assemble")

    def interrupt(self):
        """Unblock a concurrent :meth:`next_batch` and make subsequent calls
        return immediately.  Used to hand queue ownership from a consumer
        thread to :meth:`terminate`'s drain — the queue and shm ring are
        strictly single-consumer, so the old consumer must be out before the
        drain starts."""
        self._interrupt.set()

    def _ack_chunk(self):
        if self._chunk_q is not None:
            self._chunk_q.task_done()
            self._chunk_q = None

    def _ack_read(self):
        """:meth:`_ack_chunk` from inside ``next_batch*``: the ``task_done``
        round trip to the manager belongs to phase ``read``."""
        self._clock.switch("read")
        with telemetry.annotation("feed/read"):
            self._ack_chunk()
        self._clock.switch("assemble")

    def _note_transport(self, fmt):
        self.wire_formats[fmt] = self.wire_formats.get(fmt, 0) + 1

    def _ring_read(self, token, timeout_secs=600, into=None):
        """Pop one chunk payload from the shm ring named by the token;
        returns the chunk object (:class:`~tensorflowonspark_tpu.marker.Chunk`
        or :class:`~tensorflowonspark_tpu.marker.ColChunk`; legacy payloads
        may be bare item lists, returned wrapped in a Chunk).

        ``fmt`` on the token picks the record decoding: framed columnar
        records (:data:`~tensorflowonspark_tpu.wire.WIRE_COLV1`) take the
        two-phase peek/consume path — the in-ring bytes are wrapped with
        ``np.frombuffer`` views and each column is copied exactly once, with
        no intermediate record buffer and no unpickle.  Where to: into a
        chunk of its own (the row path and the drain), or, with ``into``
        (``into(columns, tuple_rows, count)``, the array path's
        :meth:`_copy_rows`), straight from the views into the batch's
        buffers, in which case nothing is returned.  Either way the ring
        slot is consumed when the copy is done, and also when it raises."""
        import pickle

        from tensorflowonspark_tpu import shmring, wire

        ring = shmring.get_ring(token.ring_name)
        if ring is None:
            raise RuntimeError(
                "feeder sent a shm-ring chunk but ring {} cannot be attached "
                "in the consumer process".format(token.ring_name))
        fmt = getattr(token, "fmt", wire.WIRE_PICKLE)
        if fmt == wire.WIRE_COLV1:
            view = ring.peek(timeout_secs)
            try:
                columns, n, tuple_rows = wire.decode(view, copy=into is None)
                self._note_transport(fmt)
                self._check_count(token, n)
                if into is None:
                    return marker.ColChunk(columns, n, tuple_rows)
                into(columns, tuple_rows, n)
                return None
            finally:
                # Consume even when decode or the copy raises: tokens and
                # records must stay 1:1 or every later chunk on this ring
                # desyncs.
                ring.consume()
        obj = pickle.loads(ring.get_bytes(timeout_secs))
        self._note_transport(fmt)
        if isinstance(obj, list):
            obj = marker.Chunk(obj)
        self._check_count(
            token,
            obj.count if isinstance(obj, marker.ColChunk) else len(obj.items))
        return obj

    @staticmethod
    def _check_count(token, n):
        if n != token.count:
            # Token/record desync would silently deliver wrong training data;
            # must survive python -O, so not an assert.
            raise RuntimeError(
                "shm ring {} desync: token promised {} items, record has "
                "{}".format(token.ring_name, token.count, n))

    def next_batch_arrays(self, batch_size, dtypes=None):
        """TPU-first variant: assemble the batch directly into numpy arrays.

        Columnar end to end: feeders ship
        :class:`~tensorflowonspark_tpu.marker.ColChunk` blocks (a few
        contiguous ndarrays), and this method copies each block's columns
        **once**, into their rows of the batch's buffers (one array a
        column, ``[batch_size, ...]``, in the dtype asked for) — for a
        framed ring chunk straight from the in-ring bytes, with the
        ``dtypes`` cast in that same copy.  No per-row Python objects ever
        exist on this path.  Object chunks / loose items degrade gracefully
        to per-row ``np.asarray``.  Pairs with
        ``parallel.infeed.ShardedFeed`` for a single per-host device
        transfer.

        Returns ``(arrays, count)`` where ``count`` is the number of real
        rows (may be < batch_size at end of feed) and ``arrays`` is:

        - a dict ``{tensor_name: ndarray}`` when ``input_mapping`` was given
          (row fields map positionally to the sorted column order, exactly
          like :meth:`next_batch`);
        - a tuple of per-field ndarrays when rows are tuples;
        - a single ndarray when rows are single values.

        ``dtypes``: optional cast — a dict keyed by tensor name (with
        input_mapping), a sequence matching the field count (tuple rows), or
        a single dtype (single-value rows).  Pass the same ``batch_size``
        and ``dtypes`` from call to call: rows that a chunk's tail put into
        the next batch already are in that batch's dtype.

        **Who owns a batch.**  The caller does, for as long as it keeps the
        arrays: no later call writes to them.  A caller that is done with a
        whole batch (its host-to-device transfer is over, nothing it keeps
        refers to the memory) may hand the arrays back with :meth:`release`;
        a later batch of the same kind is then built in that memory, which
        is already mapped, instead of in new pages.  Never handing back is
        fine and costs only that.
        """
        self._clock.switch("assemble")
        try:
            return self._next_batch_arrays(batch_size, dtypes)
        finally:
            self._clock.switch("away")

    def _next_batch_arrays(self, batch_size, dtypes):
        queue = self.mgr.get_queue(self.qname_in)
        batches = self._batches

        def into(fields, tuple_rows, n):
            self._copy_rows(fields, tuple_rows, n, batch_size, dtypes)

        if batches and not self._as_asked(batches[0], batch_size, dtypes):
            self._unbatch()
        if len(batches) == 1:
            # the last rows copied ahead are in the batch in hand now
            self._ack_read()
        # More than one batch waits only between calls, and the head is
        # then full: inside the loop the deque holds the batch in hand or
        # nothing, until a ring chunk's tail spills and ends the loop.
        while not batches or batches[0].count < batch_size:
            buflen = self._buflen()
            if self._buffer_idx < buflen:
                take = batch_size - batches[0].count if batches else batch_size
                take = min(take, buflen - self._buffer_idx)
                i0 = self._buffer_idx
                buf = self._buffer
                if isinstance(buf, marker.ColChunk):
                    fields = tuple(c[i0:i0 + take] for c in buf.columns)
                    tr = buf.tuple_rows
                else:
                    fields, tr = _rows_to_fields(buf[i0:i0 + take])
                into(fields, tr, take)
                self._buffer_idx += take
                if self._buffer_idx >= buflen:
                    self._ack_read()
                continue
            item = self._pull(queue, into)
            if item is _INTERRUPTED:
                logger.info("next_batch_arrays: interrupted at %d rows",
                            batches[0].count if batches else 0)
                break
            if item is _BUFFERED:
                continue
            if item is None:
                logger.info("next_batch_arrays: end of feed")
                self.done_feeding = True
                queue.task_done()
                break
            if isinstance(item, marker.EndPartition):
                queue.task_done()
                if batches:
                    break
                continue
            # A loose (unchunked) item: a one-row part, under the same
            # structure-consistency contract as the chunk path.
            into(*_rows_to_fields([item]), 1)
            queue.task_done()
        if not batches:
            return assemble_columns([], None, None, self.input_tensors), 0
        batch = batches.popleft()
        count = batch.count
        self.items_consumed += count
        self._fault.on_items(count)
        cols = (batch.cols if count == batch.size
                else [c[:count] for c in batch.cols])
        if self.input_tensors is not None:
            return dict(zip(self.input_tensors, cols)), count
        return (tuple(cols) if batch.tuple_rows else cols[0]), count

    def _field_dtypes(self, dtypes, arity, tuple_rows):
        """``dtypes`` as the caller gives it -> one dtype (or None) a field."""
        if self.input_tensors is not None:
            if arity != len(self.input_tensors):
                raise ValueError(
                    "input_mapping names {} tensors but feed rows have {} "
                    "fields".format(len(self.input_tensors), arity))
            if dtypes is not None:
                return [dtypes.get(t) for t in self.input_tensors]
        if dtypes is None:
            return [None] * arity
        return [dtypes[f] for f in range(arity)] if tuple_rows else [dtypes]

    def _as_asked(self, batch, batch_size, dtypes):
        """Whether a batch begun by an earlier call is what this call asks
        for: its size, and the dtypes named."""
        return batch.size == batch_size and batch.asked == self._field_dtypes(
            dtypes, len(batch.cols), batch.tuple_rows)

    def _new_batch(self, fields, tuple_rows, batch_size, dtypes):
        """Buffers for a batch whose rows look like ``fields``: handed-back
        ones of that kind if the feed holds any, else new memory."""
        asked = self._field_dtypes(dtypes, len(fields), tuple_rows)
        key = tuple(
            ((batch_size,) + f.shape[1:],
             (f.dtype if d is None else np.dtype(d)).str)
            for f, d in zip(fields, asked))
        if self._free and key == self._free_key:
            cols = self._free.pop()
            self.buffers_reused += 1
        else:
            cols = [np.empty(shape, dtype) for shape, dtype in key]
            self.buffers_new += 1
        return _Batch(cols, asked, tuple_rows, batch_size)

    def _copy_rows(self, fields, tuple_rows, n, batch_size, dtypes):
        """Copy ``n`` rows, given as one array (or in-ring view) a field,
        into the batches under construction: the last one while it has
        room, then new ones.  The only copy of a row on the array path; a
        cast to ``dtypes`` happens in it."""
        batches = self._batches
        at = 0
        while at < n:
            batch = batches[-1] if batches else None
            if batch is None or batch.count >= batch.size:
                batch = self._new_batch(fields, tuple_rows, batch_size, dtypes)
                batches.append(batch)
            elif (batch.tuple_rows != tuple_rows
                  or len(batch.cols) != len(fields)):
                raise ValueError(
                    "inconsistent row structure across feed chunks "
                    "(tuple_rows {} vs {})".format(batch.tuple_rows,
                                                   tuple_rows))
            lo = batch.count
            take = min(batch.size - lo, n - at)
            for f, src in enumerate(fields):
                dst = batch.cols[f]
                if src.shape[1:] != dst.shape[1:]:
                    raise ValueError(
                        "inconsistent row structure across feed chunks "
                        "(field {} has shape {} after {})".format(
                            f, src.shape[1:], dst.shape[1:]))
                if src.dtype != dst.dtype and batch.asked[f] is None and \
                        not np.can_cast(src.dtype, dst.dtype, "safe"):
                    # what np.concatenate would have given: the rows so far
                    # move to the wider dtype (a rare, slow path: object
                    # chunks whose python numbers change kind mid-batch)
                    dst = batch.cols[f] = dst.astype(
                        np.result_type(src.dtype, dst.dtype))
                np.copyto(dst[lo:lo + take], src[at:at + take],
                          casting="unsafe")
            batch.count = lo + take
            at += take

    def _unbatch(self):
        """Rows that the array path copied ahead go back into the row
        buffer as one columnar chunk, ack and all: for :meth:`next_batch`,
        and for a ``next_batch_arrays`` call that asks for another batch
        size or dtype than the one that copied them."""
        batches = list(self._batches)
        self._batches.clear()
        parts = [[c[:b.count] for c in b.cols] for b in batches]
        cols = tuple(p[0] if len(p) == 1 else np.concatenate(p)
                     for p in zip(*parts))
        self._buffer = marker.ColChunk(
            cols, sum(b.count for b in batches), batches[0].tuple_rows)
        self._buffer_idx = 0

    def release(self, arrays):
        """Hand a batch's arrays back: ``arrays`` as
        :meth:`next_batch_arrays` returned them, from a caller that will
        not touch that memory again (see "Who owns a batch" there).  A
        later batch of the same kind is built in them.  Returns whether the
        feed took them: only whole batches are taken (not the views of a
        partial one), and only once."""
        if isinstance(arrays, dict):
            if self.input_tensors is None:
                return False
            cols = [arrays.get(t) for t in self.input_tensors]
        else:
            cols = list(arrays) if isinstance(arrays, tuple) else [arrays]
        key = _buffers_key(cols)
        if key is None:
            return False
        if key != self._free_key:
            # one kind at a time: what a changed batch size or row shape
            # left behind goes, so the list never outgrows its reader
            self._free, self._free_key = [], key
        if any(held[0] is cols[0] for held in self._free):
            return False
        self._free.append(cols)
        return True

    def counters_snapshot(self):
        """Flat telemetry counters for heartbeat payloads.

        Schema: ``feed_items`` (rows delivered), ``feed_stall_secs`` (time
        blocked on an empty queue), ``feed_<phase>_us`` for each of
        :data:`FEED_PHASES` (they sum to this feed's age), ``wire_<fmt>``
        (chunks per transport —
        ``wire_colv1``/``wire_pickle``/``wire_queue``; data-service feeds
        additionally mint ``wire_colv1+<codec>`` keys for compressed
        streams plus the ``dataservice_cache_*`` / ``wire_compress_*``
        vocabulary, see ``ServiceFeed.counters_snapshot``), and what this
        node's feed tasks published from the executor's process
        (``feeder_<phase>_us``, ``feeder_items``, ``feeder_bytes``,
        ``feeder_tasks``, ring writes: ``node._publish_feeder_metrics``).
        Safe from any thread at any moment of a running feed.
        """
        snap = self._own_counters()
        try:
            # one manager round trip, numbers only; a manager that cannot be
            # asked costs the feeders' counters, never the snapshot
            snap.update(telemetry.merge_counters(
                [self.mgr.get("feeder_metrics")]))
        except Exception:
            pass
        return snap

    def _own_counters(self):
        """This feed's counters alone, with no manager round trip (the
        heartbeat provider's view: it merges the feeders' KV itself, once)."""
        snap = {"feed_items": self.items_consumed,
                "feed_stall_secs": round(self.stall_secs, 6),
                "feed_batch_buffers_reused": self.buffers_reused,
                "feed_batch_buffers_new": self.buffers_new}
        snap.update(self._clock.snapshot("feed_"))
        for fmt, n in self.wire_formats.copy().items():
            snap["wire_{}".format(fmt)] = n
        return snap

    def should_stop(self):
        """True once end-of-feed was observed (reference ``TFNode.py:153-155``)."""
        return self.done_feeding

    def batch_results(self, results):
        """Push a batch of inference results to the output queue
        (reference ``TFNode.py:157-170``); the whole batch travels as one
        chunk (see :class:`~tensorflowonspark_tpu.marker.Chunk`)."""
        results = list(results)
        if results:
            queue = self.mgr.get_queue(self.qname_out)
            queue.put(marker.Chunk(results), block=True)

    def terminate(self):
        """Terminate data feeding early (e.g. training reached max steps with
        epochs of data left).  Sets the node state to ``'terminating'`` so
        upcoming feed partitions are skipped, then drains the input queue
        (reference ``TFNode.py:172-194``)."""
        logger.info("terminate() invoked: draining remaining input")
        try:
            self.mgr.set("state", "terminating")
            self._ack_chunk()  # release a partially-consumed chunk's join hold
            self._buffer, self._buffer_idx = [], 0
            self._batches.clear()
            queue = self.mgr.get_queue(self.qname_in)
        except (EOFError, BrokenPipeError, ConnectionError, OSError):
            # the manager died before the drain even started (driver-side
            # shutdown won the race) — nothing left to mark or drain
            logger.info("manager gone at terminate(); assuming shutdown")
            self._buffer, self._buffer_idx = [], 0
            self._batches.clear()
            return
        count = 0
        done = False
        while not done:
            try:
                item = queue.get(block=True, timeout=5)
                queue.task_done()
                if item is None:
                    done = True
                else:
                    if isinstance(item, marker.ShmChunk):
                        # Pop the ring record too, so a producer blocked on a
                        # full ring unblocks (tokens and records stay 1:1).
                        try:
                            self._ring_read(item, timeout_secs=5)
                        except Exception:
                            pass
                    count += 1
            except _queue.Empty:
                logger.info("dropped %d items after terminate", count)
                done = True
            except (EOFError, BrokenPipeError, ConnectionError, OSError):
                # The manager died under the drain — the driver shut the
                # cluster down while we were still discarding leftover
                # input.  A dead manager means there is nothing left to
                # drain (or ack to); finishing quietly is the correct
                # outcome, not an error in the user's fn.
                logger.info("manager gone during terminate drain "
                            "(%d items dropped); assuming shutdown", count)
                done = True
