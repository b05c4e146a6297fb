"""Sharded per-host infeed: Spark-pushed partitions -> device-resident global batches.

This is the TPU-first rewrite of the reference's InputMode.SPARK hot path.
The reference moved every RDD element individually through a manager proxy
into a ``tf.data.from_generator`` (reference ``TFNode.py:105-151`` +
``examples/mnist/keras/mnist_spark.py:31-47``) — a per-element IPC hop that
caps accelerator utilization.  Here each host:

1. drains its queue into **columnar numpy batches** (feeders ship ColChunks
   as zero-copy framed ring records — :mod:`~tensorflowonspark_tpu.wire` —
   so assembly is columnar, amortized, and unpickle-free on the fast path),
2. forms its *local shard* of the global batch and transfers it in a single
   ``jax.make_array_from_process_local_data`` call,
3. runs a tiny cross-host consensus each step so all hosts agree whether a
   full step's worth of data exists — replacing the reference's fragile
   "90% of steps" workaround (``mnist_spark.py:58-66``) with an exact
   end-of-data barrier (SURVEY §7.4.1),
4. double-buffers by default (prefetch) so host assembly AND the
   host->device transfer overlap the device step: the dispatch loop only
   ever sees already-device-resident, freshly-allocated (donation-safe)
   arrays, and never blocks on PCIe/transport.  The overlap is measured,
   not assumed: always-on ``infeed_assembly_us`` / ``infeed_put_us``
   counters (+ ``_hwm``) ride heartbeats into the driver's
   ``metrics_snapshot()``, and ``infeed/assemble`` / ``infeed/device_put``
   spans land on the telemetry timeline when tracing is enabled.
"""

import collections
import logging
import os
import queue as _queue
import threading
import time

import numpy as np

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.parallel import collectives, mesh as mesh_mod

logger = logging.getLogger(__name__)

#: prefetch depth used when the ctor gets ``prefetch=None`` (device-resident
#: double buffering by default; 0 disables the prefetch thread entirely and
#: moves assembly + transfer back onto the dispatch path)
PREFETCH_ENV = "TFOS_INFEED_PREFETCH"
DEFAULT_PREFETCH = 2

#: how K-step groups are assembled when ``group_assembly=None``:
#: ``"device"`` (default) transfers each batch as it arrives and stacks the
#: group on device under a tiny jitted assembler — the host never
#: materializes the K× copy and assembly overlaps the previous dispatch;
#: ``"host"`` restores the old behavior (np.stack on the prefetch thread,
#: one big transfer per group).
GROUP_ASSEMBLY_ENV = "TFOS_GROUP_ASSEMBLY"
DEFAULT_GROUP_ASSEMBLY = "device"

#: how long :meth:`ShardedFeed.terminate` waits for the prefetch thread — it
#: can be mid device_put (not interruptible), so the join is bounded, re-
#: interrupting the feed each round; past the deadline the queue drain is
#: skipped (single-consumer invariant) and the daemon thread is abandoned.
TERMINATE_JOIN_SECS = 30.0

_GROUP_SLICER = None


def _group_slicer():
    """Jitted ``(tree, i) -> tree[i]`` along the leading (scan) dim.  The
    index is a traced scalar, so all k slices share one compilation."""
    global _GROUP_SLICER
    if _GROUP_SLICER is None:
        import jax

        _GROUP_SLICER = jax.jit(
            lambda tree, i: jax.tree_util.tree_map(lambda x: x[i], tree))
    return _GROUP_SLICER


class ShardedFeed(object):
    """Iterator of device-resident, mesh-sharded global batches from a DataFeed.

    Args:
      feed: a :class:`~tensorflowonspark_tpu.datafeed.DataFeed`.
      mesh: the device mesh; batches are sharded over its data-like axes.
      global_batch_size: total batch across all hosts; this host contributes
        ``global_batch_size / process_count`` rows per step.
      preprocess: optional ``fn(items) -> pytree of np.ndarray`` turning a
        list of queue items into columnar arrays.  This is the *row-list*
        path (per-item Python objects); prefer ``transform``.
      transform: optional ``fn(arrays) -> pytree of np.ndarray`` applied to
        the **columnar** batch from ``DataFeed.next_batch_arrays`` (a tuple
        of per-field arrays, a dict when the feed has an input_mapping, or a
        single array) — e.g. reshape ``(N, 784) -> (N, 28, 28, 1)`` and name
        the fields.  The columnar path never materializes per-row objects;
        pair with feeders' ColChunk blocks for the full zero-object plane.
      pad_final: when the feed ends mid-batch, pad the final global batch to
        full size and attach a validity mask instead of dropping the tail.
      prefetch: number of batches to assemble ahead on a host thread — each
        buffered batch is already **device-resident** (the host->device
        transfer runs on the prefetch thread, not the dispatch path), at a
        cost of ``prefetch`` extra batches of HBM.  ``None`` reads
        ``TFOS_INFEED_PREFETCH`` (default 2); 0 disables the thread.
      sharding: optional NamedSharding overriding the default batch
        sharding for data leaves — e.g. ``PartitionSpec(("data",), "seq")``
        to shard LM token batches over the sequence axis too.  The spec is
        truncated to each leaf's rank (labels ``(B,)`` take just the batch
        axes) and the mask always uses the batch-dim entry alone.
      group_assembly: how :meth:`grouped_batches` builds its K-step stacks —
        ``"device"`` (default) transfers each batch as it arrives and stacks
        on device under a tiny jitted assembler (the host never materializes
        the K× copy; fresh buffers every group, so the trainer may donate
        the stack), ``"host"`` keeps the old np.stack-then-one-transfer path
        (reuses one mask stack, NOT donation-safe).  ``None`` reads
        ``TFOS_GROUP_ASSEMBLY``.
    """

    def __init__(self, feed, mesh, global_batch_size, preprocess=None,
                 transform=None, pad_final=True, prefetch=None, sharding=None,
                 group_assembly=None):
        import jax

        assert preprocess is None or transform is None, \
            "pass either preprocess (row-list path) or transform (columnar)"
        self.feed = feed
        self.mesh = mesh
        self.global_batch_size = global_batch_size
        self.local_batch_size = mesh_mod.local_batch_size(mesh, global_batch_size)
        self.preprocess = preprocess  # None = columnar next_batch_arrays path
        self.transform = transform
        self.pad_final = pad_final
        if prefetch is None:
            prefetch = int(os.environ.get(PREFETCH_ENV, "")
                           or DEFAULT_PREFETCH)
        self._prefetch_depth = prefetch
        if group_assembly is None:
            group_assembly = (os.environ.get(GROUP_ASSEMBLY_ENV, "")
                              or DEFAULT_GROUP_ASSEMBLY)
        if group_assembly not in ("device", "host"):
            raise ValueError(
                "group_assembly must be 'device' or 'host', got {!r}".format(
                    group_assembly))
        self._group_assembly = group_assembly
        # Live group size: grouped_batches(k) seeds _group_k; an autopilot
        # train_steps_per_call push lands in _group_k_target and is picked
        # up at the next group-fill START (never mid-group), so K changes
        # only between groups and every yielded stack is internally uniform.
        self._group_k = 0
        self._group_k_target = None
        self._group_assembler = None   # jitted device-side stack (lazy)
        self._scan_shardings = {}      # stacked-ndim -> NamedSharding
        self._group_assemble_us = 0
        self._group_assemble_us_hwm = 0
        # Always-on plain-int tallies (the DataFeed/shmring pattern —
        # telemetry reads them at heartbeat cadence, the hot path never
        # pays for a lock or a tracer call): batches transferred, host
        # assembly time, and host->device transfer time, with per-batch
        # high-water marks.  Single writer (the prefetch thread, or the
        # consumer when prefetch=0); heartbeat reads tolerate staleness.
        self._n_batches = 0
        self._assembly_us = 0
        self._assembly_us_hwm = 0
        self._put_us = 0
        self._put_us_hwm = 0
        self._sharding = sharding or mesh_mod.batch_sharding(mesh)
        from jax.sharding import NamedSharding, PartitionSpec

        self._mask_sharding = NamedSharding(
            mesh, PartitionSpec(*tuple(self._sharding.spec)[:1]))
        self._leaf_shardings = {}    # ndim -> NamedSharding (hot-path cache)
        self._num_processes = jax.process_count()
        self._stop = None            # prefetch stop event (set in batches())
        self._prefetch_thread = None
        self._prefetch_buf = None    # live prefetch queue (apply_knob target)
        # Trace-flow relay: ids popped from the upstream feed
        # (ServiceFeed.pop_flow_id) at device-put time, re-parked here for
        # the trainer's dispatch leg (pop_dispatch_flow).  Best-effort,
        # bounded; single producer (the prefetch thread), single consumer.
        self._dispatch_flows = collections.deque(maxlen=16)
        # The feed's host arrays whose transfer may still be under way,
        # with the device arrays made of them: (lent, leaves), oldest
        # first (see _hand_back).  One thread: the one that assembles.
        self._lent = collections.deque()
        # Ride this node's heartbeats: the metrics provider duck-types
        # counters_snapshot() over every registered source, so the infeed_*
        # tallies reach the driver's metrics_snapshot() aggregate.  Guarded:
        # standalone use (no node runtime) must not care.
        try:
            from tensorflowonspark_tpu import node as _node_mod

            _node_mod._register_feed(self)
        except Exception:  # pragma: no cover - import cycles / stripped envs
            pass

    def _leaf_sharding(self, ndim):
        """Data-leaf sharding with the spec truncated to the leaf's rank
        (cached per rank — this sits on the per-step transfer path)."""
        if ndim not in self._leaf_shardings:
            from jax.sharding import NamedSharding, PartitionSpec

            spec = tuple(self._sharding.spec)[:ndim]
            self._leaf_shardings[ndim] = NamedSharding(
                self.mesh, PartitionSpec(*spec))
        return self._leaf_shardings[ndim]

    # -- host-side batch assembly ----------------------------------------

    # -- overlap accounting ----------------------------------------------

    def _tally_assembly(self, start):
        us = int((time.perf_counter() - start) * 1e6)
        self._assembly_us += us
        if us > self._assembly_us_hwm:
            self._assembly_us_hwm = us

    def _tally_put(self, start):
        us = int((time.perf_counter() - start) * 1e6)
        self._put_us += us
        if us > self._put_us_hwm:
            self._put_us_hwm = us

    def _note_flow(self, leg, **attrs):
        """Relay a committed-split trace-flow id (if the upstream feed
        carries one) through the device-put leg to the dispatch leg."""
        pop = getattr(self.feed, "pop_flow_id", None)
        if pop is None:
            return
        try:
            fid = pop()
        except Exception:  # pragma: no cover - duck-typed feeds
            return
        if fid:
            telemetry.get_tracer().flow_step(
                "dataservice/split_flow", fid, leg=leg, **attrs)
            self._dispatch_flows.append(int(fid))

    def pop_dispatch_flow(self):
        """Oldest undrained trace-flow id that reached device infeed (or
        None); drained by ``Trainer.fit_feed`` to end the flow at the
        dispatch leg."""
        try:
            return self._dispatch_flows.popleft()
        except IndexError:
            return None

    def counters_snapshot(self):
        """Flat infeed overlap counters for heartbeat payloads /
        :func:`~tensorflowonspark_tpu.telemetry.merge_counters`:
        ``infeed_batches`` (device transfers), ``infeed_assembly_us`` (host
        columnar assembly, INCLUDING time blocked on the upstream feed —
        starvation is separately visible as ``feed_stall_secs``),
        ``infeed_put_us`` (host->device transfer), per-batch ``_hwm``
        high-water marks of both, and ``train_group_assemble_us`` (host wall
        spent dispatching the jitted device-side K-stack; ~free next to the
        transfers it replaced)."""
        return {
            "infeed_batches": self._n_batches,
            "infeed_assembly_us": self._assembly_us,
            "infeed_assembly_us_hwm": self._assembly_us_hwm,
            "infeed_put_us": self._put_us,
            "infeed_put_us_hwm": self._put_us_hwm,
            "train_group_assemble_us": self._group_assemble_us,
            "train_group_assemble_us_hwm": self._group_assemble_us_hwm,
            # gauge (never summed): the CURRENT depth, so the driver can
            # confirm a live autopilot retune landed
            "infeed_prefetch_depth_max": self._prefetch_depth,
        }

    def apply_knob(self, name, value):
        """Live-knob hook (autopilot KNOB pushes; see docs/AUTOPILOT.md).

        ``infeed_prefetch`` retunes the prefetch depth mid-run: the new
        bound is applied to the RUNNING prefetch queue in place (under its
        mutex, waking blocked putters — a raise takes effect on the very
        next produced batch).  A feed built with ``prefetch=0`` has no
        producer thread to rebound, so a raise there takes effect at the
        next ``batches()`` call.

        ``train_steps_per_call`` retunes the grouped-iteration K: the new
        size is parked in a target slot that the grouped iterator reads at
        each group-fill START, so the change lands exactly on a group
        boundary (groups already buffered keep their old K; the trainer's
        per-K program cache handles the mix).  Refused on multi-process
        meshes: knob pushes arrive per-host on heartbeats, and a transient
        skew would desync the SPMD group lock-step.  Returns True when the
        knob was claimed.
        """
        if name == "train_steps_per_call":
            if self._num_processes > 1:
                logger.warning(
                    "refusing live train_steps_per_call retune on a "
                    "%d-process mesh (per-host knob delivery skew would "
                    "desync grouped lock-step)", self._num_processes)
                return False
            self._group_k_target = max(int(value), 1)
            return True
        if name != "infeed_prefetch":
            return False
        depth = max(int(value), 1)
        self._prefetch_depth = depth
        buf = self._prefetch_buf
        if buf is not None:
            with buf.mutex:
                buf.maxsize = depth
                buf.not_full.notify_all()
        return True

    @property
    def group_assembly(self):
        """``"device"`` or ``"host"`` — how grouped stacks are built."""
        return self._group_assembly

    @property
    def group_donation_safe(self):
        """True when every grouped stack (batches AND masks) is built from
        fresh device buffers each group, so ``multi_step`` may donate them
        back to the allocator.  Host-stack mode reuses one transferred mask
        stack across groups and is therefore not donation-safe."""
        return self._group_assembly == "device"

    def _next_local(self):
        """Assemble this host's local batch as final columnar arrays;
        returns (arrays, count, lent) or None when no usable rows remain.
        ``lent`` is the batch as the feed's ``next_batch_arrays`` returned
        it, before the transform (``None`` on the row-list path): what
        :meth:`_shard` queues for the hand-back (:meth:`_hand_back`)."""
        start = time.perf_counter()
        with telemetry.span("infeed/assemble"):
            local = self._next_local_inner()
        if local is not None:
            self._tally_assembly(start)
        return local

    def _next_local_inner(self):
        if self.preprocess is not None:
            # row-list path: user preprocess consumes the raw item lists
            items = self.feed.next_batch(self.local_batch_size)
            if isinstance(items, dict):
                count = len(next(iter(items.values()))) if items else 0
            else:
                count = len(items)
            if count == 0:
                return None
            with telemetry.span("infeed/transform"):
                arrays = self.preprocess(items)
            lent = None
        else:
            self._hand_back()
            arrays, count = self.feed.next_batch_arrays(self.local_batch_size)
            if count == 0:
                return None
            lent = arrays
            if self.transform is not None:
                with telemetry.span("infeed/transform"):
                    arrays = self.transform(arrays)
        if count < self.local_batch_size and not self.pad_final:
            # partial tail with padding disabled: drop it (documented)
            logger.info("dropping %d-row partial tail (pad_final=False)", count)
            return None
        return arrays, count, lent

    def _shard(self, arrays, count, lent=None):
        """Pad to the local batch size and transfer to devices as this
        process's shard of the global batch; returns (batch, mask).
        ``lent``, the feed's own arrays that ``arrays`` were made of, go
        back to the feed once the transfer has read them
        (:meth:`_hand_back`).

        The transfer is an explicit ``make_array_from_process_local_data``
        into freshly-allocated device buffers — donation-safe (the step may
        donate the batch) and legal under a host->device transfer guard on
        the dispatch path, because when prefetch is on this runs on the
        prefetch thread."""
        import jax

        def to_padded(col):
            col = np.asarray(col)
            if count < self.local_batch_size:
                pad = [(0, self.local_batch_size - count)] + \
                      [(0, 0)] * (col.ndim - 1)
                col = np.pad(col, pad)
            return col

        local = jax.tree_util.tree_map(to_padded, arrays)
        mask = np.zeros((self.local_batch_size,), dtype=np.float32)
        mask[:count] = 1.0

        def put(x):
            return jax.make_array_from_process_local_data(
                self._leaf_sharding(np.ndim(x)), x)

        start = time.perf_counter()
        with telemetry.span("infeed/device_put", rows=count):
            batch = jax.tree_util.tree_map(put, local)
            mask = jax.make_array_from_process_local_data(
                self._mask_sharding, mask)
        self._tally_put(start)
        self._n_batches += 1
        self._note_flow("infeed_device_put", rows=count)
        if lent is not None:
            self._lent.append((lent, jax.tree_util.tree_leaves(batch)))
            self._hand_back()
        return batch, mask

    def _hand_back(self):
        """Give the feed its batch buffers back (``DataFeed.release``,
        ``FileFeed.release``; a feed without the method keeps its arrays
        its own), oldest first, as
        far as nothing on the device side reads them any more: the
        transfer that was made of them is complete, and no device array is
        the host memory itself.  A device with memory of its own copied;
        the CPU client may have wrapped an aligned numpy buffer instead,
        which its buffer's address shows.  Never waits: a transfer still
        under way is looked at again before the next batch is asked for.
        Where it cannot be told (a device array deleted by a donating
        step), nothing is handed back."""
        import jax

        release = getattr(self.feed, "release", None)
        if release is None:
            self._lent.clear()
            return
        while self._lent:
            lent, leaves = self._lent[0]
            try:
                # a donating step deleted them: it cannot be told (and
                # is_ready() on a deleted array does not raise, it takes
                # the process down: jax 0.9.0, CPU client)
                mine = any(leaf.is_deleted() for leaf in leaves)
                if not mine:
                    if not all(leaf.is_ready() for leaf in leaves):
                        return
                    spans = [(a.ctypes.data, a.ctypes.data + a.nbytes)
                             for a in jax.tree_util.tree_leaves(lent)]
                    mine = any(
                        lo <= shard.data.unsafe_buffer_pointer() < hi
                        for leaf in leaves
                        for shard in leaf.addressable_shards
                        if shard.device.platform == "cpu"
                        for lo, hi in spans)
            except Exception:  # noqa: BLE001 — cannot tell
                mine = True
            self._lent.popleft()
            if not mine:
                release(lent)

    # -- public iteration -------------------------------------------------

    def batches(self, drain="any"):
        """Generator of ``(batch, mask)`` sharded global batches.

        Every host must iterate in lock-step (they all run the same SPMD
        program); the per-step consensus guarantees they agree on when to
        stop, even when partitions are uneven across hosts.

        ``drain`` picks the uneven-tail semantics:

        - ``"any"`` (training default): stop as soon as ANY host runs out —
          a full global batch exists every step; stragglers' tails drop.
        - ``"all"`` (exact evaluation): run until EVERY host is exhausted —
          hosts that ran out keep stepping with a zero-mask dummy batch (a
          masked copy of their last real batch), so no host's rows are ever
          dropped.  Requires each host to produce at least one real batch.
        """
        if drain not in ("any", "all"):
            raise ValueError(
                "drain must be 'any' or 'all', got {!r}".format(drain))
        if drain == "all" and not self.pad_final:
            # pad_final=False drops partial tails before the drain logic
            # ever sees them — silently violating exact-eval semantics.
            raise ValueError(
                "drain='all' (exact evaluation) requires pad_final=True")
        stop = self._stop = threading.Event()
        source = (self._prefetched(stop, self._sharded_iter())
                  if self._prefetch_depth else self._sharded_iter())
        template = None
        try:
            for item in source:
                has_data = item is not None
                if drain == "all":
                    if has_data:
                        template = item
                        if not collectives.any_host_has_data(self.mesh, True):
                            break  # unreachable, keeps call counts aligned
                        yield item[0], item[1]
                    else:
                        yield from self._drain_dummies(template)
                        return
                    continue
                if not collectives.end_of_data_consensus(self.mesh, has_data):
                    if has_data:
                        logger.info(
                            "dropping a final partial step (%d local rows): "
                            "another host exhausted its feed", item[2])
                    break
                batch, mask, _ = item
                yield batch, mask
        finally:
            stop.set()  # wind the prefetch thread down on any exit path

    def _drain_dummies(self, template):
        """drain="all" epilogue: this host is exhausted — keep the SPMD
        programs in lock-step with zero-mask dummy steps until every other
        host is exhausted too."""
        import jax

        if template is None:
            # Raise BEFORE joining any collective: joining first would let
            # the other hosts proceed into their next SPMD step and block
            # on a cross-host reduction this process never enters.  Failing
            # fast here propagates through the cluster's error plane.
            raise RuntimeError(
                "drain='all' needs at least one local batch to shape "
                "dummy steps; this host's feed was empty (rebalance "
                "shards so every process gets data)")
        zero_mask = None
        while collectives.any_host_has_data(self.mesh, False):
            if zero_mask is None:
                zero_mask = jax.jit(lambda m: m * 0.0)(template[1])
            yield template[0], zero_mask

    def grouped_batches(self, k):
        """Generator of ``("multi", batch_stack, mask_stack)`` groups of K
        device-resident full batches (leaves shaped ``(k, local_batch, ...)``,
        sharded per :func:`~...mesh.scan_batch_sharding`) and
        ``("single", batch, mask)`` items for tails that can't fill a group.

        SPMD lock-step across hosts: before each group all hosts agree they
        ALL hold a full group; the first disagreement permanently degrades
        everyone to single-step mode (groups already assembled are split back
        into singles on device), where the per-step end-of-data consensus of
        :meth:`batches` takes over.  This keeps the sequence of jitted
        programs (K-step scan vs single step) identical on every host even
        when Spark partitions are uneven.
        """
        stop = self._stop = threading.Event()
        source = (self._prefetched(stop, self._grouped_sharded_iter(k))
                  if self._prefetch_depth else self._grouped_sharded_iter(k))
        grouped_ok = True
        try:
            for item in source:
                if grouped_ok:
                    is_group = item is not None and item[0] == "multi"
                    if collectives.all_hosts_agree(is_group):
                        yield item
                        continue
                    grouped_ok = False
                    logger.info("degrading to single-step mode (a host "
                                "cannot fill a %d-step group)", k)
                for single in self._degrade(item):
                    has_data = single is not None
                    if not collectives.end_of_data_consensus(
                            self.mesh, has_data):
                        return
                    yield single
        finally:
            stop.set()

    @staticmethod
    def _degrade(item):
        """Split one grouped-iterator item into single-step items (device
        slicing for an assembled group); a trailing ``None`` stays ``None``
        so the caller's consensus sees end-of-feed.

        The group size is read off the mask stack's leading dim (global
        shape, no transfer) rather than taken from the caller: under the
        live ``train_steps_per_call`` knob, buffered groups may carry an
        older K than the current target.

        The slice runs under jit: on a multi-host mesh the stacked arrays
        are global (not fully addressable), so eager indexing would be
        rejected — and multi-host uneven partitions are exactly when this
        path runs.  The index is a traced argument (one compile for all k).
        """
        if item is None:
            return [None]
        if item[0] == "single":
            return [item]
        _, stack, masks = item
        slice_fn = _group_slicer()
        return [("single",) + slice_fn((stack, masks), i)
                for i in range(masks.shape[0])]

    def wire_formats(self):
        """Transport/format counts the underlying feed observed, e.g.
        ``{"colv1": 120}`` when the zero-copy framed ring path carried every
        chunk (see
        :attr:`~tensorflowonspark_tpu.datafeed.DataFeed.wire_formats`)."""
        return dict(getattr(self.feed, "wire_formats", None) or {})

    def terminate(self):
        """Terminate feeding early (training hit max steps with data left):
        marks the node terminating and drains the input queue so blocked
        feeders unblock (reference ``TFNode.terminate``, ``TFNode.py:172-194``).

        The queue and shm ring are strictly single-consumer, so the prefetch
        thread must be fully out before the drain starts: concurrent get/
        task_done from two threads can double-ack (spurious ValueError after
        successful training) or desync the ring tail.  Stop the producer,
        interrupt its blocked get, join it — then drain.

        The join is BOUNDED (:data:`TERMINATE_JOIN_SECS`): the producer may
        be mid ``device_put`` (not interruptible) or racing the interrupt
        flag (interrupt-then-get windows), so each round re-interrupts the
        feed and waits briefly instead of a single unbounded join.  If the
        thread still hasn't exited by the deadline (a wedged backend), the
        queue drain is skipped — draining concurrently with a live producer
        would break the single-consumer invariant — and the daemon thread is
        abandoned with a loud log instead of hanging shutdown forever.
        """
        if self._stop is not None:
            self._stop.set()
        t = self._prefetch_thread
        if t is not None and t.is_alive():
            deadline = time.monotonic() + TERMINATE_JOIN_SECS
            while t.is_alive() and time.monotonic() < deadline:
                self.feed.interrupt()
                t.join(timeout=0.2)
            if t.is_alive():
                logger.error(
                    "infeed prefetch thread did not exit within %.0fs of "
                    "terminate(); skipping the queue drain (single-consumer "
                    "invariant) and abandoning the daemon thread",
                    TERMINATE_JOIN_SECS)
                return
        self.feed.terminate()

    def _local_iter(self):
        """Yields (arrays, count, lent) per step (see :meth:`_next_local`),
        then a single None at end-of-feed.

        Stops *without another blocking queue read* once the feed reported
        end-of-feed — the final partial batch consumes the queue's only None
        sentinel, so a further next_batch() would block forever.
        """
        while not self.feed.should_stop():
            local = self._next_local()
            if local is None:
                break
            yield local
        yield None

    def _sharded_iter(self):
        """Yields device-resident ``(batch, mask, count)`` per step, then a
        single None at end-of-feed."""
        for local in self._local_iter():
            if local is None:
                yield None
                return
            arrays, count, lent = local
            batch, mask = self._shard(arrays, count, lent)
            yield batch, mask, count

    def _scan_sharding(self, ndim_stacked):
        """Sharding for a ``(k, B, ...)`` scan stack: leading scan dim
        unsharded; the rest follows the (possibly overridden) batch sharding
        truncated to the leaf's rank (cached per rank)."""
        if ndim_stacked not in self._scan_shardings:
            from jax.sharding import NamedSharding, PartitionSpec

            spec = (None,) + tuple(self._sharding.spec)[:ndim_stacked - 1]
            self._scan_shardings[ndim_stacked] = NamedSharding(
                self.mesh, PartitionSpec(*spec))
        return self._scan_shardings[ndim_stacked]

    def _live_group_k(self):
        """Current group size, folding in a pending autopilot retune.  Read
        only at group-fill starts so K changes land on group boundaries."""
        target = self._group_k_target
        if target and target != self._group_k:
            logger.info("grouped infeed: steps_per_call %d -> %d (group "
                        "boundary)", self._group_k, target)
            self._group_k = target
        return self._group_k

    def _grouped_sharded_iter(self, k):
        """Yields ``("multi", stack, masks)`` for runs of K full local
        batches and ``("single", batch, mask)`` for tails, then a single
        ``None``.

        Once any batch arrives short (end of feed / epoch tail) the iterator
        stays in single mode — partial batches only occur at the end of the
        feed, and a deterministic mode switch keeps hosts alignable."""
        self._group_k = max(int(k), 1)
        if self._group_assembly == "host":
            return self._grouped_host_iter()
        return self._grouped_device_iter()

    def _group_assembler_fn(self):
        """Jitted device-side stacker: k device-resident (batch, mask) pairs
        -> ``(k, B, ...)`` stacks laid out for the scan program.  Retraces
        only when k (the input list length) changes — expected and cheap
        under adaptive K."""
        if self._group_assembler is None:
            import jax
            import jax.numpy as jnp

            def assemble(batches, masks):
                def stack(*xs):
                    s = jnp.stack(xs)
                    return jax.lax.with_sharding_constraint(
                        s, self._scan_sharding(s.ndim))

                return (jax.tree_util.tree_map(stack, *batches),
                        stack(*masks))

            self._group_assembler = jax.jit(assemble)
        return self._group_assembler

    def _assemble_group(self, pending):
        """Stack k already-device-resident (batch, mask) pairs on DEVICE.
        The host never materializes the K× copy; every output buffer is
        fresh (donation-safe), and when prefetch is on this runs on the
        prefetch thread, overlapping the previous dispatch."""
        group = len(pending)
        start = time.perf_counter()
        stack, masks = self._group_assembler_fn()(
            [b for b, _ in pending], [m for _, m in pending])
        us = int((time.perf_counter() - start) * 1e6)
        self._group_assemble_us += us
        if us > self._group_assemble_us_hwm:
            self._group_assemble_us_hwm = us
        self._note_flow("infeed_group_assemble", group=group)
        return ("multi", stack, masks)

    def _grouped_device_iter(self):
        """Device-stack grouped path: each full batch transfers individually
        as it arrives (overlapping the previous dispatch), then a tiny
        jitted assembler stacks the group on device.  Per-batch masks are
        fresh buffers, so the whole group is donation-safe."""
        pending = []   # device-resident (batch, mask) pairs awaiting a group
        singles_mode = False
        group_k = self._live_group_k()
        for local in self._local_iter():
            if local is None:
                break
            arrays, count, lent = local
            if not singles_mode and count == self.local_batch_size:
                if not pending:
                    group_k = self._live_group_k()
                pending.append(self._shard(arrays, count, lent))
                if len(pending) >= group_k:
                    item = self._assemble_group(pending)
                    pending = []
                    yield item
                continue
            singles_mode = True
            for b, m in pending:
                yield ("single", b, m)
            pending = []
            b, m = self._shard(arrays, count, lent)
            yield ("single", b, m)
        for b, m in pending:
            yield ("single", b, m)
        yield None

    def _grouped_host_iter(self):
        """Host-stack grouped path (``group_assembly="host"``): K host
        batches np.stack into one ``(k, B, ...)`` array, ONE transfer per
        group.  Kept as the fallback for hosts where per-batch transfers
        are slower than one big put; reuses a single transferred all-ones
        mask stack per K, so it is NOT donation-safe."""
        import jax

        def put_stack(cols):
            stacked = np.stack([np.asarray(c) for c in cols])
            return jax.make_array_from_process_local_data(
                self._scan_sharding(stacked.ndim), stacked)

        # Loop invariant: every group's rows are all real, so the (k, B)
        # mask stack is built and transferred once PER GROUP SIZE and reused
        # (multi_step must not donate it — group_donation_safe is False).
        mask_cache = {}
        pending = []  # full columnar locals awaiting a k-group
        singles_mode = False
        group_k = self._live_group_k()
        for local in self._local_iter():
            if local is None:
                break
            arrays, count, _ = local  # stacked on the host: never handed back
            if not singles_mode and count == self.local_batch_size:
                if not pending:
                    group_k = self._live_group_k()
                pending.append(arrays)
                if len(pending) >= group_k:
                    start = time.perf_counter()
                    with telemetry.span("infeed/device_put",
                                                     group=group_k):
                        stack = jax.tree_util.tree_map(
                            lambda *cols: put_stack(cols), *pending)
                        if group_k not in mask_cache:
                            mask_cache[group_k] = put_stack(
                                [np.ones((self.local_batch_size,),
                                         np.float32)] * group_k)
                    self._tally_put(start)
                    self._n_batches += group_k
                    self._note_flow("infeed_device_put", group=group_k)
                    pending = []
                    yield ("multi", stack, mask_cache[group_k])
                continue
            singles_mode = True
            for p in pending:
                b, m = self._shard(p, self.local_batch_size)
                yield ("single", b, m)
            pending = []
            b, m = self._shard(arrays, count)
            yield ("single", b, m)
        for p in pending:
            b, m = self._shard(p, self.local_batch_size)
            yield ("single", b, m)
        yield None

    def _prefetched(self, stop, source_iter):
        """Host-thread prefetch: overlap queue drain, numpy assembly AND the
        host->device transfer with the device step (double buffering by
        default — each prefetched batch is already device-resident, so the
        accelerator never waits on PCIe/transport; costs ``prefetch`` extra
        batches of HBM).  ``stop`` aborts the producer when the consumer
        exits early (max_steps / consensus)."""
        buf = _queue.Queue(maxsize=self._prefetch_depth)
        self._prefetch_buf = buf

        def _put(item):
            if stop.is_set():
                return False
            try:
                buf.put_nowait(item)
                return True
            except _queue.Full:
                pass
            # the consumer is behind: this thread waits, the device does not
            with telemetry.span("infeed/queue_full"):
                while not stop.is_set():
                    try:
                        buf.put(item, timeout=0.2)
                        return True
                    except _queue.Full:
                        continue
            return False

        def _producer():
            # An exception in the feed (e.g. a dead manager) travels through
            # the buffer so the consumer re-raises instead of blocking forever
            # on a producer that died without its None sentinel.
            try:
                for item in source_iter:
                    if not _put(item):
                        return
            except BaseException as exc:  # noqa: B036 — relayed, not handled
                _put(exc)

        t = threading.Thread(target=_producer, name="infeed-prefetch",
                             daemon=True)
        self._prefetch_thread = t
        t.start()
        while True:
            # Timed get + producer-liveness check: terminate() from another
            # thread sets stop and the producer exits WITHOUT its None
            # sentinel (its pending _put aborts) — a bare blocking get here
            # would then wait forever on a buffer nobody will ever fill.
            try:
                item = buf.get(timeout=0.2)
            except _queue.Empty:
                if stop.is_set() and not t.is_alive():
                    return
                continue
            if isinstance(item, BaseException):
                raise item
            yield item
            if item is None:
                return
