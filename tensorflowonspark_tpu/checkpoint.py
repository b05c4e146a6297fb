"""Checkpoint / export conventions (reference SURVEY §5.4).

The reference delegated checkpointing to TF inside ``main_fun`` (Keras
``ModelCheckpoint``; estimator ``save_checkpoints_steps``) and contributed the
*conventions*: ``model_dir``/``export_dir`` args, chief-only export
(reference ``mnist_spark.py:68-72``), shared-storage path normalization, and
a shutdown grace period so the chief finishes exporting
(``TFCluster.py:123``, ``TFSparkNode.py:542-545``).

This module implements those conventions over orbax:

- :class:`CheckpointManager` — periodic, retained, atomic checkpoints of any
  pytree (TrainState), chief-only by default, with restore-latest for
  mid-training recovery (the reference's recovery story was "Spark retries
  the job and TF restores from the last checkpoint", SURVEY §5.3).
- :func:`export_model` / :func:`load_model` — the serving export consumed by
  the pipeline's model-transform path (reference SavedModel; here an orbax
  params checkpoint + a JSON descriptor naming the apply function).
"""

import json
import logging
import os
import queue as _queue
import threading

logger = logging.getLogger(__name__)

_DESCRIPTOR = "export.json"
_PARAMS_DIR = "params"

#: async ``maybe_save`` toggle (default ON): "0"/"off" forces the legacy
#: synchronous save, where maybe_save blocks the dispatch loop for the full
#: serialization+write.  See :class:`CheckpointManager`.
ASYNC_CKPT_ENV = "TFOS_ASYNC_CKPT"

#: how long :meth:`CheckpointManager.close` waits for the async worker
_CLOSE_JOIN_SECS = 120.0


def _fs_path(path):
    """Resolve a (possibly ``file://``-prefixed) path for local-fs IO.

    ``ctx.absolute_path`` hands out ``file://`` URIs (reference ``hdfs_path``
    convention); strip the scheme so ``os`` / ``open`` treat it as the local
    path it names.  Other schemes (``gs://`` etc.) pass through for
    orbax-compatible stores.
    """
    from tensorflowonspark_tpu import fsio

    path = fsio.strip_file_scheme(path)
    return path if fsio.is_remote(path) else os.path.abspath(path)


def aot_root(directory):
    """The AOT executable store beside a checkpoint root.

    Warm rejoin and restore share one directory tree: a replacement node
    that can see the checkpoints can also see the serialized step
    executables (:mod:`~tensorflowonspark_tpu.compilecache`), so
    ``fit_supervised`` restores state AND dispatches without retracing
    from the same mount.  The subdirectory name is outside the
    ``ckpt-<step>`` namespace, so checkpoint retention/quarantine never
    touches it.
    """
    return os.path.join(_fs_path(directory), "aot_executables")


def _nonfinite_leaves(state):
    """Key paths of floating-point leaves holding any NaN/Inf — the
    poison-step marker :meth:`CheckpointManager.restore_latest_valid` uses
    to quarantine checkpoints saved AFTER a nonfinite update landed.  One
    device sync per float leaf; recovery-path only."""
    import jax
    import jax.numpy as jnp

    bad = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        dtype = getattr(leaf, "dtype", None)
        if dtype is None or not jnp.issubdtype(dtype, jnp.floating):
            continue
        if not bool(jnp.all(jnp.isfinite(leaf))):
            bad.append(jax.tree_util.keystr(path) or "<root>")
    return bad


class CheckpointManager(object):
    """Chief-only periodic checkpointing of a train-state pytree.

    Args:
      directory: checkpoint root (shared storage in multi-host runs).
      save_interval_steps: save every N steps (0 = only explicit saves).
      max_to_keep: retained checkpoints.
      is_chief: informational; orbax itself writes from the primary host
        only.  Every host MUST still call :meth:`maybe_save` — the save is a
        cross-process collective (all hosts contribute their array shards
        and enter a sync barrier), so gating the *call* on chiefness would
        deadlock multi-host runs.  The reference's chief-only pattern
        applies to the single-file export path, not here.
      async_save: ``True`` (the default; ``None`` reads ``TFOS_ASYNC_CKPT``)
        makes :meth:`maybe_save` return as soon as the state is snapshotted
        to fresh device buffers and handed to a background worker thread —
        the orbax serialization + write overlap the next dispatches instead
        of stalling the step loop.  The snapshot is **donation-safe**: a
        jitted device-side copy of every ``jax.Array`` leaf, so the very
        next train step may donate the live state without garbling the save
        in flight.  At most one save is queued and one in flight (a
        ``Queue(maxsize=1)`` blocking put is the backpressure: a third save
        request waits, bounding extra state copies to two).  All read paths
        (:meth:`restore_latest`, :meth:`restore_latest_valid`,
        :meth:`latest_step`, :meth:`wait_until_finished`, :meth:`close`)
        drain pending saves first, and a worker failure surfaces on the
        next :meth:`maybe_save` or :meth:`wait_until_finished` — a save is
        never silently lost.  ``False`` restores the legacy synchronous
        behavior.
    """

    def __init__(self, directory, save_interval_steps=100, max_to_keep=3,
                 is_chief=True, async_save=None):
        import orbax.checkpoint as ocp

        self.directory = _fs_path(directory)
        self.is_chief = is_chief
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                save_interval_steps=save_interval_steps or 1,
                max_to_keep=max_to_keep,
                create=True,
            ),
        )
        self.save_interval_steps = save_interval_steps
        if async_save is None:
            async_save = os.environ.get(ASYNC_CKPT_ENV, "1").lower() not in (
                "0", "off", "false", "")
        self.async_save = bool(async_save)
        self._save_queue = _queue.Queue(maxsize=1)
        self._save_thread = None      # started lazily on first async save
        self._save_error = None       # worker exception, re-raised at a sync
        self._last_requested = None   # newest step handed to the worker
        self._copy_fn = None          # cached jitted device-side leaf copy
        # Resolved once: the corrupt_checkpoint fault fires ONCE per process,
        # and a fresh from_env() per save would re-arm it every time.
        from tensorflowonspark_tpu import fault

        self._injector = fault.from_env()

    def _latest_effective(self):
        """Newest step saved OR handed to the async worker: the save gates
        must be computed against requested steps, not just landed ones —
        orbax's ``latest_step`` lags while a save is in flight, and gating
        on it alone would enqueue the same boundary twice."""
        latest = self._mgr.latest_step()
        if self._last_requested is not None and (
                latest is None or self._last_requested > latest):
            return self._last_requested
        return latest

    def maybe_save(self, step, state, force=False):
        """Save if an interval boundary was CROSSED since the last save;
        returns True if a save landed (sync) or was accepted (async).

        Boundary-crossing (not ``step % interval == 0``): callers that see
        steps at a stride — ``fit_feed(steps_per_call=K)`` reports once per
        K-step dispatch, possibly offset by a restored step — would
        otherwise save never (misaligned residues) or at lcm(K, interval).

        Must be called by ALL hosts each step (collective; see class doc) —
        the check below is deterministic so hosts agree.  Async mode keeps
        that determinism: the gate decides at enqueue time from locally-
        tracked request state, the snapshot is taken synchronously (device-
        side copy — cheap), and only the orbax serialization/write moves to
        the worker, in strict request order on every host."""
        self._raise_pending_error()
        if not force:
            if not self.save_interval_steps:
                return False  # interval 0: explicit (force=True) saves only
            last = self._latest_effective() or 0
            if (step // self.save_interval_steps
                    <= last // self.save_interval_steps):
                return False
        if step == self._latest_effective():
            return False  # already saved (e.g. final force after interval hit)
        import orbax.checkpoint as ocp

        from tensorflowonspark_tpu import telemetry

        if self.async_save:
            snapshot = self._snapshot_for_save(state)
            self._ensure_worker()
            telemetry.get_tracer().instant("checkpoint/save_requested",
                                           step=step, force=force)
            # Blocking put is the backpressure: with one save in flight and
            # one queued, a third request waits here instead of stacking
            # unbounded state snapshots.
            self._save_queue.put((step, snapshot, force))
            self._last_requested = step
            return True

        with telemetry.get_tracer().span("checkpoint/save", step=step,
                                         force=force):
            saved = self._mgr.save(step, args=ocp.args.StandardSave(
                _globalize(state)), force=force)
        if saved:
            logger.info("checkpointed step %d to %s", step, self.directory)
            self._maybe_inject_corruption()
        return saved

    # -- async save machinery ---------------------------------------------

    def _raise_pending_error(self):
        if self._save_error is not None:
            err, self._save_error = self._save_error, None
            # Re-derive the request watermark from what actually landed, so
            # a retry after the failure can save the same step again.
            self._last_requested = self._mgr.latest_step()
            raise err

    def _snapshot_for_save(self, state):
        """Donation-safe snapshot: fresh device-side copies of every
        ``jax.Array`` leaf (jitted — legal on multi-host global arrays,
        where eager copies are rejected; PJRT orders the copy before any
        later donation of the originals), ``np.copy`` for host arrays.
        Cached single compilation — the state structure is fixed."""
        import jax
        import numpy as np

        leaves, treedef = jax.tree_util.tree_flatten(state)
        device_ix = [i for i, l in enumerate(leaves)
                     if isinstance(l, jax.Array)]
        if device_ix:
            if self._copy_fn is None:
                import jax.numpy as jnp

                self._copy_fn = jax.jit(
                    lambda xs: [jnp.copy(x) for x in xs])
            copies = self._copy_fn([leaves[i] for i in device_ix])
            for i, c in zip(device_ix, copies):
                leaves[i] = c
        for i, l in enumerate(leaves):
            if isinstance(l, np.ndarray):
                leaves[i] = np.copy(l)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _ensure_worker(self):
        if self._save_thread is not None and self._save_thread.is_alive():
            return
        t = threading.Thread(target=self._save_worker, name="ckpt-async-save",
                             daemon=True)
        self._save_thread = t
        t.start()

    def _save_worker(self):
        import orbax.checkpoint as ocp

        from tensorflowonspark_tpu import telemetry

        while True:
            item = self._save_queue.get()
            try:
                if item is None:
                    return
                step, state, force = item
                # force=True always: the maybe_save gate IS the policy and
                # already passed at enqueue time; orbax's own interval check
                # (which disagrees with boundary-crossing at step strides)
                # must not silently drop an accepted save.
                with telemetry.get_tracer().span(
                        "checkpoint/save", step=step, force=force,
                        asynchronous=True):
                    self._mgr.save(step, args=ocp.args.StandardSave(
                        _globalize(state)), force=True)
                logger.info("checkpointed step %d to %s (async)", step,
                            self.directory)
                self._maybe_inject_corruption()
            except BaseException as e:  # surfaced at the next sync point
                logger.exception("async checkpoint save of step %s failed",
                                 item[0] if item else "?")
                self._save_error = e
            finally:
                self._save_queue.task_done()

    def _maybe_inject_corruption(self):
        if self._injector.enabled:
            # chaos only: the injector garbles finalized step dirs, so
            # flush the async save before handing it the directory
            self._mgr.wait_until_finished()
            self._injector.corrupt_checkpoint(self.directory)

    def _drain_pending(self):
        """Block until every queued async save has been handed to orbax
        (the orbax-internal async commit is flushed separately by
        ``_mgr.wait_until_finished``)."""
        if self._save_thread is not None and self._save_thread.is_alive():
            self._save_queue.join()

    def restore_latest(self, abstract_state):
        """Restore the newest checkpoint into the structure of
        ``abstract_state``; returns (state, step) or (None, None).

        Re-reads the step list from storage first: orbax caches it at
        manager creation, and the callers of this method (recovery after
        restart, a polling evaluator node) are exactly the ones racing
        another process's writes."""
        self._drain_pending()
        self._mgr.wait_until_finished()
        self._mgr.reload()
        step = self._mgr.latest_step()
        if step is None:
            return None, None
        import orbax.checkpoint as ocp

        from tensorflowonspark_tpu import telemetry

        with telemetry.get_tracer().span("checkpoint/restore", step=step):
            state = self._mgr.restore(
                step, args=ocp.args.StandardRestore(abstract_state))
        logger.info("restored checkpoint step %d from %s", step, self.directory)
        return state, step

    def restore_latest_valid(self, abstract_state):
        """Like :meth:`restore_latest`, but VALIDATE before trusting: a
        checkpoint can be partial (the writer was preempted mid-finalize) or
        corrupt (bit rot, injected faults), and recovery crashing on it
        defeats the point of retaining ``max_to_keep`` steps.

        Per candidate (newest first): the step dir must exist under its
        final (committed) name with content, the restore itself must
        succeed into ``abstract_state`` — the restore is the authoritative
        structure/integrity check, there is no cheaper proxy orbax exposes —
        and every floating-point leaf must be FINITE (a checkpoint saved
        after a poison step carries NaN/Inf params; restoring it would
        resume training on poisoned state, which is exactly what the
        remediator's rollback exists to undo).
        An invalid step is QUARANTINED by renaming its dir to
        ``<step>.corrupt`` (orbax no longer lists it; operators can inspect
        it), then the previous retained step is tried.  Returns
        ``(state, step)`` from the newest valid step, or ``(None, None)``
        when no valid checkpoint remains (train from scratch)."""
        import orbax.checkpoint as ocp

        from tensorflowonspark_tpu import telemetry

        self._drain_pending()
        self._mgr.wait_until_finished()
        tracer = telemetry.get_tracer()
        tried = set()
        while True:
            self._mgr.reload()
            step = self._mgr.latest_step()
            if step is None:
                return None, None
            if step in tried:
                # quarantine did not remove it from the listing; give up
                # rather than loop forever
                logger.error("checkpoint step %d remains listed after "
                             "quarantine; recovering from scratch", step)
                return None, None
            tried.add(step)
            step_dir = os.path.join(self.directory, str(step))
            try:
                with tracer.span("checkpoint/restore", step=step,
                                 validated=True):
                    if not os.path.isdir(step_dir) or not os.listdir(step_dir):
                        raise ValueError(
                            "step dir {} missing or empty (uncommitted "
                            "save)".format(step_dir))
                    state = self._mgr.restore(
                        step, args=ocp.args.StandardRestore(abstract_state))
                    poisoned = _nonfinite_leaves(state)
                    if poisoned:
                        raise ValueError(
                            "nonfinite values in restored state: {}".format(
                                ", ".join(poisoned[:4])))
            except Exception:
                logger.warning(
                    "checkpoint step %d failed validation; quarantining and "
                    "falling back to the previous retained step", step,
                    exc_info=True)
                tracer.instant("checkpoint/quarantine", step=step)
                self._quarantine(step_dir)
                continue
            logger.info("restored validated checkpoint step %d from %s",
                        step, self.directory)
            return state, step

    @staticmethod
    def _quarantine(step_dir):
        """Rename a bad step dir to ``<step>.corrupt`` (suffixed ``.N`` if
        taken) so orbax stops listing it; tolerates a dir that is already
        gone."""
        if not os.path.isdir(step_dir):
            return
        target = step_dir + ".corrupt"
        n = 0
        while os.path.exists(target):
            n += 1
            target = "{}.corrupt.{}".format(step_dir, n)
        try:
            os.rename(step_dir, target)
            logger.warning("quarantined bad checkpoint: %s -> %s",
                           step_dir, target)
        except OSError:
            logger.exception("could not quarantine %s", step_dir)

    def latest_step(self, reload=True):
        """Newest saved step, or None.  ``reload=True`` re-reads the step
        list from storage (orbax caches it), so polling evaluators can
        probe for new checkpoints cheaply without a full restore.  Pending
        async saves are flushed first, so "latest" includes every accepted
        :meth:`maybe_save`."""
        self._drain_pending()
        self._mgr.wait_until_finished()
        if reload:
            self._mgr.reload()
        return self._mgr.latest_step()

    def wait_until_finished(self):
        """Barrier: every accepted save is durably on storage when this
        returns, and a failed async save raises here instead of vanishing.
        Called on all exit paths (end-of-fit, preemption drain, emergency
        save) — see :func:`~tensorflowonspark_tpu.train.fit_supervised`."""
        self._drain_pending()
        self._mgr.wait_until_finished()
        self._raise_pending_error()

    def close(self):
        """Flush pending saves, stop the async worker, close orbax.  Never
        raises for a failed in-flight save (close runs on unwind paths);
        the failure is logged by the worker."""
        if self._save_thread is not None and self._save_thread.is_alive():
            try:
                self._save_queue.join()
            except Exception:  # pragma: no cover - defensive
                pass
            self._save_queue.put(None)  # shutdown sentinel
            self._save_thread.join(timeout=_CLOSE_JOIN_SECS)
            if self._save_thread.is_alive():  # pragma: no cover - wedged fs
                logger.error("async checkpoint worker did not exit within "
                             "%.0fs; abandoning it", _CLOSE_JOIN_SECS)
        self._mgr.close()


def abstract_state(state):
    """Abstract (shape/dtype/sharding) view of a live state pytree — the
    template :meth:`CheckpointManager.restore_latest` restores into, so the
    restored arrays land with the SAME sharding the running state uses
    (restore-then-reshard would double peak memory)."""
    import jax
    import numpy as np

    def one(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        arr = np.asarray(x)
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

    return jax.tree_util.tree_map(one, state)


def _globalize(tree):
    """Make every leaf serializable in multi-host worlds.

    Orbax refuses host-local ``jax.Array`` leaves when
    ``process_count() > 1`` (e.g. a bare ``jnp.asarray(step)`` counter that
    never went through a mesh sharding).  Such leaves are per-host values
    that are identical across hosts by construction (step counters, scalars
    computed from the replicated state), so re-wrap them as globally
    replicated arrays over all devices.  Mesh-sharded/global leaves pass
    through untouched.  No-op in single-process worlds.
    """
    import jax

    if jax.process_count() <= 1:
        return tree
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(jax.devices()), ("_ckpt",))
    replicated = NamedSharding(mesh, PartitionSpec())

    def one(x):
        if isinstance(x, jax.Array) and x.is_fully_addressable:
            host = np.asarray(jax.device_get(x))
            return jax.make_array_from_callback(
                host.shape, replicated, lambda idx: host[idx])
        return x

    return jax.tree_util.tree_map(one, tree)


def should_export(ctx):
    """Who calls :func:`export_model` under the chief-only convention.

    - Single-process worlds (each executor its own jax runtime, e.g.
      InputMode.SPARK without ``initialize_distributed``): chief only —
      others writing the same dir would race.
    - Multi-process worlds (``ctx.initialize_distributed()`` ran): EVERY
      process — the orbax save is a cross-process collective (all hosts
      contribute shards + sync barrier); gating on chiefness would crash
      or deadlock the collective.  Only the primary actually writes.
    """
    import jax

    return jax.process_count() > 1 or ctx.is_chief()


_STABLEHLO_FILE = "apply.stablehlo"


_EMBEDDED_MLIR_FILE = "apply_embedded.mlir"
_COMPILE_OPTIONS_FILE = "compile_options.pb"


def export_model(export_dir, params, model_name, model_config=None,
                 input_signature=None, model=None,
                 serialize_platforms=("cpu", "tpu"),
                 embed_batch_size=None, embed_platform="tpu",
                 extra_variables=None):
    """Export params + model descriptor for serving.

    Call according to :func:`should_export` (chief-only convention,
    reference ``mnist_spark.py:68-72``; collective in multi-process worlds).
    The pipeline's model-transform path loads this on executors — the
    portability role SavedModel played for the reference
    (``pipeline.py:474-481``).

    When ``model`` (the flax module) and ``input_signature`` are given, the
    serving fn is ALSO serialized to portable StableHLO (``jax.export``,
    batch-polymorphic, lowered for ``serialize_platforms``): serving hosts
    then need jax alone — no flax, no model registry, no user code (the
    reference's user-code-free SavedModel/JNI path,
    ``TFModel.scala:245-292``).  Registry-based serving remains the
    fallback whenever the artifact is absent or platform-mismatched.

    ``embed_batch_size`` additionally writes a **params-embedded**,
    fixed-batch StableHLO module (+ serialized compile options) for the
    native C++ PJRT runner (``native/pjrt_runner.cc``) — serving with no
    Python at all; ``embed_platform`` picks its single lowering target.

    ``extra_variables``: the model's non-trainable collections
    (``{"batch_stats": ...}`` for a BatchNorm model — the Trainer's
    ``state.extra``).  The export then holds the whole variables dict and
    serving applies it as such; without them such a model cannot be applied
    at all.
    """
    import jax
    import orbax.checkpoint as ocp

    if extra_variables:
        params = dict(extra_variables, params=params)

    # Cross-process-sharded params (e.g. Trainer(param_sharding="fsdp") on
    # a multi-host mesh) are not fully addressable: device_get below would
    # raise after a full training run.  Re-replicate through a jit identity
    # (SPMD all-gather) first; fully-addressable trees pass through as-is.
    leaves = [l for l in jax.tree_util.tree_leaves(params)
              if isinstance(l, jax.Array)]
    if any(not l.is_fully_addressable for l in leaves):
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = next(l.sharding.mesh for l in leaves
                    if not l.is_fully_addressable)
        params = jax.jit(
            lambda p: p,
            out_shardings=NamedSharding(mesh, PartitionSpec()))(params)

    export_dir = _fs_path(export_dir)
    os.makedirs(export_dir, exist_ok=True)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(export_dir, _PARAMS_DIR), _globalize(params),
               force=True)
    ckptr.wait_until_finished()
    ckptr.close()
    descriptor = {
        "model_name": model_name,
        "model_config": model_config or {},
        "input_signature": input_signature or {},
    }
    if extra_variables:
        descriptor["variables"] = sorted(params)
    if model is not None and input_signature and jax.process_index() == 0:
        from tensorflowonspark_tpu import serving

        try:
            blob, platforms = serving.serialize_apply(
                model, jax.device_get(params), input_signature,
                platforms=serialize_platforms,
                variables=bool(extra_variables))
            with open(os.path.join(export_dir, _STABLEHLO_FILE), "wb") as f:
                f.write(blob)
            descriptor["stablehlo"] = {"file": _STABLEHLO_FILE,
                                       "platforms": list(platforms)}
        except Exception:
            # The orbax+registry path still serves; don't fail the export.
            logger.warning("StableHLO serialization failed; export remains "
                           "registry-served", exc_info=True)
        if embed_batch_size:
            try:
                mlir, options, meta = serving.serialize_embedded(
                    model, jax.device_get(params), input_signature,
                    batch_size=embed_batch_size, platform=embed_platform,
                    variables=bool(extra_variables))
                with open(os.path.join(export_dir, _EMBEDDED_MLIR_FILE),
                          "wb") as f:
                    f.write(mlir)
                with open(os.path.join(export_dir, _COMPILE_OPTIONS_FILE),
                          "wb") as f:
                    f.write(options)
                meta["file"] = _EMBEDDED_MLIR_FILE
                meta["options_file"] = _COMPILE_OPTIONS_FILE
                descriptor["embedded_mlir"] = meta
            except Exception:
                logger.warning("embedded-MLIR serialization failed; native "
                               "runner artifact omitted", exc_info=True)
    if jax.process_index() == 0:
        with open(os.path.join(export_dir, _DESCRIPTOR), "w") as f:
            json.dump(descriptor, f)
    logger.info("exported %s to %s", model_name, export_dir)


def load_model(export_dir, validate=False):
    """Load an export: returns ``(params, descriptor_dict)``.

    ``validate=True`` additionally runs the nonfinite-leaf scan
    :func:`restore_latest_valid` applies to training checkpoints and
    raises ``ValueError`` on a poisoned export — the fleet's live-swap
    path refuses to flip a replica onto NaN/Inf weights.
    """
    import orbax.checkpoint as ocp

    export_dir = _fs_path(export_dir)
    with open(os.path.join(export_dir, _DESCRIPTOR)) as f:
        descriptor = json.load(f)
    ckptr = ocp.StandardCheckpointer()
    params = ckptr.restore(os.path.join(export_dir, _PARAMS_DIR))
    ckptr.close()
    if validate:
        bad = _nonfinite_leaves(params)
        if bad:
            raise ValueError(
                "export {} has nonfinite params at {}".format(
                    export_dir, bad[:4]))
    return params, descriptor
