"""Warm-start compile plane: persistent XLA compilation cache + serialized
AOT executables.

The reference framework restarts a failed TF node cheaply because graph
construction is fast; the jax_graft equivalent pays a full XLA recompile of
every jitted step (and every serving bucket rung) on each elastic
replacement and gateway restart.  This module makes that a one-time cost
shared across runs and replicas (the tf.data fixed-cost amortization
argument, arXiv:2101.12127), on two tiers:

1. **Persistent compilation cache** (:func:`configure`): points JAX's
   ``jax_compilation_cache_dir`` at a cluster-shared directory resolved
   from :data:`JAX_CACHE_DIR_ENV` (wins when set) / cluster config /
   :data:`CACHE_DIR_ENV`.  Every ``.compile()`` in the process (trainer
   steps, serving rungs) then reads/writes the disk cache, so a
   replacement node's compiles collapse to deserialization.
   Hit/miss/saved-time counters are derived from jax's monitoring events
   and ride heartbeats into the observatory as ``tfos_compile_cache_*``.
   The same listeners keep the compile plane's own books in every process
   that hosts jax, cache directory or none (:func:`listen`): the time
   spent tracing, lowering, in the backend's compiler and reading the
   cache, each instant booked once, and the programs made executable.

2. **AOT executable store** (:class:`AOTCache`): explicit
   ``jax.experimental.serialize_executable`` round trips, keyed by a
   field-by-field :func:`fingerprint` (jax/jaxlib + backend version, mesh
   shape, donation signature, batch/param avals).  A warm rejoin
   deserializes and dispatches **without ever tracing**; any fingerprint
   mismatch, corrupt artifact, or unsupported executable is a clean miss
   — the caller falls back to ordinary JIT and ``compile_cache_fallback``
   increments.  A warm start is an optimization, never a correctness
   dependency.

Scoping contract: fingerprints cover everything jax can see (versions,
devices, mesh, donation, avals) plus whatever program identity the caller
mixes in — the trainer hashes its loss fn + optimizer structurally
(:func:`program_identity`) so resuming a run after editing the loss or
hyperparameters rejects the stale executable; serving keys by model
name/config.  The structural hash is best-effort (bytecode + consts +
closure values), so callers should still scope the store directory per
model run (the trainer defaults it beside the checkpoint root, see
``checkpoint.aot_root``; serving keys by export dir) and can pin an
explicit ``program_version`` when the automatic hash can't see a change.

Trust boundary: artifacts carry a ``jax.experimental.serialize_executable``
payload that is ultimately unpickled on load — anyone with WRITE access to
a store directory can execute arbitrary code in every process that warms
from it.  The store therefore (a) creates its directory ``0o700``, (b)
verifies the plain-JSON fingerprint header *before* any ``pickle.loads``
so mismatched artifacts never reach the unpickler, and (c) must live on a
mount whose writers you trust exactly as much as the training job itself
(same bar as the checkpoint root).  Remote object-store URLs are rejected
— this store is local-filesystem / shared-mount only.
"""

import collections
import logging
import os
import pickle
import threading
import time

logger = logging.getLogger(__name__)

#: env fallback for the shared cache root (cluster config wins; see
#: :func:`configure_from_meta`).  ``configure`` re-exports the resolved
#: path here so forked children (manager, feed tasks) inherit it.
CACHE_DIR_ENV = "TFOS_COMPILE_CACHE_DIR"

#: jax's own variable for the same directory.  Where it is set, it is the
#: cache of every process of the program and :func:`configure` yields to it.
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: bump when the artifact layout changes — old artifacts then read as
#: fingerprint mismatches (clean JIT fallback), not crashes
_FORMAT = 3

_SUFFIX = ".aotx"

#: artifact layout: magic, one line of canonical-JSON fingerprint, then
#: the pickled ``(payload, in_tree, out_tree, device_ids)``.  The JSON
#: header is what load() checks — only a fingerprint-matched artifact ever
#: reaches pickle.
_MAGIC = b"TFOS-AOTX3\n"

# jax monitoring event names the counters are derived from
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# jax 0.9.0 times these three with ``dispatch.log_elapsed_time``: a scalar
# event on entry, a duration event on exit.  They nest: a jitted function
# called while another is traced is traced inside the outer one's time, a
# constant computed while tracing is a whole small program (trace, lower,
# compile) inside it, and the backend-compile event wraps
# ``compile_or_get_cached``, so on a persistent-cache hit it fires too and
# holds the retrieval.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_STAGE_OF = {_TRACE_EVENT: "trace_us", _LOWER_EVENT: "lower_us",
             _BACKEND_EVENT: "backend_us"}


class _CacheStats(object):
    """Process-global compile-plane tallies (plain ints, the DataFeed
    pattern: written on the compile path, read torn-but-harmlessly by the
    heartbeat thread).  Registered once as a node metrics feed by
    :func:`configure`, so the counters ride HBEAT payloads and render on
    the observatory as ``tfos_compile_cache_*``."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.cache_hit = 0          # persistent-cache hits (jax event)
        self.cache_miss = 0         # persistent-cache misses (jax event)
        self.fallback = 0           # AOT artifacts rejected -> JIT fallback
        self.saved_us = 0           # compile time the disk cache saved
        self.retrieval_us = 0       # time spent reading cached executables
        # the plane's own books, cache directory or none: each instant under
        # one of jax's compile events is booked once, on the innermost
        self.trace_us = 0           # tracing to a jaxpr
        self.lower_us = 0           # jaxpr -> MLIR module
        self.backend_us = 0         # the backend's compiler (less retrieval)
        self.programs = 0           # programs made executable, from the
        #                             cache or by the compiler
        # the last step programs a Trainer's dispatch made, each with its
        # name, the step and the cost (Trainer._note_compile writes them;
        # the flight recorder tells them as ``compile_programs``)
        self.record = collections.deque(maxlen=32)
        self.aot_load = 0           # AOT executables deserialized + loaded
        self.aot_save = 0           # AOT executables serialized + persisted
        self.aot_load_us = 0
        self.aot_compile_us = 0     # explicit lower+compile on cold stores
        self.aot_bytes_read = 0
        self.aot_bytes_written = 0
        self._dir_bytes = 0
        self._dir_scan_t = 0.0

    def _dir_bytes_now(self):
        """Cache-directory footprint gauge, rescanned at most every 5s
        (the cache writes flat files; a beat-rate listdir is cheap but
        not free)."""
        d = _configured_dir
        if not d:
            return 0
        now = time.time()
        if now - self._dir_scan_t >= 5.0:
            self._dir_scan_t = now
            total = 0
            try:
                for name in os.listdir(d):
                    try:
                        total += os.path.getsize(os.path.join(d, name))
                    except OSError:
                        pass
            except OSError:
                pass
            self._dir_bytes = total
        return self._dir_bytes

    def counters_snapshot(self):
        """Flat counters for heartbeat payloads /
        :func:`~tensorflowonspark_tpu.telemetry.merge_counters`:
        ``compile_cache_hit`` / ``compile_cache_miss`` persistent-cache
        outcomes, ``compile_cache_saved_us`` compile time the cache saved,
        ``compile_cache_retrieval_us`` time spent reading cached
        executables, ``compile_cache_fallback`` AOT artifacts rejected
        (mismatch/corrupt) in favor of JIT, ``compile_cache_aot_load`` /
        ``compile_cache_aot_save`` AOT store traffic with byte and
        microsecond tallies, and ``compile_cache_dir_bytes_hwm`` the
        cache directory footprint (``_hwm`` -> merged by max, rendered
        as a gauge); beside them :meth:`tallies`."""
        return dict(self.tallies(), **{
            "compile_cache_hit": self.cache_hit,
            "compile_cache_miss": self.cache_miss,
            "compile_cache_fallback": self.fallback,
            "compile_cache_saved_us": self.saved_us,
            "compile_cache_aot_load": self.aot_load,
            "compile_cache_aot_save": self.aot_save,
            "compile_cache_aot_load_us": self.aot_load_us,
            "compile_cache_aot_compile_us": self.aot_compile_us,
            "compile_cache_aot_bytes_read": self.aot_bytes_read,
            "compile_cache_aot_bytes_written": self.aot_bytes_written,
            "compile_cache_dir_bytes_hwm": self._dir_bytes_now(),
        })

    def tallies(self):
        """What making programs executable has cost this process so far:
        ``compile_trace_us``, ``compile_lower_us``, ``compile_backend_us``
        and ``compile_cache_retrieval_us`` (no instant in two of them: a
        nested event's time is taken out of the one around it, so the four
        add up to the time spent under any of them) and ``compile_programs``
        (backend-compile events: one a program, hit or miss)."""
        return {
            "compile_trace_us": self.trace_us,
            "compile_lower_us": self.lower_us,
            "compile_backend_us": self.backend_us,
            "compile_cache_retrieval_us": self.retrieval_us,
            "compile_programs": self.programs,
        }


#: the process-global tally instance every helper below writes to
stats = _CacheStats()

_lock = threading.Lock()
_listeners_installed = False
_feed_registered = False
_configured_dir = None


# the compile events this thread is inside, innermost last: [stage, the
# microseconds of what ended inside it]
_nest = threading.local()


def _on_event(event, **kwargs):
    if event == _HIT_EVENT:
        stats.cache_hit += 1
    elif event == _MISS_EVENT:
        stats.cache_miss += 1


def _on_scalar(event, value=0.0, **kwargs):
    # log_elapsed_time's entry: one of the three stages begins
    stage = _STAGE_OF.get(event)
    if stage is not None:
        if not hasattr(_nest, "open"):
            _nest.open = []
        _nest.open.append([stage, 0])


def _on_duration(event, duration=0.0, **kwargs):
    micros = int(duration * 1e6)
    if event == _SAVED_EVENT:
        # jax reports saved = original compile - retrieval, which goes
        # NEGATIVE for millisecond-scale programs; clamp per event so the
        # counter stays a monotone "time not spent recompiling"
        stats.saved_us += max(0, micros)
        return
    opened = getattr(_nest, "open", None)
    if event == _RETRIEVAL_EVENT:
        stats.retrieval_us += micros
    elif event in _STAGE_OF:
        stage, inside = _STAGE_OF[event], 0
        while opened:                # the entry that this exit answers
            top = opened.pop()
            if top[0] == stage:
                inside = top[1]
                break
        setattr(stats, stage, getattr(stats, stage) + max(0, micros - inside))
        if event == _BACKEND_EVENT:
            stats.programs += 1
    else:
        return
    if opened:
        opened[-1][1] += micros      # not the time of the stage around it


def _install_listeners():
    """Subscribe the tallies to jax's monitoring events (idempotent)."""
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listeners_installed = True


def _register_stats_feed():
    """Publish :data:`stats` on this node's heartbeats (idempotent; no-op
    outside a node process — gateway replicas merge the snapshot into
    their own heartbeat_metrics instead)."""
    global _feed_registered
    with _lock:
        if _feed_registered:
            return
        _feed_registered = True
    from tensorflowonspark_tpu import node

    node._register_feed(stats)


def _when_jax_is_imported(callback):
    """Call ``callback()`` now if this process has imported jax, else right
    after it does (a one-shot finder in front of ``sys.meta_path`` that
    lets the ordinary machinery find jax and runs the callback once the
    package's own code has): a worker whose user function never touches
    jax never pays for importing it."""
    import importlib.util
    import sys

    if "jax" in sys.modules:
        return callback()

    class _Finder(object):
        def find_spec(self, name, path=None, target=None):
            if name != "jax":
                return None
            sys.meta_path.remove(self)
            spec = importlib.util.find_spec(name)
            if spec is not None and spec.loader is not None:
                exec_module = spec.loader.exec_module

                def exec_then_call(module):
                    exec_module(module)
                    callback()

                spec.loader.exec_module = exec_then_call
            return spec

    sys.meta_path.insert(0, _Finder())


def listen():
    """Keep the compile plane's books in this process (:meth:`tallies`) and
    send them with the node's heartbeats, whether or not a cache directory
    is named: a user without a persistent cache compiles too.  ``node.run``
    calls it in the process that runs a worker's user function, before that
    function: the listeners are on from the moment the process imports jax
    (at once where :func:`configure` already has)."""
    from tensorflowonspark_tpu import telemetry

    _when_jax_is_imported(_install_listeners)
    _register_stats_feed()
    telemetry.register_flight_source("compile_programs",
                                     lambda: list(stats.record))


def configured_dir():
    """The active persistent-cache directory, or None before
    :func:`configure` succeeds."""
    return _configured_dir


def configure(cache_dir=None, register_feed=True):
    """Point JAX's persistent compilation cache at ``cache_dir``.

    Resolution order: JAX's own :data:`JAX_CACHE_DIR_ENV` (a cache placed
    from outside the program wins — jax has already read it, and nothing
    here sets another), then the explicit argument, then
    :data:`CACHE_DIR_ENV`.  Returns the resolved (created) directory, or
    None when nothing names one — the whole compile plane is then inert,
    zero-cost.

    Side effects on success: ``jax_compilation_cache_dir`` set (unless the
    environment placed it), the min-compile-time threshold dropped to 0
    (CI-scale programs compile in milliseconds — the default 1s gate
    would exclude exactly the compiles the warm-rejoin story needs cached),
    monitoring listeners installed, the env var re-exported for forked
    children, and (``register_feed=True``) :data:`stats` registered as a
    node heartbeat feed.
    """
    global _configured_dir
    placed = os.environ.get(JAX_CACHE_DIR_ENV)
    cache_dir = placed or cache_dir or os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        return None
    cache_dir = os.path.abspath(cache_dir)
    os.makedirs(cache_dir, exist_ok=True)

    import jax

    if not placed:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _install_listeners()
    os.environ[CACHE_DIR_ENV] = cache_dir
    with _lock:
        _configured_dir = cache_dir
    if register_feed:
        _register_stats_feed()
    from tensorflowonspark_tpu import telemetry

    telemetry.get_tracer().instant("compile/cache_configured", dir=cache_dir)
    logger.info("persistent compilation cache at %s", cache_dir)
    return cache_dir


def configure_from_meta(cluster_meta):
    """Configure from ``cluster_meta["compile_cache_dir"]`` (remote
    processes — replacement nodes re-run the same start closure, so warm
    rejoin needs no extra plumbing); falls back to the env toggle, same
    policy as ``telemetry.configure_from_meta``."""
    return configure((cluster_meta or {}).get("compile_cache_dir"))


# -- AOT executable store -------------------------------------------------

def _aval_signature(tree):
    """Stable hash of a pytree's array avals (tree structure + per-leaf
    shape/dtype) — the batch/param half of a fingerprint.  Hashed rather
    than stored raw: a params tree's treedef repr runs to kilobytes."""
    import hashlib

    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = [str(treedef)]
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        parts.append("%s:%s" % (dtype if dtype is not None
                                else type(leaf).__name__, shape))
    return hashlib.sha256("|".join(parts).encode("utf-8")).hexdigest()


def fingerprint(avals=None, mesh=None, donate=(), extra=None):
    """The compatibility key an AOT artifact is stored and checked under.

    A field-by-field dict (not one opaque hash) so a mismatch names the
    field that moved — the load path logs and traces exactly which of
    jax/jaxlib version, backend, device count, mesh shape, donation
    signature, or aval signature diverged before falling back to JIT.
    """
    import jax
    import jaxlib

    fp = {
        "format": _FORMAT,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "donate": tuple(donate),
    }
    if mesh is not None:
        fp["mesh"] = repr(tuple(zip(mesh.axis_names, mesh.devices.shape)))
    if avals is not None:
        fp["avals"] = _aval_signature(avals)
    if extra:
        fp.update(extra)
    return fp


def _fp_canonical(fp):
    """Canonical JSON form of a fingerprint dict — the representation
    stored in the artifact header and compared on load (tuples coerce to
    lists identically on both sides; non-JSON values go through repr)."""
    import json

    return json.dumps(fp, sort_keys=True, default=repr)


def _identity_parts(obj, parts, seen, depth=0):
    """Recursive structural walk feeding :func:`program_identity`.

    Functions contribute bytecode, consts, names, defaults, and closure
    cell VALUES (recursively — optax transforms are namedtuples of
    closures, so hyperparameters like a learning rate live in cells);
    arrays contribute shape/dtype plus a content digest when small;
    containers and plain objects recurse sorted.  Anything opaque falls
    back to its type name — a too-coarse hash only risks a spurious
    mismatch, which degrades to a clean recompile, never a stale load."""
    if depth > 12:
        parts.append("<depth>")
        return
    if obj is None or isinstance(obj, (bool, int, float, complex, str,
                                       bytes)):
        parts.append(repr(obj))
        return
    if id(obj) in seen:
        parts.append("<cycle>")
        return
    seen.add(id(obj))
    import functools

    if isinstance(obj, functools.partial):
        parts.append("partial")
        _identity_parts(obj.func, parts, seen, depth + 1)
        for a in obj.args:
            _identity_parts(a, parts, seen, depth + 1)
        for k in sorted(obj.keywords or {}):
            parts.append(repr(k))
            _identity_parts(obj.keywords[k], parts, seen, depth + 1)
        return
    func = getattr(obj, "__func__", None)
    if func is not None:                       # bound method
        _identity_parts(func, parts, seen, depth + 1)
        _identity_parts(getattr(obj, "__self__", None), parts, seen,
                        depth + 1)
        return
    code = getattr(obj, "__code__", None)
    if code is not None:                       # plain function / lambda
        parts.append("fn:%s" % getattr(obj, "__qualname__", ""))
        parts.append(code.co_code.hex())
        parts.append(repr(code.co_names))
        for c in code.co_consts:
            if hasattr(c, "co_code"):          # nested function's code
                parts.append(c.co_code.hex())
            else:
                parts.append(repr(c))
        for cell in getattr(obj, "__closure__", None) or ():
            try:
                _identity_parts(cell.cell_contents, parts, seen, depth + 1)
            except ValueError:                 # empty cell
                parts.append("<empty-cell>")
        for d in getattr(obj, "__defaults__", None) or ():
            _identity_parts(d, parts, seen, depth + 1)
        return
    if hasattr(obj, "shape") and hasattr(obj, "dtype"):   # array-likes
        shape = tuple(getattr(obj, "shape", ()))
        parts.append("arr:%s:%s" % (obj.dtype, shape))
        try:
            import hashlib

            import numpy as np

            arr = np.asarray(obj)
            if arr.size <= 4096:
                parts.append(hashlib.sha256(arr.tobytes()).hexdigest())
        except Exception:                      # non-addressable etc.
            pass
        return
    if isinstance(obj, dict):
        for k in sorted(obj, key=repr):
            parts.append(repr(k))
            _identity_parts(obj[k], parts, seen, depth + 1)
        return
    if isinstance(obj, (list, tuple)):         # incl. namedtuples (optax)
        parts.append(type(obj).__name__)
        for v in obj:
            _identity_parts(v, parts, seen, depth + 1)
        return
    if isinstance(obj, (set, frozenset)):
        for v in sorted(obj, key=repr):
            _identity_parts(v, parts, seen, depth + 1)
        return
    parts.append(type(obj).__qualname__)
    d = getattr(obj, "__dict__", None)
    if isinstance(d, dict):
        for k in sorted(d, key=repr):
            parts.append(repr(k))
            _identity_parts(d[k], parts, seen, depth + 1)


def program_identity(*objs):
    """Best-effort structural hash of the PYTHON half of a compiled
    program — the part no aval fingerprint can see.

    The trainer feeds its loss fn and optimizer through this and mixes
    the digest into every AOT fingerprint, so resuming in the same
    checkpoint dir after editing the loss or an optimizer hyperparameter
    (same shapes, different program) rejects the stale serialized
    executable and recompiles instead of silently training the old
    program.  Best-effort by design: an over-sensitive hash (e.g. a
    docstring edit) costs one recompile; only the caller can assert true
    equivalence, via an explicit ``program_version``."""
    import hashlib

    parts = []
    seen = set()
    for obj in objs:
        try:
            _identity_parts(obj, parts, seen)
        except Exception:                      # pragma: no cover - exotic
            parts.append("<opaque:%s>" % type(obj).__name__)
    return hashlib.sha256(
        "|".join(parts).encode("utf-8", "backslashreplace")).hexdigest()


class AOTCache(object):
    """Serialized-executable store: ``name`` -> one fingerprinted artifact.

    Artifacts are ``<name>.aotx`` files: :data:`_MAGIC`, one line of
    canonical-JSON fingerprint, then the pickled
    ``jax.experimental.serialize_executable`` triple plus the ids of the
    devices the program was compiled for
    ``(payload, in_tree, out_tree, device_ids)``, written atomically (tmp + rename)
    so a killed writer can never leave a half artifact under a reader.
    Absent / mismatched / corrupt artifacts are all clean misses.

    Trust boundary (see the module docstring): the executable payload is
    unpickled on load, so the store directory must only be writable by
    principals trusted to run code in the warming processes — it is
    created ``0o700``, and the JSON header is verified BEFORE the payload
    is ever unpickled.  Local filesystem / shared mount only: remote
    object-store URLs raise (``fit_supervised`` skips auto-attaching the
    store for remote checkpoint roots for the same reason).
    """

    def __init__(self, directory):
        from tensorflowonspark_tpu import fsio

        directory = fsio.strip_file_scheme(str(directory))
        if fsio.is_remote(directory):
            raise ValueError(
                "AOTCache needs a local or shared-mount directory; remote "
                "URL %r is not supported (artifacts are local files and "
                "their executable payload is unpickled on load — see the "
                "compilecache trust-boundary note)" % (directory,))
        self.directory = os.path.abspath(directory)
        # 0o700 on creation: artifacts execute-by-deserialization in every
        # process that warms from here (no-op for pre-existing dirs)
        os.makedirs(self.directory, mode=0o700, exist_ok=True)

    def path(self, name):
        return os.path.join(self.directory, name + _SUFFIX)

    def load(self, name, fp):
        """Deserialize + load ``name``'s executable when its stored
        fingerprint equals ``fp`` exactly; None otherwise.  Mismatch,
        corruption, and deserialize failures bump
        ``compile_cache_fallback`` and emit a ``compile/jit_fallback``
        instant naming the reason — absence is silent (a cold store is
        not a fallback)."""
        from tensorflowonspark_tpu import telemetry

        import json

        path = self.path(name)
        if not os.path.exists(path):
            return None
        tracer = telemetry.get_tracer()
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                blob = f.read()
            if not blob.startswith(_MAGIC):
                raise ValueError("bad magic")
            header_end = blob.index(b"\n", len(_MAGIC))
            stored = json.loads(blob[len(_MAGIC):header_end]
                                .decode("utf-8"))
        except Exception as e:
            stats.fallback += 1
            logger.warning("AOT artifact %s unreadable (%s: %s); "
                           "falling back to JIT", path, type(e).__name__, e)
            tracer.instant("compile/jit_fallback", program=name,
                           reason="corrupt")
            return None
        # fingerprint gate runs on the plain-JSON header — a mismatched
        # artifact is rejected before its pickled payload is ever touched
        expect = json.loads(_fp_canonical(fp))
        if stored != expect:
            stats.fallback += 1
            diff = sorted(k for k in set(stored) | set(expect)
                          if stored.get(k) != expect.get(k))
            logger.warning("AOT artifact %s fingerprint mismatch on %s; "
                           "falling back to JIT", path, diff)
            tracer.instant("compile/jit_fallback", program=name,
                           reason="fingerprint:" + ",".join(diff))
            return None
        try:
            from jax.experimental import serialize_executable as se

            import jax

            payload, in_tree, out_tree, device_ids = pickle.loads(
                blob[header_end + 1:])
            # the program runs on the devices it was compiled for, not on
            # every device of the backend (deserialize_and_load's default)
            by_id = {d.id: d for d in jax.devices()}
            compiled = se.deserialize_and_load(
                payload, in_tree, out_tree,
                backend=jax.default_backend(),
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:
            stats.fallback += 1
            logger.warning("AOT artifact %s failed to load (%s: %s); "
                           "falling back to JIT", path, type(e).__name__, e)
            tracer.instant("compile/jit_fallback", program=name,
                           reason="deserialize")
            return None
        micros = int((time.perf_counter() - t0) * 1e6)
        stats.aot_load += 1
        stats.aot_load_us += micros
        stats.aot_bytes_read += len(blob)
        tracer.instant("compile/aot_load", program=name, micros=micros,
                       bytes=len(blob))
        return compiled

    def save(self, name, fp, compiled):
        """Serialize ``compiled`` under ``name``; returns whether an
        artifact landed.  Never raises: executables that don't support
        serialization (no unloaded form) and I/O failures log and skip —
        the run proceeds on its live executable either way."""
        from tensorflowonspark_tpu import telemetry

        t0 = time.perf_counter()
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()]
            blob = (_MAGIC + _fp_canonical(fp).encode("utf-8") + b"\n"
                    + pickle.dumps((payload, in_tree, out_tree, device_ids),
                                   protocol=pickle.HIGHEST_PROTOCOL))
        except Exception as e:
            logger.warning("AOT serialize of %s failed (%s: %s); "
                           "artifact skipped", name, type(e).__name__, e)
            return False
        path = self.path(name)
        tmp = "%s.tmp.%d" % (path, os.getpid())
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except OSError as e:
            logger.warning("AOT artifact write %s failed (%s); skipped",
                           path, e)
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        micros = int((time.perf_counter() - t0) * 1e6)
        stats.aot_save += 1
        stats.aot_bytes_written += len(blob)
        telemetry.get_tracer().instant("compile/aot_save", program=name,
                                       micros=micros, bytes=len(blob))
        return True


def load_or_compile(cache, name, fp, jit_fn, args):
    """The load-or-compile decision shared by the trainer and serving.

    Returns ``(compiled, verdict, micros)``: the AOT store's deserialized
    executable (``"loaded"`` — zero tracing, the warm-rejoin path), or an
    explicitly lowered+compiled one persisted for the next restart
    (``"compiled"``), or ``(None, "jit", 0)`` when there is no store /
    even explicit compilation fails — callers then dispatch the plain
    jit fn.
    """
    from tensorflowonspark_tpu import telemetry

    if cache is None:
        return None, "jit", 0
    t0 = time.perf_counter()
    compiled = cache.load(name, fp)
    if compiled is not None:
        return compiled, "loaded", int((time.perf_counter() - t0) * 1e6)
    t0 = time.perf_counter()
    try:
        with telemetry.get_tracer().span("compile/aot_compile",
                                         program=name):
            compiled = jit_fn.lower(*args).compile()
    except Exception as e:
        logger.warning("explicit AOT compile of %s failed (%s: %s); "
                       "dispatching via JIT", name, type(e).__name__, e)
        return None, "jit", 0
    micros = int((time.perf_counter() - t0) * 1e6)
    stats.aot_compile_us += micros
    cache.save(name, fp, compiled)
    return compiled, "compiled", micros
