"""Trainer: mesh-sharded pjit training loop over flax models.

The reference delegated "the math" to TF inside the user fn (strategy scope +
``model.fit``, e.g. ``examples/mnist/keras/mnist_spark.py:11-66``); users of
this framework can do the same with raw jax — but this module is the batteries
-included path: it owns the train_step (donated state, bf16 compute, grads
allreduced implicitly by sharded batch + replicated params), the metrics
(:mod:`~tensorflowonspark_tpu.metrics`), and end-of-data consensus when fed
from Spark partitions.
"""

import contextlib
import dataclasses
import logging
import math
import os
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import compilecache
from tensorflowonspark_tpu import metrics as metrics_mod
from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.parallel import mesh as mesh_mod

logger = logging.getLogger(__name__)

#: opt-in hot-loop transfer guard (see :func:`_resolve_transfer_guard`):
#: "1"/"on"/"disallow" makes any implicit host->device transfer inside a
#: fit_feed dispatch a hard error; "log" logs instead; ""/"0"/"off"/"allow"
#: disables (the default — guards cost a context switch per dispatch).
TRANSFER_GUARD_ENV = "TFOS_TRANSFER_GUARD"

#: default K for :meth:`Trainer.fit_feed` when the caller leaves
#: ``steps_per_call=1`` — lets cluster runs arm K-step grouped dispatch
#: (the megastep path) without code changes; see docs/API.md.
STEPS_PER_CALL_ENV = "TFOS_STEPS_PER_CALL"


def _resolve_transfer_guard(mode):
    """Normalize a ``fit_feed(transfer_guard=...)`` / env value to a jax
    transfer-guard level string, or None when guarding is off.

    Only the **host->device** direction is guarded: the dispatch path must
    never re-transfer batch data (that is the infeed prefetch thread's job),
    but the metrics recorder legitimately syncs the loss device->host at
    window boundaries — a full ``jax.transfer_guard`` would flag it.
    """
    if mode is None:
        mode = os.environ.get(TRANSFER_GUARD_ENV, "")
    if not mode or mode in ("0", "off", "allow", "allow_explicit", False):
        return None
    if mode in ("1", "on", True):
        return "disallow"
    return mode  # "disallow" / "log" / "log_explicit" pass through


def _transfer_guard_ctx(level):
    """Fresh guard context per dispatch (jax's config contexts are
    contextmanager-based generators — not re-enterable)."""
    if level is None:
        return contextlib.nullcontext()
    return jax.transfer_guard_host_to_device(level)


@dataclasses.dataclass
class TrainState:
    """Minimal functional train state: trainable params + optimizer state +
    step + non-trainable collections (e.g. BatchNorm ``batch_stats``)."""

    step: Any
    params: Any
    opt_state: Any
    extra: Any = None

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state, self.extra), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def _own(state):
    """``state`` in buffers that are this Trainer's alone: what the caller
    handed over (``params``, ``extra``) copied, and of the optimizer's state,
    which ``optimizer.init`` made here, only the leaves that share a buffer
    with those or with each other (an optimizer that keeps the parameters
    themselves).  A copy of the whole state would hold it twice for a
    moment, and Adam's two moments are two thirds of it: at 766 M parameters
    18.4 GB where the chip has 16.9.  Jitted copies (not eager ``.copy()``):
    global arrays on a multi-host mesh are not fully addressable, so eager
    ops on them are rejected; a jit identity runs SPMD and always
    materializes fresh output buffers."""
    copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))

    def buffers(leaf):
        return {shard.data.unsafe_buffer_pointer()
                for shard in getattr(leaf, "addressable_shards", ())}

    handed = (state.params, state.extra)
    taken = set()
    for leaf in jax.tree_util.tree_leaves(handed):
        taken |= buffers(leaf)

    def fresh(leaf):
        mine = buffers(leaf)
        if mine & taken:
            return copy(leaf)
        taken.update(mine)
        return leaf

    opt_state = jax.tree_util.tree_map(fresh, state.opt_state)
    params, extra = copy(handed)
    return TrainState(step=state.step, params=params, opt_state=opt_state,
                      extra=extra)


@jax.jit
def _acc_add(acc, new):
    """Jitted pytree add for on-device metric accumulation: keeps
    :meth:`Trainer.evaluate` sums device-resident between batches (no
    per-batch host sync) and stays legal on multi-host global arrays,
    where the eager equivalent raises."""
    return jax.tree_util.tree_map(jnp.add, acc, new)


class Trainer(object):
    """Builds and runs a sharded training step.

    Args:
      loss_fn: ``fn(params, batch, mask) -> (loss, aux)`` — or, when
        ``extra_state`` is given, ``fn(params, extra, batch, mask)`` where
        ``extra`` carries non-trainable collections (BatchNorm stats); the
        updated collections are returned in ``aux["extra_state"]``.  The
        one other key of ``aux`` the trainer knows is ``aux["counters"]``, a
        flat dict of device scalars, added up over the steps and published
        by :meth:`counters_snapshot` under their keys.  ``mask``
        is the per-row validity mask from the infeed (1.0 = real row) and
        must be applied by the loss so padded rows contribute nothing.
      init_params: parameter pytree (replicated over the mesh).
      extra_state: initial non-trainable state pytree (not optimized).
      optimizer: an optax GradientTransformation.
      mesh: device mesh (defaults to a pure data-parallel mesh).
      compute_dtype: cast batch inputs to this dtype inside the step (bf16 by
        default on TPU: keeps matmuls on the MXU's native precision while
        params/optimizer state stay fp32).
      batch_size: global batch size (for throughput metrics).
      log_steps: TimeHistory window.
      param_sharding: ``None`` replicates params/optimizer state over the
        mesh (reference-parity data parallel); ``"fsdp"`` shards them over
        the mesh's ``fsdp`` axis (per-device state memory divided by the
        axis size; XLA inserts the weight all-gathers and grad
        reduce-scatters — see :mod:`~tensorflowonspark_tpu.parallel.fsdp`);
        or an explicit pytree of shardings matching the TrainState.
      accum_steps: gradient accumulation — split each batch into this many
        sequential microbatch grad passes (lax.scan) with one optimizer
        update; peak activation memory drops by ~accum_steps and the batch
        dim must be divisible by it.  Microbatch grads/losses are averaged
        weighted by each microbatch's mask count, which reproduces the
        full-batch update EXACTLY for masked-MEAN losses
        (``masked_sum / mask.sum()`` plus mask-independent terms like
        weight decay — the form every framework loss uses); a masked-SUM
        loss would instead see its microbatch grads reweighted.  Note the
        ``aux`` returned by :meth:`step` is the LAST microbatch's aux only
        (auxes are not averaged — they may be arbitrary pytrees), so
        aux-derived metrics like accuracy sample 1/accum_steps of the
        batch; the loss itself IS the full-batch value.
      step_flops_override: per-device MODEL FLOPs of one optimizer step,
        stated by the model's owner from shapes (recomputed work under
        rematerialization does not count) — the only source of the MFU and
        achieved-FLOP/s figures.  Not given: none is reported.
      aot_cache: warm-start executable store — a directory path or a
        :class:`~tensorflowonspark_tpu.compilecache.AOTCache`.  The step
        and multi-step programs are resolved through it: a
        fingerprint-matched serialized executable dispatches WITHOUT ever
        tracing (second-scale elastic rejoin); a cold store compiles once
        and persists for the next restart; any mismatch falls back to
        plain JIT.  Fingerprints cover versions/mesh/avals PLUS a
        structural hash of the loss fn + optimizer
        (:func:`~tensorflowonspark_tpu.compilecache.program_identity`),
        so resuming after editing the loss or a hyperparameter rejects
        the stale executable; still scope the directory per model run
        (see :mod:`~tensorflowonspark_tpu.compilecache`).
        :func:`fit_supervised` defaults it beside a LOCAL checkpoint
        root (remote roots skip the default — the store is
        local-filesystem only).
      aot_program_version: optional caller-asserted program identity mixed
        into the AOT fingerprint VERBATIM.  The structural hash is
        best-effort (bytecode + consts + closure values); bump this string
        on any program change it cannot see — a mismatch is a clean
        recompile, never a crash.
    """

    def __init__(self, loss_fn, init_params, optimizer, mesh=None,
                 extra_state=None, compute_dtype=None, batch_size=None,
                 log_steps=20, donate=True, accum_steps=1,
                 summary_writer=None, param_sharding=None,
                 step_flops_override=None,
                 aot_cache=None, aot_program_version=None):
        # the bring-up's account: ``trainer_init`` from here to the return
        resume = telemetry.bringup.mark("trainer_init")
        self.mesh = mesh if mesh is not None else mesh_mod.build_mesh()
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.compute_dtype = compute_dtype
        self.batch_size = batch_size
        self.log_steps = log_steps
        self.accum_steps = accum_steps
        # optional summary.SummaryWriter: window scalars -> TensorBoard
        # (create it on the chief only; see checkpoint.should_export)
        self.summary_writer = summary_writer
        # the MFU numerator and its only source (None: no MFU is reported)
        self.step_flops_override = step_flops_override
        self._has_extra = extra_state is not None

        self.state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=init_params,
            opt_state=optimizer.init(init_params),
            extra=extra_state,
        )
        if param_sharding == "fsdp":
            # FSDP: params + optimizer state shard over the mesh's "fsdp"
            # axis (per-device state memory / axis size); XLA inserts the
            # weight all-gathers and grad reduce-scatters.  Elementwise
            # optimizer updates preserve the sharding, so the state stays
            # sharded across steps with no re-annotation.
            from tensorflowonspark_tpu.parallel import fsdp as fsdp_mod

            self.state = fsdp_mod.shard_tree(self.state, self.mesh)
        elif param_sharding is not None:
            # explicit pytree of shardings matching the TrainState
            self.state = jax.device_put(self.state, param_sharding)
        else:
            self.state = jax.device_put(self.state,
                                        mesh_mod.replicated(self.mesh))
        # Own our buffers: device_put is a no-op for already-resident arrays,
        # and the donated step would then delete buffers the caller (or a
        # sibling Trainer built from the same init_params) still holds.
        if donate:
            self.state = _own(self.state)

        def grad_micro(params, extra, batch, mask):
            """Loss + grads on one (micro)batch against fixed params;
            returns the updated non-trainable state and the aux dict with
            ``extra_state`` split out (so scan doesn't stack A copies)."""
            if self._has_extra:
                def wrapped(p):
                    return self.loss_fn(p, extra, batch, mask)
            else:
                def wrapped(p):
                    return self.loss_fn(p, batch, mask)
            (loss, aux), grads = jax.value_and_grad(
                wrapped, has_aux=True)(params)
            new_extra = extra
            if self._has_extra and isinstance(aux, dict) and "extra_state" in aux:
                new_extra = aux["extra_state"]
                aux = {k: v for k, v in aux.items() if k != "extra_state"}
            return loss, aux, grads, new_extra

        def cast_batch(batch):
            if self.compute_dtype is None:
                return batch
            return jax.tree_util.tree_map(
                lambda x: x.astype(self.compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, batch)

        def apply_update(state, grads, loss, aux, new_extra):
            """Shared tail: one optimizer update + next TrainState.  The
            global grad norm is computed INSIDE the jitted step (one
            norm-reduce, negligible next to the matmuls) and carried out
            as a device scalar alongside the user aux; :meth:`step`
            separates them again, so the user-visible aux contract is
            unchanged and nothing syncs until a TimeHistory window
            boundary reads it (training-health telemetry)."""
            import optax

            grad_norm = optax.global_norm(grads)
            updates, new_opt = self.optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            return (TrainState(state.step + 1, new_params, new_opt, new_extra),
                    loss, (aux, grad_norm))

        def train_step_accum(state, batch, mask):
            """One optimizer step from ``accum_steps`` sequential microbatch
            grad passes (lax.scan): grads/loss are mask-weighted means,
            which equals the full-batch update exactly for masked-MEAN
            losses (incl. mask-independent terms like weight decay — see
            the ctor docstring for the contract); BatchNorm stats thread
            through the microbatches sequentially.  Peak activation memory
            drops by ~accum_steps."""
            a = self.accum_steps
            batch = cast_batch(batch)

            def resh(x):
                if x.shape[0] % a:
                    raise ValueError(
                        "batch dim {} not divisible by accum_steps {}".format(
                            x.shape[0], a))
                return x.reshape((a, x.shape[0] // a) + x.shape[1:])

            micro = jax.tree_util.tree_map(resh, batch)
            micro_mask = resh(mask)
            zero_g = jax.tree_util.tree_map(jnp.zeros_like, state.params)
            zero = jnp.zeros((), jnp.float32)

            def body(carry, bm):
                g_acc, l_acc, w_acc, extra = carry
                b, m = bm
                loss, aux, grads, new_extra = grad_micro(
                    state.params, extra, b, m)
                w = m.sum()
                g_acc = jax.tree_util.tree_map(
                    lambda acc, g: acc + g * w, g_acc, grads)
                return (g_acc, l_acc + loss * w, w_acc + w, new_extra), aux

            (g_sum, l_sum, w_sum, new_extra), aux_stack = jax.lax.scan(
                body, (zero_g, zero, zero, state.extra), (micro, micro_mask))
            w_safe = jnp.maximum(w_sum, 1.0)
            grads = jax.tree_util.tree_map(lambda x: x / w_safe, g_sum)
            aux = jax.tree_util.tree_map(lambda x: x[-1], aux_stack)
            return apply_update(state, grads, l_sum / w_safe, aux, new_extra)

        def train_step(state, batch, mask):
            loss, aux, grads, new_extra = grad_micro(
                state.params, state.extra, cast_batch(batch), mask)
            return apply_update(state, grads, loss, aux, new_extra)

        self._step_core = train_step if accum_steps == 1 else train_step_accum
        self._donate = (0,) if donate else ()
        # Every step program hands the state back laid out as it came in.
        # Left to itself the compiler picks the output layout: under an
        # explicit ``param_sharding`` the requested layout was gone after
        # one step, and the second call compiled a second program for the
        # new one.
        self._state_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, self.state)
        self._train_step = jax.jit(
            self._step_core, donate_argnums=self._donate,
            out_shardings=(self._state_shardings, None, None))
        self._multi_cache = {}  # k -> jitted k-step scan program
        # Warm-start compile plane (compilecache): the AOT executable
        # store, the per-program resolution memo (name -> deserialized /
        # explicitly compiled executable, or None = plain jit), and the
        # load-vs-compile verdicts for status reporting.
        self._aot = None
        self._aot_exec = {}
        self._aot_verdicts = {}
        self._aot_program_version = aot_program_version
        self._aot_program_id = None   # memoized program_identity digest
        if aot_cache is not None:
            self.set_aot_cache(aot_cache)
        self._eval_cache = {}   # metric_fn -> jitted wrapper (evaluate)
        self.history = None
        # Always-on dispatch-overlap tallies (plain ints, the DataFeed
        # pattern): the host-side gap between a dispatch returning and the
        # next one starting — the serial section the device-resident infeed
        # + async checkpointing exist to shrink.  Written by the fit_feed
        # loop only; heartbeat reads tolerate staleness.
        self._dispatch_count = 0
        self._dispatch_gap_us = 0
        self._dispatch_gap_us_hwm = 0
        # Runtime goodput accountant (observability tier): wall time
        # attributed to productive dispatch vs infeed starvation vs
        # checkpoint drain vs recovery, plus a bucketed step-time histogram
        # and achieved-FLOP/s / MFU gauges.  Step timing comes from
        # TimeHistory's SYNCED window boundaries (dispatch wall alone
        # measures dispatch rate, not device time — see TimeHistory), so
        # the gauges agree with TimeHistory.mfu by construction: both call
        # metrics.mfu_from_step_time on the same step_flops and a
        # device-synced clock.
        self._goodput_dispatch_us = 0
        self._goodput_infeed_starved_us = 0
        self._goodput_ckpt_drain_us = 0
        self._goodput_recovery_us = 0
        self._last_drain_us = 0
        # bucket bound (ms) -> window steps; every key from construction, so
        # a snapshot from another thread never meets a growing dict
        self._step_ms_hist = dict.fromkeys(metrics_mod.STEP_MS_BUCKETS, 0)
        self._step_ms_count = 0      # steps covered by closed windows
        self._step_ms_sum_us = 0     # wall us covered by closed windows
        self._mfu_pct = None         # latest closed window's MFU, percent
        self._flops_per_sec = None   # latest achieved per-device FLOP/s
        self._acct_history = None    # TimeHistory the accountant follows
        self._windows_seen = 0       # timestamp_log entries consumed
        # Training-health telemetry, observed ONLY at TimeHistory window
        # boundaries (the one place the pipeline already syncs): last
        # finite loss / grad-norm gauges plus cumulative nonfinite tallies.
        # The watchtower's nonfinite rule and the heartbeat channel read
        # these via counters_snapshot.
        self._health_grad_norm = None  # device scalar from the last step
        self._health_windows = 0       # boundary observations folded in
        self._health_loss = None       # last FINITE loss
        self._health_grad = None       # last finite grad norm
        self._nonfinite_loss = 0
        self._nonfinite_grad = 0
        # Poison-step rollback (remediator ``train_rollback`` command knob):
        # a pending request token armed by apply_knob, the set of tokens
        # already honoured (the knob coordinator re-broadcasts on every
        # heartbeat, so dedupe lives here).
        self._rollback_req = None
        self._rollback_tokens = set()
        # Megastep telemetry: dispatched train steps (counter), the K of
        # the most recent dispatch, and the session-max K (the heartbeat
        # gauge — the tail of a feed degrades to K=1 singles, so "last K"
        # would hide that a live train_steps_per_call retune landed), plus
        # the last requested K from a knob push (recorded for stats
        # stamping; the grouped feed applies the change on a boundary).
        self._steps_total = 0
        self._steps_per_call_gauge = 0
        self._steps_per_call_hwm = 0
        self._steps_per_call_req = None
        # What the model counts: each step's ``aux["counters"]`` waits here
        # as device scalars until the device has produced them;
        # ``_fold_aux_counters`` adds the finished ones into host totals and
        # never waits, so neither the step loop nor a heartbeat's
        # counters_snapshot syncs on a step in flight.
        self._aux_pending = []
        self._aux_totals = {}
        self._aux_lock = threading.Lock()
        # Which program was made, at which step (see _note_compile): the
        # compile plane's tallies as of the last program seen, the names of
        # the step programs that have run, the programs made again under a
        # name that had run (the records themselves are the compile
        # plane's, ``compilecache.stats.record``).
        self._compile_seen = compilecache.stats.tallies()
        self._programs_run = set()
        self._recompiles = 0
        telemetry.bringup.mark(resume)

    def _note_compile(self, name):
        """A dispatch of step program ``name`` made programs executable
        (``compile_programs`` moved across it): keep which, at which step
        and at what cost.  The cost is the compile plane's tallies since
        the last program this trainer saw made, so the tracing and lowering
        in front of the compile are in it."""
        now = compilecache.stats.tallies()
        cost = {k: v - self._compile_seen[k] for k, v in now.items()}
        self._compile_seen = now
        again = name in self._programs_run
        if again:
            self._recompiles += 1
        entry = dict(cost, program=name, steps_total=self._steps_total,
                     recompile=again)
        compilecache.stats.record.append(entry)
        telemetry.get_tracer().instant("compile/program", **entry)
        if again:
            logger.warning(
                "step program %s was made again at step %d (%s): a batch of "
                "another shape, or a state laid out anew", name,
                self._steps_total, cost)

    def _note_aux_counters(self, aux):
        counts = aux.get("counters") if isinstance(aux, dict) else None
        if not counts:
            return
        with self._aux_lock:
            self._aux_pending.append(counts)
        if len(self._aux_pending) > 8:
            self._fold_aux_counters()

    def _fold_aux_counters(self):
        """Add the counts of every step the device has finished to the host
        totals, oldest first; stops at the first step still in flight."""
        with self._aux_lock:
            while self._aux_pending and all(
                    v.is_ready() for v in self._aux_pending[0].values()):
                for key, val in jax.device_get(
                        self._aux_pending.pop(0)).items():
                    self._aux_totals[key] = self._aux_totals.get(
                        key, 0) + val.item()

    def _own_counters(self):
        """Flat overlap + goodput counters for heartbeat payloads /
        :func:`~tensorflowonspark_tpu.telemetry.merge_counters`:
        ``dispatch_count`` dispatches, ``dispatch_gap_us`` total host-side
        time between dispatches (feed wait + checkpoint hook + bookkeeping;
        device idle time when steps don't pipeline), ``dispatch_gap_us_hwm``
        the worst single gap.

        Goodput breakdown (all wall microseconds): ``goodput_dispatch_us``
        time inside dispatch calls, ``goodput_infeed_starved_us`` the
        between-dispatch gap net of checkpoint-hook time (waiting on the
        feed), ``goodput_ckpt_drain_us`` time inside the ``on_steps`` hook,
        ``goodput_recovery_us`` restore + retry-backoff time (written by
        :func:`fit_supervised`).  ``step_ms_le_<bound>`` /``step_ms_count``
        /``step_ms_sum_us`` form a cumulative step-time histogram over
        :data:`~tensorflowonspark_tpu.metrics.STEP_MS_BUCKETS`;
        ``train_mfu_pct_max`` / ``train_flops_per_sec_max`` are the latest
        window's gauges (``_max`` suffix -> merged by max, rendered as
        Prometheus gauges): the stated ``step_flops_override`` over the
        window's device-synced step time, absent when no count was stated.

        What the model counts, where its loss returns ``aux["counters"]``
        (a flat dict of device scalars a step): each key as it came, summed
        over the steps the device has finished (a step in flight is counted
        by a later snapshot).  The keys and their meanings are the model's
        (``models/transformer.py``, where a layer sows them;
        ``docs/OBSERVABILITY.md``).

        ``train_recompiles_total``: dispatches that made a step program
        executable under a name (``step``, ``multi_<k>``) that had run
        before: a batch of another shape, a state laid out anew.  Which
        program, at which step and at what cost is in
        ``compilecache.stats.record`` (the flight recorder's
        ``compile_programs``) and in the instant ``compile/program``."""
        snap = {
            "dispatch_count": self._dispatch_count,
            "dispatch_gap_us": self._dispatch_gap_us,
            "dispatch_gap_us_hwm": self._dispatch_gap_us_hwm,
        }
        if self._steps_total:
            # dispatched train steps (each multi_step adds K) — pairs with
            # dispatch_gap_us to give the autopilot a per-dispatched-step
            # host-overhead signal
            snap["train_steps_total"] = self._steps_total
        if self._steps_per_call_hwm:
            # gauge (merged by max): the largest K any dispatch armed this
            # session, so the driver can confirm a live train_steps_per_call
            # retune landed even after the feed tail degrades to singles
            snap["train_steps_per_call_max"] = self._steps_per_call_hwm
        if self._step_ms_count:
            running = 0
            for bound in metrics_mod.STEP_MS_BUCKETS:
                running += self._step_ms_hist.get(bound, 0)
                snap["step_ms_le_%s" % bound] = running
            snap["step_ms_count"] = self._step_ms_count
            snap["step_ms_sum_us"] = self._step_ms_sum_us
        for key, val in (
                ("goodput_dispatch_us", self._goodput_dispatch_us),
                ("goodput_infeed_starved_us", self._goodput_infeed_starved_us),
                ("goodput_ckpt_drain_us", self._goodput_ckpt_drain_us),
                ("goodput_recovery_us", self._goodput_recovery_us)):
            if val:
                snap[key] = val
        if self._mfu_pct is not None:
            snap["train_mfu_pct_max"] = round(self._mfu_pct, 4)
        if self._flops_per_sec is not None:
            snap["train_flops_per_sec_max"] = self._flops_per_sec
        # Training-health block (first window boundary onward):
        # train_health_windows boundary observations, train_loss_max /
        # train_grad_norm_max the last FINITE readings (gauges — never
        # NaN), train_nonfinite_loss / train_nonfinite_grad cumulative
        # tallies of nonfinite observations (the watchtower's nonfinite
        # rule fires on any increase).
        if self._health_windows:
            snap["train_health_windows"] = self._health_windows
            snap["train_nonfinite_loss"] = self._nonfinite_loss
            snap["train_nonfinite_grad"] = self._nonfinite_grad
            if self._health_loss is not None:
                snap["train_loss_max"] = self._health_loss
            if self._health_grad is not None:
                snap["train_grad_norm_max"] = round(self._health_grad, 6)
        if self._recompiles:
            snap["train_recompiles_total"] = self._recompiles
        if self._aux_pending or self._aux_totals:
            self._fold_aux_counters()
            snap.update(self._aux_totals)
        return snap

    def counters_snapshot(self):
        """:meth:`_own_counters` (see there: the step loop's, and what the
        model counts in ``aux["counters"]``) and what the process keeps
        for all its trainers: the compile plane's tallies
        (``compilecache.stats.tallies()``: ``compile_trace_us``,
        ``compile_lower_us``, ``compile_backend_us``,
        ``compile_cache_retrieval_us``, ``compile_programs``) and, once the
        first dispatch of ``fit_feed`` has returned and nothing of it
        before, the bring-up's account (``telemetry.bringup``:
        ``bringup_<phase>_us`` for the nine phases and ``bringup_wall_us``,
        which they sum to).  A node's heartbeats carry these two once a
        process, not once a trainer (``node._node_metrics_provider``)."""
        snap = self._own_counters()
        snap.update(compilecache.stats.tallies())
        snap.update(telemetry.bringup.snapshot())
        return snap

    def apply_knob(self, name, value):
        """Live-knob hook (autopilot KNOB pushes via ``node.apply_knobs``;
        the trainer registers itself in :meth:`fit_feed`).

        ``train_steps_per_call`` is recorded here for stats stamping and
        claimed so a trainer-only registry still acks the push; the actual
        regrouping is done by the :class:`ShardedFeed` (registered in the
        same process), which applies the new K at the next group-fill
        start — never mid-group.

        ``train_rollback`` is the remediator's poison-step command: the
        value is a one-shot token (knob pushes re-broadcast on every
        heartbeat, so tokens already honoured are dropped here).  Arming it
        makes the next :meth:`fit_feed` iteration raise
        :class:`~tensorflowonspark_tpu.fault.PoisonRollback`, which
        :func:`fit_supervised` turns into a validated restore — the
        poisoned checkpoint step(s) are quarantined and training resumes
        from the last valid one."""
        if name == "train_rollback":
            token = str(value)
            if token not in self._rollback_tokens:
                self._rollback_tokens.add(token)
                self._rollback_req = token
            return True
        if name != "train_steps_per_call":
            return False
        self._steps_per_call_req = max(int(value), 1)
        return True

    def _account_windows(self):
        """Fold newly-closed TimeHistory windows into the step-time
        histogram and the MFU / achieved-FLOP/s gauges.  Window boundaries
        carry a forced device sync (see TimeHistory), so the per-step time
        derived here is honest under async dispatch — the same clock
        ``TimeHistory.build_stats``' MFU uses."""
        hist = self.history
        if hist is None:
            return
        if hist is not self._acct_history:
            # reset_history / first use: start from this recorder's origin
            self._acct_history = hist
            self._windows_seen = 1
        elif self._windows_seen >= len(hist.timestamp_log):
            # No window closed since the last call — the common case on the
            # per-dispatch path (boundaries come every log_steps).  O(1)
            # exit keeps the between-dispatch host work independent of the
            # accounting below.
            return
        before_windows = self._windows_seen
        log = hist.timestamp_log
        while self._windows_seen < len(log):
            s0, t0 = log[self._windows_seen - 1]
            s1, t1 = log[self._windows_seen]
            self._windows_seen += 1
            steps, span = s1 - s0, t1 - t0
            if steps <= 0 or span <= 0:
                continue
            step_s = span / steps
            step_ms = step_s * 1e3
            for bound in metrics_mod.STEP_MS_BUCKETS:
                if step_ms <= bound:
                    self._step_ms_hist[bound] = (
                        self._step_ms_hist.get(bound, 0) + steps)
                    break
            self._step_ms_count += steps
            self._step_ms_sum_us += int(span * 1e6)
            flops_ps = metrics_mod.achieved_flops_per_sec(
                hist.step_flops, step_s)
            if flops_ps is not None:
                self._flops_per_sec = flops_ps
            mfu = metrics_mod.mfu_from_step_time(hist.step_flops, step_s)
            if mfu is not None:
                self._mfu_pct = 100.0 * mfu
        if self._windows_seen != before_windows:
            self._sync_health(hist)

    def _sync_health(self, hist):
        """Fold one window-boundary health observation: the boundary just
        forced a device sync, so reading the synced loss (and the buffered
        grad-norm device scalar) here adds no pipeline stall.  Nonfinite
        observations bump the cumulative tallies; the published gauges
        keep the last FINITE values, so heartbeat payloads and Prometheus
        scrapes never carry NaN."""
        self._health_windows += 1
        val = getattr(hist, "last_synced_value", None)
        if val is not None:
            try:
                import numpy as np

                arr = np.asarray(val, dtype=np.float64).ravel()
            except (TypeError, ValueError):
                arr = None
            if arr is not None and arr.size:
                bad = int((~np.isfinite(arr)).sum())
                if bad:
                    self._nonfinite_loss += bad
                last = float(arr[-1])
                if math.isfinite(last):
                    self._health_loss = last
        gnorm, self._health_grad_norm = self._health_grad_norm, None
        if gnorm is not None:
            try:
                gval = float(jax.device_get(gnorm))
            except (TypeError, ValueError):
                gval = None
            if gval is not None:
                if math.isfinite(gval):
                    self._health_grad = gval
                else:
                    self._nonfinite_grad += 1

    def _get_multi_step(self, k):
        """Jitted program running ``k`` train steps in ONE dispatch via
        ``lax.scan`` over a stacked group of batches (leaves shaped
        ``(k, batch, ...)``).  Amortizes per-step dispatch latency and lets
        XLA overlap the scan iterations' host interactions.

        The scan also reduces its window metrics ON DEVICE — per-step
        losses AND grad norms come out as the full vector plus O(1) means,
        so the host reads back nothing until a TimeHistory window boundary.

        Note the batch/mask stacks are NOT in ``donate_argnums``: XLA
        donation is input-output aliasing, and this program has no
        batch-stack-shaped output to alias into, so donating them would
        only warn ("donated buffers were not usable") and change nothing.
        Stack handover is instead the dispatch-side deletion in
        :meth:`multi_step` (``donate_batches=True``)."""
        if k not in self._multi_cache:
            def multi(state, batches, masks):
                def body(st, bm):
                    b, m = bm
                    new_st, loss, packed = self._step_core(st, b, m)
                    return new_st, (loss, packed[1])
                state, (losses, gnorms) = jax.lax.scan(
                    body, state, (batches, masks))
                # reductions + final loss extracted INSIDE jit: eager
                # indexing on the scan output would raise on a multi-host
                # mesh, where jit outputs are global (not fully
                # addressable) arrays
                return state, (losses, losses[-1],
                               losses.mean(), gnorms.mean())
            self._multi_cache[k] = jax.jit(
                multi, donate_argnums=self._donate,
                out_shardings=(self._state_shardings, None))
        return self._multi_cache[k]

    def set_aot_cache(self, cache):
        """Attach a warm-start AOT executable store (a directory path or
        :class:`~tensorflowonspark_tpu.compilecache.AOTCache`).  No-op when
        one is already attached, so :func:`fit_supervised` can default the
        store beside the checkpoint root without clobbering an explicit
        ctor choice."""
        if self._aot is not None or cache is None:
            return
        self._aot = (cache if isinstance(cache, compilecache.AOTCache)
                     else compilecache.AOTCache(cache))

    def _aot_resolve(self, name, jit_fn, args):
        """Dispatchable executable for program ``name``, or None (plain jit
        dispatch).  First call per name decides: a fingerprint-matched
        artifact deserializes and dispatches without ever tracing (the
        warm-rejoin path); a cold store lowers+compiles once and persists
        the executable for the next restart; no store / unsupported
        serialization memoizes None.  Shape drift after resolution is
        handled at dispatch (see :meth:`step`)."""
        if self._aot is None:
            return None
        if name in self._aot_exec:
            return self._aot_exec[name]
        if self._aot_program_id is None:
            # the Python half of the program — avals alone cannot tell two
            # losses (or two learning rates) with identical shapes apart
            self._aot_program_id = compilecache.program_identity(
                self.loss_fn, self.optimizer)
        fp = compilecache.fingerprint(
            avals=args, mesh=self.mesh, donate=self._donate,
            extra={"program": name, "accum_steps": self.accum_steps,
                   "compute_dtype": str(self.compute_dtype),
                   "program_id": self._aot_program_id,
                   "program_version": self._aot_program_version,
                   # output-structure revision of the loop programs (multi
                   # grew on-device window reductions): a serialized
                   # executable from an older revision would deserialize
                   # fine but return the old structure, so it must miss
                   "loop_rev": 2})
        compiled, verdict, micros = compilecache.load_or_compile(
            self._aot, name, fp, jit_fn, args)
        self._aot_verdicts[name] = verdict
        if verdict == "loaded":
            # loud on purpose: this dispatch runs a PRE-EXISTING serialized
            # program (trace-free warm start) — the fingerprint vouches for
            # versions/mesh/avals/program-identity, the operator should
            # still see which store it came from
            logger.warning(
                "AOT program %s: loaded serialized executable from %s "
                "(%.1f ms, trace-free; program_id %s)", name,
                self._aot.directory, micros / 1e3,
                self._aot_program_id[:12])
        else:
            logger.info("AOT program %s: %s (%.1f ms)", name, verdict,
                        micros / 1e3)
        self._aot_exec[name] = compiled
        return compiled

    def _aot_dispatch(self, name, jit_fn, args):
        """Run ``name`` via its resolved executable, falling back to the
        jit fn — permanently for this program name — if the shape-locked
        executable rejects the call (e.g. an odd tail batch after
        resolution).  The rejection raises before execution, so donated
        buffers are still intact for the retry — jax raises TypeError for
        aval mismatches and ValueError for sharding/layout mismatches
        (version-dependent), both from pre-execution argument checks."""
        made = compilecache.stats.programs
        if made != self._compile_seen["compile_programs"]:
            # programs made since the last dispatch are not this one's
            self._compile_seen = compilecache.stats.tallies()
        try:
            fn = self._aot_resolve(name, jit_fn, args)
            if fn is not None:
                try:
                    return fn(*args)
                except (TypeError, ValueError):
                    logger.warning(
                        "AOT executable %s rejected the call (aval drift); "
                        "reverting this program to JIT dispatch", name)
                    self._aot_exec[name] = None
            return jit_fn(*args)
        finally:
            if compilecache.stats.programs != made:
                self._note_compile(name)
            self._programs_run.add(name)

    def _ensure_history(self):
        """Build the metrics recorder on first use.  A step's FLOPs are the
        stated ``step_flops_override`` or unknown: nothing is lowered or
        compiled here."""
        if self.history is None:
            self.history = metrics_mod.TimeHistory(
                batch_size=self.batch_size or 0, log_steps=self.log_steps,
                step_flops=self.step_flops_override,
                summary_writer=self.summary_writer)
            self.history.on_train_begin()

    def multi_step(self, batches, masks, donate_batches=False):
        """Run K steps in one dispatch; ``batches``/``masks`` leaves carry a
        leading scan dim K (see :func:`~...parallel.mesh.scan_batch_sharding`
        and :meth:`~...parallel.infeed.ShardedFeed.grouped_batches`).
        Returns the final step's loss; the per-step loss vector feeds the
        metrics recorder (dense TensorBoard curve under K-steps-per-
        dispatch), while window boundaries sync only the O(1) on-device
        loss mean and the grad-norm mean buffers for the health gauges —
        between boundaries the host reads back nothing.

        ``donate_batches=True`` hands the stacks' device memory back to the
        allocator right after dispatch: the buffers are deleted caller-side
        (PJRT holds them alive until the in-flight dispatch drains), so the
        K× staging memory is recycled across groups instead of riding the
        Python references, and any accidental reuse of a handed-over stack
        raises instead of silently recomputing.  Only legal with a feed
        whose ``group_donation_safe`` is True — i.e. one that builds FRESH
        device stacks every group.  (Not ``donate_argnums``: XLA could
        never alias the stacks into this program's outputs, see
        :meth:`_get_multi_step`.)"""
        k = int(jax.tree_util.tree_leaves(masks)[0].shape[0])
        fn = self._get_multi_step(k)
        self._ensure_history()
        self.state, (losses, final, loss_mean, gnorm_mean) = \
            self._aot_dispatch("multi_%d" % k, fn,
                               (self.state, batches, masks))
        if donate_batches:
            for leaf in jax.tree_util.tree_leaves((batches, masks)):
                if hasattr(leaf, "delete"):
                    leaf.delete()
        self._health_grad_norm = gnorm_mean
        self._steps_per_call_gauge = k
        self._steps_per_call_hwm = max(self._steps_per_call_hwm, k)
        self._steps_total += k
        self.history.on_steps_end(k, losses, window_value=loss_mean)
        return final

    def evaluate(self, sharded_feed, metric_fn, cache_key=None):
        """Exact evaluation over a feed: iterates
        ``sharded_feed.batches(drain="all")`` (every host's rows count —
        exhausted hosts step zero-mask dummies) and accumulates
        mask-weighted metric sums.

        ``metric_fn(params[, extra], batch, mask) -> (sums, weight)`` runs
        jitted per batch: ``sums`` is a dict of mask-weighted SUMS over the
        global batch, ``weight`` the batch's real-row count (``mask.sum()``
        for per-row metrics).  Returns ``{name: total_sum / total_weight}``
        — e.g. top-1 accuracy from
        ``{"accuracy": ((pred == label) * mask).sum()}, mask.sum()``.

        Jitted sums over globally-sharded batches are already all-host
        totals (replicated), so host-side accumulation needs no extra
        collective.

        The jit wrapper is cached on ``cache_key`` when given (pass a
        stable name like ``"top1"`` and fresh closures are fine — each call
        reuses the first compilation), else on the metric fn's identity —
        in that case pass the SAME function object every call (define it
        once, not as a fresh closure per evaluation) or each call retraces
        and the cache grows."""
        key = cache_key if cache_key is not None else metric_fn
        if key not in self._eval_cache:
            if len(self._eval_cache) >= 8:
                # runaway guard: fresh-closure callers would otherwise pin
                # one compiled executable per evaluation forever
                self._eval_cache.clear()
            self._eval_cache[key] = jax.jit(metric_fn)
        fn = self._eval_cache[key]
        if self._has_extra:
            call = lambda b, m: fn(self.state.params, self.state.extra, b, m)
        else:
            call = lambda b, m: fn(self.state.params, b, m)
        # Accumulate ON DEVICE (jitted tree-add): a per-batch float() would
        # block the host on every dispatch, and eager adds on multi-host jit
        # outputs raise.  One sync at the very end.
        totals = None
        weight_total = None
        for batch, mask in sharded_feed.batches(drain="all"):
            sums, weight = call(batch, mask)
            if totals is None:
                totals, weight_total = sums, weight
            else:
                totals, weight_total = _acc_add((totals, weight_total),
                                                (sums, weight))
        if totals is None:
            return {}
        weight_total = max(float(weight_total), 1.0)
        return {k: float(v) / weight_total for k, v in totals.items()}

    def reset_history(self):
        """Replace the metrics recorder with a fresh one (same stated step
        FLOPs), so compile/warmup steps don't pollute the reported stats.
        No-op before the first step."""
        if self.history is not None:
            self.history = None
            self._ensure_history()

    def step(self, batch, mask=None):
        """Run one global step; returns (loss, aux)."""
        if mask is None:
            first = jax.tree_util.tree_leaves(batch)[0]
            mask = jnp.ones((first.shape[0],), jnp.float32)
        self._ensure_history()
        self.state, loss, packed = self._aot_dispatch(
            "step", self._train_step, (self.state, batch, mask))
        # apply_update rides the grad norm out next to the user aux; keep
        # it as an un-synced device scalar until a window boundary reads it
        # (multi_step buffers its scan's on-device grad-norm mean the same
        # way).
        aux, self._health_grad_norm = packed
        self._note_aux_counters(aux)
        self._steps_per_call_gauge = 1
        self._steps_per_call_hwm = max(self._steps_per_call_hwm, 1)
        self._steps_total += 1
        # Passing the loss lets TimeHistory sync on device completion at
        # window boundaries (honest ms/step + MFU under async dispatch);
        # within a window steps still pipeline.
        self.history.on_step_end(loss)
        return loss, aux

    def fit_feed(self, sharded_feed, max_steps=None, steps_per_call=1,
                 on_steps=None, transfer_guard=None):
        """Train from a :class:`~tensorflowonspark_tpu.parallel.infeed.ShardedFeed`
        until end-of-data consensus (or ``max_steps``); returns final stats.

        ``max_steps`` is an **absolute** target for the state's step counter
        — warmup steps taken before ``fit_feed`` count toward it (offset by
        ``int(trainer.state.step)`` for a relative budget).

        ``steps_per_call > 1`` pulls K-step groups from the feed
        (:meth:`ShardedFeed.grouped_batches`) and runs each group as one
        ``lax.scan`` dispatch (:meth:`multi_step`); tail batches that can't
        fill a group run as ordinary single steps.  ``max_steps`` may be
        overshot by at most K-1 steps.  Leaving ``steps_per_call=1`` reads
        :data:`STEPS_PER_CALL_ENV` (``TFOS_STEPS_PER_CALL``) as the
        default, and a live ``train_steps_per_call`` autopilot knob can
        retune K between groups mid-run.  When the feed's
        ``group_donation_safe`` is True (device-side group assembly) the
        batch/mask stacks are donated back to the allocator each dispatch.

        ``on_steps``: optional ``fn(steps_done)`` called after every
        dispatch (so once per K-step group) — the hook for periodic
        checkpointing: ``on_steps=lambda s: ckpt.maybe_save(s,
        trainer.state)`` (reading ``trainer.state`` there doesn't sync; the
        manager pulls values only when the interval fires, and with async
        saves the serialization overlaps the following dispatches).

        ``transfer_guard``: opt-in hot-loop invariant — wrap every dispatch
        in ``jax.transfer_guard_host_to_device`` at this level
        (``"disallow"``/``"log"``; ``None`` reads :data:`TRANSFER_GUARD_ENV`)
        so a batch that is NOT already device-resident (an implicit
        ``device_put`` sneaking back onto the dispatch path) is a hard error
        instead of a silent MFU regression.  The guard wraps only the
        dispatch calls, not the feed pulls: the infeed's own explicit
        transfers (prefetch thread) stay legal either way.

        The returned stats carry ``stats["overlap"]`` — this trainer's
        dispatch-gap counters merged with the feed's ``infeed_*`` tallies
        (see :meth:`counters_snapshot`)."""
        from tensorflowonspark_tpu import fault as fault_mod

        # The bring-up's account: from here to the first batch in hand is
        # ``first_batch``.
        bringing_up = telemetry.bringup.open
        resume = telemetry.bringup.mark("first_batch")
        tracer = telemetry.get_tracer()
        guard_level = _resolve_transfer_guard(transfer_guard)
        # Chaos hooks (null-object when TFOS_FAULT_SPEC is unset: one env
        # lookup here, one attribute call per dispatch): per-step straggler
        # sleep and one-shot NaN batch corruption.
        injector = fault_mod.from_env()
        # Ride heartbeats like the feeds do (duck-typed counters_snapshot;
        # guarded for standalone use outside the node runtime).
        try:
            from tensorflowonspark_tpu import node as node_mod

            node_mod._register_feed(self)
        except Exception:  # pragma: no cover - stripped envs
            pass
        # Step-counted profile captures (GET /profile?steps=N) watch this
        # trainer's dispatch counter to know when N steps have passed.
        try:
            from tensorflowonspark_tpu import profiling as profiling_mod

            profiling_mod.register_step_counter(lambda: self._dispatch_count)
        except Exception:  # pragma: no cover - stripped envs
            pass
        last_loss = None
        # Host-side step counter: reading state.step would sync on the
        # just-dispatched device step and defeat the infeed's double
        # buffering (steps dispatch asynchronously).
        steps_done = int(self.state.step)
        steps_per_call = int(steps_per_call)
        if steps_per_call <= 1:
            # env default so cluster runs can arm grouped (megastep)
            # dispatch without code changes; an explicit steps_per_call > 1
            # always wins
            env_k = os.environ.get(STEPS_PER_CALL_ENV, "")
            if env_k:
                try:
                    steps_per_call = max(int(env_k), 1)
                except ValueError:
                    logger.warning("ignoring non-integer %s=%r",
                                   STEPS_PER_CALL_ENV, env_k)
        # Donate the batch/mask stacks back to the allocator only when the
        # feed guarantees fresh device buffers every group (device-side
        # assembly); host-stack mode and duck-typed feeds handing over
        # host-backed arrays fall back to the non-donating program.
        donate_batches = bool(self._donate) and bool(
            getattr(sharded_feed, "group_donation_safe", False))
        if steps_per_call > 1:
            source = sharded_feed.grouped_batches(steps_per_call)
        else:
            source = (("single", b, m) for b, m in sharded_feed.batches())
        # Cross-process flow: a data-service feed hands over the flow id of
        # the split a dispatched batch came from (see ServiceFeed /
        # ShardedFeed ``pop_dispatch_flow``); ending the flow here gives
        # Perfetto the full worker-serve -> commit -> infeed -> dispatch
        # chain.  Duck-typed and optional — plain feeds have no flows.
        pop_flow = getattr(sharded_feed, "pop_dispatch_flow", None)
        prev_return = None
        source = iter(source)
        while True:
            # an explicit next(), so that the wait for a batch has a span
            with telemetry.span("train/next_batch"):
                item = next(source, None)
            if bringing_up:
                telemetry.bringup.mark(resume)
            if item is None:
                break
            kind, batch, mask = item
            if self._rollback_req is not None:
                # Remediator poison-step command: stop dispatching NOW —
                # every further step trains on poisoned params.  Drain the
                # feed (unblocks producers, like the max_steps early stop)
                # and hand control to fit_supervised's rollback path.
                token, self._rollback_req = self._rollback_req, None
                if hasattr(sharded_feed, "terminate"):
                    sharded_feed.terminate()
                raise fault_mod.PoisonRollback(step=steps_done, token=token)
            injector.on_step(steps_done)
            batch = injector.corrupt_batch(batch, steps_done)
            start = time.perf_counter()
            if prev_return is not None:
                gap_us = int((start - prev_return) * 1e6)
                self._dispatch_gap_us += gap_us
                if gap_us > self._dispatch_gap_us_hwm:
                    self._dispatch_gap_us_hwm = gap_us
                # Goodput: the slice of the gap not spent in the previous
                # iteration's on_steps hook was spent waiting on the feed.
                self._goodput_infeed_starved_us += max(
                    0, gap_us - self._last_drain_us)
            if bringing_up:
                telemetry.bringup.mark("first_dispatch")
            with telemetry.span("train/dispatch", kind=kind), \
                    _transfer_guard_ctx(guard_level):
                if kind == "multi":
                    loss = self.multi_step(batch, mask,
                                           donate_batches=donate_batches)
                    steps_done += int(
                        jax.tree_util.tree_leaves(mask)[0].shape[0])
                else:
                    loss, _ = self.step(batch, mask)
                    steps_done += 1
            prev_return = time.perf_counter()
            if bringing_up:
                # the first dispatch has returned: the account is whole
                bringing_up = telemetry.bringup.close()
            self._goodput_dispatch_us += int((prev_return - start) * 1e6)
            self._dispatch_count += 1
            self._account_windows()
            if pop_flow is not None:
                fid = pop_flow()
                if fid:
                    tracer.flow_end("dataservice/split_flow", fid,
                                    leg="train_dispatch", kind=kind,
                                    steps_done=steps_done)
            last_loss = loss
            if on_steps is not None:
                drain_t0 = time.perf_counter()
                with telemetry.span("train/on_steps"):
                    on_steps(steps_done)
                self._last_drain_us = int(
                    (time.perf_counter() - drain_t0) * 1e6)
                self._goodput_ckpt_drain_us += self._last_drain_us
            else:
                self._last_drain_us = 0
            if max_steps and steps_done >= max_steps:
                # Early stop with epochs of data still queued: drain it so
                # blocked feed tasks unblock and the driver stops scheduling
                # more partitions (reference StopFeedHook/terminate pattern,
                # estimator/mnist_spark.py:14-22 + TFNode.py:172-194).
                if hasattr(sharded_feed, "terminate"):
                    sharded_feed.terminate()
                break
        overlap = dict(self.counters_snapshot())
        if hasattr(sharded_feed, "counters_snapshot"):
            try:
                overlap.update(sharded_feed.counters_snapshot())
            except Exception:  # pragma: no cover - duck-typed feeds
                pass
        if self.history:
            self.history.on_train_end(last_loss)
            stats = self.history.log_stats(
                loss=None if last_loss is None else float(last_loss))
        else:
            stats = {}
        stats["overlap"] = overlap
        # Megastep stamp: how this fit's dispatches were shaped — the CI
        # gates copy this block into their evidence so every reported
        # number says which engine produced it.
        stats["megastep"] = {
            "steps_per_call": steps_per_call,
            "steps_per_call_last": self._steps_per_call_gauge or 1,
            "group_assembly": (getattr(sharded_feed, "group_assembly", None)
                               if steps_per_call > 1 else None),
            "donate_state": bool(self._donate),
            "donate_batches": bool(donate_batches and steps_per_call > 1),
        }
        return stats

    def restore_latest(self, ckpt_manager, validate=False):
        """Restore the newest checkpoint INTO this trainer's state (same
        shardings — see :func:`~tensorflowonspark_tpu.checkpoint.abstract_state`);
        returns the restored step, or None when no checkpoint exists yet.
        The recovery half of the reference's story "Spark retries the job and
        TF restores from the last checkpoint" (SURVEY §5.3).

        ``validate=True`` uses
        :meth:`~tensorflowonspark_tpu.checkpoint.CheckpointManager.restore_latest_valid`:
        a partial/corrupt newest step is quarantined and the previous
        retained step restored instead of crashing recovery."""
        from tensorflowonspark_tpu import checkpoint as ckpt_mod

        restore = (ckpt_manager.restore_latest_valid if validate
                   else ckpt_manager.restore_latest)
        state, step = restore(ckpt_mod.abstract_state(self.state))
        if step is None:
            return None
        if self._aot is not None and self._donate:
            # Donating checkpoint-restored buffers into a DESERIALIZED
            # executable corrupts the heap (jaxlib 0.4.37, multi-device CPU:
            # the restore path's externally-owned buffers double-free under
            # donation; an in-process-compiled executable tolerates them).
            # An identity jit rewrites the state into fresh runtime-owned
            # buffers — one device-to-device copy, same shardings, paid only
            # on the restore-then-warm-rejoin path that hits the bug.
            state = jax.jit(lambda t: t)(state)
        self.state = state
        logger.info("trainer state restored at step %d", step)
        return step


def fit_supervised(trainer, feed_factory, ckpt_manager, retry_policy=None,
                   max_steps=None, steps_per_call=1, profiler=None,
                   transfer_guard=None, publish=None):
    """Supervised :meth:`Trainer.fit_feed`: restore-latest, train with
    periodic checkpoints, and on a retryable failure back off, re-restore,
    and try again from the last saved step.

    Args:
      trainer: a :class:`Trainer` (its current state seeds attempt 1 when no
        checkpoint exists yet).
      feed_factory: zero-arg callable returning a FRESH feed per attempt —
        a feed whose consumer crashed mid-batch cannot be reused (its queue
        join state is undefined), so supervision owns feed construction.
      ckpt_manager: a :class:`~tensorflowonspark_tpu.checkpoint.CheckpointManager`;
        ``maybe_save`` runs after every dispatch and a final ``force`` save
        lands before returning.
      retry_policy: a :class:`~tensorflowonspark_tpu.fault.RetryPolicy`
        (default policy when None).  Only retryable failures re-enter the
        loop; user-code bugs re-raise immediately.
      max_steps / steps_per_call / transfer_guard: forwarded to
        :meth:`Trainer.fit_feed`.
      profiler: optional :class:`~tensorflowonspark_tpu.profiler.StepProfiler`;
        it is stepped once per dispatch and used as a context manager around
        every attempt, so an exception mid-capture stops the trace instead
        of leaking it into the retry's capture.
      publish: optional train-to-serve handoff spec
        (``fleet.publish_trained``): after the final checkpoint lands, the
        run's finiteness-validated params are exported and published to the
        model registry as a ``staging`` version — which a running canary
        controller walks to live with no operator action.  The registry
        entry rides the stats dict as ``stats["published"]``; a publish
        failure is logged and reported as ``stats["publish_error"]``
        without failing the (already successful) training run.  Chief-only.

    Returns the final fit stats dict.
    """
    from tensorflowonspark_tpu import fault as fault_mod
    from tensorflowonspark_tpu import node as node_mod

    policy = retry_policy or fault_mod.RetryPolicy()
    tracer = telemetry.get_tracer()

    # Warm-start default: the AOT executable store lives beside the
    # checkpoints, so a restarted/replacement supervisor that can see the
    # checkpoint root can also see the serialized executables (restore and
    # warm rejoin share one directory tree).  set_aot_cache is a no-op
    # when the Trainer ctor already chose a store.  Remote roots (gs://
    # etc.) skip the default: AOTCache is local-filesystem only, and a
    # store silently landing on node-local disk would LOOK shared while
    # never actually warming a rejoining node.
    from tensorflowonspark_tpu import checkpoint as ckpt_mod
    from tensorflowonspark_tpu import fsio

    if fsio.is_remote(ckpt_manager.directory):
        logger.info(
            "checkpoint root %s is remote; warm-start AOT store not "
            "auto-attached (pass Trainer(aot_cache=<shared local mount>) "
            "to opt in)", ckpt_manager.directory)
    else:
        try:
            trainer.set_aot_cache(ckpt_mod.aot_root(ckpt_manager.directory))
        except (OSError, ValueError) as e:  # read-only roots: optional
            logger.warning("AOT store beside checkpoints unavailable (%s); "
                           "training proceeds with plain JIT", e)

    def _emergency_save():
        # Preemption drain: land whatever progress exists before the process
        # unwinds.  Runs after the feed drain (registration order), so the
        # step counter is final.  force=True bypasses the interval gate.
        step = int(trainer.state.step)
        logger.warning("preemption: emergency checkpoint at step %d", step)
        ckpt_manager.maybe_save(step, trainer.state, force=True)
        ckpt_manager.wait_until_finished()

    # Chief-only: the emergency save runs inside a signal handler on ONE
    # preempted host — it cannot be a cross-host collective, and on
    # multi-host meshes a single host cannot write sharded state anyway.
    # (Single-host worlds, where chaos tests live, are exactly where this
    # works; multi-host preemption recovery rides the periodic saves.)
    if ckpt_manager.is_chief:
        node_mod.on_preemption(_emergency_save)
    def _on_steps(s):
        ckpt_manager.maybe_save(s, trainer.state)
        if profiler is not None:
            profiler.on_step_end()

    def _fit_once():
        return trainer.fit_feed(feed_factory(), max_steps=max_steps,
                                steps_per_call=steps_per_call,
                                on_steps=_on_steps,
                                transfer_guard=transfer_guard)

    # Poison-step rollbacks (remediator ``train_rollback`` command) are
    # control-plane signals, not failures: they re-enter the restore path
    # WITHOUT consuming a retry attempt or paying backoff.  The bound only
    # stops a pathological loop (e.g. every checkpoint quarantined and the
    # in-memory seed state itself poisoned).
    max_rollbacks = 4
    attempt = 0
    rollbacks = 0
    try:
        while True:
            restore_t0 = time.perf_counter()
            with tracer.span("train/restore", attempt=attempt + 1):
                restored = trainer.restore_latest(ckpt_manager, validate=True)
            trainer._goodput_recovery_us += int(
                (time.perf_counter() - restore_t0) * 1e6)
            if restored is not None:
                logger.info("supervised fit: resuming from checkpoint step %d",
                            restored)
            try:
                with tracer.span("train/fit_attempt", attempt=attempt + 1,
                                 restored_step=restored):
                    if profiler is not None:
                        # Context-manager form: stop() runs on the exception
                        # path too, so a failed attempt cannot leak an active
                        # trace into the next attempt's capture.
                        with profiler:
                            stats = _fit_once()
                    else:
                        stats = _fit_once()
                ckpt_manager.maybe_save(int(trainer.state.step), trainer.state,
                                        force=True)
                ckpt_manager.wait_until_finished()
                if publish and ckpt_manager.is_chief:
                    from tensorflowonspark_tpu import fleet as fleet_mod

                    try:
                        with tracer.span("train/publish"):
                            stats["published"] = fleet_mod.publish_trained(
                                publish, trainer.state.params,
                                int(trainer.state.step))
                        logger.info(
                            "supervised fit: published %s@%s to registry",
                            stats["published"]["model"],
                            stats["published"]["version"])
                    except Exception as e:
                        # the training run succeeded; a handoff failure is
                        # reported, not raised
                        logger.warning("train-to-serve publish failed",
                                       exc_info=True)
                        stats["publish_error"] = repr(e)
                return stats
            except fault_mod.PoisonRollback as rb:
                rollbacks += 1
                if rollbacks > max_rollbacks:
                    raise
                logger.warning(
                    "poison rollback %d/%d at host step %s: restoring last "
                    "VALID checkpoint (poisoned steps quarantined as "
                    "<step>.corrupt)", rollbacks, max_rollbacks, rb.step)
                # Loop straight back to restore_latest(validate=True): it
                # walks newest-first, quarantines every checkpoint that
                # fails validation, and restores the last valid one.
            except Exception as e:
                attempt += 1
                if (not policy.is_retryable(e)
                        or attempt >= policy.max_attempts):
                    raise
                delay = policy.backoff(attempt - 1)
                logger.warning(
                    "supervised fit attempt %d/%d failed (%s: %s); restoring "
                    "latest checkpoint and retrying in %.1fs", attempt,
                    policy.max_attempts, type(e).__name__, e, delay)
                tracer.instant("train/retry", attempt=attempt,
                               delay_secs=delay, error=repr(e))
                time.sleep(delay)
                # Backoff is pure recovery wall time: the devices sit idle.
                trainer._goodput_recovery_us += int(delay * 1e6)
    finally:
        node_mod.remove_preemption_callback(_emergency_save)
