#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the full
width of the models the repo supports, and checks what comes out:

1. ``feed``    the MNIST CNN fed through ``cluster.run(InputMode.SPARK)`` ->
               ``c.train`` -> shm ring -> ``DataFeed`` -> ``ShardedFeed`` ->
               ``Trainer.fit_feed(steps_per_call=K)``; the loss must fall.
2. ``resnet``  ResNet-50 v1.5 ([3,4,6,3], 224x224, bf16, batch 256) through
               ``examples/resnet/resnet_imagenet.py``'s ``main_fun`` on its file
               branch (predecoded shards -> ``FileFeed`` -> ``ShardedFeed`` ->
               ``fit_feed``, crop and flip on the device), then an export.
3. ``flash``   the transformer LM (8 layers, d_model 1024, 16x64 heads, seq
               1024, vocab 32,000, batch 8, bf16) with ``attention="flash"``:
               the kernel against a reference on a small input, the compiled
               step's text, and the loss against ``attention="full"`` held
               to the plain contraction (on a TPU it takes the kernels too).
4. ``serve``   the ResNet export behind one ``inference_cli --serve`` replica,
               answering ``gateway.ServingClient`` requests of mixed batch size.
5. ``direct``  the same inputs through ``serving.ModelServer(...).predict_feed``
               in a process of its own; the replica's answers must agree.

``--chips 4`` runs instead the two four-chip phases: ``mesh4`` (one executor
owning all four chips: the LM on ``{"data": 4}`` and ``{"data": 2, "tensor":
2}`` against a one-device mesh) and ``pinned4`` (four executors, one chip
each by ``device_info.pin_chips``: four one-chip worlds at the same time,
whose joining into one world ``ctx.initialize_distributed()`` refuses).

One process for each chip: this process never imports jax.  Every phase runs in
a child of its own, whose executor (or replica) is the process that opens the
chip and prints what it found there; the chip changes hands between phases.
All of them share one persistent compile cache: ``JAX_COMPILATION_CACHE_DIR``
where it is set, else ``<checkout>/.jax_cache``.

The last line of standard output is one JSON object, ``{"ok": ..., "device":
{"platform", "kind", "count"}}``; the exit code is 0 only when every phase
passed on a TPU.
"""

import argparse
import contextlib
import glob
import json
import os
import queue
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
RESNET_EXAMPLE = os.path.join(ROOT, "examples", "resnet")

ONE_CHIP_PHASES = ("feed", "resnet", "flash", "serve", "direct")
FOUR_CHIP_PHASES = ("mesh4", "pinned4")

#: The whole run must end inside the chip check's 1200 s, compiles included.
DEADLINE_SECS = 1150
PHASE_TIMEOUT_SECS = {"feed": 360, "resnet": 600, "flash": 600, "serve": 480,
                      "direct": 300, "mesh4": 900, "pinned4": 240}

#: What each phase runs at.  These are the sizes the chip check names; the
#: tests call the phase functions with tiny ones.
SIZES = {
    "feed": {"rows": 60000, "batch": 1024, "epochs": 4, "steps_per_call": 8,
             "log_steps": 20},
    "resnet": {"images": 512, "batch": 256, "image_size": 224,
               "store_px": 256, "steps_per_call": 4, "train_steps": 16,
               "epochs": 10, "blocks_per_stage": None},
    "flash": {"layers": 8, "heads": 16, "head_dim": 64, "seq": 1024,
              "vocab": 32000, "batch": 8, "steps": 6},
    "serve": {"requests": 36, "max_batch": 8},
    "direct": {"requests": 36, "max_batch": 8},
    "mesh4": {"layers": 8, "heads": 16, "head_dim": 64, "seq": 1024,
              "vocab": 32000, "batch": 8, "steps": 4, "devices": 4},
    "pinned4": {"devices": 4},
}

#: |loss(flash) - loss(full)| after the last step, relative to the loss: both
#: run bf16 matmuls with fp32 softmax statistics and differ by rounding only
#: (observed on the v5e: 2e-6).
FLASH_LOSS_RTOL = 1e-3
#: Sharded against one-device loss: the same program up to reduction order
#: (observed on four v5e chips: 1e-5 data-parallel, 1.3e-4 with tensor
#: parallelism).
MESH_LOSS_RTOL = 2e-3
#: Kernel against the fp32 reference on a small bf16 input, values and grads,
#: by ``_deviation`` (observed on the v5e: at most 0.009).
KERNEL_TOL = 3e-2
#: Replica against in-process predict: the same program on the same chip.
SERVE_TOL = 1e-3


class SmokeError(Exception):
    """A check of the smoke did not hold."""


# ---------------------------------------------------------------------------
# In the process that holds the chip
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _reporting(args, phase):
    """Collect one chip-holding process's findings, print them as one line
    and leave them as JSON where the phase's driver looks; a failure is
    written down the same way and raised again."""
    report = {"phase": phase, "ok": False, "pid": os.getpid()}
    try:
        yield report
        report["ok"] = True
    except BaseException:
        report["error"] = traceback.format_exc()
        raise
    finally:
        print("chip_smoke[{}] {}".format(phase, json.dumps(
            report, default=float)), flush=True)
        tmp = "{}.tmp.{}".format(args.result_path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(report, f, default=float)
        os.replace(tmp, args.result_path)


class _CompileClock(object):
    """Seconds this process spent tracing, lowering and compiling (or reading
    a compiled program back from the persistent cache), from jax's own
    monitoring events — so that compile time is told apart from run time."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring

        self.secs = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.secs += duration


def _open_device(report, platform, min_devices=1):
    """The process's first touch of the device, written into the report.
    Refuses any platform but the one the phase was started for: a run that
    found no chip fails here rather than measuring the CPU."""
    import jax

    from tensorflowonspark_tpu import compilecache, metrics

    t0 = time.perf_counter()
    devices = jax.devices()
    info = report["device"] = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "open_secs": round(time.perf_counter() - t0, 3),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir}
    if info["platform"] != platform:
        raise SmokeError("this phase needs platform {!r} but JAX found {}"
                         .format(platform, info))
    if info["count"] < min_devices:
        raise SmokeError("this phase needs {} devices but JAX found {}"
                         .format(min_devices, info))
    if platform == "tpu":
        # raises for a device_kind the peak table has no row for
        info["peak_flops"] = metrics.peak_flops_per_device()
    placed = os.environ.get(compilecache.JAX_CACHE_DIR_ENV)
    if placed and os.path.abspath(placed) != info["compile_cache_dir"]:
        raise SmokeError("JAX_COMPILATION_CACHE_DIR={} but the process "
                         "caches in {}".format(placed,
                                               info["compile_cache_dir"]))


def _cache_counts():
    from tensorflowonspark_tpu import compilecache

    return {"hit": compilecache.stats.cache_hit,
            "miss": compilecache.stats.cache_miss}


def _deviation(got, ref):
    """Largest |got - ref| / (1 + |ref|): absolute where the reference is
    small, relative where it is large."""
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref) / (1.0 + np.abs(ref))))


def _dispatch_probe(n=200):
    """Median wall microseconds of one tiny jitted dispatch, completion
    awaited: the per-dispatch constant that steps_per_call amortizes."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8, 128), jnp.float32)
    f(x).block_until_ready()
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    return round(times[len(times) // 2] * 1e6, 1)


def _window_rates(trainer):
    """Steps per second of each closed TimeHistory window (device-synced at
    its boundaries), in order."""
    log = trainer.history.timestamp_log
    return [round((s1 - s0) / (t1 - t0), 3)
            for (s0, t0), (s1, t1) in zip(log, log[1:]) if t1 > t0]


def feed_main(args, ctx):
    """MNIST CNN fed uint8 rows from the cluster's columnar data plane.  In
    SPARK mode this runs in a child forked from the executor shell."""
    with _reporting(args, "feed") as report:
        from tensorflowonspark_tpu import device_info

        # the fork copied the shell's memory: a backend found here is one the
        # shell had opened before it forked, and the chip would have two owners
        report["shell_backend_at_fork"] = device_info.backends_initialized()
        if report["shell_backend_at_fork"]:
            raise SmokeError("the executor shell had initialised a JAX "
                             "backend before forking the user function")
        clock = _CompileClock()
        _open_device(report, args.platform)

        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from tensorflowonspark_tpu import shmring
        from tensorflowonspark_tpu import train as train_mod
        from tensorflowonspark_tpu.models import mnist as mnist_mod
        from tensorflowonspark_tpu.parallel import infeed, mesh as mesh_mod

        report["dispatch_us_median"] = _dispatch_probe()
        ctx.initialize_distributed()
        mesh = mesh_mod.build_mesh()
        model = mnist_mod.build_mnist(dtype="bfloat16")
        params = model.init(jax.random.PRNGKey(args.seed),
                            jnp.zeros((1, 28, 28, 1)))["params"]
        base_loss = mnist_mod.loss_fn(model)

        def loss(params, batch, mask):
            # uint8 pixels -> bf16 in [0,1] on the device: the feed carries
            # one byte a pixel
            batch = dict(batch)
            batch["image"] = batch["image"].astype(jnp.bfloat16) / 255.0
            return base_loss(params, batch, mask)

        trainer = train_mod.Trainer(
            loss, params, optax.sgd(0.01, momentum=0.9), mesh=mesh,
            compute_dtype=None, batch_size=args.batch,
            log_steps=args.log_steps)

        # Compile both programs the run uses (the K-step scan group and the
        # single step) on zero batches shaped and sharded like the fed ones,
        # so that compile time and run time are told apart.
        k = args.steps_per_call
        t0 = time.perf_counter()
        shard = mesh_mod.batch_sharding(mesh)
        scan_shard = mesh_mod.scan_batch_sharding(mesh)
        trainer.step(
            {"image": jax.device_put(
                np.zeros((args.batch, 28, 28, 1), np.uint8), shard),
             "label": jax.device_put(
                 np.zeros((args.batch,), np.int32), shard)},
            jax.device_put(np.ones((args.batch,), np.float32), shard))
        trainer.multi_step(
            {"image": jax.device_put(
                np.zeros((k, args.batch, 28, 28, 1), np.uint8), scan_shard),
             "label": jax.device_put(
                 np.zeros((k, args.batch), np.int32), scan_shard)},
            jax.device_put(np.ones((k, args.batch), np.float32), scan_shard))
        jax.block_until_ready(trainer.state)
        report["warmup_secs"] = round(time.perf_counter() - t0, 3)
        trainer.reset_history()

        feed = ctx.get_data_feed(train_mode=True)
        sharded = infeed.ShardedFeed(
            feed, mesh, args.batch,
            transform=lambda cols: {
                "image": cols[0].reshape(-1, 28, 28, 1),
                "label": cols[1].astype(np.int32)})
        first_window = []

        def on_steps(steps_done):
            # the loss of the first closed window; its boundary already read
            # the value back
            value = trainer.history.last_synced_value
            if value is not None and not first_window:
                first_window.append(float(np.mean(value)))

        # a SPARK-mode feed sends no end-of-data, so the budget is whole
        # K-groups only (a partial last group would wait for ever)
        whole_groups = (args.max_steps // k) * k
        compile_before = clock.secs
        t0 = time.perf_counter()
        stats = trainer.fit_feed(
            sharded, max_steps=int(trainer.state.step) + whole_groups,
            steps_per_call=k, on_steps=on_steps)
        report["run_secs"] = round(time.perf_counter() - t0, 3)
        report["compile_secs"] = round(clock.secs, 3)
        report["compile_secs_in_run"] = round(clock.secs - compile_before, 3)
        report["steps"] = stats["global_steps"]
        report["steps_per_sec_windows"] = _window_rates(trainer)
        report["images_per_sec"] = round(stats["avg_exp_per_second"], 1)
        report["loss_first"] = first_window[0] if first_window else None
        report["loss_last"] = stats.get("loss")
        report["megastep"] = stats["megastep"]
        report["compile_cache"] = _cache_counts()

        wire = {key: n for key, n in feed.counters_snapshot().items()
                if key.startswith("wire_")}
        report["transport"] = dict(wire, **shmring.counters_snapshot())
        report["native_ring"] = shmring.available()
        carried = wire.get("wire_colv1", 0) + wire.get("wire_pickle", 0)
        took = (report["transport"]["ring_reads"]
                + report["transport"]["ring_peeks"])
        if not report["native_ring"] or wire.get("wire_queue") or not (
                carried and took):
            raise SmokeError("the rows did not travel through the native "
                             "shm ring: {}".format(report["transport"]))
        if report["steps"] < whole_groups:
            raise SmokeError("ran {} steps of {}".format(
                report["steps"], whole_groups))
        first, last = report["loss_first"], report["loss_last"]
        if first is None or not np.isfinite([first, last]).all() \
                or not last < first:
            raise SmokeError("loss must be finite and fall: first {} last {}"
                             .format(first, last))


def resnet_main(args, ctx):
    """ResNet-50 through the example's own ``main_fun``, file branch."""
    with _reporting(args, "resnet") as report:
        clock = _CompileClock()
        _open_device(report, args.platform)

        import numpy as np

        sys.path.insert(0, RESNET_EXAMPLE)
        import resnet_imagenet

        t0 = time.perf_counter()
        stats = resnet_imagenet.main_fun(args, ctx)
        wall = time.perf_counter() - t0
        report["wall_secs"] = round(wall, 3)
        report["compile_secs"] = round(clock.secs, 3)
        # what is left of main_fun's wall: building the model, filling the
        # feed's buffers, the steps, the export
        report["run_secs"] = round(wall - clock.secs, 3)
        report["steps"] = stats["global_steps"]
        report["steps_per_sec"] = round(
            stats["global_steps"] / (wall - clock.secs), 3)
        report["loss"] = stats.get("loss")
        report["megastep"] = stats["megastep"]
        report["infeed"] = {key: v for key, v in stats["overlap"].items()
                            if key.startswith(("infeed_", "goodput_"))}
        report["compile_cache"] = _cache_counts()
        with open(os.path.join(args.export_dir, "export.json")) as f:
            descriptor = json.load(f)
        report["export"] = {
            "model_config": descriptor["model_config"],
            "variables": descriptor.get("variables"),
            "stablehlo": descriptor.get("stablehlo")}
        if report["loss"] is None or not np.isfinite(report["loss"]):
            raise SmokeError("loss is not finite: {}".format(report["loss"]))
        if report["steps"] < 2 * args.steps_per_call \
                or stats["megastep"]["steps_per_call_last"] != \
                args.steps_per_call:
            raise SmokeError("fewer than two K-groups ran: {}".format(
                report["megastep"]))
        if "tpu" not in (descriptor.get("stablehlo") or {}).get(
                "platforms", []):
            raise SmokeError("the export has no StableHLO artifact lowered "
                             "for tpu: {}".format(report["export"]))


def _lm_tokens(seed, batch, seq, vocab):
    """Token rows a model can learn from quickly: arithmetic progressions
    over a small alphabet (start and stride drawn from the seed)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    alphabet = min(vocab, 257)
    start = rng.integers(0, alphabet, (batch, 1))
    stride = rng.integers(1, 5, (batch, 1))
    return ((start + stride * np.arange(seq)[None, :]) % alphabet).astype(
        np.int32)


def _lm_trainer(args, mesh, attention, param_sharding=None):
    """(trainer, batch, mask) for the LM at ``args``' widths on ``mesh``;
    the same seed gives the same weights whatever the attention kind or
    the mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    def build(kind):
        return transformer.build_transformer(
            vocab_size=args.vocab, num_layers=args.layers,
            num_heads=args.heads, head_dim=args.head_dim,
            max_seq_len=args.seq, attention=kind, mesh=mesh,
            dtype="bfloat16")

    tokens = _lm_tokens(args.seed, args.batch, args.seq, args.vocab)
    # parameters do not depend on the attention kind
    params = build("full").init(jax.random.PRNGKey(args.seed),
                                jnp.asarray(tokens[:1]))["params"]
    optimizer = optax.adam(1e-3)
    if param_sharding is not None:
        abstract = jax.eval_shape(
            lambda p: train_mod.TrainState(
                jnp.zeros((), jnp.int32), p, optimizer.init(p)), params)
        param_sharding = param_sharding(abstract, mesh)
    trainer = train_mod.Trainer(
        transformer.loss_fn(build(attention)), params, optimizer, mesh=mesh,
        compute_dtype=jnp.bfloat16, batch_size=args.batch, log_steps=2,
        param_sharding=param_sharding)
    batch = {"tokens": jax.device_put(
        tokens, mesh_mod.batch_sharding(mesh, extra_dims=1))}
    mask = jax.device_put(np.ones((args.batch,), np.float32),
                          mesh_mod.batch_sharding(mesh))
    return trainer, batch, mask


def _lm_run(trainer, batch, mask, steps):
    """``steps`` Trainer steps on one resident batch; returns the losses,
    the seconds the first step took (compiles included) and the steps per
    second of the rest."""
    t0 = time.perf_counter()
    losses = [float(trainer.step(batch, mask)[0])]
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        losses.append(float(trainer.step(batch, mask)[0]))
    rest = time.perf_counter() - t0
    return losses, round(first, 3), round((steps - 1) / rest, 3)


def _drop(trainer):
    """Free a finished trainer's device memory now: its jitted closures hold
    it in a reference cycle that only the collector breaks."""
    import gc

    trainer.state = None
    gc.collect()


def _step_text(trainer, batch, mask):
    """Text of the compiled train step, as the chip's compiler left it."""
    return trainer._train_step.lower(trainer.state, batch,
                                     mask).compile().as_text()


def _kernel_parity(seed):
    """The flash kernel against the fp32 reference contraction on a small
    bf16 input, values and gradients; returns the largest deviations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.ops import flash_attention
    from tensorflowonspark_tpu.parallel import ring

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (2, 256, 4, 64)
    q, k, v, w = (jax.random.normal(key, shape, jnp.float32) for key in keys)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def flash(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return (out.astype(jnp.float32) * w).sum(), out

    def reference(q, k, v):
        with jax.default_matmul_precision("highest"):
            out = ring.reference_attention(
                *(x.astype(jnp.float32) for x in (q, k, v)), causal=True)
        return (out * w).sum(), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        flash, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    worst = {}
    for name, got, ref in zip(("out", "dq", "dk", "dv"),
                              (out,) + tuple(grads),
                              (want,) + tuple(want_grads)):
        if got.shape != ref.shape or not np.isfinite(
                np.asarray(got, np.float32)).all():
            raise SmokeError("flash {}: bad shape or values".format(name))
        worst[name] = _deviation(got, ref)
        if worst[name] > KERNEL_TOL:
            raise SmokeError("flash {} deviates from the reference by {} "
                             "(tolerance {})".format(name, worst[name],
                                                     KERNEL_TOL))
    return worst


def flash_main(args, ctx):
    """The LM with the flash kernel on its path, against full attention."""
    with _reporting(args, "flash") as report:
        clock = _CompileClock()
        _open_device(report, args.platform)

        from tensorflowonspark_tpu.parallel import mesh as mesh_mod

        ctx.initialize_distributed()
        mesh = mesh_mod.build_mesh()
        report["kernel_parity"] = _kernel_parity(args.seed)
        # the reference side is the plain contraction: on a TPU "full" takes
        # the kernels too wherever the row tiles (the one rule of
        # ops/flash_attention.py), which would hold kernels against kernels
        import importlib

        importlib.import_module(
            "tensorflowonspark_tpu.ops.flash_attention"
        ).full_attention_block = lambda q, k, v, mesh=None: None
        for attention in ("flash", "full"):
            trainer, batch, mask = _lm_trainer(args, mesh, attention)
            if args.platform == "tpu":
                # compiled or plain, by the step's own text: not by trust in
                # a default or in the steering above
                calls = _step_text(trainer, batch, mask).count(
                    "tpu_custom_call")
                if attention == "flash":
                    report["tpu_custom_calls"] = calls
                    right = calls >= 3 * args.layers
                else:
                    right = calls == 0
                if not right:
                    raise SmokeError(
                        "the compiled {} step holds {} tpu_custom_call: "
                        "flash wants {} (3 kernels a layer), full none (the "
                        "plain contraction)".format(attention, calls,
                                                    3 * args.layers))
            losses, first, rate = _lm_run(trainer, batch, mask, args.steps)
            report[attention] = {"losses": losses, "first_step_secs": first,
                                 "steps_per_sec": rate}
            _drop(trainer)
        report["compile_secs"] = round(clock.secs, 3)
        report["compile_cache"] = _cache_counts()
        _check_losses(report["flash"]["losses"], report["full"]["losses"],
                      FLASH_LOSS_RTOL, "flash", "full")


def _check_losses(got, want, rtol, got_name, want_name):
    import numpy as np

    if not np.isfinite(got).all() or not np.isfinite(want).all():
        raise SmokeError("losses are not finite: {} {}".format(got, want))
    if abs(got[-1] - want[-1]) > rtol * abs(want[-1]):
        raise SmokeError(
            "loss after {} steps: {} {} against {} {} (rtol {})".format(
                len(got), got_name, got[-1], want_name, want[-1], rtol))


def direct_main(args):
    """The export through ``ModelServer.predict_feed`` in this process, on
    the inputs the replica answered; the answers must agree."""
    with _reporting(args, "direct") as report:
        clock = _CompileClock()
        _open_device(report, args.platform)

        import numpy as np

        from tensorflowonspark_tpu import compilecache, serving

        compilecache.configure(register_feed=False)
        server = serving.ModelServer(args.export_dir, args.max_batch)
        with np.load(args.answers_path) as answers:
            worst = 0.0
            t0 = time.perf_counter()
            for i, x in enumerate(_serve_inputs(args)):
                got = server.predict_feed({"image": x}, len(x))["output"]
                want = answers["answer_%d" % i]
                if got.shape != want.shape or not np.isfinite(got).all():
                    raise SmokeError("request {}: shape {} against {}, or "
                                     "values not finite".format(
                                         i, got.shape, want.shape))
                worst = max(worst, _deviation(got, want))
        report["wall_secs"] = round(time.perf_counter() - t0, 3)
        report["compile_secs"] = round(clock.secs, 3)
        report["compile_cache"] = _cache_counts()
        report["from_stablehlo"] = server.from_stablehlo
        report["worst_deviation"] = worst
        _require_stablehlo(server.from_stablehlo, server.stablehlo_fallback)
        if worst > SERVE_TOL:
            raise SmokeError("the replica's answers deviate from in-process "
                             "predict by {} (tolerance {})".format(
                                 worst, SERVE_TOL))


def _require_stablehlo(from_stablehlo, fallback):
    """An artifact exported on the chip is served as that artifact on the
    chip; the registry rebuild taking over is a failure here."""
    if not from_stablehlo:
        raise SmokeError("the export's StableHLO artifact is not what "
                         "serves: {}".format(fallback))


def mesh4_main(args, ctx):
    """One executor owning every chip: the LM on two four-device meshes
    against a one-device mesh built from the first chip."""
    with _reporting(args, "mesh4") as report:
        clock = _CompileClock()
        _open_device(report, args.platform, args.devices)

        import jax

        from tensorflowonspark_tpu.parallel import mesh as mesh_mod, tp

        ctx.initialize_distributed()
        layouts = [
            ("one", mesh_mod.build_mesh(devices=jax.devices()[:1]), None),
            ("data4", mesh_mod.build_mesh({"data": args.devices}), None),
            ("data2_tensor2", mesh_mod.build_mesh(
                {"data": args.devices // 2, "tensor": 2}),
             tp.tp_param_shardings),
        ]
        for name, mesh, param_sharding in layouts:
            trainer, batch, mask = _lm_trainer(args, mesh, "flash",
                                               param_sharding)
            found = {}
            if name != "one":
                text = _step_text(trainer, batch, mask)
                found["collectives"] = {
                    op: text.count(op + "(") + text.count(op + "-start(")
                    for op in ("all-reduce", "all-gather", "reduce-scatter")}
                if args.platform == "tpu":
                    found["tpu_custom_calls"] = text.count("tpu_custom_call")
            losses, first, rate = _lm_run(trainer, batch, mask, args.steps)
            found.update(losses=losses, first_step_secs=first,
                         steps_per_sec=rate)
            if name != "one":
                found.update(_spread(trainer, args.devices,
                                     tensor=param_sharding is not None))
            report[name] = found
            _drop(trainer)
        report["compile_secs"] = round(clock.secs, 3)
        report["compile_cache"] = _cache_counts()
        for name, _, _ in layouts[1:]:
            _check_losses(report[name]["losses"], report["one"]["losses"],
                          MESH_LOSS_RTOL, name, "one")
            if not any(report[name]["collectives"].values()):
                raise SmokeError("no collective in {}'s compiled step"
                                 .format(name))


def _spread(trainer, n_devices, tensor):
    """Proof that the work is on every chip: memory in use per device, and
    the devices that hold the shards of the largest parameter."""
    import jax

    found = {}
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    found["bytes_in_use"] = in_use
    if all(b is not None for b in in_use):  # the CPU backend keeps no stats
        if min(in_use) <= 0 or max(in_use) > 2 * min(in_use):
            raise SmokeError("device memory is not spread: {}".format(in_use))
    leaves = jax.tree_util.tree_leaves(trainer.state.params)
    big = max(leaves, key=lambda x: x.size)
    shards = big.addressable_shards
    found["param_shard_devices"] = sorted(s.device.id for s in shards)
    found["param_shard_shape"] = list(shards[0].data.shape)
    found["param_shape"] = list(big.shape)
    if len(set(found["param_shard_devices"])) != n_devices:
        raise SmokeError("the parameter's shards are on devices {}, not on "
                         "{} distinct ones".format(
                             found["param_shard_devices"], n_devices))
    if tensor and found["param_shard_shape"] == found["param_shape"]:
        raise SmokeError("the largest parameter is not tensor-sharded")
    return found


def pinned4_main(args, ctx):
    """The framework's other layout: one executor for each chip, pinned by
    ``device_info.pin_chips`` and joined by ``initialize_distributed``.
    Either the executors form one world (then a collective over it must
    add up), or each is a world of its own chip, all held at the same time,
    and the join was refused with a clear error; a hang is neither."""
    stem = args.result_path[:-len(".json")]
    args.result_path = "{}.{}.json".format(stem, ctx.executor_id)
    with _reporting(args, "pinned4") as report:
        from tensorflowonspark_tpu import device_info

        report["pinned"] = device_info.pin_chips(
            ctx.executor_id, 1, total_chips=args.devices)
        try:
            ctx.initialize_distributed()
        except RuntimeError as e:
            report["join_refused"] = str(e)

        import jax
        import jax.numpy as jnp

        _open_device(report, args.platform)
        report["process_count"] = jax.process_count()
        report["local_devices"] = [str(d) for d in jax.local_devices()]
        if jax.local_device_count() != 1:
            raise SmokeError("pinned to one chip but {} local devices"
                             .format(jax.local_device_count()))
        # hold the chip until every executor holds its own
        open("{}.{}.holds".format(stem, ctx.executor_id), "w").close()
        deadline = time.time() + 90
        while len(glob.glob(stem + ".*.holds")) < args.devices:
            if time.time() > deadline:
                raise SmokeError("the executors did not hold their chips at "
                                 "the same time")
            time.sleep(0.2)
        if "join_refused" in report:
            if jax.device_count() != 1:
                raise SmokeError("the join was refused in a world of {} "
                                 "devices".format(jax.device_count()))
            report["layout"] = "independent one-chip worlds, join refused"
            total = float(jax.jit(lambda x: x.sum())(jnp.ones((8, 128))))
            if total != 8 * 128:
                raise SmokeError("the pinned chip computed {}".format(total))
            return
        if jax.device_count() != args.devices:
            raise SmokeError("joined, yet {} global devices, not {}".format(
                jax.device_count(), args.devices))
        report["layout"] = "one world"
        # one collective across the processes: every process adds its index
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(jax.devices(), ("data",))
        x = jax.make_array_from_process_local_data(
            NamedSharding(mesh, PartitionSpec("data")),
            jnp.full((1,), float(jax.process_index())))
        total = float(jax.jit(lambda x: x.sum())(x))
        if total != sum(range(args.devices)):
            raise SmokeError("the all-reduce over the processes gave {}"
                             .format(total))


# ---------------------------------------------------------------------------
# Phase drivers: they start the process that holds the chip and stay off JAX
# ---------------------------------------------------------------------------

def _namespace(name, seed, workdir, sizes, platform, **extra):
    return argparse.Namespace(
        seed=seed, platform=platform,
        result_path=os.path.join(workdir, name + ".json"),
        **dict(SIZES[name], **dict(sizes or {}, **extra)))


def _read_result(path):
    with open(path) as f:
        return json.load(f)


def _run_cluster(main_fun, args, input_mode, partitions=None, num_epochs=1,
                 num_executors=1):
    """Run ``main_fun`` on a LocalBackend cluster, as the examples do;
    returns once the chip-holding process has left its result and the
    cluster is down."""
    from tensorflowonspark_tpu import backend, cluster

    b = backend.LocalBackend(num_executors)
    try:
        c = cluster.run(b, main_fun, args, num_executors=num_executors,
                        input_mode=input_mode)
        if partitions is not None:
            c.train(partitions, num_epochs=num_epochs, chunk_size=2048)
            # the worker leaves its result shortly after its step budget;
            # wait for it before the shutdown poisons the queues
            deadline = time.time() + 600
            while not os.path.exists(args.result_path):
                if time.time() > deadline:
                    raise SmokeError("no result from the worker at "
                                     + args.result_path)
                time.sleep(0.2)
        c.shutdown(grace_secs=2)
    finally:
        b.stop()


def phase_feed(seed, workdir, sizes=None, platform="tpu"):
    import numpy as np

    from tensorflowonspark_tpu import backend, cluster

    args = _namespace("feed", seed, workdir, sizes, platform)
    args.max_steps = (args.rows * args.epochs) // args.batch
    # ten class templates with their low six bits scrambled: rows a CNN
    # learns in a few hundred steps, made in bulk
    rng = np.random.default_rng(seed)
    templates = rng.integers(0, 256, (10, 784), np.uint8)
    labels = rng.integers(0, 10, (args.rows,))
    noise = rng.integers(0, 64, (4096, 784), np.uint8)
    images = templates[labels] ^ noise[rng.integers(0, 4096, (args.rows,))]
    data = [(images[i], int(labels[i])) for i in range(args.rows)]
    _run_cluster(feed_main, args, cluster.InputMode.SPARK,
                 partitions=backend.partition(data, 8),
                 num_epochs=args.epochs)
    return _read_result(args.result_path)


def phase_resnet(seed, workdir, sizes=None, platform="tpu"):
    from tensorflowonspark_tpu import cluster

    sys.path.insert(0, RESNET_EXAMPLE)
    import imagenet_input
    import resnet_imagenet

    size = _namespace("resnet", seed, workdir, sizes, platform)
    jpeg_dir = os.path.join(workdir, "imagenet_jpeg")
    data_dir = os.path.join(workdir, "imagenet_raw")
    imagenet_input.write_synthetic_shards(
        jpeg_dir, num_examples=size.images, num_shards=4,
        image_size=size.store_px, seed=seed)
    imagenet_input.predecode_shards(
        sorted(glob.glob(os.path.join(jpeg_dir, "train-*"))), data_dir,
        store_px=size.store_px)
    argv = ["--cluster_size", "1", "--data_dir", data_dir, "--predecoded",
            "--stem", "s2d", "--dtype", "bfloat16",
            "--batch_size", str(size.batch),
            "--image_size", str(size.image_size),
            "--store_px", str(size.store_px),
            "--steps_per_call", str(size.steps_per_call),
            "--train_steps", str(size.train_steps),
            "--train_epochs", str(size.epochs),
            "--log_steps", str(size.steps_per_call),
            "--shuffle_buffer", str(size.images),
            "--export_dir", export_path(workdir)]
    if size.blocks_per_stage:
        argv += ["--blocks_per_stage", str(size.blocks_per_stage)]
    args = resnet_imagenet.build_parser().parse_args(argv)
    args.platform, args.result_path = platform, size.result_path
    _run_cluster(resnet_main, args, cluster.InputMode.FILES)
    return _read_result(args.result_path)


def export_path(workdir):
    return os.path.join(workdir, "resnet_export")


def _executor_phase(name, main_fun):
    """The driver of a phase that is one FILES-mode executor running
    ``main_fun`` at the phase's sizes."""
    def phase(seed, workdir, sizes=None, platform="tpu"):
        from tensorflowonspark_tpu import cluster

        args = _namespace(name, seed, workdir, sizes, platform)
        _run_cluster(main_fun, args, cluster.InputMode.FILES)
        return _read_result(args.result_path)

    return phase


def _serve_inputs(args):
    """The requests of the serving phases, from the seed: float32 images in
    batches of mixed size."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        n = int(rng.integers(1, args.max_batch + 1))
        yield rng.standard_normal(
            (n, args.image_size, args.image_size, 3)).astype(np.float32)


def _serving_namespace(name, seed, workdir, sizes, platform):
    args = _namespace(name, seed, workdir, sizes, platform,
                      export_dir=export_path(workdir),
                      answers_path=os.path.join(workdir, "serve_answers.npz"))
    with open(os.path.join(args.export_dir, "export.json")) as f:
        args.image_size = json.load(f)["input_signature"]["image"][1]
    return args


def _stdout_lines(process):
    """A queue of the process's standard output lines, None at its end."""
    lines = queue.Queue()

    def pump():
        for line in process.stdout:
            lines.put(line)
        lines.put(None)

    threading.Thread(target=pump, daemon=True).start()
    return lines


def phase_serve(seed, workdir, sizes=None, platform="tpu"):
    """One replica process serving the export; this process is its client."""
    import numpy as np

    from tensorflowonspark_tpu import gateway

    args = _serving_namespace("serve", seed, workdir, sizes, platform)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p))
    with _reporting(args, "serve") as report:
        t0 = time.perf_counter()
        replica = subprocess.Popen(
            [sys.executable, "-m", "tensorflowonspark_tpu.inference_cli",
             "--export_dir", args.export_dir, "--serve", "--port", "0",
             "--max-batch", str(args.max_batch)],
            stdout=subprocess.PIPE, text=True, env=env, cwd=workdir)
        try:
            lines = _stdout_lines(replica)
            ready = chip = None
            while chip is None:
                try:
                    line = lines.get(timeout=PHASE_TIMEOUT_SECS["serve"] - 120)
                except queue.Empty:
                    line = None
                if line is None:
                    raise SmokeError("the replica gave no report (exit code "
                                     "{})".format(replica.poll()))
                print(line.rstrip(), flush=True)
                if " ready on " in line:
                    ready = line.split(" ready on ")[1].split()[0]
                elif " report " in line:
                    chip = json.loads(line.split(" report ", 1)[1])
            report["replica"] = chip
            report["start_secs"] = round(time.perf_counter() - t0, 3)
            report["device"] = {"platform": chip["platform"],
                                "kind": chip["device_kind"],
                                "count": chip["device_count"]}
            if chip["platform"] != platform:
                raise SmokeError("the replica runs on {}, not on {}".format(
                    chip["platform"], platform))
            _require_stablehlo(chip["from_stablehlo"],
                               chip["stablehlo_fallback"])
            client = gateway.ServingClient(replicas=[ready], timeout=120.0)
            answers = {}
            t0 = time.perf_counter()
            for i, x in enumerate(_serve_inputs(args)):
                out = client.predict({"image": x}, len(x))["output"]
                if out.shape[0] != len(x) or not np.isfinite(out).all():
                    raise SmokeError(
                        "request {}: {} rows back for {}, or values not "
                        "finite".format(i, out.shape[0], len(x)))
                answers["answer_%d" % i] = out
            wall = time.perf_counter() - t0
            client.close()
            np.savez(args.answers_path, **answers)
            report["requests"] = len(answers)
            report["rows"] = int(sum(len(a) for a in answers.values()))
            report["requests_per_sec"] = round(len(answers) / wall, 2)
        finally:
            # the replica lets go of the chip before the next phase opens it
            replica.terminate()
            try:
                report["replica_exit"] = replica.wait(timeout=60)
            except subprocess.TimeoutExpired:
                replica.kill()
                report["replica_exit"] = "killed"
    return report


def phase_direct(seed, workdir, sizes=None, platform="tpu"):
    """This process opens the chip itself (no executor under it)."""
    args = _serving_namespace("direct", seed, workdir, sizes, platform)
    direct_main(args)
    return _read_result(args.result_path)


def phase_pinned4(seed, workdir, sizes=None, platform="tpu"):
    from tensorflowonspark_tpu import cluster

    args = _namespace("pinned4", seed, workdir, sizes, platform)
    failure = None
    try:
        _run_cluster(pinned4_main, args, cluster.InputMode.FILES,
                     num_executors=args.devices)
    except BaseException as e:  # each executor has left its own account
        failure = e
    results = [_read_result(path) for path in sorted(
        glob.glob(args.result_path[:-len(".json")] + ".*.json"))]
    report = {"phase": "pinned4", "executors": results,
              "ok": failure is None and len(results) == args.devices
              and all(r["ok"] for r in results)}
    if results:
        # the device line of the run counts the chips the executors held
        report["device"] = dict(results[0].get("device") or {},
                                count=sum(r["ok"] for r in results))
        report["layout"] = results[0].get("layout")
    with open(args.result_path, "w") as f:
        json.dump(report, f, default=float)
    if failure is not None:
        raise failure
    return report


PHASES = {"feed": phase_feed, "resnet": phase_resnet,
          "flash": _executor_phase("flash", flash_main),
          "serve": phase_serve, "direct": phase_direct,
          "mesh4": _executor_phase("mesh4", mesh4_main),
          "pinned4": phase_pinned4}


# ---------------------------------------------------------------------------
# The parent: builds, starts one child for each phase, reads what they left
# ---------------------------------------------------------------------------

def build_native():
    """Build the native libraries the feed uses from ``native/*.cc``, here
    and now: a stale or foreign binary lying in the tree proves nothing."""
    if shutil.which("g++") is None:
        raise SmokeError("g++ not found: the shm ring and the TFRecord codec "
                         "cannot be built")
    for name in ("libshmring.so", "libtfrecord.so"):
        path = os.path.join(ROOT, "native", name)
        if os.path.exists(path):
            os.remove(path)
    from tensorflowonspark_tpu import shmring, tfrecord

    if not shmring.available() or tfrecord._lib() is None:
        raise SmokeError("the native libraries did not build")


def _run_phase_child(name, seed, workdir, timeout):
    """One phase in a child and process group of its own, killed as a group
    when it overruns — and swept as a group when it ends, so that nothing it
    started outlives it.  Its standard output is relayed once it is over."""
    out_path = os.path.join(workdir, name + ".out")
    with open(out_path, "w") as out:
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--seed", str(seed),
             "--phase", name, "--workdir", workdir],
            stdout=out, cwd=ROOT, start_new_session=True)
        try:
            code = child.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            code = "timeout after {}s".format(int(timeout))
        finally:
            try:
                os.killpg(child.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            child.wait()
    with open(out_path) as out:
        sys.stdout.write(out.read())
    sys.stdout.flush()
    result_path = os.path.join(workdir, name + ".json")
    result = _read_result(result_path) if os.path.exists(result_path) else {}
    if code != 0:
        result["ok"] = False
        result.setdefault("error", "phase child ended with {}".format(code))
    return result


def run(seed, chips):
    """Run every phase; returns (ok, device)."""
    if not os.path.isdir(os.path.join(ROOT, "tensorflowonspark_tpu")):
        raise SmokeError("no tensorflowonspark_tpu package beside "
                         + os.path.abspath(__file__))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    print("chip_smoke: compile cache at {}".format(
        os.environ["JAX_COMPILATION_CACHE_DIR"]), flush=True)
    start = time.time()
    jax_before = "jax" in sys.modules
    build_native()
    print("chip_smoke: native libraries built from native/*.cc in {:.1f}s"
          .format(time.time() - start), flush=True)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    device, failed = None, []
    try:
        for name in (FOUR_CHIP_PHASES if chips == 4 else ONE_CHIP_PHASES):
            left = DEADLINE_SECS - (time.time() - start)
            t0 = time.time()
            result = _run_phase_child(
                name, seed, workdir, max(1, min(PHASE_TIMEOUT_SECS[name],
                                                left)))
            print("chip_smoke: phase {} {} in {:.1f}s".format(
                name, "passed" if result.get("ok") else "FAILED",
                time.time() - t0), flush=True)
            device = device or result.get("device")
            if not result.get("ok"):
                failed.append(name)
                print("chip_smoke: {} failed:\n{}".format(
                    name, result.get("error")), flush=True)
                if device is None or device["platform"] != "tpu":
                    break  # no chip: no later phase can pass either
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print("chip_smoke: failed phases: {}, wall {:.1f}s".format(
        failed or "none", time.time() - start), flush=True)
    if "jax" in sys.modules and not jax_before:
        raise SmokeError("the parent process imported jax")
    ok = not failed and device is not None and device["platform"] == "tpu"
    return ok, device


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="feeds every generator (data, weights, requests)")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-chip phases")
    # how this script starts itself once for each phase
    parser.add_argument("--phase", choices=sorted(PHASES),
                        help=argparse.SUPPRESS)
    parser.add_argument("--workdir", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.phase:
        PHASES[args.phase](args.seed, args.workdir)
        return 0
    ok, device = False, None
    try:
        ok, device = run(args.seed, args.chips)
    except Exception:
        traceback.print_exc()
    if device is not None:
        device = {key: device[key] for key in ("platform", "kind", "count")}
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
