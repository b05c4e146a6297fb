"""ResNet-50 v1.5 / ImageNet distributed training (reference
``examples/resnet/resnet_imagenet_main.py``).

ResNet-50 with ImageNet scale
constants (1,281,167 train images, 90 epochs, batch 256 — reference
``imagenet_preprocessing.py:46-49``, ``resnet_imagenet_main.py:271``),
piecewise LR decay with linear warmup (reference
``resnet_imagenet_main.py:37-71``), label smoothing + L2 weight decay
(reference ``resnet_imagenet_main.py:98-100,182-187`` fp16 analog is bf16
here), synthetic-data mode for benchmarking (reference
``common.py:315-363``), TimeHistory/MFU stats, periodic checkpoints, and
the FILES-mode cluster lifecycle.

Run (CPU mesh; tiny smoke):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/resnet/resnet_imagenet.py --cluster_size 2 \
        --use_synthetic_data --train_steps 2 --batch_size 16 --image_size 64

Run (one v5e chip, synthetic benchmark):
    python examples/resnet/resnet_imagenet.py --cluster_size 1 \
        --use_synthetic_data --train_steps 100 --batch_size 128
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

NUM_CLASSES = 1001      # reference uses 1001 (background class), resnet_model
NUM_IMAGES = 1281167    # reference imagenet_preprocessing.py:46-49
DEFAULT_IMAGE_SIZE = 224

# Reference LR schedule: 0.1 * batch/256 base, x0.1 at epochs 30, 60, 80,
# 5-epoch linear warmup (resnet_imagenet_main.py:37-71).
LR_BOUNDARY_EPOCHS = (30, 60, 80)
LR_DECAY = 0.1
WARMUP_EPOCHS = 5


def synthetic_imagenet(n, image_size, seed=13):
    """Learnable synthetic stand-in (reference synthetic input_fn,
    ``common.py:315-363``): class templates + noise."""
    import numpy as np

    rng = np.random.default_rng(seed)
    few_classes = min(NUM_CLASSES, 32)  # keep the template table small
    templates = rng.random((few_classes, image_size, image_size, 3)).astype("f")
    labels = rng.integers(0, few_classes, (n,))
    noise = rng.normal(0, 0.1, (n, image_size, image_size, 3)).astype("f")
    return (templates[labels] + noise).astype("float32"), labels.astype("int32")


def main_fun(args, ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import checkpoint
    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.models import resnet as resnet_mod
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    ctx.initialize_distributed()
    mesh = mesh_mod.build_mesh()
    size = args.image_size

    if not args.data_dir:
        images, labels = synthetic_imagenet(args.synthetic_examples, size)
        shard = slice(jax.process_index(), None, max(jax.process_count(), 1))
        images, labels = images[shard], labels[shard]

    # blocks_per_stage is the size knob (the reference's resnet_size):
    # None -> ResNet-50's [3,4,6,3]; 1 -> a 14-layer smoke model.
    model = resnet_mod.build_resnet50(num_classes=NUM_CLASSES,
                                      dtype=args.dtype,
                                      blocks_per_stage=args.blocks_per_stage,
                                      stem=args.stem)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, size, size, 3)), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    steps_per_epoch = max(NUM_IMAGES // args.batch_size, 1)
    total_steps = args.train_steps or steps_per_epoch * args.train_epochs
    base_lr = args.base_lr * args.batch_size / 256.0
    warmup_steps = min(WARMUP_EPOCHS * steps_per_epoch,
                       max(total_steps // 10, 1))
    boundaries_and_scales = {
        e * steps_per_epoch: LR_DECAY
        for e in LR_BOUNDARY_EPOCHS if e * steps_per_epoch < total_steps}
    schedule = optax.join_schedules(
        [optax.linear_schedule(0.0, base_lr, warmup_steps),
         optax.piecewise_constant_schedule(base_lr, boundaries_and_scales)],
        [warmup_steps])
    optimizer = optax.sgd(schedule, momentum=0.9)

    base_loss = resnet_mod.loss_fn(model, weight_decay=args.weight_decay,
                                   label_smoothing=args.label_smoothing)
    in_dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.data_dir:
        # TFRecord rows arrive uint8 (1 byte/pixel over the host->device
        # link); the reference's channel-mean normalization happens HERE,
        # inside the jitted step (imagenet_preprocessing.py equivalent).
        # Pre-decoded rows additionally carry their sampled crop/flip ints:
        # the crop itself runs on device too (ops.augment.crop_and_flip),
        # so the host never touches a pixel.
        import imagenet_input

        def loss(p, bs, batch, mask):
            from tensorflowonspark_tpu.ops import augment

            batch = dict(batch)
            img = batch.pop("image")
            if args.predecoded:
                img = augment.crop_and_flip(
                    img, batch.pop("cropx"), batch.pop("cropy"),
                    batch.pop("flip"), size)
            batch["image"] = imagenet_input.normalize_on_device(
                img, in_dtype)
            return base_loss(p, bs, batch, mask)
    else:
        loss = base_loss

    writer = None
    if args.log_dir and ctx.is_chief():
        from tensorflowonspark_tpu import summary

        writer = summary.SummaryWriter(args.log_dir)

    trainer = train_mod.Trainer(
        loss,
        params, optimizer, mesh=mesh, extra_state=batch_stats,
        compute_dtype=jnp.bfloat16 if args.dtype == "bfloat16" else None,
        batch_size=args.batch_size, log_steps=args.log_steps,
        summary_writer=writer)

    ckpt = None
    if args.model_dir:
        ckpt = checkpoint.CheckpointManager(
            ctx.absolute_path(args.model_dir),
            save_interval_steps=args.save_interval)

    prof = None
    if args.profile_steps:
        from tensorflowonspark_tpu import profiler

        prof = profiler.StepProfiler(
            args.profile_dir or "profile_logs", args.profile_steps)

    if args.data_dir:
        # Real ImageNet TFRecord shards: stream through data.FileFeed with
        # the reference's preprocessing (imagenet_input) and the same
        # device plane as SPARK mode (prefetch, consensus, K-step groups).
        from tensorflowonspark_tpu import data as data_mod
        from tensorflowonspark_tpu.datafeed import strip_scheme
        from tensorflowonspark_tpu.parallel import infeed
        import imagenet_input

        if args.predecoded:
            reader = imagenet_input.predecoded_reader(
                train=True, image_size=size, store_px=args.store_px,
                seed=jax.process_index(), device_crop=True)
            pattern = "train-*.raw"
        else:
            reader = imagenet_input.imagenet_reader(
                train=True, image_size=size, seed=jax.process_index())
            pattern = "train-*"
        files = data_mod.list_shards(
            strip_scheme(ctx.absolute_path(args.data_dir)), pattern=pattern)
        if args.decode_procs:
            # decode is CPU-bound: scale it across cores with worker
            # processes (the tf.data num_parallel_calls role)
            feed = data_mod.ProcessPoolFeed(
                files, row_reader=reader,
                shuffle_buffer=args.shuffle_buffer,
                num_epochs=args.train_epochs, num_procs=args.decode_procs)
        else:
            feed = data_mod.FileFeed(
                files, row_reader=reader,
                shuffle_buffer=args.shuffle_buffer,
                num_epochs=args.train_epochs,
                reader_threads=args.reader_threads,
                # decoded 224px uint8 rows are ~147 KB: bound the reader
                # queue (blocks of FileFeed.BLOCK rows) so it can't buffer
                # gigabytes
                queue_size=8)
        sharded = infeed.ShardedFeed(
            feed, mesh, args.batch_size,
            # generic passthrough: the predecoded path adds cropx/cropy/flip
            # int columns next to image/label
            transform=lambda cols: {
                k: np.asarray(v, np.int32 if k != "image" else None)
                for k, v in cols.items()})

        def on_steps(s):
            if ckpt:
                ckpt.maybe_save(s, trainer.state)
            if prof:
                # dispatch granularity: a K-step group counts as one hop
                prof.on_step_end()
                prof.on_step_begin()

        if prof:
            prof.on_step_begin()
        stats = trainer.fit_feed(sharded, max_steps=total_steps,
                                 steps_per_call=args.steps_per_call,
                                 on_steps=on_steps)
        if prof:
            prof.stop()
        _maybe_eval(args, ctx, mesh, model, trainer, size, in_dtype, stats)
        _finish(args, ctx, model, trainer, ckpt, int(trainer.state.step),
                size)
        return stats

    local_bs = mesh_mod.local_batch_size(mesh, args.batch_size)
    sharding = mesh_mod.batch_sharding(mesh)
    rng = np.random.default_rng(jax.process_index())
    mask_np = np.ones((local_bs,), np.float32)
    step = 0
    loss = aux = None
    while step < total_steps:
        order = rng.permutation(len(labels))
        for s in range(max(len(labels) // local_bs, 1)):
            idx = order[s * local_bs:(s + 1) * local_bs]
            if len(idx) < local_bs:
                break
            batch = {
                "image": jax.make_array_from_process_local_data(
                    sharding, images[idx]),
                "label": jax.make_array_from_process_local_data(
                    sharding, labels[idx]),
            }
            mask = jax.make_array_from_process_local_data(sharding, mask_np)
            if prof:
                prof.on_step_begin()
            loss, aux = trainer.step(batch, mask)
            if prof:
                prof.on_step_end()
            step += 1
            if ckpt:
                ckpt.maybe_save(step, trainer.state)
            if step >= total_steps:
                break

    if prof:
        prof.stop()
    trainer.history.on_train_end(loss)
    stats = trainer.history.log_stats(
        loss=float(loss), accuracy=float(aux["accuracy"]))
    _maybe_eval(args, ctx, mesh, model, trainer, size, in_dtype, stats)
    _finish(args, ctx, model, trainer, ckpt, step, size)
    return stats


def _maybe_eval(args, ctx, mesh, model, trainer, size, in_dtype, stats):
    """Run the exact validation top-1 when --eval_data_dir is set (works
    from both the synthetic and TFRecord train paths — e.g. evaluating a
    restored checkpoint against real validation shards)."""
    if args.eval_data_dir:
        acc = _evaluate(args, ctx, mesh, model, trainer, size, in_dtype)
        stats["eval_accuracy_top_1"] = acc
        print("eval accuracy: {:.4f}".format(acc))
        if trainer.summary_writer is not None:
            trainer.summary_writer.add_scalar(
                "eval_accuracy_top_1", acc, int(trainer.state.step))


def _evaluate(args, ctx, mesh, model, trainer, size, in_dtype):
    """Top-1 over the validation shards (reference ``eval_input_fn`` +
    ``accuracy_top_1``): each process reads its file shard with the eval
    transform (resize + center crop, BatchNorm running averages); the
    jitted sums run over the globally-sharded batch, so correct/total are
    already all-host totals (replicated on every process) — no further
    cross-host merge is needed."""
    import jax
    import numpy as np

    from tensorflowonspark_tpu import data as data_mod
    from tensorflowonspark_tpu.datafeed import strip_scheme
    from tensorflowonspark_tpu.parallel import infeed
    import imagenet_input

    feed = data_mod.FileFeed(
        data_mod.list_shards(
            strip_scheme(ctx.absolute_path(args.eval_data_dir)),
            pattern="validation-*"),
        row_reader=imagenet_input.imagenet_reader(
            train=False, image_size=size),
        reader_threads=args.reader_threads, queue_size=8)
    sharded = infeed.ShardedFeed(
        feed, mesh, args.batch_size,
        transform=lambda cols: {
            "image": np.asarray(cols["image"]),
            "label": np.asarray(cols["label"], np.int32)})

    def metric_fn(params, batch_stats, batch, mask):
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats},
            imagenet_input.normalize_on_device(batch["image"], in_dtype),
            train=False)
        correct = ((logits.argmax(-1) == batch["label"]) * mask).sum()
        return {"accuracy": correct}, mask.sum()

    # Trainer.evaluate: drain="all" exact evaluation (exhausted hosts step
    # zero-mask dummies, no validation row dropped), jitted per batch.
    return trainer.evaluate(sharded, metric_fn)["accuracy"]


def _finish(args, ctx, model, trainer, ckpt, step, size):
    """Final checkpoint + chief-only export (shared by the synthetic and
    TFRecord-streaming paths).  The export carries the BatchNorm running
    statistics beside the params (the model cannot be applied without them)
    and the serving fn as a StableHLO artifact."""
    import jax

    from tensorflowonspark_tpu import checkpoint

    if trainer.summary_writer is not None:
        trainer.summary_writer.close()
    if ckpt:
        ckpt.maybe_save(step, trainer.state, force=True)
        ckpt.wait_until_finished()
        ckpt.close()
    if args.export_dir and checkpoint.should_export(ctx):
        checkpoint.export_model(
            ctx.absolute_path(args.export_dir),
            jax.device_get(trainer.state.params), "resnet50",
            model_config={"num_classes": NUM_CLASSES, "dtype": args.dtype,
                          "blocks_per_stage": args.blocks_per_stage,
                          "stem": args.stem},
            input_signature={"image": [None, size, size, 3]},
            model=model,
            extra_variables={"batch_stats": trainer.state.extra})


def build_parser():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cluster_size", type=int, default=1)
    parser.add_argument("--batch_size", type=int, default=256,
                        help="global batch (reference default 256)")
    parser.add_argument("--train_epochs", type=int, default=90,
                        help="reference default 90 epochs")
    parser.add_argument("--train_steps", type=int, default=None,
                        help="overrides train_epochs when set")
    parser.add_argument("--image_size", type=int, default=DEFAULT_IMAGE_SIZE)
    parser.add_argument("--blocks_per_stage", type=int, default=None,
                        help="bottleneck blocks per stage (None = ResNet-50's "
                             "[3,4,6,3]; the reference's resnet_size knob)")
    parser.add_argument("--base_lr", type=float, default=0.1)
    parser.add_argument("--weight_decay", type=float, default=1e-4)
    parser.add_argument("--label_smoothing", type=float, default=0.1,
                        help="reference resnet_imagenet_main.py:98-100")
    parser.add_argument("--stem", default="conv7", choices=["conv7", "s2d"],
                        help="s2d = space-to-depth stem (same math, "
                             "MXU-friendly; models/resnet.py)")
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--use_synthetic_data", action="store_true")
    parser.add_argument("--synthetic_examples", type=int, default=1024)
    parser.add_argument("--data_dir", default=None,
                        help="ImageNet TFRecord shard dir (train-*): "
                             "streams via data.FileFeed + imagenet_input; "
                             "synthetic data when omitted")
    parser.add_argument("--eval_data_dir", default=None,
                        help="validation-* shard dir: exact top-1 after "
                             "training (drain='all', center-crop eval)")
    parser.add_argument("--steps_per_call", type=int, default=1,
                        help="train steps per device dispatch (data_dir "
                             "path)")
    parser.add_argument("--shuffle_buffer", type=int, default=10000)
    parser.add_argument("--reader_threads", type=int, default=4)
    parser.add_argument("--decode_procs", type=int, default=0,
                        help="JPEG-decode worker PROCESSES for the train "
                        "feed (0 = in-process reader threads); decode is "
                        "CPU-bound, so size this to the host's spare cores")
    parser.add_argument("--predecoded", action="store_true",
                        help="data_dir holds predecode_imagenet.py output "
                        "(fixed-size uint8 rows, *.raw): decode-free hot "
                        "path, crop/flip on DEVICE (ops.augment)")
    parser.add_argument("--store_px", type=int, default=256,
                        help="stored row size of the predecoded shards")
    parser.add_argument("--model_dir", default=None)
    parser.add_argument("--export_dir", default=None)
    parser.add_argument("--save_interval", type=int, default=1000)
    parser.add_argument("--log_steps", type=int, default=20)
    parser.add_argument("--log_dir", default=None,
                        help="TensorBoard event dir (chief writes loss/"
                             "throughput/MFU curves + eval accuracy)")
    parser.add_argument("--profile_steps", default=None)
    parser.add_argument("--profile_dir", default=None)
    return parser


def main(argv=None):
    from tensorflowonspark_tpu import backend, cluster, device_info

    args, rem = build_parser().parse_known_args(argv)
    args.remaining_argv = rem

    b = backend.LocalBackend(args.cluster_size)
    try:
        c = cluster.run(b, main_fun, args, num_executors=args.cluster_size,
                        input_mode=cluster.InputMode.FILES,
                        executor_env=device_info.tpu_env())
        c.shutdown(grace_secs=2)
    finally:
        b.stop()


if __name__ == "__main__":
    main()
