"""ResNet-56 / CIFAR-10 distributed training (reference ``examples/resnet/``).

The reference carries the tensorflow/models official ResNet with a "10-line
conversion": ``main(_)`` becomes ``main_fun(argv, ctx)`` and leftover argv
passes through (reference ``resnet_cifar_spark.py:19-21``,
``resnet_cifar_dist.py:233-240``).  This example keeps that shape — the
driver forwards unparsed argv into ``main_fun`` — over the TPU-native stack:
flax ResNet-56 with BatchNorm extra-state, bf16 compute, cosine LR with
linear warmup (reference ``common.py:76-140`` schedule family), synthetic
data option (reference ``--use_synthetic_data``, ``common.py:315-363``),
TimeHistory/MFU stats (reference ``common.py:177-245``), periodic
checkpoints, and FILES-mode cluster lifecycle.

Run (CPU mesh; tiny smoke):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/resnet/resnet_cifar.py --cluster_size 2 \
        --use_synthetic_data --train_steps 2 --batch_size 32
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

HEIGHT, WIDTH, CHANNELS = 32, 32, 3  # reference cifar_preprocessing.py
NUM_CLASSES = 10
NUM_IMAGES = 50000


def synthetic_cifar(n, seed=11):
    """Deterministic learnable stand-in for CIFAR-10 (reference synthetic
    input_fn, ``common.py:315-363``)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    templates = rng.random((NUM_CLASSES, HEIGHT, WIDTH, CHANNELS)).astype("f")
    labels = rng.integers(0, NUM_CLASSES, (n,))
    noise = rng.normal(0, 0.15, (n, HEIGHT, WIDTH, CHANNELS)).astype("f")
    return (templates[labels] + noise).astype("float32"), labels.astype("int32")


def main_fun(args, ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import checkpoint, dfutil
    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.models import resnet as resnet_mod
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    ctx.initialize_distributed()
    mesh = mesh_mod.build_mesh()

    if args.use_synthetic_data:
        images, labels = synthetic_cifar(args.synthetic_examples)
    else:
        rows = dfutil.load_tfrecords(os.path.join(args.data_dir, "train"))
        images = np.asarray([r["image"] for r in rows], np.float32)
        images = images.reshape(-1, HEIGHT, WIDTH, CHANNELS)
        labels = np.asarray([r["label"] for r in rows], np.int32)
    shard = slice(jax.process_index(), None, max(jax.process_count(), 1))
    images, labels = images[shard], labels[shard]

    # blocks_per_stage is the size knob (reference resnet_size): 6n+2
    # layers; 9 -> ResNet-56, 1 -> an 8-layer smoke model.
    model = resnet_mod.build_resnet56(dtype=args.dtype,
                                      blocks_per_stage=args.blocks_per_stage)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, HEIGHT, WIDTH, CHANNELS)),
                           train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    steps_per_epoch = max(NUM_IMAGES // args.batch_size, 1)
    total_steps = args.train_steps or steps_per_epoch * args.train_epochs
    # Linear warmup + cosine decay (reference LR schedule family,
    # resnet_imagenet_main.py:37-71 / common.py:76-140), scaled by batch
    # size as the reference scales its base LR.
    base_lr = args.base_lr * args.batch_size / 128.0
    warmup = min(max(total_steps // 20, 1), 5 * steps_per_epoch)
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, base_lr, warmup, max(total_steps, warmup + 1))
    optimizer = optax.sgd(schedule, momentum=0.9, nesterov=True)

    trainer = train_mod.Trainer(
        resnet_mod.loss_fn(model, weight_decay=args.weight_decay),
        params, optimizer, mesh=mesh, extra_state=batch_stats,
        compute_dtype=jnp.bfloat16 if args.dtype == "bfloat16" else None,
        batch_size=args.batch_size, log_steps=args.log_steps)

    ckpt = None
    if args.model_dir:
        ckpt = checkpoint.CheckpointManager(
            ctx.absolute_path(args.model_dir),
            save_interval_steps=args.save_interval)

    # --profile_steps "start,stop" captures a device trace over that range
    # (reference common.py:192-197,293-300).
    prof = None
    if args.profile_steps:
        from tensorflowonspark_tpu import profiler

        prof = profiler.StepProfiler(
            args.profile_dir or "profile_logs", args.profile_steps)

    local_bs = mesh_mod.local_batch_size(mesh, args.batch_size)
    sharding = mesh_mod.batch_sharding(mesh)
    rng = np.random.default_rng(jax.process_index())
    step = 0
    loss = aux = None
    while step < total_steps:
        order = rng.permutation(len(labels))
        for s in range(len(labels) // local_bs):
            idx = order[s * local_bs:(s + 1) * local_bs]
            x = images[idx]
            if not args.use_synthetic_data or args.augment:
                # random flip + pad-crop (reference cifar_preprocessing.py)
                flip = rng.random(local_bs) < 0.5
                x = x.copy()
                x[flip] = x[flip, :, ::-1]
            batch = {
                "image": jax.make_array_from_process_local_data(sharding, x),
                "label": jax.make_array_from_process_local_data(
                    sharding, labels[idx]),
            }
            mask = jax.make_array_from_process_local_data(
                sharding, np.ones((local_bs,), np.float32))
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
    if ckpt:
        ckpt.maybe_save(step, trainer.state, force=True)
        ckpt.wait_until_finished()
        ckpt.close()
    if args.export_dir and checkpoint.should_export(ctx):
        checkpoint.export_model(
            ctx.absolute_path(args.export_dir),
            jax.device_get(trainer.state.params), "resnet56_cifar",
            model_config={"dtype": args.dtype,
                          "blocks_per_stage": args.blocks_per_stage},
            input_signature={"image": [None, HEIGHT, WIDTH, CHANNELS]},
            model=model,
            extra_variables={"batch_stats": trainer.state.extra})
    return stats


def main(argv=None):
    from tensorflowonspark_tpu import backend, cluster

    parser = argparse.ArgumentParser()
    parser.add_argument("--cluster_size", type=int, default=2)
    parser.add_argument("--batch_size", type=int, default=128,
                        help="global batch (reference default 128)")
    parser.add_argument("--train_epochs", type=int, default=182,
                        help="reference default 182 epochs")
    parser.add_argument("--train_steps", type=int, default=None,
                        help="overrides train_epochs when set")
    parser.add_argument("--base_lr", type=float, default=0.1)
    parser.add_argument("--blocks_per_stage", type=int, default=9,
                        help="basic blocks per stage: 6n+2 layers (9 = "
                             "ResNet-56; the reference's resnet_size knob)")
    parser.add_argument("--weight_decay", type=float, default=2e-4)
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--use_synthetic_data", action="store_true")
    parser.add_argument("--synthetic_examples", type=int, default=2048)
    parser.add_argument("--augment", action="store_true")
    parser.add_argument("--data_dir", default=None,
                        help="TFRecord root with train/ (image: 3072 floats)")
    parser.add_argument("--model_dir", default=None)
    parser.add_argument("--export_dir", default=None)
    parser.add_argument("--save_interval", type=int, default=500)
    parser.add_argument("--log_steps", type=int, default=20)
    parser.add_argument("--profile_steps", default=None,
                        help='"start,stop" device-trace capture range '
                             "(reference --profile_steps)")
    parser.add_argument("--profile_dir", default=None)
    # parse_known_args: leftover argv rides along inside args for user code
    # (reference passthrough convention, resnet_cifar_spark.py:19-21)
    args, rem = parser.parse_known_args(argv)
    args.remaining_argv = rem

    b = backend.LocalBackend(args.cluster_size)
    try:
        c = cluster.run(b, main_fun, args, num_executors=args.cluster_size,
                        input_mode=cluster.InputMode.FILES)
        c.shutdown(grace_secs=2)
    finally:
        b.stop()


if __name__ == "__main__":
    main()
