"""Long-context transformer LM on a dp x sp x tp mesh — the TPU-native flagship.

No reference counterpart (the reference's workloads are CNNs; SURVEY §5.7
records sequence parallelism as absent).  This example shows the axes the
TPU-first design adds beyond parity: the same cluster lifecycle and infeed
as the MNIST examples, but the model is a decoder-only LM whose sequence
dim is sharded over the mesh's ``seq`` axis with ring attention
(:mod:`tensorflowonspark_tpu.parallel.ring`), params tensor-parallel over
``tensor``, and the batch over ``data``.

Run (CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/transformer/transformer_lm.py --cluster_size 1 \
        --data 2 --seq 2 --tensor 2 --seq_len 256 --train_steps 4
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main_fun(args, ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from tensorflowonspark_tpu import checkpoint
    from tensorflowonspark_tpu.models import transformer as tfm
    from tensorflowonspark_tpu import metrics as metrics_mod
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    ctx.initialize_distributed()
    mesh = mesh_mod.build_mesh(
        mesh_mod.MeshSpec(data=args.data, fsdp=args.fsdp, seq=args.seq,
                          expert=args.expert, tensor=args.tensor),
        keep_trivial_axes=True)

    # batch: dp (data, fsdp AND expert axes all carry distinct rows) x sp —
    # computed before the model so the shard_map EP kernel can keep the
    # group dim partitioned over the same axes (ep_batch_axes) instead of
    # all-gathering the batch onto every expert shard
    batch_axes = tuple(a for a, n in (("data", args.data), ("fsdp", args.fsdp),
                                      ("expert", args.expert)) if n != 1)
    batch_axes = batch_axes or "data"

    model = tfm.build_transformer(
        vocab_size=args.vocab_size, num_layers=args.num_layers,
        num_heads=args.num_heads, head_dim=args.head_dim,
        max_seq_len=args.seq_len,
        attention=args.attention or ("ring" if args.seq > 1 else "full"),
        mlp=args.mlp, num_experts=args.num_experts,
        ep_mode=args.ep_mode, mesh=mesh, ep_batch_axes=batch_axes,
        dtype=args.dtype)
    # Init through a full-attention twin: same params, no divisibility
    # constraint on the init batch (see __graft_entry__.dryrun_multichip).
    init_model = tfm.build_transformer(
        mlp=args.mlp, num_experts=args.num_experts,
        vocab_size=args.vocab_size, num_layers=args.num_layers,
        num_heads=args.num_heads, head_dim=args.head_dim,
        max_seq_len=args.seq_len, dtype=args.dtype)
    params = init_model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, args.seq_len), jnp.int32))["params"]

    optimizer = optax.adamw(args.lr)
    loss = tfm.loss_fn(model)

    # params/opt state: replicated, or fsdp-sharded when the fsdp axis is
    # real (parallel/fsdp.py), with expert-stacked MoE weights overlaid on
    # the expert axis (parallel/ep.py) when it is
    batch_sharding = NamedSharding(mesh, PartitionSpec(batch_axes, "seq"))
    mask_sharding = NamedSharding(mesh, PartitionSpec(batch_axes))
    def layout(tree):
        # fsdp rule by shape (scalars/small leaves replicate), then the
        # expert-stacked MoE leaves overlaid on the expert axis; applies
        # uniformly to params AND optimizer state (mu/nu mirror the param
        # paths, so the moe/w* regex matches them too)
        if args.fsdp > 1:
            from tensorflowonspark_tpu.parallel import fsdp as fsdp_mod

            shardings = fsdp_mod.tree_shardings(tree, mesh)
        else:
            shardings = jax.tree_util.tree_map(
                lambda _: mesh_mod.replicated(mesh), tree)
        if args.expert > 1:
            from tensorflowonspark_tpu.parallel import ep as ep_mod

            shardings = ep_mod.merge_ep_shardings(shardings, tree, mesh)
        return shardings

    params = jax.device_put(params, layout(params))
    opt_state = optimizer.init(params)
    opt_state = jax.device_put(opt_state, layout(opt_state))

    def train_step(params, opt_state, tokens, mask):
        (l, _), grads = jax.value_and_grad(loss, has_aux=True)(
            params, {"tokens": tokens}, mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    step_fn = jax.jit(train_step, donate_argnums=(0, 1))

    # Synthetic token stream with learnable n-gram structure.
    rng = np.random.default_rng(jax.process_index())
    base = np.arange(args.seq_len) % args.vocab_size

    def next_batch():
        offs = rng.integers(0, args.vocab_size, (args.batch_size, 1))
        toks = ((base[None, :] + offs) % args.vocab_size).astype(np.int32)
        return (jax.device_put(toks, batch_sharding),
                jax.device_put(np.ones((args.batch_size,), np.float32),
                               mask_sharding))

    # A step's model FLOPs from shapes, per device: 6 for each matmul
    # parameter and token (12 d^2 a layer, d V for the read-out) plus the
    # causal half of the attention products.  An MoE model's active share
    # follows its router: it states no count and reports no MFU.
    flops = None
    if args.mlp == "dense":
        d = args.num_heads * args.head_dim
        macs = args.seq_len * (args.num_layers * 12 * d * d
                               + d * args.vocab_size)
        macs += args.num_layers * args.seq_len * (args.seq_len + 1) * d
        flops = 6 * macs * args.batch_size / mesh.size
    history = metrics_mod.TimeHistory(args.batch_size,
                                      log_steps=args.log_steps,
                                      step_flops=flops)
    history.on_train_begin()

    feed_batches = None
    if args.data_dir:
        # Real text: raw files -> byte-level token stream (vocab 256, no
        # tokenizer deps) packed to seq_len, streamed via FileFeed and
        # sequence-sharded through the standard plane (the ShardedFeed
        # sharding override puts tokens on ("data", "seq")).
        assert args.vocab_size >= 256, \
            "--data_dir byte-level LM needs --vocab_size >= 256"
        from tensorflowonspark_tpu import data as data_mod
        from tensorflowonspark_tpu.datafeed import strip_scheme
        from tensorflowonspark_tpu.parallel import infeed

        feed = data_mod.FileFeed(
            data_mod.list_shards(
                strip_scheme(ctx.absolute_path(args.data_dir)), pattern="*"),
            row_reader=data_mod.byte_lm_reader(args.seq_len),
            shuffle_buffer=args.shuffle_buffer, num_epochs=args.epochs,
            seed=jax.process_index())
        sharded = infeed.ShardedFeed(feed, mesh, args.batch_size,
                                     sharding=batch_sharding)
        feed_batches = sharded.batches()

    l = None
    with mesh:
        for _ in range(args.train_steps):
            if feed_batches is not None:
                try:
                    batch, mask = next(feed_batches)
                except StopIteration:
                    break
                tokens = batch["tokens"]
            else:
                tokens, mask = next_batch()
            params, opt_state, l = step_fn(params, opt_state, tokens, mask)
            history.on_step_end(l)
    if feed_batches is not None:
        # early-exit protocol (mirrors Trainer.fit_feed): stop the prefetch
        # and reader threads instead of letting them decode/transfer
        # batches through the export epilogue
        sharded.terminate()
        feed_batches.close()
    if l is None:
        raise RuntimeError(
            "no training batches produced — are the --data_dir files "
            "shorter than --seq_len bytes?")
    lval = float(l)
    history.on_train_end(l)
    stats = history.log_stats(loss=lval)

    if args.export_dir and checkpoint.should_export(ctx):
        # pass device params as-is: export_model re-replicates
        # cross-process-sharded (fsdp) trees itself; an eager device_get
        # here would raise on not-fully-addressable arrays
        checkpoint.export_model(
            ctx.absolute_path(args.export_dir), params,
            "transformer_lm",
            model_config={"vocab_size": args.vocab_size,
                          "num_layers": args.num_layers,
                          "num_heads": args.num_heads,
                          "head_dim": args.head_dim,
                          "max_seq_len": args.seq_len,
                          "dtype": args.dtype},
            input_signature={"tokens": [None, args.seq_len]})
    return stats


def main(argv=None):
    from tensorflowonspark_tpu import backend, cluster

    parser = argparse.ArgumentParser()
    parser.add_argument("--cluster_size", type=int, default=1)
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--train_steps", type=int, default=20)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--vocab_size", type=int, default=512)
    parser.add_argument("--num_layers", type=int, default=4)
    parser.add_argument("--num_heads", type=int, default=8)
    parser.add_argument("--head_dim", type=int, default=32)
    parser.add_argument("--seq_len", type=int, default=1024)
    parser.add_argument("--fsdp", type=int, default=1,
                        help="fsdp-axis size: shards params + optimizer "
                        "state (and contributes to batch parallelism)")
    parser.add_argument("--data", type=int, default=2,
                        help="data-parallel mesh degree")
    parser.add_argument("--seq", type=int, default=2,
                        help="sequence-parallel (ring attention) degree")
    parser.add_argument("--mlp", default="dense",
                        choices=["dense", "moe"],
                        help="FFN flavor; 'moe' = Switch-style mixture of "
                             "experts (shard experts over the mesh's "
                             "expert axis)")
    parser.add_argument("--num_experts", type=int, default=8)
    parser.add_argument("--ep_mode", default="gspmd",
                        choices=["gspmd", "shard_map"],
                        help="expert parallelism flavor: gspmd lets XLA "
                        "partition the dispatch einsums; shard_map runs "
                        "the explicit all_to_all schedule (parallel/ep)")
    parser.add_argument("--expert", type=int, default=1,
                        help="mesh expert-axis size (shards the stacked "
                        "expert weights; tokens route via all_to_all)")
    parser.add_argument("--attention", default=None,
                        choices=[None, "full", "flash", "ring", "ulysses"],
                        help="override the attention kernel (default: ring "
                             "when --seq > 1, else full; 'flash' uses the "
                             "pallas FlashAttention-2 kernels)")
    parser.add_argument("--tensor", type=int, default=2,
                        help="tensor-parallel degree")
    parser.add_argument("--dtype", default="float32",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--export_dir", default=None)
    parser.add_argument("--log_steps", type=int, default=10)
    parser.add_argument("--data_dir", default=None,
                        help="dir of raw text files: byte-level LM via "
                             "data.byte_lm_reader (synthetic when omitted)")
    parser.add_argument("--shuffle_buffer", type=int, default=2048)
    parser.add_argument("--epochs", type=int, default=1,
                        help="file passes in --data_dir mode")
    args, _ = parser.parse_known_args(argv)

    b = backend.LocalBackend(args.cluster_size)
    try:
        c = cluster.run(b, main_fun, args, num_executors=args.cluster_size,
                        input_mode=cluster.InputMode.FILES)
        c.shutdown(grace_secs=2)
    finally:
        b.stop()


if __name__ == "__main__":
    main()
