"""Microbenchmark: where do ResNet-50's 407 ms/step go?

Separates (a) pure device compute (K steps dispatched back-to-back, one sync
at the end) from (b) per-step sync'd latency (sync every step) from (c) the
forward pass alone, and prints XLA cost-analysis FLOPs for each.  Run on the
real chip; compares against the v5e 197 TFLOP/s bf16 peak.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tensorflowonspark_tpu import train as train_mod
from tensorflowonspark_tpu.models import resnet as resnet_mod
from tensorflowonspark_tpu.parallel import mesh as mesh_mod


def timed(fn, sync_value_fn, steps, per_step_sync=False):
    # Sync = device->host readback of a value data-dependent on the work
    # (same rule as metrics.TimeHistory._sync).
    out = None
    t0 = time.time()
    for _ in range(steps):
        out = fn()
        if per_step_sync:
            jax.device_get(sync_value_fn(out))
    jax.device_get(sync_value_fn(out))
    return (time.time() - t0) / steps


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch_size", type=int, default=256)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--repeat_k", type=int, default=10)
    p.add_argument("--stem", default="s2d", choices=["conv7", "s2d"],
                   help="s2d matches the bench leg's (cached) program")
    args = p.parse_args()

    dev = jax.devices()[0]
    print("device:", dev.device_kind, flush=True)
    mesh = mesh_mod.build_mesh()
    sharding = mesh_mod.batch_sharding(mesh)

    model = resnet_mod.build_resnet50(dtype="bfloat16", stem=args.stem)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))
    trainer = train_mod.Trainer(
        resnet_mod.loss_fn(model, weight_decay=1e-4),
        variables["params"], optax.sgd(0.1, momentum=0.9),
        extra_state=variables["batch_stats"], mesh=mesh,
        compute_dtype=jnp.bfloat16, batch_size=args.batch_size, log_steps=10**9)

    rng = np.random.default_rng(0)
    batch = {
        "image": jax.device_put(
            rng.random((args.batch_size, 224, 224, 3), np.float32), sharding),
        "label": jax.device_put(
            rng.integers(0, 1000, (args.batch_size,)), sharding),
    }
    mask = jnp.ones((args.batch_size,), jnp.float32)

    # warm up / compile
    for _ in range(3):
        loss, _ = trainer.step(batch, mask)
    jax.device_get(loss)

    from tensorflowonspark_tpu import metrics as metrics_mod

    flops = trainer.history.step_flops
    peak = metrics_mod.peak_flops_per_device()
    if peak is None:
        raise SystemExit("no accelerator: this profile measures a chip")
    print("xla cost-analysis flops/step: %.3e (peak %.0fT)"
          % (flops or -1, peak / 1e12), flush=True)

    def mfu(flops_, secs):
        return 100 * flops_ / peak / secs if flops_ else float("nan")

    t_pipe = timed(lambda: trainer.step(batch, mask)[0], lambda x: x,
                   args.steps)
    t_sync = timed(lambda: trainer.step(batch, mask)[0], lambda x: x,
                   args.steps, per_step_sync=True)
    print("train step, pipelined: %.1f ms  (%.1f%% MFU)"
          % (1000 * t_pipe, mfu(flops, t_pipe)), flush=True)
    print("train step, per-step sync: %.1f ms  (%.1f%% MFU)"
          % (1000 * t_sync, mfu(flops, t_sync)), flush=True)

    # K steps per dispatch: isolates pure device compute from dispatch
    # latency (one host round trip per K steps).
    k = args.repeat_k
    trainer.repeat_step(batch, mask, k)  # compile
    t_rep = timed(lambda: trainer.repeat_step(batch, mask, k), lambda x: x,
                  max(args.steps // k, 2), per_step_sync=True) / k
    print("train step, scan k=%d: %.1f ms/step  (%.1f%% MFU)"
          % (k, 1000 * t_rep, mfu(flops, t_rep)), flush=True)

    # forward only
    @jax.jit
    def fwd(params, extra, image):
        out = model.apply({"params": params, "batch_stats": extra},
                          image.astype(jnp.bfloat16), train=False)
        return out.sum()

    params = trainer.state.params
    extra = trainer.state.extra
    s = fwd(params, extra, batch["image"])
    jax.device_get(s)
    c = fwd.lower(params, extra, batch["image"]).compile().cost_analysis()
    if isinstance(c, list):
        c = c[0]
    fflops = float(c.get("flops", 0))
    t_fwd = timed(lambda: fwd(params, extra, batch["image"]), lambda x: x,
                  args.steps)
    print("forward only: %.1f ms  (flops %.3e, %.1f%% MFU)"
          % (1000 * t_fwd, fflops, mfu(fflops, t_fwd)), flush=True)

    # dispatch latency probe: trivial op, per-step sync
    @jax.jit
    def tiny(x):
        return x + 1

    x = jax.device_put(jnp.zeros((8,), jnp.float32))
    jax.device_get(tiny(x))
    t_tiny = timed(lambda: tiny(x), lambda x: x, 50, per_step_sync=True)
    print("tiny-op round trip (dispatch+sync latency): %.2f ms"
          % (1000 * t_tiny), flush=True)

    # host->device transfer probe (the MNIST e2e path pays this per step)
    host = np.zeros((1024, 28, 28, 1), np.uint8)
    t_put = timed(lambda: jax.device_put(host, sharding), lambda x: x, 30,
                  per_step_sync=True)
    print("device_put 0.8MB: %.2f ms" % (1000 * t_put), flush=True)


if __name__ == "__main__":
    main()
