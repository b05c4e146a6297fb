"""K-ladder: measured ms/step vs steps-per-dispatch on the real device.

Validates (or falsifies) the dispatch-amortization model behind the
K-steps-per-dispatch design (``Trainer.repeat_step`` / ``multi_step``,
bench.py RESNET_STEPS_PER_CALL): every dispatch pays a fixed host cost, so

    t_total(K) = overhead + K * t_step

and measured points at several K let us fit both terms.  The reference's
benchmark-mode measurement obligation (reference
``examples/resnet/common.py:236-244``) is step time; this script is the
same obligation plus the K dimension.  Not measured on this code:
``chip_smoke.py`` prints the per-dispatch constant where the chip is
attached, and the ladder is the benchmark's to run.

Timing discipline: every sample below ends with a device->host readback of
a loss value data-dependent on the work (same rule as
``metrics.TimeHistory._sync``).

Usage:  python scripts/k_ladder.py [--out k_ladder.json] [--ks 1,5,20]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np


def _fit_overhead(ks, totals):
    """Least-squares fit of t_total(K) = overhead + K * t_step."""
    ks = np.asarray(ks, np.float64)
    ts = np.asarray(totals, np.float64)
    a = np.stack([np.ones_like(ks), ks], axis=1)
    (overhead, t_step), *_ = np.linalg.lstsq(a, ts, rcond=None)
    return float(overhead), float(t_step)


def _measure(trainer, batch, mask, ks, repeats):
    """ms/step at each K via repeat_step; every sample syncs via float()."""
    rows = []
    for k in ks:
        # compile + warm this K's program
        float(trainer.repeat_step(batch, mask, k))
        samples = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            final = trainer.repeat_step(batch, mask, k)
            float(final)  # host readback: the only real barrier
            samples.append(time.perf_counter() - t0)
        samples.sort()
        med = samples[len(samples) // 2]
        rows.append({"k": k, "dispatch_ms": round(1e3 * med, 2),
                     "ms_per_step": round(1e3 * med / k, 2),
                     "min_dispatch_ms": round(1e3 * samples[0], 2),
                     "runs": repeats})
    overhead, t_step = _fit_overhead(
        [r["k"] for r in rows], [r["dispatch_ms"] / 1e3 for r in rows])
    return {"ladder": rows,
            "fit_overhead_ms": round(1e3 * overhead, 2),
            "fit_ms_per_step": round(1e3 * t_step, 2)}


def mnist_ladder(ks, repeats):
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.models import mnist as mnist_mod
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.build_mesh()
    model = mnist_mod.build_mnist(dtype="bfloat16")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 28, 28, 1)))["params"]
    trainer = train_mod.Trainer(
        mnist_mod.loss_fn(model), params, optax.sgd(0.01, momentum=0.9),
        mesh=mesh, compute_dtype=None, batch_size=1024, log_steps=10**9)
    rng = np.random.default_rng(0)
    shard = mesh_mod.batch_sharding(mesh)
    batch = {"image": jax.device_put(
                 rng.random((1024, 28, 28, 1), np.float32), shard),
             "label": jax.device_put(
                 rng.integers(0, 10, (1024,)), shard)}
    mask = jax.device_put(np.ones((1024,), np.float32), shard)
    return _measure(trainer, batch, mask, ks, repeats)


def resnet_ladder(ks, repeats, batch_size, blocks):
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.models import resnet as resnet_mod
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.build_mesh()
    model = resnet_mod.build_resnet50(
        dtype="bfloat16", stem="s2d", blocks_per_stage=blocks or None)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 224, 224, 3)))
    trainer = train_mod.Trainer(
        resnet_mod.loss_fn(model, weight_decay=1e-4), variables["params"],
        optax.sgd(0.1, momentum=0.9), extra_state=variables["batch_stats"],
        mesh=mesh, compute_dtype=jnp.bfloat16, batch_size=batch_size,
        log_steps=10**9)
    rng = np.random.default_rng(0)
    shard = mesh_mod.batch_sharding(mesh)
    batch = {"image": jax.device_put(
                 rng.random((batch_size, 224, 224, 3), np.float32), shard),
             "label": jax.device_put(
                 rng.integers(0, 1000, (batch_size,)), shard)}
    mask = jax.device_put(np.ones((batch_size,), np.float32), shard)
    return _measure(trainer, batch, mask, ks, repeats)


def transformer_ladder(ks, repeats, **overrides):
    """The MXU-friendly flagship: a ~134M-param decoder-only LM (bf16,
    weight-tied readout).  Attention is quadratic-but-small at this seq;
    ~90% of FLOPs are dense matmuls, so this leg shows what fraction of
    the matmul ceiling (82-87% of peak measured, device_validate) the full
    Trainer path keeps.

    Model + shapes come from ``bench.build_lm_trainer`` (same LM_* env
    knobs) so the ladder always measures exactly the model the bench's
    ``transformer_lm_train_mfu`` headline runs."""
    import bench

    trainer, batch_d, mask, config = bench.build_lm_trainer(
        log_steps=10 ** 9, **overrides)
    out = _measure(trainer, batch_d, mask, ks, repeats)
    from tensorflowonspark_tpu import metrics as metrics_mod

    flops = trainer.history.step_flops
    peak = metrics_mod.peak_flops_per_device()
    if flops and peak:
        out["step_flops"] = flops
        out["peak_flops"] = peak
        for row in out["ladder"]:
            row["mfu_pct"] = round(
                100 * flops / peak / (row["ms_per_step"] / 1e3), 1)
    out["config"] = config
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="k_ladder.json")
    p.add_argument("--ks", default="1,5,20")
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--resnet_batch", type=int, default=256)
    # 0 = full [3,4,6,3] ResNet-50; N = smoke [N,N,N,N]
    p.add_argument("--resnet_blocks", type=int, default=1)
    p.add_argument("--legs", default="mnist,resnet")
    args = p.parse_args()
    ks = [int(k) for k in args.ks.split(",")]

    import jax
    out = {"device_kind": jax.devices()[0].device_kind,
           "ks": ks, "ts": time.time()}
    legs = args.legs.split(",")
    if "mnist" in legs:
        out["mnist"] = mnist_ladder(ks, args.repeats)
        print("mnist:", json.dumps(out["mnist"]))
    if "resnet" in legs:
        out["resnet"] = resnet_ladder(
            ks, args.repeats, args.resnet_batch, args.resnet_blocks)
        out["resnet"]["batch"] = args.resnet_batch
        out["resnet"]["blocks_per_stage_override"] = args.resnet_blocks
        print("resnet:", json.dumps(out["resnet"]))
    if "transformer" in legs:
        out["transformer"] = transformer_ladder(ks, args.repeats)
        print("transformer:", json.dumps(out["transformer"]))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", args.out)


if __name__ == "__main__":
    main()
