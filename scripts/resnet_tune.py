"""ResNet-50 MFU tuning ladder: measure ms/step for targeted variants.

Nothing here has been measured on this code (ROADMAP Queue 1 item 3: trace
first, tune after).  The script isolates the usual suspects of a conv-mix
efficiency gap one variant at a time, each in a FRESH subprocess (XLA flags
and libtpu knobs only apply at client creation):

- ``baseline``        exactly the bench leg's config (bs256, s2d, bf16
                      compute, f32 feed) — the control
- ``bf16_feed``       feed the device batch as bf16 (halves input HBM
                      traffic; the cast happens host-side once)
- ``eval_bn``         BatchNorm in inference mode — no batch-stats
                      reductions or state threading; isolates BN's cost.
                      NOT a valid training config: a diagnostic bound on
                      what fusing/folding BN could buy
- ``no_wd``           weight_decay=0 — isolates the L2-over-params term
- ``conv7``           the reference 7x7/stride-2 stem instead of s2d
                      (checks the s2d claim on real hardware)
- ``lhs``             --xla_tpu_enable_latency_hiding_scheduler=true
- ``async_fusion``    --xla_tpu_enable_async_collective_fusion=true (noop
                      single-chip; included to confirm that, not assume it)

Timing discipline: every sample ends with a host readback data-dependent
on the work (same rule as ``metrics.TimeHistory._sync``).

Usage:
    python scripts/resnet_tune.py                    # all variants
    python scripts/resnet_tune.py --variants baseline,eval_bn
    python scripts/resnet_tune.py --one baseline --out /tmp/x.json  # child
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np

VARIANT_FLAGS = {
    "lhs": "--xla_tpu_enable_latency_hiding_scheduler=true",
    "async_fusion": "--xla_tpu_enable_async_collective_fusion=true",
}
VARIANTS = ("baseline", "bf16_feed", "eval_bn", "no_wd", "conv7",
            "lhs", "async_fusion")


def run_one(variant, batch_size, k, repeats):
    """Build the variant's trainer, measure median ms/step at K."""
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import metrics as metrics_mod
    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.models import resnet as resnet_mod
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    stem = "conv7" if variant == "conv7" else "s2d"
    wd = 0.0 if variant == "no_wd" else 1e-4
    feed_dtype = np.float32
    if variant == "bf16_feed":
        import ml_dtypes

        feed_dtype = ml_dtypes.bfloat16

    # smoke knobs (CI / 1-core hosts, where conv compiles run minutes):
    # N shrinks stages to [N,N,N,N]; TFOS_TUNE_IMG shrinks the input.
    # 0/unset = the real [3,4,6,3] / 224px ResNet-50 every published row
    # uses.
    blocks = int(os.environ.get("TFOS_TUNE_BLOCKS", 0))
    img = int(os.environ.get("TFOS_TUNE_IMG", 0)) or 224
    mesh = mesh_mod.build_mesh()
    model = resnet_mod.build_resnet50(dtype="bfloat16", stem=stem,
                                      blocks_per_stage=blocks or None)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, img, img, 3)))

    if variant == "eval_bn":
        # diagnostic-only loss: BN in inference mode, stats passed through
        # untouched (same Trainer extra-state contract as the real loss)
        def loss(params, batch_stats, batch, mask):
            logits = model.apply(
                {"params": params, "batch_stats": batch_stats},
                batch["image"], train=False)
            labels = batch["label"].astype(jnp.int32)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels)
            ce = (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)
            l2 = sum(jnp.sum(p ** 2) for p in
                     jax.tree_util.tree_leaves(params) if p.ndim > 1)
            return ce + wd * l2, {"extra_state": batch_stats}
    else:
        loss = resnet_mod.loss_fn(model, weight_decay=wd)

    trainer = train_mod.Trainer(
        loss, variables["params"], optax.sgd(0.1, momentum=0.9),
        extra_state=variables["batch_stats"], mesh=mesh,
        compute_dtype=jnp.bfloat16, batch_size=batch_size, log_steps=10**9)

    rng = np.random.default_rng(0)
    shard = mesh_mod.batch_sharding(mesh)
    batch = {"image": jax.device_put(
                 rng.random((batch_size, img, img, 3),
                            np.float32).astype(feed_dtype), shard),
             "label": jax.device_put(
                 rng.integers(0, 1000, (batch_size,)), shard)}
    mask = jax.device_put(np.ones((batch_size,), np.float32), shard)

    t0 = time.perf_counter()
    float(trainer.repeat_step(batch, mask, k))   # compile + warm
    compile_s = time.perf_counter() - t0
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        final = trainer.repeat_step(batch, mask, k)
        float(final)                             # readback: the real barrier
        samples.append(time.perf_counter() - t0)
    samples.sort()
    med = samples[len(samples) // 2]
    ms_per_step = 1e3 * med / k
    out = {"variant": variant, "batch": batch_size, "k": k,
           "runs": repeats, "compile_s": round(compile_s, 1),
           "ms_per_step": round(ms_per_step, 2),
           "min_ms_per_step": round(1e3 * samples[0] / k, 2),
           "images_per_sec": round(batch_size / (med / k), 1),
           "device_kind": jax.devices()[0].device_kind}
    flops = trainer.history.step_flops
    peak = metrics_mod.peak_flops_per_device()
    if flops and peak:
        out["mfu_pct"] = round(100 * flops / peak / (med / k), 2)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--variants", default=",".join(VARIANTS))
    p.add_argument("--one", help="(child mode) run a single variant")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--k", type=int, default=20)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--out", default="resnet_tune.json")
    p.add_argument("--timeout", type=int, default=900,
                   help="per-variant subprocess budget (cold remote "
                        "compiles run minutes)")
    args = p.parse_args()

    if args.one:
        stats = run_one(args.one, args.batch, args.k, args.repeats)
        with open(args.out, "w") as f:
            json.dump(stats, f)
        print(json.dumps(stats))
        return

    import ladder

    def env_for(variant):
        env = dict(os.environ)
        if variant in VARIANT_FLAGS:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                                + VARIANT_FLAGS[variant]).strip()
        return env

    ladder.run_ladder(
        [v for v in args.variants.split(",") if v],
        lambda v, child_out: [
            sys.executable, os.path.abspath(__file__), "--one", v,
            "--batch", str(args.batch), "--k", str(args.k),
            "--repeats", str(args.repeats), "--out", child_out],
        args.out, args.timeout,
        meta={"batch": args.batch, "k": args.k}, env_for=env_for,
        cwd=ROOT, label="resnet_tune")


if __name__ == "__main__":
    main()
