"""Transformer-LM MFU tuning ladder: which axis of the config moves MFU?

Nothing here has been measured on this code (ROADMAP Queue 1 item 6 keeps the
bench LM only as a tripwire).  The suspects for a gap between the LM's MFU
and plain-matmul throughput are arithmetic-intensity edges: d_model-1024
weights are small for the MXU, the attention inner matmuls have K=64
contraction dims, and layernorm/softmax/adam are HBM-bound elementwise passes
whose relative cost shrinks as the matmuls grow.  Each variant below scales
ONE axis of the baseline so the measured curve attributes the gap; each runs
in a fresh subprocess (compile state, XLA flags, and HBM all reset) and the
aggregate JSON is rewritten after every variant so a killed ladder keeps
finished rows.

Same measurement obligation as the reference's benchmark mode
(reference examples/resnet/common.py:236-244) and the same timing
discipline as scripts/k_ladder.py: every sample ends with a host readback
data-dependent on the work.

Usage:
    python scripts/lm_tune.py                       # all variants
    python scripts/lm_tune.py --variants baseline,wide
    python scripts/lm_tune.py --one wide --out /tmp/x.json   # child mode
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# variant -> build_lm_trainer overrides (None = the bench leg's default)
VARIANTS = {
    "baseline": {},
    # d_model 1024 -> 2048: 4x the per-layer matmul FLOPs at the same
    # elementwise/dispatch cost -- the arithmetic-intensity lever
    "wide": {"heads": 32},
    # twice the layers at baseline width: scales FLOPs without changing
    # matmul shapes -- separates "shapes too small" from "edges too thick"
    "deep": {"layers": 16},
    # 4x the token batch at baseline width: fattens EVERY matmul's
    # non-contracted dim, incl. the K=64 attention inner products
    "batch32": {"batch_size": 32},
    # wide + fatter batch together (the presumptive flagship config)
    "wide_b16": {"heads": 32, "batch_size": 16},
    # longer sequences at constant tokens/batch: attention share grows
    # (quadratic), feed-forward share constant -- prices the flash kernel
    "seq4096": {"seq": 4096, "batch_size": 2},
    # pallas FlashAttention-2 instead of full causal attention: skips the
    # masked half of the S^2 score work and never materializes the S x S
    # matrix.  mfu_pct IS comparable with the other rungs: the kernel is a
    # custom call XLA's cost analysis can't see into, so build_lm_trainer
    # supplements the analytic attention FLOPs via extra_step_flops
    "flash": {"attention": "flash"},
    # top-k gated MoE FFN (8 experts, GSPMD layer; experts local on one
    # chip): what the grouped expert einsums cost vs the dense MLP --
    # the on-chip half of the EP story the CPU-mesh suite can't price
    "moe": {"mlp": "moe"},
    # every arithmetic-intensity lever at once (d2048 x 16L x b16):
    # ~870M params, the largest config that plausibly fits one v5e chip
    # with adam state -- if 50% MFU is reachable through the Trainer
    # path, this is the rung that shows it.  remat is required: without
    # it the backward pass stores each layer's S x S attention probs
    # (b16 x H32 x 1024^2 bf16 = ~1 GB/layer x 16L) and activations well
    # past 16 GB HBM; recompute trades ~1/3 more FLOPs for fitting
    # (subprocess isolation means an HBM OOM just fails this rung, not
    # the ladder)
    "big": {"heads": 32, "layers": 16, "batch_size": 16, "remat": True},
}


def run_one(variant, k, repeats):
    import jax

    from bench import build_lm_trainer
    from tensorflowonspark_tpu import metrics as metrics_mod

    trainer, batch, mask, config = build_lm_trainer(
        log_steps=10 ** 9, **VARIANTS[variant])

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
    tokens = config["batch"] * config["seq"]
    out = {"variant": variant, "k": k, "runs": repeats,
           "config": config,
           "compile_s": round(compile_s, 1),
           "ms_per_step": round(ms_per_step, 2),
           "min_ms_per_step": round(1e3 * samples[0] / k, 2),
           "tokens_per_sec": round(tokens / (med / k), 0),
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
    p.add_argument("--k", type=int, default=20)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--out", default="lm_tune.json")
    p.add_argument("--timeout", type=int, default=900,
                   help="per-variant child budget (compile is minutes-slow)")
    args = p.parse_args()

    if args.one:
        row = run_one(args.one, args.k, args.repeats)
        with open(args.out, "w") as f:
            json.dump(row, f)
        print(json.dumps(row))
        return

    import ladder

    wanted = []
    for variant in args.variants.split(","):
        if variant not in VARIANTS:
            print("unknown variant %s (have %s)"
                  % (variant, ",".join(VARIANTS)), file=sys.stderr)
            continue
        wanted.append(variant)
    ladder.run_ladder(
        wanted,
        lambda v, child_out: [
            sys.executable, os.path.abspath(__file__), "--one", v,
            "--k", str(args.k), "--repeats", str(args.repeats),
            "--out", child_out],
        args.out, args.timeout, meta={"k": args.k}, cwd=ROOT,
        label="lm_tune")


if __name__ == "__main__":
    main()
