#!/usr/bin/env python3
"""Leaf by leaf, the first gradient of the timed ``Trainer`` against the plain
float32 reference's, at a configuration's own sizes, for several (seed, row)
pairs in one process: which leaf a cell's ``grad_norm_gap`` (the worst leaf's)
is, and how far every leaf's norm and direction differ.  What a limits file
records when a seed reads over a limit (``keyevl2_train_files_long.json``,
``first_step_by_leaf``).

    python3 benchmark/tools/leaf_gaps.py --config keye_vl2_30b_a3b_ep8 \
        --pairs 1769232048:55,1769232048:7 --out chiprun_out/<cell>.leaves.json

One process, on the chip (or, at a tiny size, anywhere).  The harness's own
pieces, without its window: ``adapter.build``, one ``Trainer.step`` on the
batch that starts at the row named (a FILES cell's first row is whichever of
``FileFeed``'s reader threads comes first: run the candidates), the
adapter's ``first_gradient``, ``reference.train_steps`` on that one batch.
Per leaf: the two norms (``p``, ``r``), the norm of the difference (``d``)
and the gap as ``correctness.norm_gap`` takes it."""

import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--configs-dir",
                        default=os.path.join(ROOT, "benchmark", "configs"))
    parser.add_argument("--pairs", required=True,
                        help="seed:row,seed:row,...")
    parser.add_argument("--out", required=True)
    args = parser.parse_args()

    import jax
    import numpy as np

    from benchmark import flops
    from benchmark.drivers import train_feed
    from tensorflowonspark_tpu import train as train_mod

    with open(os.path.join(args.configs_dir, args.config + ".json")) as f:
        cfg = json.load(f)
    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    batch_size = cfg["batch_size"]
    out = []
    for pair in args.pairs.split(","):
        seed, row = (int(x) for x in pair.split(":"))
        t0 = time.perf_counter()
        batch = train_feed._remake(
            adapter, cfg, seed, [row + i for i in range(batch_size)])[0]
        built = adapter.build(cfg, seed)
        names = built["names"]
        trainer = train_mod.Trainer(
            built["loss"], built["params"], built["optimizer"],
            extra_state=built["extra"], compute_dtype=built["compute_dtype"],
            batch_size=batch_size, log_steps=1,
            step_flops_override=flops.train_flops_per_example(cfg)
            * batch_size / len(jax.devices()))
        first_gradient = built["first_gradient"]
        del built
        loss, _ = trainer.step(jax.tree_util.tree_map(np.asarray, batch))
        loss = float(loss)
        gp = train_feed._flat(first_gradient(trainer.state.opt_state), names)
        trainer.state = None
        del trainer
        gc.collect()
        t1 = time.perf_counter()
        got = ref.train_steps(cfg, seed, [batch])
        t2 = time.perf_counter()
        leaves = {}
        for k, r in got["first_gradient"].items():
            r = np.asarray(r, np.float32).ravel()
            p = np.asarray(gp[k], np.float32).ravel()
            d = p - r
            leaves[k] = {"p": float(np.sqrt(np.dot(p, p))),
                         "r": float(np.sqrt(np.dot(r, r))),
                         "d": float(np.sqrt(np.dot(d, d))), "n": int(r.size)}
        floor = float(np.median([v["r"] for v in leaves.values()]))
        for v in leaves.values():
            v["gap"] = abs(v["p"] - v["r"]) / max(v["r"], floor)
        worst = sorted(leaves, key=lambda k: -leaves[k]["gap"])[:6]
        record = {"seed": seed, "row": row, "loss": loss,
                  "ref_loss": got["losses"][0], "floor": floor,
                  "grad_norm_gap": leaves[worst[0]]["gap"],
                  "worst": [(k, leaves[k]["gap"]) for k in worst],
                  "program_s": t1 - t0, "reference_s": t2 - t1,
                  "leaves": leaves}
        out.append(record)
        print(json.dumps({k: v for k, v in record.items() if k != "leaves"}),
              flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
        del gp, got
        gc.collect()


if __name__ == "__main__":
    main()
