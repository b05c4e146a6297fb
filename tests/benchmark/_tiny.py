"""Shared by the benchmark's tests: the tiny configurations, seeded batches
for them, and the program's first steps taken as the training driver takes
them (``Trainer.step``, the first gradient out of the optimizer's state, the
parameters' change)."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(ROOT, "tests", "benchmark", "tiny")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def config(name):
    return load(TINY, "configs", name + ".json")


def batches(cfg, seed, steps=3):
    from benchmark import generate

    b = cfg["batch_size"]
    if cfg["reference"] == "resnet50":
        traffic = {"store_px": cfg["store_px"], "image_size": cfg["image_size"],
                   "num_classes": cfg["num_classes"]}
        out = []
        for s in range(steps):
            rows = [generate.image_row(seed, s * b + i, traffic)
                    for i in range(b)]
            out.append({"image": np.stack([r[0] for r in rows]),
                        "label": np.asarray([r[1] for r in rows], np.int32),
                        "cropx": np.asarray([r[3] for r in rows], np.int32),
                        "cropy": np.asarray([r[4] for r in rows], np.int32),
                        "flip": np.asarray([r[5] for r in rows], np.int32)})
        return out
    traffic = {"seq_len": cfg["n_positions"], "vocab_size": cfg["vocab_size"]}
    return [{"tokens": generate.token_rows(seed, traffic, s * b, b)[:, 1:]}
            for s in range(steps)]


def program_first_steps(cfg, seed, rows):
    """What the driver reads of the program over its first steps."""
    import importlib

    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    from tensorflowonspark_tpu import train as train_mod

    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    built = adapter.build(cfg, seed)
    names = built["names"]
    trainer = train_mod.Trainer(
        built["loss"], built["params"], built["optimizer"],
        extra_state=built["extra"], compute_dtype=built["compute_dtype"],
        batch_size=cfg["batch_size"], log_steps=1, step_flops_override=1.0)

    def flat(tree):
        return {names[k]: np.asarray(v) for k, v in traverse_util.flatten_dict(
            jax.device_get(tree), sep="/").items()}

    def flat_extra(tree):
        return {built["extra_names"][k]: np.asarray(v) for k, v in
                traverse_util.flatten_dict(jax.device_get(tree),
                                           sep="/").items()}

    start = flat(trainer.state.params)
    extra = flat_extra(trainer.state.extra) if built.get("extra_names") \
        else None
    out = {"losses": []}
    for i, batch in enumerate(rows):
        loss, _ = trainer.step(
            {k: jnp.asarray(v) for k, v in batch.items()},
            jnp.ones((cfg["batch_size"],), jnp.float32))
        out["losses"].append(float(loss))
        if i == 0:
            out["first_gradient"] = flat(
                built["first_gradient"](trainer.state.opt_state))
            if extra is not None:
                after = flat_extra(trainer.state.extra)
                out["extra_delta"] = {k: after[k] - extra[k] for k in after}
    end = flat(trainer.state.params)
    out["delta_norms"] = {k: float(np.linalg.norm((end[k] - start[k]).ravel()))
                          for k in end}
    return out
