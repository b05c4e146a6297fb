"""Shared by the benchmark's tests: the tiny configurations, seeded batches
for them, and the program's first steps taken as the training driver takes
them (``Trainer.step``, the first gradient out of the optimizer's state, the
parameters' change)."""

import glob
import importlib
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


SECTIONS = ("configs", "workloads", "end_to_end", "per_layer")


def merge_fragment(manifest, fragment, origin="fragment"):
    """One fragment into ``manifest``, in place.  A fragment is a JSON object
    with any of: ``configs``, ``workloads``, ``end_to_end``, ``per_layer``
    (entries appended to the section of that name; a name that is there
    already is an error) and ``append_workloads`` (``{metric: [cells]}``:
    cell names appended to the ``workloads`` list of a metric that is
    there)."""
    unknown = set(fragment) - set(SECTIONS) - {"what", "append_workloads"}
    if unknown:
        raise ValueError("{}: unknown keys {}".format(origin, sorted(unknown)))
    for section in SECTIONS:
        named = {e["name"] for e in manifest[section]}
        for entry in fragment.get(section, []):
            if entry["name"] in named:
                raise ValueError("{}: {} already has {!r}".format(
                    origin, section, entry["name"]))
            named.add(entry["name"])
            manifest[section].append(entry)
    metrics = {m["name"]: m
               for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name, cells in fragment.get("append_workloads", {}).items():
        if "workloads" not in metrics.get(name, {}):
            raise ValueError("{}: no metric {!r} with a workloads list"
                             .format(origin, name))
        metrics[name]["workloads"] += [
            c for c in cells if c not in metrics[name]["workloads"]]
    return manifest


def manifest(tiny=TINY):
    """The rehearsal manifest: ``manifest.json`` under ``tiny`` with every
    ``manifest.d/*.json`` beside it merged in, in name order.  A new family,
    cell or metric brings a fragment; nobody edits the base file."""
    merged = load(tiny, "manifest.json")
    for path in sorted(glob.glob(os.path.join(tiny, "manifest.d", "*.json"))):
        merge_fragment(merged, load(path), os.path.basename(path))
    return merged


def training_cells(tiny=TINY):
    """{configuration: its first one-chip training cell} of the merged
    manifest: the cells whose limits the first-steps comparison is held to,
    a new family's among them as soon as its fragment is there."""
    merged, out = manifest(tiny), {}
    root = os.path.dirname(os.path.dirname(os.path.dirname(tiny)))
    for cell in merged["workloads"]:
        mix = next(p for p in (os.path.join(
            root, base, "traffic", cell["traffic"] + ".json")
            for base in merged["paths"]) if os.path.exists(p))
        if load(mix)["driver"] == "train_feed" and cell["chips"] == 1:
            out.setdefault(cell["config"], cell["name"])
    return out


def manifest_path(directory, tiny=TINY):
    """The merged manifest written under ``directory``, for ``run.py
    --manifest``."""
    path = os.path.join(str(directory), "manifest.json")
    with open(path, "w") as f:
        json.dump(manifest(tiny), f, indent=1)
    return path


def batches(cfg, seed, steps=3):
    """The first ``steps`` batches of the configuration's seeded table, made
    as the training driver makes them again for the reference: rows by index
    through the adapter (``make_row``, ``to_batch``)."""
    from benchmark.drivers import train_feed

    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    b = cfg["batch_size"]
    return [train_feed._remake(adapter, cfg, seed, range(s * b, (s + 1) * b))[0]
            for s in range(steps)]


def program_first_steps(cfg, seed, rows):
    """What the driver reads of the program over its first steps."""
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


if __name__ == "__main__":
    # python3 tests/benchmark/_tiny.py <directory>: the merged manifest's path
    print(manifest_path(sys.argv[1]))
