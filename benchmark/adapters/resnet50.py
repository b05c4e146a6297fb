"""How the ``resnet50`` reference's weights and batches enter the program:
the model, loss and optimizer a user of the repo's ResNet example builds
(``examples/resnet/resnet_imagenet.py``), fed the benchmark's seeded weights.
Everything here calls the program; the reference never sees it."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# -- rows: what a training row of this configuration is -----------------------

def row_dtype(cfg):
    """One stored row (numpy structured dtype; its field order is the order
    of a row's tuple on the SPARK transport)."""
    import numpy as np

    px = cfg["store_px"]
    return np.dtype([("image", np.uint8, (px, px, 3)), ("label", np.int32),
                     ("index", np.int32), ("cropx", np.int32),
                     ("cropy", np.int32), ("flip", np.int32)])


def make_row(cfg, seed, index):
    """Row ``index`` of the seeded table, in ``row_dtype``'s field order."""
    from benchmark import generate

    return generate.image_row(seed, index, cfg)


def to_batch(cols):
    """Columns of rows (field -> array) -> (the loss's batch, each row's tag
    for the conservation check: its label)."""
    import numpy as np

    batch = {"image": cols["image"],
             "label": np.asarray(cols["label"], np.int32),
             "cropx": np.asarray(cols["cropx"], np.int32),
             "cropy": np.asarray(cols["cropy"], np.int32),
             "flip": np.asarray(cols["flip"], np.int32)}
    return batch, np.asarray(cols["label"], np.int64)


# -- weights and the program's objects -----------------------------------------

def _flax_path(name):
    """Reference leaf name -> (collection, flax path)."""
    parts = name.split("/")
    if parts[0] == "stem":
        mod = []
    elif parts[0] == "fc":
        return "params", "Dense_0/" + parts[1]
    else:
        mod = ["BottleneckBlock_" + parts[0][len("block"):]]
    layer = parts[1]
    if layer == "conv":
        return "params", "/".join(mod + ["Conv_0", "kernel"])
    if layer == "proj":
        return "params", "/".join(mod + ["Conv_3", "kernel"])
    if layer.startswith("conv"):
        return "params", "/".join(mod + ["Conv_" + layer[4:], "kernel"])
    index = "3" if layer == "projbn" else (
        "0" if layer == "bn" else layer[2:])
    coll = "params" if parts[2] in ("scale", "bias") else "batch_stats"
    return coll, "/".join(mod + ["BatchNorm_" + index, parts[2]])


def to_program(params, stats):
    """The reference's (params, stats) as the flax variables of the model."""
    from flax import traverse_util

    flat = {"params": {}, "batch_stats": {}}
    for name, value in list(params.items()) + list(stats.items()):
        coll, path = _flax_path(name)
        flat[coll][path] = value
    return {coll: traverse_util.unflatten_dict(tree, sep="/")
            for coll, tree in flat.items()}


def reference_names(cfg):
    """flax parameter path -> reference leaf name."""
    from benchmark.references import resnet50 as ref

    names = ["stem/conv", "stem/bn/scale", "stem/bn/bias", "fc/kernel",
             "fc/bias"]
    for name, _, _, _, proj in ref.block_plan(cfg):
        for j in range(3):
            names += ["%s/conv%d" % (name, j), "%s/bn%d/scale" % (name, j),
                      "%s/bn%d/bias" % (name, j)]
        if proj:
            names += [name + "/proj", name + "/projbn/scale",
                      name + "/projbn/bias"]
    return {_flax_path(n)[1]: n for n in names}


def statistic_names(cfg):
    """flax batch_stats path -> reference leaf name."""
    names = {}
    for param_path, name in reference_names(cfg).items():
        if name.endswith("/scale"):
            for stat in ("mean", "var"):
                ref = name[:-len("scale")] + stat
                names[_flax_path(ref)[1]] = ref
    return names


def _model(cfg):
    """(flax module, blocks_per_stage) of the configuration."""
    from tensorflowonspark_tpu.models import resnet as resnet_mod

    stages = list(cfg["stage_sizes"])
    blocks = None if stages == [3, 4, 6, 3] else stages[0]
    return resnet_mod.build_resnet50(
        num_classes=cfg["num_classes"], dtype=cfg["dtype"], stem=cfg["stem"],
        blocks_per_stage=blocks), blocks


def build(cfg, seed, mesh=None):
    """What a training cell needs of the program, at ``cfg``'s sizes."""
    import jax.numpy as jnp
    import optax

    from benchmark.references import resnet50 as ref
    from tensorflowonspark_tpu.models import resnet as resnet_mod
    from tensorflowonspark_tpu.ops import augment

    sys.path.insert(0, os.path.join(ROOT, "examples", "resnet"))
    import imagenet_input

    model, _ = _model(cfg)
    variables = to_program(*ref.init_weights(cfg, seed))
    base_loss = resnet_mod.loss_fn(model, weight_decay=cfg["weight_decay"],
                                   label_smoothing=cfg["label_smoothing"])
    in_dtype = jnp.dtype(cfg["dtype"])
    size = cfg["image_size"]

    def loss(p, bs, batch, mask):
        batch = dict(batch)
        img = augment.crop_and_flip(
            batch.pop("image"), batch.pop("cropx"), batch.pop("cropy"),
            batch.pop("flip"), size)
        batch["image"] = imagenet_input.normalize_on_device(img, in_dtype)
        return base_loss(p, bs, batch, mask)

    opt = cfg["optimizer"]
    return {
        "model": model, "loss": loss, "params": variables["params"],
        "extra": variables["batch_stats"],
        "optimizer": optax.sgd(opt["learning_rate"],
                               momentum=opt["momentum"]),
        "compute_dtype": jnp.bfloat16 if cfg["dtype"] == "bfloat16" else None,
        # optax.sgd(momentum) keeps trace = momentum * trace + g: after one
        # step from zero it is the first gradient as the optimizer got it
        "first_gradient": lambda opt_state: opt_state[0].trace,
        "names": reference_names(cfg),
        "extra_names": statistic_names(cfg),
    }


def export(cfg, seed, export_dir):
    """The serving cell's export, made from the seeded weights alone (no
    training): what ``resnet_imagenet._finish`` writes."""
    import jax

    from benchmark.references import resnet50 as ref
    from tensorflowonspark_tpu import checkpoint

    model, blocks = _model(cfg)
    variables = to_program(*ref.init_weights(cfg, seed))
    size = cfg["image_size"]
    checkpoint.export_model(
        export_dir, jax.device_get(variables["params"]), "resnet50",
        model_config={"num_classes": cfg["num_classes"],
                      "dtype": cfg["dtype"], "blocks_per_stage": blocks,
                      "stem": cfg["stem"]},
        input_signature={"image": [None, size, size, 3]}, model=model,
        extra_variables={"batch_stats": variables["batch_stats"]})
