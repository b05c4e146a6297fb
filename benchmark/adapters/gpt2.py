"""How the ``gpt2`` reference's weights enter the program: the model, loss
and optimizer of ``models/transformer.py`` at the configuration's sizes, fed
the benchmark's seeded weights.  The reference never sees any of it."""


# -- rows: what a training row of this configuration is -----------------------

def row_dtype(cfg):
    """One stored row (numpy structured dtype; its field order is the order
    of a row's tuple on the SPARK transport)."""
    import numpy as np

    return np.dtype([("index", np.int32),
                     ("tokens", np.int32, (cfg["n_positions"],))])


def make_row(cfg, seed, index):
    """Row ``index`` of the seeded table, in ``row_dtype``'s field order."""
    from benchmark import generate

    row = generate.token_rows(seed, {"seq_len": cfg["n_positions"],
                                     "vocab_size": cfg["vocab_size"]},
                              index, 1)[0]
    return int(row[0]), row[1:]


def to_batch(cols):
    """Columns of rows (field -> array) -> (the loss's batch, each row's tag
    for the conservation check: the sum of its tokens)."""
    import numpy as np

    tokens = np.asarray(cols["tokens"], np.int32)
    return {"tokens": tokens}, tokens.sum(axis=1, dtype=np.int64)


# -- weights and the program's objects -----------------------------------------

def _layer_paths(i):
    b = "block_%d/" % i
    return {
        "ln1_g": b + "LayerNorm_0/scale", "ln1_b": b + "LayerNorm_0/bias",
        "qkv_w": b + "Attention_0/qkv/kernel",
        "qkv_b": b + "Attention_0/qkv/bias",
        "proj_w": b + "Attention_0/proj/kernel",
        "proj_b": b + "Attention_0/proj/bias",
        "ln2_g": b + "LayerNorm_1/scale", "ln2_b": b + "LayerNorm_1/bias",
        "fc_w": b + "Dense_0/kernel", "fc_b": b + "Dense_0/bias",
        "out_w": b + "Dense_1/kernel", "out_b": b + "Dense_1/bias",
    }


_PLAIN = {"wte": "embed/embedding", "wpe": "pos_embed/embedding",
          "lnf_g": "LayerNorm_0/scale", "lnf_b": "LayerNorm_0/bias"}


def to_program(weights, cfg):
    """The reference's stacked weights as the flax params of TransformerLM
    (one jitted call: the slices and reshapes stay on the device)."""
    import jax
    from flax import traverse_util

    d, heads = cfg["n_embd"], cfg["n_head"]
    hd = d // heads

    def convert(w):
        flat = {path: w[name] for name, path in _PLAIN.items()}
        for i in range(cfg["n_layer"]):
            for name, path in _layer_paths(i).items():
                x = w[name][i]
                if name == "qkv_w":
                    x = x.reshape(d, 3, heads, hd)
                elif name == "qkv_b":
                    x = x.reshape(3, heads, hd)
                flat[path] = x
        return flat

    return traverse_util.unflatten_dict(jax.jit(convert)(weights), sep="/")


def reference_names(cfg):
    names = {path: name for name, path in _PLAIN.items()}
    for i in range(cfg["n_layer"]):
        for name, path in _layer_paths(i).items():
            names[path] = "%s/%d" % (name, i)
    return names


def build(cfg, seed, mesh=None):
    import jax.numpy as jnp
    import optax

    from benchmark.references import gpt2 as ref
    from tensorflowonspark_tpu.models import transformer

    model = transformer.build_transformer(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], head_dim=cfg["n_embd"] // cfg["n_head"],
        max_seq_len=cfg["n_positions"], attention=cfg["attention"],
        mesh=mesh, dtype=cfg["dtype"])
    opt = cfg["optimizer"]
    b1 = opt["b1"]
    params = to_program(ref.init_weights(cfg, seed), cfg)
    optimizer = optax.adam(opt["learning_rate"], b1=b1, b2=opt["b2"],
                           eps=opt["eps"])
    return {
        "model": model, "loss": transformer.loss_fn(model),
        "params": params, "extra": None, "optimizer": optimizer,
        "param_sharding": _tensor_sharding(params, optimizer, mesh),
        "compute_dtype": jnp.bfloat16 if cfg["dtype"] == "bfloat16" else None,
        # Adam's mu after one step from zero is (1 - b1) g
        "first_gradient": lambda opt_state: _scaled(opt_state[0].mu,
                                                    1.0 / (1.0 - b1)),
        "names": reference_names(cfg),
    }


def _tensor_sharding(params, optimizer, mesh):
    """The ``Trainer``'s ``param_sharding`` on a mesh with a ``tensor`` axis
    (the traffic mix's ``mesh`` layout), as ``chip_smoke.py`` builds it;
    None on any other mesh."""
    if mesh is None or "tensor" not in mesh.axis_names:
        return None
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.parallel import tp

    abstract = jax.eval_shape(
        lambda p: train_mod.TrainState(jnp.zeros((), jnp.int32), p,
                                       optimizer.init(p)), params)
    return tp.tp_param_shardings(abstract, mesh)


def _scaled(tree, factor):
    import jax

    return jax.tree_util.tree_map(lambda x: x * factor, tree)
