"""The worked example's adapter (``benchmark/README.md``, "Adding things"):
how the ``mlp`` reference's seeded weights enter the program.  The program
has no such model of its own, so the loss is written here, as a user of
``train.Trainer`` would write one: bfloat16 matrix products, float32
parameters, logits and loss, each layer under the ``jax.named_scope`` that
``counts/mlp.py`` counts and a ``<scope>_roofline_pct`` reader divides by.
Its rows are token rows: it takes what a row is from the ``gpt2`` adapter."""

from benchmark.adapters.gpt2 import make_row, row_dtype, to_batch  # noqa: F401

NAMES = {"embed/embedding": "wte", "hidden/kernel": "w1", "hidden/bias": "b1",
         "readout/kernel": "w2", "readout/bias": "b2"}


def build(cfg, seed, mesh=None):
    import jax
    import jax.numpy as jnp
    import optax
    from flax import traverse_util

    from benchmark.references import mlp as ref

    weights = ref.init_weights(cfg, seed)
    params = traverse_util.unflatten_dict(
        {path: weights[name] for path, name in NAMES.items()}, sep="/")
    dtype = jnp.dtype(cfg["dtype"])

    def loss(params, batch, mask):
        tokens = batch["tokens"].astype(jnp.int32)
        x = params["embed"]["embedding"][tokens[:, :-1]].astype(dtype)
        with jax.named_scope("mlp/hidden"):
            h = jax.nn.gelu(
                x @ params["hidden"]["kernel"].astype(dtype)
                + params["hidden"]["bias"].astype(dtype), approximate=True)
        with jax.named_scope("mlp/readout"):
            logits = (h @ params["readout"]["kernel"].astype(dtype)).astype(
                jnp.float32) + params["readout"]["bias"]
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean(-1)
        return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0), {}

    opt = cfg["optimizer"]
    b1 = opt["b1"]
    return {
        "model": None, "loss": loss, "params": params, "extra": None,
        "optimizer": optax.adam(opt["learning_rate"], b1=b1, b2=opt["b2"],
                                eps=opt["eps"]),
        "compute_dtype": jnp.bfloat16 if cfg["dtype"] == "bfloat16" else None,
        # Adam's mu after one step from zero is (1 - b1) g
        "first_gradient": lambda opt_state: jax.tree_util.tree_map(
            lambda m: m / (1.0 - b1), opt_state[0].mu),
        "names": NAMES,
    }
