"""How the ``olmo_hybrid`` reference's weights enter the program: the one
decoder of ``models/transformer.py`` under the configuration's description
(``register_decoder("olmo_hybrid")``: Gated DeltaNet layers and
full-attention layers with a QK-norm over the whole projection, each with a
SwiGLU feed-forward, in blocks that norm a part's output; an untied
read-out), its loss and Adam, fed the benchmark's seeded weights.  The
reference never sees any of it.

A row is a token row as the ``gpt2`` adapter makes it, at the
configuration's ``seq_len`` and (sliced) vocabulary: the ``lfm2_moe``
adapter's three functions, which do just that."""

from benchmark.adapters.lfm2_moe import (make_row, row_dtype,  # noqa: F401
                                         to_batch)

# reference leaf (after "L<i>.") -> (the program's path under "block_<i>/",
# the configuration's key of the heads the leaf's second dimension splits
# into, or None)
_LAYER = {
    "op_norm": ("RMSNorm_0/scale", None),
    "ff_norm": ("RMSNorm_1/scale", None),
    "in_proj": ("delta/in_proj/kernel", None), "conv": ("delta/conv", None),
    "A_log": ("delta/A_log", None), "dt_bias": ("delta/dt_bias", None),
    "gate_norm": ("delta/norm", None),
    "out_proj": ("delta/out_proj/kernel", None),
    "wq": ("attention/q/kernel", "num_attention_heads"),
    "wk": ("attention/k/kernel", "num_key_value_heads"),
    "wv": ("attention/v/kernel", "num_key_value_heads"),
    "q_norm": ("attention/q_norm/scale", None),
    "k_norm": ("attention/k_norm/scale", None),
    "wo": ("attention/proj/kernel", None),
    "w1": ("mlp/w1/kernel", None), "w3": ("mlp/w3/kernel", None),
    "w2": ("mlp/w2/kernel", None),
}
_PLAIN = {"embed": "embed/embedding", "head": "head",
          "norm_f": "RMSNorm_0/scale"}


def _paths(cfg):
    """{reference leaf: (program path, heads key or None)}."""
    from benchmark.references import olmo_hybrid as ref

    out = {name: (path, None) for name, path in _PLAIN.items()}
    for name in ref.leaves(cfg):
        if name not in _PLAIN:
            layer, leaf = name.split(".")
            path, heads = _LAYER[leaf]
            out[name] = ("block_%s/%s" % (layer[1:], path), heads)
    return out


def to_program(weights, cfg):
    """The reference's weights as the flax params of TransformerLM (one
    jitted call: the reshapes stay on the device)."""
    import jax
    from flax import traverse_util

    paths = _paths(cfg)

    def convert(w):
        return {path: (w[name] if heads is None else w[name].reshape(
            w[name].shape[0], cfg[heads], -1))
                for name, (path, heads) in paths.items()}

    return traverse_util.unflatten_dict(jax.jit(convert)(weights), sep="/")


def program_config(cfg):
    """The configuration in the source's own terms, as the program's
    ``olmo_hybrid`` spec function reads it: the file's keys are the source's
    (the head counts are those held), so it goes over as it is."""
    return cfg


def build(cfg, seed, mesh=None):
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.references import olmo_hybrid as ref
    from tensorflowonspark_tpu.models import get_model, transformer

    model = get_model("olmo_hybrid", config=program_config(cfg),
                      attention=cfg["attention"], mesh=mesh,
                      remat=cfg["remat"], dtype=cfg["dtype"])
    opt = cfg["optimizer"]
    b1, warmup = opt["b1"], opt.get("warmup_steps", 0)
    rate = opt["learning_rate"]
    if warmup:      # linear, the first update at rate / warmup
        rate = lambda count: opt["learning_rate"] * jnp.minimum(  # noqa: E731
            1.0, (count + 1) / warmup)
    return {
        "model": model, "loss": transformer.loss_fn(model),
        "params": to_program(ref.init_weights(cfg, seed), cfg),
        "extra": None,
        "optimizer": optax.adam(rate, b1=b1, b2=opt["b2"], eps=opt["eps"]),
        "compute_dtype": jnp.bfloat16 if cfg["dtype"] == "bfloat16" else None,
        # Adam's mu after one step from zero is (1 - b1) g
        "first_gradient": lambda opt_state: jax.tree_util.tree_map(
            lambda m: m / (1.0 - b1), opt_state[0].mu),
        "names": {path: name for name, (path, _) in _paths(cfg).items()},
    }
