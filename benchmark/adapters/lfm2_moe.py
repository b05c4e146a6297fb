"""How the ``lfm2_moe`` reference's weights enter the program: the one
decoder of ``models/transformer.py`` under the configuration's layer pattern
(``register_model("lfm2_moe")``), its loss and Adam, fed the benchmark's
seeded weights.  The reference never sees any of it.

A row is a token row as the ``gpt2`` adapter makes it, at the
configuration's ``seq_len`` and (sliced) vocabulary."""

from benchmark.adapters import gpt2 as _tokens
from benchmark.adapters.gpt2 import to_batch  # noqa: F401  (token rows too)


def _row_cfg(cfg):
    return {"n_positions": cfg["seq_len"], "vocab_size": cfg["vocab_size"]}


def row_dtype(cfg):
    return _tokens.row_dtype(_row_cfg(cfg))


def make_row(cfg, seed, index):
    return _tokens.make_row(_row_cfg(cfg), seed, index)


# -- weights and the program's objects ----------------------------------------

# reference leaf (after "L<i>.") -> (the program's path under "block_<i>/",
# how the leaf is reshaped: None, or the heads' key of the configuration)
_LAYER = {
    "op_norm": ("RMSNorm_0/scale", None),
    "ff_norm": ("RMSNorm_1/scale", None),
    "in_proj": ("short_conv/in_proj/kernel", None),
    "conv": ("short_conv/conv", None),
    "out_proj": ("short_conv/out_proj/kernel", None),
    "wq": ("attention/q/kernel", "num_attention_heads"),
    "wk": ("attention/k/kernel", "num_key_value_heads"),
    "wv": ("attention/v/kernel", "num_key_value_heads"),
    "q_norm": ("attention/q_norm/scale", None),
    "k_norm": ("attention/k_norm/scale", None),
    "wo": ("attention/proj/kernel", None),
    "w1": ("mlp/w1/kernel", None), "w3": ("mlp/w3/kernel", None),
    "w2": ("mlp/w2/kernel", None),
    "router": ("moe/router", None), "expert_bias": ("moe/expert_bias", None),
    "ew1": ("moe/w1", None), "ew3": ("moe/w3", None), "ew2": ("moe/w2", None),
}
_PLAIN = {"embed": "embed/embedding", "norm_f": "RMSNorm_0/scale"}


def _paths(cfg):
    """{reference leaf: (program path, heads key or None)}."""
    from benchmark.references import lfm2_moe as ref

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
    ``lfm2_moe`` builder reads it: ``num_experts`` is the router's width
    there (the file's counts the experts held, the chip's share)."""
    return dict(cfg, num_experts=cfg["router_experts"])


def build(cfg, seed, mesh=None):
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.references import lfm2_moe as ref
    from tensorflowonspark_tpu.models import get_model, transformer

    model = get_model("lfm2_moe", config=program_config(cfg),
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
