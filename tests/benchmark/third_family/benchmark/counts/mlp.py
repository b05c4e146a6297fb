"""The worked example's count (``benchmark/README.md``, "Adding things"):
found by ``benchmark/flops.py`` through the configuration's ``"reference":
"mlp"``.  The embedding is a gather, not a product; the two matrix products
cost 2 FLOPs a multiply-accumulate forward and twice that backward."""


def _tokens(cfg):
    return cfg["n_positions"] - 1       # the last position has no target


def train_flops_per_example(cfg):
    d, h, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    return 3 * 2 * _tokens(cfg) * (d * h + h * v)


def kernels(cfg):
    """One step's FLOPs and HBM bytes under each ``jax.named_scope`` of the
    adapter's loss: the matrix product forward and backward; the bytes are
    the operands and the result read or written once a pass in bfloat16 (2
    bytes), the float32 kernel once."""
    d, h, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    rows = cfg["batch_size"] * _tokens(cfg)

    def scope(k, n):
        return {"flops": 3 * 2 * rows * k * n,
                "bytes": 3 * 2 * rows * (k + n) + 4 * k * n}

    return {"mlp/hidden": scope(d, h), "mlp/readout": scope(h, v)}
