"""The ``lfm2_moe`` family's count, from the configuration's shapes
(``benchmark/flops.py`` has the rules and finds this file by the
configuration's ``reference``): every matrix product's parameters once a
token, causal attention's half of the S x S products, and of the expert
layers the **expected** share of a token's experts that is held here
(``num_experts_per_tok * held / router_experts``: one expert a token for 4 of
32 with 8 held).  Read a run's ``moe_slots_local / moe_slots_total`` against
that expectation: the counters say what the router really sent here."""


def _layers(cfg):
    kinds = cfg["layer_types"]
    return {"conv": kinds.count("conv"),
            "attention": kinds.count("full_attention"),
            "dense": cfg["num_dense_layers"],
            "experts": cfg["num_hidden_layers"] - cfg["num_dense_layers"]}


def _widths(cfg):
    d = cfg["hidden_size"]
    kv = (d // cfg["num_attention_heads"]) * cfg["num_key_value_heads"]
    return d, kv, cfg["intermediate_size"], cfg["moe_intermediate_size"]


def held_experts_per_token(cfg):
    """Expected experts of a token that are held here, under even routing."""
    return (cfg["num_experts_per_tok"] * cfg["held_experts"][1]
            / cfg["router_experts"])


def forward_macs(cfg, seq):
    """Multiply-accumulates of one sequence's forward pass (the embedding
    look-up is a gather; the convolution's taps, the norms, RoPE and the
    gates are element-wise and left out, as ``flops.py`` says)."""
    d, kv, f, fe = _widths(cfg)
    n = _layers(cfg)
    per_token = (
        n["conv"] * 4 * d * d                       # in_proj 3 d^2, out_proj
        + n["attention"] * (2 * d * d + 2 * d * kv)  # q, o; k, v
        + n["dense"] * 3 * d * f
        + n["experts"] * (d * cfg["router_experts"]
                          + held_experts_per_token(cfg) * 3 * d * fe)
        + cfg["vocab_size"] * d)                    # the tied read-out
    # causal scores and weighted sum: S (S + 1) / 2 products a head each
    attn = n["attention"] * 2 * (seq * (seq + 1) // 2) * d
    return seq * per_token + attn


def train_flops_per_example(cfg):
    """FLOPs of one optimizer step on one sequence, no recomputation."""
    return 3 * 2 * forward_macs(cfg, cfg["seq_len"])


def _product(rows, k, n, copies=1):
    """One matrix product of the forward pass: FLOPs, and HBM bytes with the
    operands and the result read or written once in bfloat16 and the
    float32 weights once (the rule of the worked example's count, one pass
    of its three)."""
    return {"flops": 2 * rows * k * n,
            "bytes": 2 * rows * (k + n) + 4 * k * n * copies}


def _total(parts):
    return {key: sum(p[key] for p in parts) for key in ("flops", "bytes")}


def kernels(cfg):
    """FLOPs and HBM bytes of **the forward pass of one step** under each
    ``jax.named_scope`` that a per-layer metric divides by, summed over the
    layers that have it (the readers sum the trace's seconds over
    ``block_*/<scope>`` likewise).

    Forward only, because that is what the trace's ``by_scope`` can show of
    a block that is recomputed: ``benchmark/trace_reduce.py`` keeps a scope's
    first four parts, and the operations of a remat block's backward and
    recomputed passes are named ``TransformerLM/TransformerLM/checkpoint/
    [rematted_computation/]block_i/...`` (the outer transformation's copy of
    the module's name, then ``checkpoint``), so only the first forward pass
    carries ``TransformerLM/block_i/moe/experts`` whole.  Counts and seconds
    are of the same operations, so each share is a true share of a roofline;
    the backward kernels are not in it (PERF.md, Open questions).

    ``moe/experts``      the three grouped SwiGLU products over the pairs
                         expected here; all held experts' weights are read;
    ``short_conv``       in_proj and out_proj, plus the gates' and the taps'
                         element-wise passes over [tokens, d] (bytes only);
    ``attention/flash``  the forward kernel alone: QK^T and PV, causal half;
                         q, k, v read and o written once."""
    d, kv, _, fe = _widths(cfg)
    n = _layers(cfg)
    seq, batch = cfg["seq_len"], cfg["batch_size"]
    tokens = batch * seq
    pairs = tokens * held_experts_per_token(cfg)
    held = cfg["held_experts"][1]
    experts = _total([_product(pairs, d, fe, held),
                      _product(pairs, d, fe, held),
                      _product(pairs, fe, d, held)])
    conv = _total([_product(tokens, d, 3 * d), _product(tokens, d, d)])
    conv["bytes"] += 2 * tokens * 4 * d         # B, u, C in; the gated out
    flash = {"flops": 2 * batch * 2 * (seq * (seq + 1) // 2) * d,
             "bytes": 2 * tokens * (2 * d + 2 * kv)}
    return {
        "moe/experts": {k: v * n["experts"] for k, v in experts.items()},
        "short_conv": {k: v * n["conv"] for k, v in conv.items()},
        "attention/flash": {k: v * n["attention"] for k, v in flash.items()},
    }
