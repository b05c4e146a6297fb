"""The ``deepseek_v2`` family's count, from the configuration's shapes
(``benchmark/flops.py`` has the rules and finds this file by the
configuration's ``reference``): every matrix product's parameters once a
token (latent attention's three projections and its output, the dense or the
shared SwiGLU, the router, the untied read-out; the embedding is a gather),
causal attention's half of the S x S products over ``qk_nope_head_dim +
qk_rope_head_dim`` for the scores and ``v_head_dim`` for the weighted sum,
and of the routed experts the **expected** share of a token's experts that
is held here (``num_experts_per_tok * held / router_experts``: 0.75 of an
expert a token for 6 of 64 with 8 held).  Read a run's ``moe_slots_local /
moe_slots_total`` against that expectation: the counters say what the router
really sent here."""

# one matrix product's FLOPs and bytes, their sum, and the expected share of
# a token's experts held here: the same rules as the other expert family's
from benchmark.counts.lfm2_moe import (_product, _total,
                                       held_experts_per_token)


def _layers(cfg):
    dense = cfg["first_k_dense_replace"]
    return {"attention": cfg["num_hidden_layers"], "dense": dense,
            "experts": cfg["num_hidden_layers"] - dense}


def _widths(cfg):
    """hidden, heads, the scores' width, the values' width, the latent with
    its rotary key."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def _latent_products(cfg):
    """(k, n) of latent attention's three projections round the kernel: q,
    the down-projection with its rotary key, the up-projection."""
    d, heads, dk, dv, down = _widths(cfg)
    return [(d, heads * dk), (d, down),
            (cfg["kv_lora_rank"], heads * (cfg["qk_nope_head_dim"] + dv))]


def forward_macs(cfg, seq):
    """Multiply-accumulates of one sequence's forward pass (norms, RoPE,
    softmax and the gates are element-wise and left out, as ``flops.py``
    says)."""
    d, heads, dk, dv, _ = _widths(cfg)
    n = _layers(cfg)
    fe = cfg["moe_intermediate_size"]
    per_token = (
        n["attention"] * (sum(k * m for k, m in _latent_products(cfg))
                          + heads * dv * d)                  # and W_o
        + n["dense"] * 3 * d * cfg["intermediate_size"]
        + n["experts"] * (d * cfg["router_experts"]
                          + 3 * d * cfg["n_shared_experts"] * fe
                          + held_experts_per_token(cfg) * 3 * d * fe)
        + cfg["vocab_size"] * d)                    # the read-out
    # causal scores over dk and weighted sum over dv: S (S + 1) / 2 products
    # a head each
    attn = n["attention"] * (seq * (seq + 1) // 2) * heads * (dk + dv)
    return seq * per_token + attn


def train_flops_per_example(cfg):
    """FLOPs of one optimizer step on one sequence, no recomputation."""
    return 3 * 2 * forward_macs(cfg, cfg["seq_len"])


def kernels(cfg):
    """FLOPs and HBM bytes of **the forward pass of one step** under each
    ``jax.named_scope`` that a per-layer metric divides by, summed over the
    layers that have it (forward only, for the reason ``counts/lfm2_moe.py``
    gives: it is what ``by_scope`` shows whole of a recomputed block).

    ``attention/flash``   the forward kernel alone: QK^T over the scores'
                          width and PV over the values', causal half; q and
                          k read at 192, v read and o written at 128, once;
    ``attention/latent``  the three projections round the kernel, plus one
                          element-wise pass for RoPE and building K (q read
                          and written, k_nope read, k written; bytes only);
    ``moe/experts``       the three grouped SwiGLU products over the pairs
                          expected here; all held experts' weights are read;
    ``moe/shared``        the shared expert's three products, every token."""
    d, heads, dk, dv, _ = _widths(cfg)
    n = _layers(cfg)
    seq, batch = cfg["seq_len"], cfg["batch_size"]
    tokens = batch * seq
    fe, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
    pairs = tokens * held_experts_per_token(cfg)
    flash = {"flops": 2 * batch * (seq * (seq + 1) // 2) * heads * (dk + dv),
             "bytes": 2 * tokens * heads * (2 * dk + 2 * dv)}
    latent = _total([_product(tokens, k, m)
                     for k, m in _latent_products(cfg)])
    latent["bytes"] += 2 * tokens * heads * (
        3 * dk + cfg["qk_nope_head_dim"])
    experts = _total([_product(pairs, d, fe, held),
                      _product(pairs, d, fe, held),
                      _product(pairs, fe, d, held)])
    fs = cfg["n_shared_experts"] * fe
    shared = _total([_product(tokens, d, fs), _product(tokens, d, fs),
                     _product(tokens, fs, d)])

    def times(count, kernel):
        return {key: value * count for key, value in kernel.items()}

    return {"attention/flash": times(n["attention"], flash),
            "attention/latent": times(n["attention"], latent),
            "moe/experts": times(n["experts"], experts),
            "moe/shared": times(n["experts"], shared)}
