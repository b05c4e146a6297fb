"""The ``mellum2`` family's count, from the configuration's shapes
(``benchmark/flops.py`` has the rules and finds this file by the
configuration's ``reference``).  The counts are of the **mathematics**, not
of what an implementation multiplies (``counts/keye_vl2.py`` says the same):

- every matrix product's parameters once a token (q, k, v, o; the router;
  the untied read-out; the embedding is a gather), and of the routed experts
  the expected share of a token's experts that is held here
  (``num_experts_per_tok * held / router_experts``: one expert a token for 8
  of 64 with 8 held);
- attention over the keys a query **reads**: a ``full_attention`` layer's
  ``t + 1``, a ``sliding_attention`` layer's ``min(t + 1, sliding_window)``:
  scores and weighted sum over ``head_dim`` a head.  A kernel that multiplies
  the pairs it then masks is credited nothing for them, so no share can read
  over 100%.

Read a run's ``moe_slots_local / moe_slots_total`` against the expected
share of experts, and ``swa_tiles_computed / swa_tiles_causal`` for what the
band leaves of the causal tiles."""

# one matrix product's FLOPs and bytes, their sum, and the expected share of
# a token's experts held here: the same rules as the other expert families
from benchmark.counts.lfm2_moe import (_product, _total,
                                       held_experts_per_token)


def _widths(cfg):
    """hidden, heads x width, KV heads x width."""
    return (cfg["hidden_size"],
            cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def layers_of(cfg):
    """{kind: how many layers}."""
    kinds = cfg["layer_types"]
    return {kind: kinds.count(kind)
            for kind in ("full_attention", "sliding_attention")}


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def window_pairs(cfg, seq):
    """(query, key) pairs a sliding layer reads of one sequence:
    ``sum_t min(t + 1, sliding_window)``."""
    short = min(cfg["sliding_window"], seq)
    return short * (short + 1) // 2 + (seq - short) * short


def forward_macs(cfg, seq):
    """Multiply-accumulates of one sequence's forward pass (norms, RoPE,
    softmax and the gates are not products and are left out, as ``flops.py``
    says)."""
    d, q, kv = _widths(cfg)
    n = layers_of(cfg)
    per_token = (
        cfg["num_hidden_layers"] * (
            2 * d * q + 2 * d * kv                           # q, o; k, v
            + d * cfg["router_experts"]
            + held_experts_per_token(cfg) * 3 * d
            * cfg["moe_intermediate_size"])
        + cfg["vocab_size"] * d)                             # the read-out
    pairs = 2 * q * (n["full_attention"] * causal_pairs(seq)  # scores, sum
                     + n["sliding_attention"] * window_pairs(cfg, seq))
    return seq * per_token + pairs


def train_flops_per_example(cfg):
    """FLOPs of one optimizer step on one sequence, no recomputation."""
    return 3 * 2 * forward_macs(cfg, cfg["seq_len"])


def kernels(cfg):
    """FLOPs and HBM bytes of **the forward pass of one step** under each
    ``jax.named_scope`` that a per-layer metric divides by, summed over the
    layers that have it (forward only, for the reason ``counts/lfm2_moe.py``
    gives: it is what ``by_scope`` shows whole of a recomputed block).

    ``attention/flash``         the full layers' forward kernel: scores and
                                weighted sum over every causal pair; q read,
                                o written, k and v read once;
    ``attention/flash_window``  the sliding layers' forward kernel: the same
                                over the band's pairs, the same bytes;
    ``moe/experts``             the three grouped SwiGLU products over the
                                pairs expected here; all held experts'
                                weights read."""
    d, q, kv = _widths(cfg)
    n = layers_of(cfg)
    seq, batch = cfg["seq_len"], cfg["batch_size"]
    tokens = batch * seq
    fe, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
    rows = 2 * tokens * (2 * q + 2 * kv)
    flash = {"flops": 2 * batch * causal_pairs(seq) * 2 * q, "bytes": rows}
    window = {"flops": 2 * batch * window_pairs(cfg, seq) * 2 * q,
              "bytes": rows}
    pairs = tokens * held_experts_per_token(cfg)
    experts = _total([_product(pairs, d, fe, held),
                      _product(pairs, d, fe, held),
                      _product(pairs, fe, d, held)])

    def times(count, kernel):
        return {key: value * count for key, value in kernel.items()}

    return {"attention/flash": times(n["full_attention"], flash),
            "attention/flash_window": times(n["sliding_attention"], window),
            "moe/experts": times(cfg["num_hidden_layers"], experts)}
