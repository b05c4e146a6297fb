"""The ``keye_vl2`` family's count, from the configuration's shapes
(``benchmark/flops.py`` has the rules and finds this file by the
configuration's ``reference``).  The counts are of the **mathematics**, not
of what an implementation multiplies:

- every matrix product's parameters once a token (q, k, v, o; the index's
  three projections; the router; the untied read-out; the embedding is a
  gather), and of the routed experts the expected share of a token's experts
  that is held here (``num_experts_per_tok * held / router_experts``: one
  expert a token for 8 of 128 with 16 held);
- the index scores over **all** causal pairs (``S (S + 1) / 2`` pairs of
  ``indexer_num_heads * indexer_head_dim`` products): the selection needs
  every one of them;
- attention over the keys a query **keeps**, ``min(t + 1, topk)`` of them:
  scores and weighted sum over ``head_dim`` a head.  A kernel that
  multiplies the pairs it then masks is credited nothing for them, so no
  share can read over 100%;
- the index's loss on the kept pairs: the heads' scores again (the
  attention kernel keeps no probabilities) and the index scores again.  Its
  gradient reaches the kept pairs only.

Read a run's ``moe_slots_local / moe_slots_total`` against the expected
share of experts, and ``dsa_tiles_touched / dsa_tiles_causal`` for what the
kept keys leave of the causal tiles."""

# one matrix product's FLOPs and bytes, their sum, and the expected share of
# a token's experts held here: the same rules as the other expert families
from benchmark.counts.lfm2_moe import (_product, _total,
                                       held_experts_per_token)


def _widths(cfg):
    """hidden, heads x width, KV heads x width, the index's heads x width."""
    sparse = cfg["sa_config"]
    return (cfg["hidden_size"],
            cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"],
            sparse["indexer_num_heads"] * sparse["indexer_head_dim"])


def _index_products(cfg):
    """(k, n) of the index's three projections: queries, its one key, the
    head weights."""
    d, _, _, index = _widths(cfg)
    sparse = cfg["sa_config"]
    return [(d, index), (d, sparse["indexer_head_dim"]),
            (d, sparse["indexer_num_heads"])]


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def kept_pairs(cfg, seq):
    """(query, key) pairs the selection keeps of one sequence:
    ``sum_t min(t + 1, topk)``."""
    short = min(cfg["sa_config"]["topk"], seq)
    return short * (short + 1) // 2 + (seq - short) * short


def forward_macs(cfg, seq):
    """Multiply-accumulates of one sequence's forward pass (norms, RoPE,
    softmax, the search for the kept keys and the gates are not products
    and are left out, as ``flops.py`` says).  The index's loss is counted
    with the backward pass it exists for (:func:`train_flops_per_example`)."""
    d, q, kv, index = _widths(cfg)
    layers = cfg["num_hidden_layers"]
    per_token = (
        layers * (2 * d * q + 2 * d * kv                     # q, o; k, v
                  + sum(k * n for k, n in _index_products(cfg))
                  + d * cfg["router_experts"]
                  + held_experts_per_token(cfg) * 3 * d
                  * cfg["moe_intermediate_size"])
        + cfg["vocab_size"] * d)                             # the read-out
    pairs = layers * (causal_pairs(seq) * index              # index scores
                      + kept_pairs(cfg, seq) * 2 * q)        # scores, sum
    return seq * per_token + pairs


def train_flops_per_example(cfg):
    """FLOPs of one optimizer step on one sequence, no recomputation: three
    times the forward pass's products, less the index scores' backward over
    the pairs that are not kept (the selection passes no gradient and the
    index's loss reaches the kept pairs only)."""
    seq = cfg["seq_len"]
    _, _, _, index = _widths(cfg)
    dropped = causal_pairs(seq) - kept_pairs(cfg, seq)
    return 2 * (3 * forward_macs(cfg, seq)
                - 2 * cfg["num_hidden_layers"] * dropped * index)


def kernels(cfg):
    """FLOPs and HBM bytes of **the forward pass of one step** under each
    ``jax.named_scope`` that a per-layer metric divides by, summed over the
    layers (forward only, for the reason ``counts/lfm2_moe.py`` gives: it is
    what ``by_scope`` shows whole of a recomputed block).

    ``attention/flash``    scores and weighted sum over the kept keys; q
                           read, o written, k and v read once;
    ``attention/select``   the index scores over all causal pairs; the
                           index's queries, keys and weights read, the kept
                           keys written as bits (a bit a pair);
    ``attention/indexer``  the index's three projections and one
                           element-wise pass for its norm and RoPE (bytes
                           only);
    ``attention/index_loss``  the index's loss: the heads' scores and the
                           index scores on the kept pairs; q, k and the
                           index's three read;
    ``moe/experts``        the three grouped SwiGLU products over the pairs
                           expected here; all held experts' weights read."""
    d, q, kv, index = _widths(cfg)
    sparse = cfg["sa_config"]
    layers = cfg["num_hidden_layers"]
    seq, batch = cfg["seq_len"], cfg["batch_size"]
    tokens = batch * seq
    fe, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
    kept, causal = batch * kept_pairs(cfg, seq), batch * causal_pairs(seq)
    index_bytes = tokens * (2 * index + 2 * sparse["indexer_head_dim"]
                            + 4 * sparse["indexer_num_heads"])
    flash = {"flops": 2 * kept * 2 * q,
             "bytes": 2 * tokens * (2 * q + 2 * kv)}
    select = {"flops": 2 * causal * index,
              "bytes": index_bytes + batch * seq * seq // 8 + 4 * tokens}
    indexer = _total([_product(tokens, k, n)
                      for k, n in _index_products(cfg)])
    indexer["bytes"] += index_bytes
    index_loss = {"flops": 2 * kept * (q + index),
                  "bytes": index_bytes + 2 * tokens * (q + kv)}
    pairs = tokens * held_experts_per_token(cfg)
    experts = _total([_product(pairs, d, fe, held),
                      _product(pairs, d, fe, held),
                      _product(pairs, fe, d, held)])

    def times(count, kernel):
        return {key: value * count for key, value in kernel.items()}

    return {"attention/flash": times(layers, flash),
            "attention/select": times(layers, select),
            "attention/indexer": times(layers, indexer),
            "attention/index_loss": times(layers, index_loss),
            "moe/experts": times(layers, experts)}
