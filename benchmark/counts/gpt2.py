"""The ``gpt2`` family's count: 6 N per token plus causal attention, from
the configuration's shapes (``benchmark/flops.py`` has the rules and finds
this file by the configuration's ``reference``)."""


def matmul_params(cfg):
    """Parameters that sit in a matrix product of the forward pass: the
    blocks' four matrices and the tied read-out (the embedding look-up and
    the positions are gathers, not products)."""
    d, inner = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    per_layer = d * 3 * d + d * d + 2 * d * inner
    return cfg["n_layer"] * per_layer + cfg["vocab_size"] * d


def forward_macs(cfg, seq):
    """Multiply-accumulates of one sequence's forward pass: every matmul
    parameter once a token, plus the attention scores and the weighted sum.
    Causal attention needs half of the S x S products (the masked half is
    not model work): S * (S + 1) / 2 per head and product."""
    d = cfg["n_embd"]
    attn = cfg["n_layer"] * 2 * (seq * (seq + 1) // 2) * d
    return seq * matmul_params(cfg) + attn


def train_flops_per_example(cfg, seq=None):
    """FLOPs of one optimizer step on one sequence (6 N per token plus
    attention, no recomputation)."""
    return 3 * 2 * forward_macs(cfg, seq or cfg["n_positions"])
