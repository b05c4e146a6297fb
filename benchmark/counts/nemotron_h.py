"""The ``nemotron_h`` family's count, from the configuration's shapes
(``benchmark/flops.py`` has the rules and finds this file by the
configuration's ``reference``).  The counts are of the **mathematics**, not
of what an implementation multiplies (``counts/keye_vl2.py`` says the same):

- every matrix product's parameters once a token (a Mamba-2 layer's two
  projections; q, k, v, o; the router; the shared expert's two matrices; the
  untied read-out; the embedding is a gather), and of the routed experts the
  expected share of a token's experts that is held here
  (``num_experts_per_tok * held / router_experts``: 0.375 of an expert a
  token for 6 of 128 with 8 held), two matrices an expert;
- attention over the ``t + 1`` keys a query reads: scores and weighted sum
  over ``head_dim`` a head;
- the state-space scan **in its chunked form**, the least that form needs
  (``ops/ssd_scan.py``'s four products): in a chunk of ``L`` positions the
  ``L (L + 1) / 2`` causal pairs of ``C B^T`` over the state's width, once a
  **group** (its heads share B and C), and of the weights times ``x`` over a
  head's width, once a head; the carried state's part of the output and the
  chunk's own state, ``L x head_dim x state`` each a head.  A kernel that
  multiplies the pairs it then masks, or the scores once a head, is
  credited nothing for them, so no share can read over 100%.  The
  recurrence taken position by position would be ``2 x head_dim x state`` a
  head and position, 2.1 MFLOP a token at the published sizes; the chunked
  form's 2.76 is what a chip can run at all.

Read a run's ``moe_slots_local / moe_slots_total`` against the expected
share of experts, and ``ssd_chunks`` against ``layers x rows x seq_len /
chunk_size``."""

# one matrix product's FLOPs and bytes, their sum, and the expected share of
# a token's experts held here: the same rules as the other expert families
from benchmark.counts.lfm2_moe import (_product, _total,
                                       held_experts_per_token)


def layers_of(cfg):
    """{kind: how many layers}, kinds ``M``, ``E``, ``*``."""
    pattern = cfg["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def _mamba(cfg):
    """heads, head width, state, groups, inner width, in_proj's columns."""
    heads, dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    state, groups = cfg["ssm_state_size"], cfg["n_groups"]
    inner = heads * dim
    return (heads, dim, state, groups, inner,
            2 * inner + 2 * groups * state + heads)


def _attention(cfg):
    """heads x width, KV heads x width."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def _shared(cfg):
    return cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]


def scan_macs_per_token(cfg):
    """Multiply-accumulates a position of one Mamba-2 layer's chunked scan
    (module docstring)."""
    heads, dim, state, groups, _, _ = _mamba(cfg)
    half = (cfg["chunk_size"] + 1) / 2      # causal pairs a position
    return groups * half * state + heads * (half * dim + 2 * dim * state)


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def forward_macs(cfg, seq):
    """Multiply-accumulates of one sequence's forward pass (norms, the
    convolution's taps, the gates and softmax are not products and are left
    out, as ``flops.py`` says)."""
    d = cfg["hidden_size"]
    n = layers_of(cfg)
    _, _, _, _, inner, wide = _mamba(cfg)
    q, kv = _attention(cfg)
    per_token = (
        n["M"] * (d * wide + inner * d + scan_macs_per_token(cfg))
        + n["E"] * (d * cfg["router_experts"]
                    + held_experts_per_token(cfg) * 2 * d
                    * cfg["moe_intermediate_size"] + 2 * d * _shared(cfg))
        + n["*"] * (2 * d * q + 2 * d * kv)
        + cfg["vocab_size"] * d)                             # the read-out
    return seq * per_token + n["*"] * 2 * q * causal_pairs(seq)


def train_flops_per_example(cfg):
    """FLOPs of one optimizer step on one sequence, no recomputation."""
    return 3 * 2 * forward_macs(cfg, cfg["seq_len"])


def kernels(cfg):
    """FLOPs and HBM bytes of **the forward pass of one step** under each
    ``jax.named_scope`` that a per-layer metric divides by, summed over the
    layers that have it (forward only, for the reason ``counts/lfm2_moe.py``
    gives: it is what ``by_scope`` shows whole of a recomputed block).

    ``mamba/scan``      the four chunked products; x read and y written,
                        B and C read, the step sizes and the decays' sums
                        read (float32, a head), the chunk states written
                        once (the backward pass reads them once);
    ``mamba/in_proj``,
    ``mamba/out_proj``  a Mamba-2 layer's two projections;
    ``attention/flash`` the forward kernel: scores and weighted sum over
                        every causal pair; q read, o written, k and v once;
    ``moe/experts``     the two grouped products over the pairs expected
                        here; all held experts' weights read;
    ``moe/shared``      the shared expert's two products, every token."""
    d = cfg["hidden_size"]
    n = layers_of(cfg)
    seq, batch = cfg["seq_len"], cfg["batch_size"]
    tokens = batch * seq
    heads, dim, state, groups, inner, wide = _mamba(cfg)
    scan = {"flops": 2 * tokens * scan_macs_per_token(cfg),
            "bytes": tokens * (2 * (2 * inner + 2 * groups * state)
                               + 2 * 4 * heads
                               + 2 * inner * state // cfg["chunk_size"])}
    q, kv = _attention(cfg)
    flash = {"flops": 2 * batch * causal_pairs(seq) * 2 * q,
             "bytes": 2 * tokens * (2 * q + 2 * kv)}
    fe, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
    pairs = tokens * held_experts_per_token(cfg)
    experts = _total([_product(pairs, d, fe, held),
                      _product(pairs, fe, d, held)])
    shared = _total([_product(tokens, d, _shared(cfg)),
                     _product(tokens, _shared(cfg), d)])

    def times(count, kernel):
        return {key: value * count for key, value in kernel.items()}

    return {"mamba/scan": times(n["M"], scan),
            "mamba/in_proj": times(n["M"], _product(tokens, d, wide)),
            "mamba/out_proj": times(n["M"], _product(tokens, inner, d)),
            "attention/flash": times(n["*"], flash),
            "moe/experts": times(n["E"], experts),
            "moe/shared": times(n["E"], shared)}
