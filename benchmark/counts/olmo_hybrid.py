"""The ``olmo_hybrid`` family's count, from the configuration's shapes
(``benchmark/flops.py`` has the rules and finds this file by the
configuration's ``reference``).  The counts are of the **mathematics**, not
of what an implementation multiplies (``counts/nemotron_h.py`` says the
same):

- every matrix product's parameters once a token (a Gated DeltaNet layer's
  two projections; q, k, v, o; the SwiGLU's three matrices; the untied
  read-out; the embedding is a gather);
- attention over the ``t + 1`` keys a query reads: scores and weighted sum
  over ``head_dim`` a head;
- the delta rule **in its chunked form**, the least that form needs
  (``ops/gated_delta.py``'s six lines): in a chunk of ``L`` positions the
  ``L (L - 1) / 2`` pairs below the diagonal of ``K K^T`` over ``dk`` and of
  the triangular solve over ``dv`` (substitution on the right-hand side; an
  inverse taken apart is credited no more), the ``L (L + 1) / 2`` causal
  pairs of ``Q K^T`` over ``dk`` and of its product with the writes over
  ``dv``, and three products with the state, ``L x dk x dv`` each: ``L (dk +
  dv) + 3 dk dv`` multiply-accumulates a position and head.  A kernel that
  multiplies the pairs it then masks is credited nothing for them, so no
  share can read over 100%.  The recurrence taken position by position
  would be ``3 dk dv`` a head and position.

Read a run's ``delta_chunks`` against ``layers x rows x seq_len /
linear_chunk_size``."""

# one matrix product's FLOPs and bytes: the same rule as the other families
from benchmark.counts.lfm2_moe import _product


def layers_of(cfg):
    """{kind: how many layers}."""
    kinds = cfg["layer_types"]
    return {kind: kinds.count(kind)
            for kind in ("linear_attention", "full_attention")}


def _delta(cfg):
    """heads, key width, value width, in_proj's columns, out_proj's rows."""
    heads = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return (heads, dk, dv, heads * (2 * dk + 2 * dv + 2), heads * dv)


def _attention(cfg):
    """heads x width, KV heads x width."""
    dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]
    return cfg["num_attention_heads"] * dim, cfg["num_key_value_heads"] * dim


def scan_macs_per_token(cfg):
    """Multiply-accumulates a position of one Gated DeltaNet layer's chunked
    delta rule (module docstring)."""
    heads, dk, dv, _, _ = _delta(cfg)
    return heads * (cfg["linear_chunk_size"] * (dk + dv) + 3 * dk * dv)


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def forward_macs(cfg, seq):
    """Multiply-accumulates of one sequence's forward pass (norms, the
    convolution's taps, the gates and softmax are not products and are left
    out, as ``flops.py`` says)."""
    d = cfg["hidden_size"]
    n = layers_of(cfg)
    _, _, _, wide, inner = _delta(cfg)
    q, kv = _attention(cfg)
    per_token = (
        n["linear_attention"] * (d * wide + inner * d
                                 + scan_macs_per_token(cfg))
        + n["full_attention"] * (2 * d * q + 2 * d * kv)
        + len(cfg["layer_types"]) * 3 * d * cfg["intermediate_size"]
        + cfg["vocab_size"] * d)                             # the read-out
    return seq * per_token + n["full_attention"] * 2 * q * causal_pairs(seq)


def train_flops_per_example(cfg):
    """FLOPs of one optimizer step on one sequence, no recomputation."""
    return 3 * 2 * forward_macs(cfg, cfg["seq_len"])


def kernels(cfg):
    """FLOPs and HBM bytes of **the forward pass of one step** under each
    ``jax.named_scope`` that a per-layer metric divides by, summed over the
    layers that have it (forward only, for the reason ``counts/lfm2_moe.py``
    gives: it is what ``by_scope`` shows whole of a recomputed block).

    ``delta/scan``      the chunked form's products; q, k and v read and o
                        written, the decays' sums and the write strengths
                        read (float32, a head), the chunk states written
                        once (the backward pass reads them once), whatever
                        implements it;
    ``delta/in_proj``,
    ``delta/out_proj``  a Gated DeltaNet layer's two projections;
    ``attention/flash`` the forward kernel: scores and weighted sum over
                        every causal pair; q read, o written, k and v once."""
    d = cfg["hidden_size"]
    n = layers_of(cfg)
    seq, batch = cfg["seq_len"], cfg["batch_size"]
    tokens = batch * seq
    heads, dk, dv, wide, inner = _delta(cfg)
    scan = {"flops": 2 * tokens * scan_macs_per_token(cfg),
            "bytes": tokens * heads * (
                2 * (2 * dk + 2 * dv) + 2 * 4
                + 2 * dk * dv // cfg["linear_chunk_size"])}
    q, kv = _attention(cfg)
    flash = {"flops": 2 * batch * causal_pairs(seq) * 2 * q,
             "bytes": 2 * tokens * (2 * q + 2 * kv)}

    def times(count, kernel):
        return {key: value * count for key, value in kernel.items()}

    return {"delta/scan": times(n["linear_attention"], scan),
            "delta/in_proj": times(n["linear_attention"],
                                   _product(tokens, d, wide)),
            "delta/out_proj": times(n["linear_attention"],
                                    _product(tokens, inner, d)),
            "attention/flash": times(n["full_attention"], flash)}
