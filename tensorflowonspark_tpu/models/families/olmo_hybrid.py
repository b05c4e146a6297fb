"""The Olmo Hybrid family (registered as ``olmo_hybrid``): **a part's output
is normed, not its input**, ``h = x + RMSNorm(mixer(x))``, ``out = h +
RMSNorm(mlp(h))`` (Olmo 2's and Olmo 3's block), and the mixer is by
``layer_types``: ``linear_attention`` a Gated DeltaNet layer
(:class:`~tensorflowonspark_tpu.models.transformer.GatedDelta`: one input
projection to q, k, v, a gate and two numbers a head, a causal depthwise
convolution over q, k and v, the chunked delta rule of
:mod:`~tensorflowonspark_tpu.ops.gated_delta`, a head's RMSNorm under the
gate, the output projection), ``full_attention`` causal softmax attention
with an RMSNorm over the **whole** q and k projections and no positions at
all (no RoPE, no table); a SwiGLU feed-forward in every layer; an untied
read-out.  ``attention`` picks the attention layers' contraction as for
``transformer_lm``; the delta rule's kernels run on a TPU and their
``jax.numpy`` form elsewhere whatever it says."""

from tensorflowonspark_tpu.models.transformer import (
    DecoderSpec, LayerSpec, register_decoder)


@register_decoder("olmo_hybrid")
def olmo_hybrid_spec(config):
    """:class:`DecoderSpec` of an Olmo Hybrid ``config.json`` (a dict with
    the source's keys: ``layer_types``, ``linear_num_key_heads``,
    ``linear_num_value_heads``, ``linear_key_head_dim``,
    ``linear_value_head_dim``, ``linear_conv_kernel_dim``,
    ``linear_allow_neg_eigval``, ``num_attention_heads``,
    ``num_key_value_heads``, ``intermediate_size``, ``rms_norm_eps``,
    ``rope_parameters``, ...).  ``head_dim`` (optional) is the attention
    heads' width where the heads held are not ``hidden_size`` over it (a
    chip's share of the heads); ``flash_block`` and ``linear_chunk_size``
    (optional) the attention kernels' block and the delta rule's chunk.
    What the family's modelling code does and no key says: the norm's place,
    the QK-norm and its extent, no positions in the attention layers, one
    convolution a stream and none on the gate, the gate after the head's
    norm."""
    kinds = config["layer_types"]
    rope = (config.get("rope_parameters") or {}).get(
        "rope_theta", config.get("rope_theta"))
    unsupported = {
        "layer_types": not set(kinds) <= {"linear_attention",
                                          "full_attention"},
        "bias": any(config.get(k) for k in ("attention_bias", "mlp_bias")),
        "tie_word_embeddings": config.get("tie_word_embeddings", False),
        "rope_theta": rope is not None,
        "hidden_act": config.get("hidden_act", "silu") != "silu",
        "linear_num_key_heads": config["linear_num_key_heads"]
        != config["linear_num_value_heads"],
        "sliding_window": config.get("sliding_window") is not None}
    if any(unsupported.values()):
        raise ValueError("olmo_hybrid: no support for this config's {}".format(
            sorted(k for k, v in unsupported.items() if v)))
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types of {} for num_hidden_layers {}".format(
            len(kinds), config["num_hidden_layers"]))
    heads = config["num_attention_heads"]
    eps = config.get("rms_norm_eps", 1e-6)
    common = dict(
        ff="swiglu", ff_size=config["intermediate_size"], norm="rmsnorm",
        norm_eps=eps, norm_place="output", positions="none", num_heads=heads,
        head_dim=config.get("head_dim") or config["hidden_size"] // heads,
        num_kv_heads=config["num_key_value_heads"], qk_norm="whole",
        flash_block=config.get("flash_block", 512),
        conv_kernel=config["linear_conv_kernel_dim"],
        delta_heads=config["linear_num_value_heads"],
        delta_key_dim=config["linear_key_head_dim"],
        delta_value_dim=config["linear_value_head_dim"],
        delta_neg_eigval=bool(config.get("linear_allow_neg_eigval", False)),
        delta_chunk=config.get("linear_chunk_size", 64))
    of_kind = {"linear_attention": LayerSpec(op="gated_delta", **common),
               "full_attention": LayerSpec(op="attention", **common)}
    return DecoderSpec(vocab_size=config["vocab_size"],
                       hidden_size=config["hidden_size"],
                       layers=tuple(of_kind[kind] for kind in kinds),
                       norm="rmsnorm", norm_eps=eps, tied_readout=False)
