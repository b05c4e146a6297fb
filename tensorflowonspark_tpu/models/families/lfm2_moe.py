"""The LFM2 mixture family (registered as ``lfm2_moe``): RMSNorm, no position
table, ``layer_types`` choosing a gated short convolution
(:class:`~tensorflowonspark_tpu.models.transformer.ShortConv`) or
grouped-query attention with per-head q/k RMSNorm and RoPE for each layer,
``num_dense_layers`` leading SwiGLU feed-forwards and then
:class:`~tensorflowonspark_tpu.models.transformer.TopKExperts` (top-k of E by
sigmoid scores with a selection bias, nothing dropped, told which experts it
holds).  ``attention`` picks the contraction as for ``transformer_lm``
(grouped KV heads reach ``flash`` as they are and are repeated for the
others)."""

from tensorflowonspark_tpu.models.transformer import (
    DecoderSpec, LayerSpec, register_decoder)


@register_decoder("lfm2_moe")
def lfm2_moe_spec(config):
    """:class:`DecoderSpec` of an LFM2 mixture ``config.json`` (a dict with
    the source's keys: ``layer_types``, ``num_dense_layers``, ``conv_L_cache``,
    ``num_experts_per_tok``, ...).  ``num_experts`` is the router's width;
    ``held_experts`` (``[first, count]``, optional) the experts this program
    holds of each expert layer; ``flash_block`` (optional) the attention
    kernel's block."""
    held = config.get("held_experts")
    common = dict(
        norm="rmsnorm", norm_eps=config["norm_eps"], positions="rope",
        num_heads=config["num_attention_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], qk_norm=True,
        rope_theta=float(config["rope_theta"]),
        flash_block=config.get("flash_block", 512),
        conv_kernel=config["conv_L_cache"],
        ff_size=config["intermediate_size"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_size=config["moe_intermediate_size"],
        held_experts=tuple(held) if held else None,
        norm_topk=config.get("norm_topk_prob", True),
        routed_scaling=float(config.get("routed_scaling_factor", 1.0)))
    kinds = {"conv": "conv", "full_attention": "attention"}
    layers = tuple(
        LayerSpec(op=kinds[kind],
                  ff="swiglu" if i < config["num_dense_layers"] else "experts",
                  **common)
        for i, kind in enumerate(config["layer_types"]))
    if len(layers) != config["num_hidden_layers"]:
        raise ValueError("{} layer_types for num_hidden_layers {}".format(
            len(layers), config["num_hidden_layers"]))
    return DecoderSpec(vocab_size=config["vocab_size"],
                       hidden_size=config["hidden_size"], layers=layers,
                       norm="rmsnorm", norm_eps=config["norm_eps"])
