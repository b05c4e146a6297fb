"""The DeepSeek-V2 family without ``q_lora_rank`` (registered as
``deepseek_v2``): RMSNorm, latent attention (``op="mla"``: a down-projection
to a ``kv_lora_rank`` latent and one shared rotary key, an RMSNorm on the
latent, an up-projection to per-head keys and values narrower than the
scores' 192 dimensions, YaRN frequencies with interleaved pairing on the
rotary part only, the family's softmax scale), ``first_k_dense_replace``
leading SwiGLU feed-forwards and then
:class:`~tensorflowonspark_tpu.models.transformer.TopKExperts` by softmax
scores without a bias leaf and without renormalisation, beside a shared
SwiGLU that every token passes through, and a read-out of its own
(``tied_readout=False``).  ``attention`` picks the contraction as for
``transformer_lm`` (``flash`` takes values narrower than the scores' width
as they are; ``full`` is the same mathematics without a kernel)."""

from tensorflowonspark_tpu.models.transformer import (
    DecoderSpec, LayerSpec, register_decoder, yarn_mscale)


@register_decoder("deepseek_v2")
def deepseek_v2_spec(config):
    """:class:`DecoderSpec` of a DeepSeek-V2 ``config.json`` without a query
    latent (a dict with the source's keys: ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rope_scaling``, ``first_k_dense_replace``, ``n_shared_experts``,
    ``scoring_func``, ...).  ``n_routed_experts`` is the router's width;
    ``held_experts`` (``[first, count]``, optional) the experts this program
    holds of each expert layer; ``flash_block`` (optional) the attention
    kernel's block.  What the family's modelling code does and no key says:
    interleaved RoPE pairing; a softmax scale of ``(nope + rope) ** -0.5``
    times YaRN's factor of ``mscale_all_dim``, squared; cos and sin times
    the ratio of the factors of ``mscale`` and ``mscale_all_dim``."""
    unsupported = {
        "q_lora_rank": config.get("q_lora_rank") is not None,
        "topk_method": config.get("topk_method", "greedy") != "greedy",
        "n_group": config.get("n_group", 1) != 1,
        "moe_layer_freq": config.get("moe_layer_freq", 1) != 1,
        "scoring_func": config.get("scoring_func", "softmax")
        not in ("softmax", "sigmoid"),
        "rope_scaling": (config.get("rope_scaling") or {"type": "yarn"})[
            "type"] != "yarn"}
    if any(unsupported.values()):
        raise ValueError("deepseek_v2: no support for this config's {}"
                         .format(sorted(k for k, v in unsupported.items()
                                        if v)))
    held = config.get("held_experts")
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    scale, yarn = (nope + rot) ** -0.5, None
    scaling = config.get("rope_scaling")
    if scaling:
        yarn = tuple(float(scaling[k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim"))
        scale *= yarn_mscale(yarn[0], yarn[5]) ** 2
    common = dict(
        op="mla", norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        positions="rope", num_heads=config["num_attention_heads"],
        head_dim=nope + rot, kv_rank=config["kv_lora_rank"], nope_dim=nope,
        rope_dim=rot, v_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]), rope_pairing="interleaved",
        rope_yarn=yarn, attn_scale=scale,
        flash_block=config.get("flash_block", 512),
        ff_size=config["intermediate_size"],
        num_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_size=config["moe_intermediate_size"],
        held_experts=tuple(held) if held else None,
        router_score=config.get("scoring_func", "softmax"),
        selection_bias=False,
        norm_topk=config.get("norm_topk_prob", False),
        routed_scaling=float(config.get("routed_scaling_factor", 1.0)),
        shared_size=(config.get("n_shared_experts") or 0)
        * config["moe_intermediate_size"])
    layers = tuple(
        LayerSpec(ff="swiglu" if i < config["first_k_dense_replace"]
                  else "experts", **common)
        for i in range(config["num_hidden_layers"]))
    return DecoderSpec(vocab_size=config["vocab_size"],
                       hidden_size=config["hidden_size"], layers=layers,
                       norm="rmsnorm", norm_eps=config["rms_norm_eps"],
                       tied_readout=config.get("tie_word_embeddings", False))
