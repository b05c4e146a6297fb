"""The Nemotron-H family (registered as ``nemotron_h``): **every layer is
one part alone**, ``x + part(RMSNorm(x))``, by ``hybrid_override_pattern``:
``M`` a Mamba-2 layer
(:class:`~tensorflowonspark_tpu.models.transformer.Mamba2`: one input
projection to a gate, ``x``, ``B``, ``C`` and a step size a head, a causal
depthwise convolution with a bias, the chunked state-space scan of
:mod:`~tensorflowonspark_tpu.ops.ssd_scan`, a gated RMSNorm by groups, the
output projection), ``E`` an expert layer (sigmoid scores with a selection
bias, top-k renormalised and scaled, experts of **two** matrices with
``relu(.)**2`` between, a shared expert of the same form), ``*``
grouped-query attention with no positions at all (no RoPE, no table), ``-``
a dense feed-forward of the experts' form; an untied read-out.
``attention`` picks the attention layers' contraction as for
``transformer_lm``; the scan's kernels run on a TPU and its ``jax.numpy``
form elsewhere whatever it says."""

from tensorflowonspark_tpu.models.transformer import (
    DecoderSpec, LayerSpec, register_decoder)


@register_decoder("nemotron_h")
def nemotron_h_spec(config):
    """:class:`DecoderSpec` of a Nemotron-H ``config.json`` (a dict with the
    source's keys: ``hybrid_override_pattern``, ``mamba_num_heads``,
    ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``conv_kernel``,
    ``chunk_size``, ``n_routed_experts``, ``num_experts_per_tok``,
    ``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``,
    ``routed_scaling_factor``, ``mlp_hidden_act``, ...).  One character of
    the pattern a layer, each one part alone: ``M`` Mamba-2, ``E`` experts,
    ``*`` attention, ``-`` a dense feed-forward of ``intermediate_size``.
    ``n_routed_experts`` is the router's width; ``held_experts`` (``[first,
    count]``, optional) the experts this program holds of each expert layer;
    ``flash_block`` (optional) the attention kernels' block.
    ``n_groups`` is the scan's (groups of B and C) and ``n_group`` /
    ``topk_group`` the router's.  What the family's modelling code does and
    no key says: the inner width is ``mamba_num_heads * mamba_head_dim``
    (``expand`` is not read), one convolution over x, B and C together, the
    gate before the grouped norm, no clamp on the step size, ``relu2(x) =
    relu(x) ** 2``, and **no rotary embedding** in the attention layers
    (``rope_theta`` and ``partial_rotary_factor`` are not read)."""
    pattern = config["hybrid_override_pattern"]
    unsupported = {
        "hybrid_override_pattern": not set(pattern) <= set("ME*-"),
        "n_group": config.get("n_group", 1) != 1
        or config.get("topk_group", 1) != 1,
        "mlp_hidden_act": config.get("mlp_hidden_act", "relu2") != "relu2",
        "mamba_hidden_act": config.get("mamba_hidden_act", "silu") != "silu",
        "bias": any(config.get(k) for k in (
            "attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias")),
        "use_conv_bias": not config.get("use_conv_bias", True),
        "sliding_window": config.get("sliding_window") is not None}
    if any(unsupported.values()):
        raise ValueError("nemotron_h: no support for this config's {}".format(
            sorted(k for k, v in unsupported.items() if v)))
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError(
            "a hybrid_override_pattern of {} for num_hidden_layers {}".format(
                len(pattern), config["num_hidden_layers"]))
    held = config.get("held_experts")
    eps = config.get("layer_norm_epsilon", config.get("norm_eps", 1e-5))
    common = dict(
        norm="rmsnorm", norm_eps=eps, positions="none",
        num_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        flash_block=config.get("flash_block", 512),
        conv_kernel=config["conv_kernel"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"], ssm_groups=config["n_groups"],
        ssm_chunk=config["chunk_size"],
        ff_size=config["intermediate_size"], expert_act="relu2",
        num_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_size=config["moe_intermediate_size"],
        held_experts=tuple(held) if held else None,
        router_score="sigmoid", selection_bias=True,
        norm_topk=config.get("norm_topk_prob", True),
        routed_scaling=float(config.get("routed_scaling_factor", 1.0)),
        shared_size=(config.get("n_shared_experts") or 0)
        * config.get("moe_shared_expert_intermediate_size", 0))
    kinds = {"M": dict(op="mamba2", ff="none"),
             "*": dict(op="attention", ff="none"),
             "E": dict(op="none", ff="experts"),
             "-": dict(op="none", ff="relu2")}
    of_kind = {kind: LayerSpec(**kinds[kind], **common)
               for kind in set(pattern)}
    return DecoderSpec(vocab_size=config["vocab_size"],
                       hidden_size=config["hidden_size"],
                       layers=tuple(of_kind[kind] for kind in pattern),
                       norm="rmsnorm", norm_eps=eps,
                       tied_readout=config.get("tie_word_embeddings", False))
