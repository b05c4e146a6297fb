"""The Mellum 2 mixture family (registered as ``mellum2``): RMSNorm,
grouped-query attention with per-head q/k RMSNorm in every layer, of two
kinds by ``layer_types``: a ``sliding_attention`` layer's query reads its
last ``sliding_window`` keys (``LayerSpec.window``: the flash kernels' grid
follows the band, the other contractions mask it) under plain RoPE, a
``full_attention`` layer's every causal key under YaRN frequencies
(``rope_parameters`` has a table for each kind); softmax top-k experts
renormalised, no bias leaf, no shared expert, and an untied read-out.  Under
``attention="flash"`` a sliding layer's kernels visit the band's tiles
alone; ``"full"`` is the same mathematics with the band as a mask; the
sequence-parallel contractions refuse a window."""

from tensorflowonspark_tpu.models.transformer import (
    DecoderSpec, LayerSpec, register_decoder)


@register_decoder("mellum2")
def mellum2_spec(config):
    """:class:`DecoderSpec` of a Mellum 2 mixture ``config.json`` (a dict
    with the source's keys: ``layer_types`` of ``sliding_attention`` and
    ``full_attention``, ``sliding_window``, ``rope_parameters`` with a table
    for each kind of layer, ``mlp_layer_types``, ``num_experts_per_tok``,
    ``norm_topk_prob``, ...).  ``num_experts`` is the router's width;
    ``held_experts`` (``[first, count]``, optional) the experts this program
    holds of each layer; ``flash_block`` (optional) the attention kernels'
    block, in both kinds of layer.
    A ``yarn`` table's ``attention_factor`` is what cos and sin are
    multiplied by (absent: ``0.1 ln(factor) + 1``).  What the family's
    modelling code does and no key says: per-head RMSNorm on q and k,
    rotate-half pairing, the window ``t - s < sliding_window``."""
    import math

    kinds = set(config["layer_types"])
    tables = config["rope_parameters"]
    unsupported = {
        "attention_bias": bool(config.get("attention_bias")),
        "layer_types": not kinds <= {"sliding_attention", "full_attention"},
        "mlp_layer_types": set(config.get("mlp_layer_types") or ["sparse"])
        != {"sparse"},
        "use_sliding_window": "sliding_attention" in kinds
        and not config.get("use_sliding_window", True),
        "rope_parameters": any(
            kind not in tables or tables[kind].get("rope_type", "default")
            not in ("default", "yarn") for kind in kinds)}
    if any(unsupported.values()):
        raise ValueError("mellum2: no support for this config's {}".format(
            sorted(k for k, v in unsupported.items() if v)))
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("{} layer_types for num_hidden_layers {}".format(
            len(config["layer_types"]), config["num_hidden_layers"]))
    held = config.get("held_experts")
    block = config.get("flash_block", 512)

    def layer(kind):
        table = tables[kind]
        yarn = None
        if table.get("rope_type", "default") == "yarn":
            factor = float(table["factor"])
            # rope_frequencies multiplies cos and sin by yarn_mscale(factor,
            # mscale) / yarn_mscale(factor, mscale_all_dim): the table's
            # attention_factor with mscale_all_dim 0
            mscale = ((float(table["attention_factor"]) - 1.0)
                      / (0.1 * math.log(factor))
                      if "attention_factor" in table else 1.0)
            yarn = (factor, float(table["original_max_position_embeddings"]),
                    float(table["beta_fast"]), float(table["beta_slow"]),
                    mscale, 0.0)
        sliding = kind == "sliding_attention"
        return LayerSpec(
            op="attention", ff="experts", norm="rmsnorm",
            norm_eps=config["rms_norm_eps"], positions="rope",
            num_heads=config["num_attention_heads"],
            head_dim=config["head_dim"],
            num_kv_heads=config["num_key_value_heads"], qk_norm=True,
            rope_theta=float(table["rope_theta"]), rope_yarn=yarn,
            window=config["sliding_window"] if sliding else 0,
            flash_block=block,
            num_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_size=config["moe_intermediate_size"],
            held_experts=tuple(held) if held else None,
            router_score="softmax", selection_bias=False,
            norm_topk=config.get("norm_topk_prob", True))

    of_kind = {kind: layer(kind) for kind in kinds}
    return DecoderSpec(vocab_size=config["vocab_size"],
                       hidden_size=config["hidden_size"],
                       layers=tuple(of_kind[kind]
                                    for kind in config["layer_types"]),
                       norm="rmsnorm", norm_eps=config["rms_norm_eps"],
                       tied_readout=config.get("tie_word_embeddings", False))
