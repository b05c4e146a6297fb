"""The language model of the Keye-VL-2.0 mixture family (registered as
``keye_vl2``): RMSNorm, grouped-query attention with per-head q/k RMSNorm and
RoPE **over the keys a learned index picks** (``sa_config``: ``index_heads``
index query heads of ``index_dim`` and one index key head score every causal
pair on a detached copy of the layer's input, each query keeps its
``index_topk`` best keys, exactly;
:mod:`tensorflowonspark_tpu.ops.sparse_index`), an expert layer in every
layer (softmax scores, top-k renormalised, no bias leaf, no shared expert)
and an untied read-out.  The index is trained by a loss of its own, sown a
layer (``dsa_index_loss``) and added by
:func:`~tensorflowonspark_tpu.models.transformer.loss_fn`: its leaves
(``index_q``, ``index_k``, ``index_k_norm``, ``index_w``) get that loss's
gradient alone and every other leaf the cross-entropy's alone.  The index
runs under ``attention="flash"`` (its kernels, and the flash kernels reading
a key set a query) alone."""

from tensorflowonspark_tpu.models.transformer import (
    DecoderSpec, LayerSpec, register_decoder)


@register_decoder("keye_vl2")
def keye_vl2_spec(config):
    """:class:`DecoderSpec` of the language model under a Keye-VL-2.0
    mixture ``config.json`` (a dict with the source's keys: ``head_dim``,
    ``num_key_value_heads``, ``num_experts_per_tok``, ``norm_topk_prob``,
    ``sa_config`` with ``indexer_num_heads``, ``indexer_head_dim``,
    ``indexer_num_kv_heads`` and ``topk``, ...).  ``num_experts`` is the
    router's width; ``held_experts`` (``[first, count]``, optional) the
    experts this program holds of each layer; ``flash_block`` (optional) the
    attention kernel's block.  Text rows only: the three sections of
    ``mrope_section`` all carry the token's position, which is plain RoPE.
    What the family's modelling code does and no key says: per-head RMSNorm
    on q and k, rotate-half pairing."""
    sparse = config.get("sa_config") or {}
    unsupported = {
        "decoder_sparse_step": config.get("decoder_sparse_step", 1) != 1,
        "mlp_only_layers": bool(config.get("mlp_only_layers")),
        "use_sliding_window": bool(config.get("use_sliding_window")),
        "attention_bias": bool(config.get("attention_bias")),
        "sa_config.indexer_num_kv_heads":
            sparse.get("indexer_num_kv_heads", 1) != 1}
    if any(unsupported.values()):
        raise ValueError("keye_vl2: no support for this config's {}".format(
            sorted(k for k, v in unsupported.items() if v)))
    held = config.get("held_experts")
    layer = LayerSpec(
        op="attention", ff="experts", norm="rmsnorm",
        norm_eps=config["rms_norm_eps"], positions="rope",
        num_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"], qk_norm=True,
        rope_theta=float(config["rope_theta"]),
        index_heads=sparse.get("indexer_num_heads", 0),
        index_dim=sparse.get("indexer_head_dim", 0),
        index_topk=sparse.get("topk", 0),
        flash_block=config.get("flash_block", 512),
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_size=config["moe_intermediate_size"],
        held_experts=tuple(held) if held else None,
        router_score="softmax", selection_bias=False,
        norm_topk=config.get("norm_topk_prob", True))
    return DecoderSpec(vocab_size=config["vocab_size"],
                       hidden_size=config["hidden_size"],
                       layers=(layer,) * config["num_hidden_layers"],
                       norm="rmsnorm", norm_eps=config["rms_norm_eps"],
                       tied_readout=config.get("tie_word_embeddings", False))
