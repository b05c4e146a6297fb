"""The whole training step of the benchmark's ``gpt2_medium``
configuration compiled for one described TPU v5e chip (see
``tests/chip_compile.py``)."""

from chip_compile import (  # noqa: F401  (fixtures)
    _benchmark_config, _steer_to_kernels, _lowered_step, _needed,
    _kernel_lines, _one_lane_arrays, no_compile_cache, topo)


def test_gpt2_medium_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``gpt2_medium`` as the benchmark's cell 2
    builds it (``attention="full"``, the default; 24 like layers, 16 heads
    of 64, batch 4 of 1,024 tokens, bf16 compute, no remat, Adam) compiled
    for one described v5e chip: under the rule of
    ``flash_attention.full_attention_block`` every layer takes the three
    flash kernels at blocks of 512 (72 calls, all under
    ``block_i/Attention_0/flash``), no float32 ``[4, 16, 1024, 1024]`` score
    tensor is left anywhere in the program (the plain contraction's step
    mentions it 2,232 times and needs 8.38 GiB of temporaries), the 24
    layers share one lowered function of each kernel (three calls in the
    StableHLO where a launcher called bare lowers 72), and XLA's memory
    analysis of it (arguments + outputs - aliased + temporaries) may not
    outgrow the 8.16 GiB it is with Adam's state (8.77 GB, PR 44; 12.1 GB
    with the scores in HBM, the configuration's ``assumed.batch_size``)."""
    from tensorflowonspark_tpu.models import transformer

    cfg = _benchmark_config("gpt2_medium")
    _steer_to_kernels(monkeypatch)
    model = transformer.build_transformer(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], head_dim=cfg["n_embd"] // cfg["n_head"],
        max_seq_len=cfg["n_positions"], attention=cfg["attention"],
        dtype=cfg["dtype"])
    assert cfg["attention"] == "full"
    lowered, parameters = _lowered_step(topo, model, cfg, cfg["n_positions"])
    assert parameters == 354_823_168
    assert lowered.as_text().count("tpu_custom_call") == 3
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = _kernel_lines(text)
    assert len(calls) == 72
    assert sum("/Attention_0/flash/" in line for line in calls) == 72
    assert not _one_lane_arrays("\n".join(calls))
    assert "f32[4,16,1024,1024]" not in text
    assert _needed(compiled) <= 8.25 * 2 ** 30, _needed(compiled)
