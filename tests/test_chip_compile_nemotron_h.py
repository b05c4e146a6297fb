"""The whole training step of the benchmark's ``nemotron3_nano_30b_a3b_ep16``
configuration compiled for one described TPU v5e chip (see
``tests/chip_compile.py``)."""

from chip_compile import (  # noqa: F401  (fixtures)
    _compiled_step, _kernel_calls, no_compile_cache, topo)


def test_nemotron3_nano_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``nemotron3_nano_30b_a3b_ep16`` (published
    widths; layers 35 to 43 of the pattern, ``MEMEMEM*E``: four Mamba-2
    layers under the chunked scan, four expert layers of two-matrix relu2
    experts, 8 of 128 held, beside a shared one, one attention layer of 32 /
    2 heads without positions; an untied read-out over 16,384 rows; rows of
    8,192 and the batch the file says) compiles for one described v5e chip
    and fits its 15.75 GiB by XLA's memory analysis, which it may not
    outgrow: 14.53 GiB at batch 3 (15.60 GB: 8.00 GB of parameters and Adam's
    moments as arguments, 7.60 GB temporaries, gradients among them; batch 4
    is refused at 16.29 GiB, batch 2 takes 13.31).  The scan kernels are in it once forward and once
    backward a layer: the checkpoint keeps their output and chunk states,
    so the recomputed pass holds none; hidden rows of 2,688 (1,344 words of
    bfloat16, ten slab rows and a half) pass through the expert layer's row
    movement, and an expert's width of 1,856, which no multiple of 128
    divides, is one tile of the grouped products."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "nemotron_h", "nemotron3_nano_30b_a3b_ep16")
    assert parameters == 666_963_456
    assert needed <= 14.6 * 2 ** 30, needed
    text = compiled.as_text()
    assert "8192,8192" not in text
    calls = _kernel_calls(compiled)
    assert sum("/mamba/scan/" in line for line in calls) == 8
    assert sum("/attention/flash/" in line for line in calls) == 3
    # 4 expert layers x 2 grouped products x (forward, recomputed forward,
    # two gradients), and between them the gate (forward, recomputed) and
    # its backward, which stop at n_local (relu2: one "up" product, no sum)
    assert sum("/moe/experts/" in line for line in calls) == 32 + 12
    for kernel, count in (("expert_gate", 8), ("expert_gate_grad", 4),
                          ("expert_gate_sum", 0)):
        assert sum("/{}/pallas_call".format(kernel) in line
                   for line in calls) == count, kernel
