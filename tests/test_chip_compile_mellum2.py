"""The whole training step of the benchmark's ``mellum2_12b_a2p5b_ep8``
configuration compiled for one described TPU v5e chip (see
``tests/chip_compile.py``)."""

from chip_compile import (  # noqa: F401  (fixtures)
    _compiled_step, _kernel_calls, _one_lane_arrays, no_compile_cache, topo)


def test_mellum2_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``mellum2_12b_a2p5b_ep8`` (published
    widths; one period of three sliding layers, 1,024 keys, and one full
    layer under YaRN, over a 32,768-token row; 8 of 64 experts by softmax
    top-8; an untied read-out over 12,288 rows; batch 1, as the file says)
    compiles for one described v5e chip with the banded flash kernels in it
    and fits its 15.75 GiB by XLA's memory analysis, which it may not
    outgrow: 11.81 GiB (12.68 GB: 4.08 GB of parameters and Adam's moments
    as arguments, 8.59 GB temporaries, gradients among them); no array of it
    is ``[T, T]``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "mellum2", "mellum2_12b_a2p5b_ep8")
    assert parameters == 340_350_208
    assert needed <= 11.9 * 2 ** 30, needed
    assert "32768,32768" not in compiled.as_text()
    calls = _kernel_calls(compiled)
    # three sliding layers x (forward, dQ, dK/dV) under attention/flash_window
    # and the full layer's three under attention/flash: the recomputed pass
    # holds no forward kernel of either
    assert sum("/attention/flash_window/" in line for line in calls) == 9
    assert sum("/attention/flash/" in line for line in calls) == 3
    assert not _one_lane_arrays("\n".join(
        line for line in calls if "/attention/flash" in line))
    assert sum("/moe/experts/" in line for line in calls) == 48 + 16
    # ... and between them the row-wise passes that stop at n_local: the
    # gate (forward, recomputed forward), its backward and the sum of the two
    # input gradients, an expert layer
    for kernel, count in (("expert_gate", 8), ("expert_gate_grad", 4),
                          ("expert_gate_sum", 4)):
        assert sum("/moe/experts/" in line
                   and "/{}/pallas_call".format(kernel) in line
                   for line in calls) == count, kernel
