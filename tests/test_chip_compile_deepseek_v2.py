"""The whole training step of the benchmark's ``deepseek_v2_lite_ep8``
configuration compiled for one described TPU v5e chip (see
``tests/chip_compile.py``)."""

from chip_compile import (  # noqa: F401  (fixtures)
    _compiled_step, _kernel_calls, _one_lane_arrays, _flash_calls,
    no_compile_cache, topo)


def test_deepseek_v2_lite_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``deepseek_v2_lite_ep8`` (published
    widths; one dense and four expert layers; latent attention through the
    flash kernels at 192 / 128; 8 of 64 experts by softmax top-6 beside the
    shared expert; an untied read-out over 12,800 rows; batch and
    8,192-token rows as the file says) compiles for one described v5e chip
    and fits its 15.75 GiB by XLA's memory analysis: 13.31 GiB at batch 4
    with five layers' kernel outputs and logsumexp rows kept across their
    recomputed blocks and the statistics as dense rows (PR 40; 13.56 while
    they were ``[.., seq, 1]``), which it may not outgrow.  The numbers of
    PR 32 are in the configuration's ``assumed.batch_size``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "deepseek_v2", "deepseek_v2_lite_ep8")
    assert parameters == 535_060_992
    assert needed <= 13.35 * 2 ** 30, needed
    calls = _kernel_calls(compiled)
    # five attention layers x (forward, dQ, dK/dV), all under
    # attention/flash: no forward kernel in the recomputed pass; four expert
    # layers x 3 grouped products x 4 passes, and their row movement
    assert sum("/attention/flash/" in line for line in calls) == 15
    assert not _one_lane_arrays(_flash_calls(calls))
    assert sum("/moe/experts/" in line for line in calls) == 48 + 16
    # ... and between them the row-wise passes that stop at n_local: the
    # gate (forward, recomputed forward), its backward and the sum of the two
    # input gradients, an expert layer
    for kernel, count in (("expert_gate", 8), ("expert_gate_grad", 4),
                          ("expert_gate_sum", 4)):
        assert sum("/moe/experts/" in line
                   and "/{}/pallas_call".format(kernel) in line
                   for line in calls) == count, kernel
    assert len(calls) >= 15 + 48 + 40
