"""The whole training step of the benchmark's ``keye_vl2_30b_a3b_ep8``
configuration compiled for one described TPU v5e chip (see
``tests/chip_compile.py``)."""

from chip_compile import (  # noqa: F401  (fixtures)
    _compiled_step, _kernel_calls, _one_lane_arrays, _flash_calls,
    no_compile_cache, topo)


def test_keye_vl2_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``keye_vl2_30b_a3b_ep8`` (published
    widths; four layers; grouped-query attention over the 2,048 keys a
    learned index picks of a 32,768-token row; 16 of 128 experts by softmax
    top-8; an untied read-out over 18,992 rows; batch 1, as the file says)
    compiles for one described v5e chip with every kernel of the index in it
    and fits its 15.75 GiB by XLA's memory analysis: 13.66 GiB with four
    layers' kernel outputs, logsumexp rows, key bits and index logsumexp
    kept across their recomputed blocks, which it may not outgrow; no array
    of it is ``[T, T]``.  PR 40: 14.25 before it; 14.47 with the flash
    kernels' statistics as dense rows (the most that is live at once fell
    0.53 GB with the ``[.., seq, 1]`` arrays, and the block the compiler
    packs the temporaries into grew: a 0.39 GB hole in the expert layer's
    backward pass that its 0.40 GB buffers do not fit, and the analysis
    counts such a hole twice); 13.66 with the index's backward kernels run
    in their own layer's backward pass (``transformer._backward_together``:
    two layers' folded ``q``, ``k`` and index queries no longer lie over the
    third's expert layer).  PR 50: 13.86 with the index's loss taken once, its
    kernel with the gradients in the forward pass and their three results
    kept by name across the recomputed block (``diq`` lane-dense ``f32[1,
    32768, 1024]`` 134.2 MB, ``dik`` 8.4, ``diw`` ``f32[1, 32768, 16]`` on
    128 lanes 16.8: 159 MB a layer, 0.59 GiB for the four; the reading rises
    by 0.20, less than that, because the backward pass no longer folds ``q``
    (268 MB) and the index queries (134 MB) for a kernel of its own); ISSUE
    50 allowed 13.66 + 4 x 159 MB = 14.3 at most.  The numbers of PR 37 are
    in the configuration's ``assumed.batch_size``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "keye_vl2", "keye_vl2_30b_a3b_ep8")
    assert parameters == 465_391_104
    assert needed <= 13.9 * 2 ** 30, needed
    assert "32768,32768" not in compiled.as_text()
    calls = _kernel_calls(compiled)

    def count(scope, kernel, calls=calls):
        return sum("/attention/{}/".format(scope) in line and kernel in line
                   for line in calls)

    # four layers x (forward, dQ, dK/dV) under attention/flash and one
    # selection each: the recomputed pass holds neither; the index's loss
    # once, with its gradients, in the forward pass (the backward and the
    # recomputed pass lie under the transposition) and never alone
    assert count("flash", "pallas_call") == 12
    assert not _one_lane_arrays(_flash_calls(calls))
    assert count("select", "dsa_select/") == 4
    assert count("index_loss", "dsa_index_loss/") == 0
    assert count("index_loss", "dsa_index_loss_grads/") == 4
    assert count("index_loss", "dsa_index_loss_grads/", [
        line for line in calls if "transpose(" not in line]) == 4
    assert sum("/moe/experts/" in line for line in calls) == 48 + 16
    # ... and between them the row-wise passes that stop at n_local: the
    # gate (forward, recomputed forward), its backward and the sum of the two
    # input gradients, an expert layer
    for kernel, count in (("expert_gate", 8), ("expert_gate_grad", 4),
                          ("expert_gate_sum", 4)):
        assert sum("/moe/experts/" in line
                   and "/{}/pallas_call".format(kernel) in line
                   for line in calls) == count, kernel
