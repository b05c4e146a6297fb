"""The whole training step of the benchmark's ``lfm2_8b_a1b_ep4``
configuration compiled for one described TPU v5e chip (see
``tests/chip_compile.py``)."""

from chip_compile import (  # noqa: F401  (fixtures)
    _compiled_step, _kernel_calls, _one_lane_arrays, _flash_calls,
    no_compile_cache, topo)


def test_lfm2_moe_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``lfm2_8b_a1b_ep4`` (the benchmark's
    configuration: published widths, the layer pattern, 8 of 32 experts,
    batch and 8,192-token rows as the file says, bf16 compute, remat per
    block, Adam) compiles for one described v5e chip, with the grouped-query
    flash kernels, the grouped expert products, the row-wise passes between
    them and the expert layer's row movement (pallas kernels all) in it, and
    XLA's memory analysis of it (arguments + outputs - aliased +
    temporaries) is no larger than the 11.73 GiB it is with the attention
    layer's kernel output and logsumexp rows kept across the recomputed
    block and the flash kernels' statistics
    as dense rows (PR 40; 12.01 while they were ``[.., seq, 1]``; a v5e
    offers 15.75).  The numbers of PR 28 are in the configuration's
    ``assumed.batch_size``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "lfm2_moe", "lfm2_8b_a1b_ep4")
    assert parameters == 507_820_288
    assert needed <= 11.8 * 2 ** 30, needed
    # 4 expert layers x 3 grouped products x (forward, recomputed forward,
    # two gradients), and the flash kernels (forward once: the checkpoint
    # keeps its output and logsumexp; dQ, dK/dV): all pallas kernels that
    # carry their scope
    calls = _kernel_calls(compiled)
    assert sum("/attention/flash/" in line for line in calls) == 3
    assert not _one_lane_arrays(_flash_calls(calls))
    # ... and ten kernels of the row movement an expert layer, under the
    # scopes moe_route_ms_per_step reads: dispatch packs the tokens and
    # gathers them (forward and recomputed forward) and its gradient packs
    # and gather-sums; combine packs and gather-sums once (its recomputed
    # forward is dead code) and its gradient packs and gathers
    assert len(calls) >= 48 + 3 + 40 + 16
    # ... and between them the row-wise passes that stop at n_local: the
    # gate (forward, recomputed forward), its backward and the sum of the two
    # input gradients, an expert layer
    for kernel, count in (("expert_gate", 8), ("expert_gate_grad", 4),
                          ("expert_gate_sum", 4)):
        assert sum("/moe/experts/" in line
                   and "/{}/pallas_call".format(kernel) in line
                   for line in calls) == count, kernel
    for scope, kernel, count in (("dispatch", "gather", 8),
                                 ("dispatch", "sum", 4),
                                 ("combine", "sum", 4),
                                 ("combine", "gather", 4)):
        assert sum("/moe/{}/".format(scope) in line
                   and "/routed_rows_{}/pallas_call".format(kernel) in line
                   for line in calls) == count, (scope, kernel)
