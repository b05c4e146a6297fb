"""The whole training step of the benchmark's ``olmo_hybrid_7b_tp2``
configuration compiled for one described TPU v5e chip (see
``tests/chip_compile.py``): the compile rehearsal that picks its
``batch_size``."""

import importlib

import pytest

from chip_compile import (  # noqa: F401  (fixtures)
    _compiled_step, _kernel_calls, no_compile_cache, topo)

LIMIT = 15.75 * 2 ** 30     # what a v5e lets a program have


@pytest.fixture
def delta_kernels(monkeypatch):
    """The delta rule takes the implementation it takes on a TPU
    (``chip_compile._steer_to_kernels`` steers the ops before it)."""
    monkeypatch.setattr(
        importlib.import_module("tensorflowonspark_tpu.ops.gated_delta"),
        "_default_impl", lambda: "pallas")


def test_olmo_hybrid_step_compiles_and_fits_v5e(topo, monkeypatch,
                                                delta_kernels):
    """The whole training step of ``olmo_hybrid_7b_tp2`` (published widths;
    layers 0 to 3: three Gated DeltaNet layers of 15 heads with keys of 96
    and values of 192 under the chunked delta rule, one full-attention layer
    of 15 heads of 128 with a QK-norm over the 1,920 held columns and no
    positions, a SwiGLU of 11,008 in every layer, blocks that norm a part's
    output; an untied read-out over 12,544 rows; rows of 8,192 and the batch
    the file says) compiles for one described v5e chip and fits its 15.75
    GiB by XLA's memory analysis, which it may not outgrow.  The delta
    rule's kernels are in it once forward and once backward a layer: the
    checkpoint keeps their output and chunk states, so the recomputed pass
    holds none; neither 96 nor 192 is a multiple of the 128 lanes, and the
    state a head is ``[96, 192]``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "olmo_hybrid", "olmo_hybrid_7b_tp2")
    assert parameters == 766_241_946
    assert needed <= LIMIT, needed
    text = compiled.as_text()
    assert "8192,8192" not in text
    calls = _kernel_calls(compiled)
    assert sum("gated_delta_fwd" in line for line in calls) == 3
    assert sum("gated_delta_bwd" in line for line in calls) == 3
    assert sum("/delta/scan/" in line for line in calls) == 6
    assert sum("/attention/flash/" in line for line in calls) == 3
