"""``TransformerLM(remat=True)`` under ``attention="flash"`` with a learned
index over the keys (the other forms are in ``tests/test_remat_kernels.py``)."""

from remat_kernels import remat_keeps_the_attention_kernels_results


def test_remat_keeps_the_attention_kernels_results_with_an_index():
    remat_keeps_the_attention_kernels_results("indexed")
