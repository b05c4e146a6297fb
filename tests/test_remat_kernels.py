"""``TransformerLM(remat=True)`` under ``attention="flash"``: the forms of
attention without a learned index (that one is in
``tests/test_remat_kernels_indexed.py``)."""

import pytest

from remat_kernels import remat_keeps_the_attention_kernels_results


@pytest.mark.parametrize("case", ["flash", "gqa", "latent", "sharded"])
def test_remat_keeps_the_attention_kernels_results(case):
    remat_keeps_the_attention_kernels_results(case)
