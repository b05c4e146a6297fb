"""First-party pallas TPU kernels for the hot ops.

The compute path is jax/XLA; these kernels cover the few ops where
hand-scheduling VMEM traffic beats XLA's fusion — attention first
(:mod:`~tensorflowonspark_tpu.ops.flash_attention`), then the grouped matrix
product of an expert layer (:mod:`~tensorflowonspark_tpu.ops.grouped_matmul`)
and the row movement around it and the row-wise passes between its
products, which stop at the rows routed here
(:mod:`~tensorflowonspark_tpu.ops.routed_rows`,
:mod:`~tensorflowonspark_tpu.ops.expert_gate`), and the chunked
state-space scan of a Mamba-2 layer with its backward
(:mod:`~tensorflowonspark_tpu.ops.ssd_scan`; its function is
``ops.ssd_scan.ssd_scan``: the name here stays the module's), and the
chunked gated delta rule of a Gated DeltaNet layer with its backward
(:mod:`~tensorflowonspark_tpu.ops.gated_delta`, ``gated_delta_rule``).
Every kernel runs in pallas interpret mode off-TPU, so the suite validates
them on the CPU mesh.

``KEPT``: the names (``jax.ad_checkpoint.checkpoint_name``) of the kernels'
residuals that cost a kernel run to make again, all modules' together: what a
rematerialised block keeps (``save_only_these_names(*KEPT)``): the flash
kernel's output and logsumexp rows, the learned index's key bits and index
logsumexp and the three gradients its loss's kernel makes in the forward pass
(``dsa_index_loss_dq``, ``_dk``, ``_dw``: all its backward rule reads), the
scans' outputs and states.  A kernel with such a residual adds its names to
its module's ``KEPT`` and its module here.
"""

from tensorflowonspark_tpu.ops import gated_delta, sparse_index, ssd_scan
from tensorflowonspark_tpu.ops.flash_attention import (  # noqa: F401
    KEPT as _FLASH_KEPT, flash_attention, flash_attention_lse)
from tensorflowonspark_tpu.ops.grouped_matmul import grouped_matmul  # noqa: F401
from tensorflowonspark_tpu.ops.routed_rows import (  # noqa: F401
    gather_rows, gather_sum_rows)

KEPT = _FLASH_KEPT + sparse_index.KEPT + ssd_scan.KEPT + gated_delta.KEPT
