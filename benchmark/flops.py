"""Operations a model's forward and backward passes require, counted from its
shapes.  One multiply-accumulate is two FLOPs; the backward pass of a
convolution or matrix product costs twice its forward pass; recomputed work
is never counted; element-wise work (normalisation, activation, softmax,
pooling, the optimizer) is left out, which makes every share of peak computed
from these counts a lower bound of the device's real work.

A family's count is a file of its own, ``benchmark/counts/<reference>.py``,
found by the configuration's ``reference``: there is no table here to
extend.  It gives ``train_flops_per_example(cfg)`` and, where the family is
served, ``infer_flops_per_example(cfg)``; it may give ``kernels(cfg)``: for
each ``jax.named_scope`` path of the model that a reader divides by
(``layer_metrics/_roofline.py``), the FLOPs and the HBM bytes of one step,
``{scope: {"flops": ..., "bytes": ...}}``.
"""

import importlib


def family(cfg):
    return importlib.import_module("benchmark.counts." + cfg["reference"])


def train_flops_per_example(cfg):
    return family(cfg).train_flops_per_example(cfg)


def infer_flops_per_example(cfg):
    return family(cfg).infer_flops_per_example(cfg)


def kernels(cfg):
    """{} for a family that names no scope."""
    count = getattr(family(cfg), "kernels", None)
    return count(cfg) if count else {}
