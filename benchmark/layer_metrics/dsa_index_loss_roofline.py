"""Kernels: the kernel of the learned index's loss (scope
``attention/index_loss``, all layers, the forward pass), which takes the
heads' scores and the index scores again on every causal tile, as a share of
the roofline of those products on the kept pairs; ``None`` where the program
has no such scope."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "attention/index_loss")
