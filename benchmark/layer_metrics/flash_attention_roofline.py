"""Kernels: the grouped-query flash attention forward kernel (scope
``attention/flash``, all attention layers) as a share of its roofline."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "attention/flash")
