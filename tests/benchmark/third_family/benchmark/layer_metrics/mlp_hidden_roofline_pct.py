"""Kernels: the hidden layer's share of its roofline (``_roofline.py``)."""
import _roofline    # beside this file; run.py puts the directory on the path


def read(report):
    return _roofline.scope_roofline_pct(report, "mlp/hidden")
