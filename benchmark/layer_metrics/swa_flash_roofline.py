"""Kernels: the banded forward flash kernel of the layers with a window
(scope ``attention/flash_window``, all sliding layers, the forward pass) as a
share of its roofline, the band's pairs over the scope's seconds; ``None``
where the program has no such scope."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "attention/flash_window")
