"""Kernels: the gated short convolutions (scope ``short_conv``, all conv
layers: in_proj, gates, taps, out_proj; the forward pass) as a share of
their roofline."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "short_conv")
