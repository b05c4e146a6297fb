"""Kernels: the expert layers' grouped SwiGLU products (scope
``moe/experts``, all layers, the forward pass: ``counts/lfm2_moe.py`` says
why the forward pass) as a share of their roofline, for the pairs expected
here under even routing."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "moe/experts")
