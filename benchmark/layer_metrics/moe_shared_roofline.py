"""Kernels: the shared expert's three SwiGLU products (scope ``moe/shared``,
all expert layers, the forward pass, every token) as a share of their
roofline; ``None`` where the program has no such scope."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "moe/shared")
