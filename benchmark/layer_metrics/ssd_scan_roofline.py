"""Kernels: the chunked state-space scan's forward kernel and the decays'
sums it reads (scope ``mamba/scan``, all Mamba-2 layers, the forward pass)
as a share of its roofline; ``None`` where the program has no such scope."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "mamba/scan")
