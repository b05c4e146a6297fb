"""Kernels: the chunked delta rule's forward kernel and what XLA puts round
it (the L2 norms of q and k, the decays' sums, the head-major layouts; scope
``delta/scan``, all Gated DeltaNet layers, the forward pass) as a share of
its roofline; ``None`` where the program has no such scope."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "delta/scan")
