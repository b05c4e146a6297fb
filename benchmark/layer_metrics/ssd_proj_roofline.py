"""Kernels: a Mamba-2 layer's two projections (scopes ``mamba/in_proj`` and
``mamba/out_proj``, all Mamba-2 layers, the forward pass) as a share of
their roofline: the least time of each over the seconds of both; ``None``
where the program has no such scopes."""
import _roofline    # beside this file; run.py puts the directory on the path
import _scopes


def read(report):
    scopes = ("mamba/in_proj", "mamba/out_proj")
    seconds = [_scopes.seconds_under(report, scope) for scope in scopes]
    if not all(seconds):
        return None
    # each scope's share times its seconds is its least time x 100
    least = [_scopes.roofline_pct(report, scope) for scope in scopes]
    if not all(least):
        return None
    return sum(p * s for p, s in zip(least, seconds)) / sum(seconds)
