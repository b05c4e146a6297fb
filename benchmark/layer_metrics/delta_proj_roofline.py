"""Kernels: a Gated DeltaNet layer's two projections (scopes
``delta/in_proj`` and ``delta/out_proj``, all such layers, the forward pass)
as a share of their roofline: the least time of each over the seconds of
both; ``None`` where the program has no such scopes."""
import _scopes


def read(report):
    scopes = ("delta/in_proj", "delta/out_proj")
    seconds = [_scopes.seconds_under(report, scope) for scope in scopes]
    if not all(seconds):
        return None
    # each scope's share times its seconds is its least time x 100
    least = [_scopes.roofline_pct(report, scope) for scope in scopes]
    if not all(least):
        return None
    return sum(p * s for p, s in zip(least, seconds)) / sum(seconds)
