"""Kernels: everything latent attention puts round the flash kernel (scope
``attention/latent``, all layers, the forward pass: the q projection, the
down-projection to the latent with its rotary key, the latent's norm, the
up-projection to per-head keys and values, RoPE and building K) as a share of
its roofline; ``None`` where the program has no such scope."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    return _scopes.roofline_pct(report, "attention/latent")
