"""Readers of a ``jax.named_scope`` that every layer of a pattern repeats:
the trace's ``by_scope`` has one path a layer
(``TransformerLM/block_3/moe/experts``), a family's ``kernels(cfg)`` one
count for all of them (``moe/experts``).  ``None``, never 0 and never an
exception, on anything missing: a program without the scope (the parent of
the PR that added it) has nothing to read."""

import _roofline    # beside this file; run.py puts the directory on the path


def seconds_under(report, scope):
    """Device-busy seconds of the profiled steps under every path that ends
    in ``scope`` (an ancestor holds the union of what lies below it, so
    deeper paths are not added again)."""
    by_scope = (report.get("trace") or {}).get("by_scope") or {}
    found = [t for path, t in by_scope.items()
             if path == scope or path.endswith("/" + scope)]
    return sum(found) if found else None


def roofline_pct(report, scope):
    """``_roofline.scope_roofline_pct`` with the layers' seconds added up."""
    seconds = seconds_under(report, scope)
    if not seconds:
        return None
    trace = dict(report["trace"], by_scope={scope: seconds})
    return _roofline.scope_roofline_pct(dict(report, trace=trace), scope)
