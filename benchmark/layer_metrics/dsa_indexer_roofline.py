"""Kernels: the learned index (scopes ``attention/indexer`` and
``attention/select``, all layers, the forward pass): its three projections,
its norm and RoPE, and the one kernel that scores every causal pair into
VMEM and searches each row's ``topk`` largest there (the scores never reach
HBM, so they have no time apart from the search), as a share of the roofline
of the projections' and the scores' products.  The search is compares, not
products: it is what keeps the share down.  ``None`` where the program lacks
either scope."""
import _roofline    # beside this file; run.py puts the directory on the path
import _scopes

SCOPES = ("attention/indexer", "attention/select")


def read(report):
    counts = (report.get("model") or {}).get("kernels") or {}
    seconds = [_scopes.seconds_under(report, scope) for scope in SCOPES]
    if not all(seconds) or not all(counts.get(scope) for scope in SCOPES):
        return None
    whole = {key: sum(counts[scope][key] for scope in SCOPES)
             for key in ("flops", "bytes")}
    return _roofline.scope_roofline_pct(
        dict(report, model=dict(report["model"], kernels={"index": whole}),
             trace=dict(report["trace"], by_scope={"index": sum(seconds)})),
        "index")
