"""Expert layer: device milliseconds a step of everything around the grouped
products in the forward pass: router, top-k and sort (``moe/route``), the
gather into expert order (``moe/dispatch``), the mask, the gather back and the
weighted sum (``moe/combine``), all expert layers, over the profiled steps
(the backward and recomputed passes of a remat block are not separable in
``by_scope``: ``counts/lfm2_moe.py``)."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    steps = (report.get("trace") or {}).get("steps")
    parts = [_scopes.seconds_under(report, scope)
             for scope in ("moe/route", "moe/dispatch", "moe/combine")]
    if not steps or all(p is None for p in parts):
        return None
    return 1e3 * sum(p or 0.0 for p in parts) / steps
