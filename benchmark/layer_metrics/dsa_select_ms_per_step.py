"""Sparse attention: device milliseconds a step of picking every query's
keys (scope ``attention/select``: the one kernel that scores all causal
pairs and searches each row's ``topk`` largest exactly), the forward pass,
all layers, over the profiled steps; ``None`` where the program has no such
scope."""
import _scopes    # beside this file; run.py puts the directory on the path


def read(report):
    steps = (report.get("trace") or {}).get("steps")
    seconds = _scopes.seconds_under(report, "attention/select")
    if not steps or seconds is None:
        return None
    return 1e3 * seconds / steps
