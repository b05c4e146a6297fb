"""Replica step: median time of one dispatched batch, the device call and
its host wrapping (``serving_dispatch_us`` histogram)."""
import _hist     # beside this file; run.py puts the directory on the path


def read(report):
    d = report["window"]["delta"].get("replica")
    if not d:
        return None
    p = _hist.percentile(d, "serving_dispatch_us", 0.50)
    return None if p is None else p / 1e3
