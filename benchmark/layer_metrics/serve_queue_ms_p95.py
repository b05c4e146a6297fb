"""Gateway / ModelServer queue: 95th percentile of the time a request waited
to be collected into a batch plus that of the time the batch waited to be
dispatched (``serving_queue_us`` and ``serving_coalesce_us`` histograms; the
sum of the two percentiles bounds the percentile of the sum from above)."""
import _hist     # beside this file; run.py puts the directory on the path


def read(report):
    d = report["window"]["delta"].get("replica")
    if not d:
        return None
    parts = [_hist.percentile(d, p, 0.95)
             for p in ("serving_queue_us", "serving_coalesce_us")]
    if any(p is None for p in parts):
        return None
    return sum(parts) / 1e3
