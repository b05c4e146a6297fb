"""A time per count over the window, from the difference of two counter
snapshots (``report["window"]["delta"][group]``): the readers of the feed
cycle's phases.  ``None`` on anything missing, zero, negative or not a
number; never an exception: a program without these counters (the parent of
the PR that added them) has nothing to read."""

import math


def _number(value):
    return (isinstance(value, (int, float)) and not isinstance(value, bool)
            and math.isfinite(value))


def per(report, group, time_keys, count_key, scale=1.0):
    """``scale * sum(delta[k] for k in time_keys) / delta[count_key]``."""
    try:
        delta = report["window"]["delta"][group]
        values = [delta[k] for k in time_keys]
        count = delta[count_key]
    except (KeyError, TypeError, IndexError):
        return None
    if not _number(count) or count <= 0 or not all(
            _number(v) and v >= 0 for v in values):
        return None
    return scale * sum(values) / count
