"""A cumulative counter as it stood when the window opened
(``report["window"]["counters0"][group]``, taken one hook call after the
opening): the readers of the set-up.  Not ``delta``: the set-up is over by
then, and a run with a compile inside its window prints no result, so what a
counter holds at the opening is the set-up's.  ``None`` on anything missing,
negative or not a number, and on a zero where ``may_be_zero`` does not allow
one; never an exception: a program without these counters (the parent of the
PR that added them) has nothing to read."""

import _per     # beside this file; run.py puts the directory on the path


def total(report, group, keys, scale=1.0, may_be_zero=False):
    """``scale * sum(counters0[group][k] for k in keys)``."""
    try:
        at_open = report["window"]["counters0"][group]
        values = [at_open[k] for k in keys]
    except (KeyError, TypeError, IndexError):
        return None
    if not all(_per._number(v) and v >= 0 for v in values):
        return None
    if sum(values) == 0 and not may_be_zero:
        return None
    return scale * sum(values)
