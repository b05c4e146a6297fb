"""Infeed: seconds from ``fit_feed`` entered to its first batch in hand
(``bringup_first_batch_us``): next to nothing where the feed was ready before
the loop asked, the Spark hop's first partition where it was not.  Read only
where the account is there (``bringup_wall_us`` is never 0)."""
import _at_open    # beside this file; run.py puts the directory on the path


def read(report):
    if _at_open.total(report, "trainer", ["bringup_wall_us"]) is None:
        return None
    return _at_open.total(report, "trainer", ["bringup_first_batch_us"],
                          scale=1e-6, may_be_zero=True)
