"""Feed plane, in the consumer: milliseconds a thousand rows that the
``DataFeed``'s thread spent blocked on the empty queue (``feed_wait_us`` over
``feed_items``)."""
import _per     # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "feed", ("feed_wait_us",),
                    "feed_items")
