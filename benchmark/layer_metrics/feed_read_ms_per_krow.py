"""Feed plane, in the consumer: milliseconds a thousand rows that the
``DataFeed``'s thread spent reading chunks out of the ring (with the decode
and the chunk's acknowledgement) and slicing and concatenating columns
(``feed_read_us`` + ``feed_assemble_us`` over ``feed_items``)."""
import _per     # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "feed", ("feed_read_us", "feed_assemble_us"),
                    "feed_items")
