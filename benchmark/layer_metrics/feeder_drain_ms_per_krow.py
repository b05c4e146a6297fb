"""Feed plane, in the executor's feed tasks: milliseconds a thousand rows
delivered that the feeder spent waiting for the consumer to empty the queue
before its task could end (``feeder_drain_us`` over ``feeder_items``)."""
import _per     # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "feed", ("feeder_drain_us",),
                    "feeder_items")
