"""Feed plane, in the executor's feed tasks: milliseconds a thousand rows
delivered that the feeder spent on the first pass of a block: columnar
packing, framing, the ring write with its wait for room, the token on the
queue (``feeder_pack_put_us`` over ``feeder_items``)."""
import _per     # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "feed", ("feeder_pack_put_us",),
                    "feeder_items")
