"""Feed plane, in the executor's feed tasks: milliseconds a thousand rows
delivered that the feeder spent replaying cached chunks (the epoch repeat:
ring write with its wait for room, and token, of every cached chunk;
``feeder_replay_us`` over ``feeder_items``)."""
import _per     # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "feed", ("feeder_replay_us",),
                    "feeder_items")
