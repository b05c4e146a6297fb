"""Feed plane, in the executor's feed tasks: milliseconds a thousand rows
delivered that the feeder spent between two tasks (the driver's scheduling,
the partition's way into the process and its unpickling, the task's connect
to the manager) and pulling rows off the partition's iterator
(``feeder_between_tasks_us`` + ``feeder_source_us`` over ``feeder_items``;
microseconds a row are milliseconds a thousand)."""
import _per     # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(
        report, "feed",
        ("feeder_between_tasks_us", "feeder_source_us"),
        "feeder_items")
