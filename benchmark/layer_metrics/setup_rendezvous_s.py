"""Driver and rendezvous: seconds the node waited for the roster
(``bringup_rendezvous_us``: ``client.register`` called until
``await_reservations`` returned; one node here, and the number that grows
with the executors)."""
import _at_open    # beside this file; run.py puts the directory on the path


def read(report):
    return _at_open.total(report, "trainer", ["bringup_rendezvous_us"],
                          scale=1e-6)
