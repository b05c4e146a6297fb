"""Driver and rendezvous: seconds from ``cluster.run`` entered to the user
function entered, the first five phases of the bring-up's account
(``Trainer.counters_snapshot()``: ``bringup_driver_us`` + ``_spawn_`` +
``_node_`` + ``_rendezvous_`` + ``_launch_``)."""
import _at_open    # beside this file; run.py puts the directory on the path


def read(report):
    return _at_open.total(report, "trainer", [
        "bringup_driver_us", "bringup_spawn_us", "bringup_node_us",
        "bringup_rendezvous_us", "bringup_launch_us"], scale=1e-6)
