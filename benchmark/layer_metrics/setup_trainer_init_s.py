"""Step loop: seconds inside ``Trainer.__init__`` (``bringup_trainer_init_us``:
state placement, shardings, the jit wrappers)."""
import _at_open    # beside this file; run.py puts the directory on the path


def read(report):
    return _at_open.total(report, "trainer", ["bringup_trainer_init_us"],
                          scale=1e-6)
