"""Compile plane: programs the chip-holding process made executable before
the window, from the persistent cache or by the compiler
(``compile_programs``: what "fewer programs" would move)."""
import _at_open    # beside this file; run.py puts the directory on the path


def read(report):
    return _at_open.total(report, "trainer", ["compile_programs"])
