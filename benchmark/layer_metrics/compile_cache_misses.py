"""Compile plane: persistent-cache misses of the chip-holding process, from
its start to the end of the window (0 in a warm run)."""


def read(report):
    counts = report.get("process_compiles")
    return None if counts is None else counts["cache_misses"]
