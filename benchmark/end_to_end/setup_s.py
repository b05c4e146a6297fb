"""Seconds from the start of ``run.py`` to the start of the window."""


def read(report):
    return report["window"]["setup_s"]
