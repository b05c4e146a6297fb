"""Optimizer-step examples completed between the two device-synced instants
that open and close the window, over the synced wall time and the chips."""


def read(report):
    w = report["window"]
    if "examples" not in w:
        return None
    return w["examples"] / w["seconds"] / w["chips"]
