"""Device: share of the window in which no operation ran: 1 - the
device-busy seconds a step (from the profiled steps' trace) times the steps a
second of the window, which is timed with the profiler off (the profiler
slows a host-bound cell, so the traced steps' own idle share overstates)."""


def read(report):
    trace, w = report.get("trace"), report["window"]
    if not trace or not trace.get("steps") or "examples" not in w:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["steps"]
                    * w["steps"] / w["seconds"])
