"""Device: share of the traced window in which no operation ran, from the
trace of the replica's own process."""


def read(report):
    trace = report.get("trace")
    if not trace or "good" not in report["window"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
