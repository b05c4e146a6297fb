"""Requests answered, with the right number of finite rows, within the mix's
latency limit, per second of window."""


def read(report):
    w = report["window"]
    if "good" not in w:
        return None
    return w["good"] / w["seconds"]
