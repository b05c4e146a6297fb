"""Step loop: host milliseconds between one dispatch returning and the next
starting, per dispatch."""


def read(report):
    d = report["window"]["delta"].get("trainer", {})
    if not d.get("dispatch_count"):
        return None
    return d["dispatch_gap_us"] / 1e3 / d["dispatch_count"]
