"""Infeed: host milliseconds per batch on the prefetch thread: columnar
assembly (which includes waiting for the feed) plus the host-to-device put."""


def read(report):
    d = report["window"]["delta"].get("infeed", {})
    if not d.get("infeed_batches"):
        return None
    return ((d["infeed_assembly_us"] + d["infeed_put_us"]) / 1e3
            / d["infeed_batches"])
