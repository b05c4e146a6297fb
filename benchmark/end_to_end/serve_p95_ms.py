"""95th percentile (nearest rank) of reply time minus due time over every
request due in the window; a request that failed, was shed or got no answer
counts as slower than any limit."""


def read(report):
    latency = report["window"].get("latency_ms")
    return None if latency is None else latency["p95"]
