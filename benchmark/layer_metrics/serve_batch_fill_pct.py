"""ModelServer batching: rows dispatched over the rows the batches could
have held at the largest bucket (``serving_rows`` / (``serving_batches`` x
max batch)): how far coalescing fills the device's batch."""


def read(report):
    d = report["window"]["delta"].get("replica")
    if not d or not d.get("serving_batches"):
        return None
    return (100.0 * d["serving_rows"]
            / (d["serving_batches"] * report["model"]["max_batch"]))
