"""Kernels: the hidden layer's model FLOPs (``counts/mlp.py``'s ``kernels``)
completed a second of the window, in TFLOP/s a chip: read from the host's
clock, so a CPU rehearsal prints it too."""


def read(report):
    window = report["window"]
    count = (report["model"].get("kernels") or {}).get("mlp/hidden")
    if not count or not window.get("steps") or not window.get("seconds"):
        return None
    return (count["flops"] * window["steps"] / window["seconds"]
            / window["chips"] / 1e12)
