"""Compile plane: programs compiled, or loaded from the persistent cache,
inside the window (a run in which this is not 0 gives no result at all)."""


def read(report):
    built = report["window"].get("compiles")
    if built is None or "examples" not in report["window"]:
        return None
    return (built["backend_compiles"] + built["cache_hits"]
            + built["cache_misses"])
