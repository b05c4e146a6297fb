"""A named scope's share of its roofline: the least time the chip could take
for the scope's FLOPs and HBM bytes of one step (the family's
``kernels(cfg)`` in ``benchmark/counts/<reference>.py``, by way of
``report["model"]["kernels"]``), over the device-busy seconds a step that the
trace found under that ``jax.named_scope`` path
(``report["trace"]["by_scope"]``, ``benchmark/trace_reduce.py``).  A reader
``<scope>_roofline_pct`` is three lines:

    import _roofline    # beside this file; run.py puts the directory on the path

    def read(report):
        return _roofline.scope_roofline_pct(report, "Model/block_0/moe")

``None``, never 0, on anything missing: no trace, a trace without origins,
a scope that no operation carries, a family that counts no such scope."""


def scope_roofline_pct(report, scope):
    trace = report.get("trace") or {}
    count = ((report.get("model") or {}).get("kernels") or {}).get(scope)
    seconds = (trace.get("by_scope") or {}).get(scope)
    peaks = (report.get("device") or {}).get("peaks")
    steps = trace.get("steps")
    if not (count and seconds and peaks and steps):
        return None
    # the count is of one whole step; each chip does its share of it
    least = max(count["flops"] / peaks["bf16_flops_per_s"],
                count["bytes"] / peaks["hbm_bytes_per_s"]) \
        / report["window"]["chips"]
    return 100.0 * least * steps / seconds
