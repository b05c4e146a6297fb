"""Kernels (the jitted step): the least time the chip could take for one
step's model FLOPs (counted from shapes, ``benchmark/flops.py``; FLOP-bound:
ResNet-50 and GPT-2 steps at these batches sit right of the ridge), over the
device-busy time per step of the profiled steps."""


def read(report):
    trace, w = report.get("trace"), report["window"]
    if not trace or not trace.get("steps") or "examples" not in w:
        return None
    peak = report["device"]["peaks"]["bf16_flops_per_s"]
    ideal = (report["model"]["flops_per_example"]
             * report["model"]["batch_size"] / w["chips"] / peak)
    return 100.0 * ideal * trace["steps"] / trace["busy_s"]
