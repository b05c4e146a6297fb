"""The expert layer's three row-wise passes alone at a cell's sorted buffers:
milliseconds a call of the front-tile kernel (``ops/expert_gate.py``) against
XLA's fusion of the plain form, with the cell's expected share of the rows in
front of ``n_local`` and with every row there.

    chiprun -- python3 scripts/time_expert_gate.py [--cells lfm2moe dsv2lite]

One jitted pass a time over ``[P, F]`` (the gate: two arrays read, one
written; its backward: three read, two written) or ``[P, D]`` (the sum: two
read, one written) bfloat16, ``--calls`` calls by the host's clock between
two ``block_until_ready``.  The operand a kernel writes its result over (the
gate's cotangent, the first addend) is donated and the result handed to the
next call in its place, under XLA's fusion too, so that neither side pays a
copy the step does not make.  XLA's fusion passes over every row whatever
``n_local`` says: its time is taken once a pass.  A width that is no multiple
of 128 lanes (cell 7's 1,856) is a column-major parameter of a program this
small, and the kernel's call then pays two copies that the step, where a
grouped product makes the array, does not: such a cell's times say little.
The result is the last line (JSON) and, with ``--out``, a file under
``chiprun_out/``.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tensorflowonspark_tpu.ops import expert_gate as eg  # noqa: E402

# P, F, D, the form, and the share of the pairs one chip of the cell's
# expert-parallel slice expects (benchmark/counts/<family>.py)
CELLS = {"lfm2moe": (131072, 1792, 2048, "swiglu", 0.25),
         "dsv2lite": (196608, 1408, 2048, "swiglu", 0.125),
         "keyevl2": (262144, 768, 2048, "swiglu", 0.125),
         "mellum2": (262144, 896, 2304, "swiglu", 0.125),
         "nemotron3nano": (147456, 1856, 2688, "relu2", 0.0625)}


def passes(rows, f, d, act):
    """``{name: (function of (impl, operands..., n), the operands' shapes,
    the donated operand)}``; the gate's "up" products are one operand,
    ``(h1, h3)`` with ``h3`` None for ``relu2``."""
    h = (rows, f)
    ups = (h, None if act == "relu2" else h)
    return {
        "gate": (lambda impl, ups, n: eg.gate(*ups, n, act, impl=impl),
                 [ups], None),
        # both cotangents kept alive: XLA would drop the one nobody reads
        "gate_grad": (lambda impl, ups, d_h, n: eg.gate_grad(
            *ups, d_h, n, act, impl=impl), [ups, h], 1),
        "sum": (lambda impl, a, b, n: eg.add_rows(a, b, n, impl=impl),
                [(rows, d)] * 2, 0),
    }


def timed(fn, arrays, n, donated, calls):
    """Milliseconds a call of ``fn(*arrays, n)``; its (first) result takes
    the donated operand's place in the next call."""
    run = jax.jit(fn, donate_argnums=() if donated is None else (donated,))
    arrays = list(arrays)

    def call():
        out = run(*arrays, n)
        if donated is not None:
            arrays[donated] = out[0] if isinstance(out, tuple) else out
        return out

    jax.block_until_ready(call())
    start = time.perf_counter()
    for _ in range(calls):
        out = call()
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / calls


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cells", nargs="+", default=["lfm2moe", "dsv2lite"],
                   choices=sorted(CELLS))
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--out", help="also write the result to chiprun_out/<out>")
    a = p.parse_args()

    result = {"device": jax.devices()[0].device_kind, "calls": a.calls,
              "rows": []}
    for cell in a.cells:
        rows, f, d, act, share = CELLS[cell]
        for name, (fn, shapes, donated) in passes(rows, f, d, act).items():
            def fresh():
                # every array the same numbers: a pass's time is its bytes'
                return jax.tree_util.tree_map(
                    lambda shape: jax.random.normal(
                        jax.random.PRNGKey(0), shape, jnp.bfloat16),
                    shapes, is_leaf=lambda v: isinstance(v, tuple)
                    and isinstance(v[0], int))

            shape = (rows, d if name == "sum" else f)
            row = {"cell": cell, "pass": name, "shape": list(shape),
                   "tile": eg.row_tile(*shape, jnp.bfloat16),
                   "xla_ms": timed(lambda *v: fn("xla", *v), fresh(),
                                   jnp.int32(rows), donated, a.calls)}
            for label, n in (("share", int(share * rows)), ("all", rows)):
                row["n_local_" + label] = n
                row["kernel_ms_" + label] = timed(
                    lambda *v: fn("pallas", *v), fresh(), jnp.int32(n),
                    donated, a.calls)
            result["rows"].append(row)
            print(json.dumps(row), flush=True)
    if a.out:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", a.out), "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
