"""The chunked gated delta rule's kernels alone at one cell's shape:
milliseconds a call, forward and backward, each against its roofline, and the
compiled kernels against the ``jax.numpy`` form at a small shape.

    chiprun -- python3 scripts/time_gated_delta.py --batch 1 --rows 8192 \\
        --heads 15 --key 96 --value 192 --chunk 64

``forward`` is ``ops/gated_delta.py``'s ``_forward`` (the decays' sums and
the head-major layouts by XLA, then the kernel), ``backward`` the whole of
its backward rule (the layouts again, the kernel, ``sum_v do o - dv v`` and
the reversed sums by XLA), bfloat16 ``q``, ``k``, ``v``; ``--calls`` calls by
the host's clock between two ``block_until_ready``.  The least time is the
larger of the chunked form's FLOPs over the chip's peak and its HBM bytes
over the bandwidth (``benchmark/peaks.json``).  Forward, a position and
head: the causal pairs of ``K K^T`` and ``Q K^T`` over ``dk``, the
triangular solve and the weights' product over ``dv`` (``L (dk + dv)``
multiply-accumulates together), and three products with the state (``3 dk
dv``); q, k, v read, o and the chunk states written, the sums and ``beta``
read.  Backward: those made again but the output's two, and their
transposes: ``L / 2 (6 dk + 5 dv) + 7 dk dv``; q, k, v, do, o and the
states read, dq, dk, dv written.  ``--heads-a-step`` sets the module's
``HEADS_A_STEP`` (the heads one grid step takes) for a sweep.  The result is
the last line (JSON) and, with ``--out``, a file under ``chiprun_out/``.
"""
import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

gd = importlib.import_module("tensorflowonspark_tpu.ops.gated_delta")


def operands(key, batch, rows, heads, dk, dv, dtype):
    """q and k a head's unit vectors (q times ``dk ** -0.5``), decays of
    softplus steps, ``beta`` in (0, 2), and a cotangent for o."""
    ks = jax.random.split(key, 6)

    def unit(k):
        x = jax.random.normal(k, (batch, rows, heads, dk))
        return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)

    g = -jax.nn.softplus(jax.random.normal(ks[3], (batch, rows, heads)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (batch, rows, heads)))
    return ((unit(ks[0]) * dk ** -0.5).astype(dtype), unit(ks[1]).astype(dtype),
            jax.random.normal(ks[2], (batch, rows, heads, dv)).astype(dtype),
            g, beta,
            jax.random.normal(ks[5], (batch, rows, heads, dv)).astype(dtype))


def least(a, peaks):
    """{direction: (flops, bytes, least seconds)} of one call."""
    positions = a.batch * a.rows * a.heads
    dk, dv, chunk = a.key, a.value, a.chunk
    states = dk * dv / chunk                # elements a position
    fwd_flops = 2 * positions * (chunk * (dk + dv) + 3 * dk * dv)
    bwd_flops = 2 * positions * (chunk / 2 * (6 * dk + 5 * dv) + 7 * dk * dv)
    fwd_bytes = positions * (2 * (2 * dk + 2 * dv + states) + 2 * 4)
    bwd_bytes = positions * (2 * (4 * dk + 5 * dv + states) + 4 * 4)
    out = {}
    for name, flops, moved in (("forward", fwd_flops, fwd_bytes),
                               ("backward", bwd_flops, bwd_bytes)):
        out[name] = (flops, moved, max(flops / peaks["bf16_flops_per_s"],
                                       moved / peaks["hbm_bytes_per_s"]))
    return out


def agreement(dtype):
    """Largest error of the compiled kernels against the ``jax.numpy`` form,
    as a share of the largest element: o and the five gradients."""
    q, k, v, g, beta, weigh = operands(jax.random.PRNGKey(1), 1, 1024, 3, 96,
                                       192, dtype)

    def run(impl):
        def loss(*ops):
            o = gd.gated_delta_rule(*ops, chunk=64, impl=impl)
            return (o.astype(jnp.float32) * weigh.astype(jnp.float32)).sum(), o

        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(q, k, v, g, beta)
        return (o,) + grads

    out = {}
    for name, got, want in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                               run("pallas"), run("xla")):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        out[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--rows", type=int, default=8192)
    p.add_argument("--heads", type=int, default=15)
    p.add_argument("--key", type=int, default=96)
    p.add_argument("--value", type=int, default=192)
    p.add_argument("--chunk", type=int, default=64)
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--heads-a-step", type=int, default=gd.HEADS_A_STEP)
    p.add_argument("--out", help="also write the result to chiprun_out/<out>")
    a = p.parse_args()
    gd.HEADS_A_STEP = a.heads_a_step

    kind = jax.devices()[0].device_kind
    with open(os.path.join(HERE, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)[kind]
    result = {"shape": vars(a).copy(), "device": kind, "kernels": {},
              "against_jax_numpy": {
                  "float32": agreement(jnp.float32),
                  "bfloat16": agreement(jnp.bfloat16)}}
    print("against_jax_numpy", json.dumps(result["against_jax_numpy"]),
          flush=True)
    q, k, v, g, beta, do = operands(jax.random.PRNGKey(0), a.batch, a.rows,
                                    a.heads, a.key, a.value, jnp.bfloat16)
    forward = jax.jit(
        lambda *ops: gd._forward(*ops, a.chunk, "pallas", False))
    o, states = jax.block_until_ready(forward(q, k, v, g, beta))
    backward = jax.jit(lambda q, k, v, g, beta, o, states, do:
                       gd._delta_vjp_bwd(a.chunk, "pallas", False,
                                         (q, k, v, g, beta, o, states), do))
    calls = {"forward": (forward, (q, k, v, g, beta)),
             "backward": (backward, (q, k, v, g, beta, o, states, do))}
    bounds = least(a, peaks)
    for name, (fn, args) in calls.items():
        jax.block_until_ready(fn(*args))
        start = time.perf_counter()
        for _ in range(a.calls):
            out = fn(*args)
        jax.block_until_ready(out)
        seconds = (time.perf_counter() - start) / a.calls
        flops, moved, floor = bounds[name]
        row = {"ms_a_call": 1e3 * seconds, "flops": flops, "bytes": moved,
               "least_ms": 1e3 * floor,
               "bound_by": "flops" if flops / peaks["bf16_flops_per_s"]
               >= floor else "bytes",
               "roofline_pct": 100.0 * floor / seconds}
        result["kernels"][name] = row
        print(name, json.dumps(row), flush=True)
    if a.out:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", a.out), "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
