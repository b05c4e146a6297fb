"""The chunked state-space scan's kernels alone at one cell's shape:
milliseconds a call, forward and backward, each against its roofline, and the
compiled kernels against the ``jax.numpy`` form at a small shape.

    chiprun -- python3 scripts/time_ssd_scan.py --batch 2 --rows 8192 \\
        --heads 64 --width 64 --groups 8 --state 128 --chunk 128

``forward`` is ``ops/ssd_scan.py``'s ``_forward`` (the decays' sums and the
layouts by XLA, then the kernel), ``backward`` its ``_backward`` (``sum_p dy
y`` and the reversed sums by XLA round the kernel), bfloat16 ``x``, ``B``,
``C``; ``--calls`` calls by the host's clock between two
``block_until_ready``.  The least time is the larger of the chunked form's
FLOPs over the chip's peak and its HBM bytes over the bandwidth
(``benchmark/peaks.json``): forward the four products and x, B, C read, y
and the chunk states written; backward ten products (the scores again, two
inside a chunk and four with the states a head, three of the scores'
gradient a group) and x, dy, y, B, C and the states read, dx, dB, dC
written.  The result is the last line (JSON) and, with ``--out``, a file
under ``chiprun_out/``.
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

# the module: ``ops.ssd_scan`` may be shadowed by a function some day
ssd = importlib.import_module("tensorflowonspark_tpu.ops.ssd_scan")


def operands(key, batch, rows, heads, width, groups, state, dtype):
    ks = jax.random.split(key, 6)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, rows, heads)) - 4.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    small = lambda k, shape: (0.3 * jax.random.normal(k, shape)  # noqa: E731
                              ).astype(dtype)
    return (jax.random.normal(ks[0], (batch, rows, heads, width)
                              ).astype(dtype), dt, dt * a,
            small(ks[3], (batch, rows, groups, state)),
            small(ks[4], (batch, rows, groups, state)),
            jax.random.normal(ks[5], (batch, rows, heads, width)
                              ).astype(dtype))


def least(a, peaks):
    """{direction: (flops, bytes, least seconds)} of one call."""
    tokens, half = a.batch * a.rows, (a.chunk + 1) / 2
    hp, gn = a.heads * a.width, a.groups * a.state
    states = hp * a.state / a.chunk         # elements a position
    fwd_flops = 2 * tokens * (a.groups * half * a.state
                              + a.heads * (half * a.width
                                           + 2 * a.width * a.state))
    bwd_flops = 2 * tokens * (3 * a.groups * half * a.state
                              + a.heads * (2 * half * a.width
                                           + 4 * a.width * a.state))
    small = 2 * 4 * a.heads                 # dt and the sums, float32
    fwd_bytes = tokens * (2 * (2 * hp + 2 * gn + states) + small)
    bwd_bytes = tokens * (2 * (4 * hp + 4 * gn + states) + 3 * small)
    out = {}
    for name, flops, moved in (("forward", fwd_flops, fwd_bytes),
                               ("backward", bwd_flops, bwd_bytes)):
        out[name] = (flops, moved, max(flops / peaks["bf16_flops_per_s"],
                                       moved / peaks["hbm_bytes_per_s"]))
    return out


def agreement(dtype):
    """Largest error of the compiled kernels against the ``jax.numpy`` form,
    as a share of the largest element: y and the five gradients."""
    x, dt, decay, b, c, weigh = operands(jax.random.PRNGKey(1), 1, 1024, 16,
                                         64, 2, 128, dtype)

    def run(impl):
        def loss(*ops):
            y = ssd.ssd_scan(*ops, chunk=128, impl=impl)
            return (y.astype(jnp.float32) * weigh.astype(jnp.float32)).sum(), y

        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, dt, decay, b, c)
        return (y,) + grads

    out = {}
    for name, got, want in zip(("y", "dx", "ddt", "dlog_decay", "db", "dc"),
                               run("pallas"), run("xla")):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        out[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--rows", type=int, default=8192)
    p.add_argument("--heads", type=int, default=64)
    p.add_argument("--width", type=int, default=64)
    p.add_argument("--groups", type=int, default=8)
    p.add_argument("--state", type=int, default=128)
    p.add_argument("--chunk", type=int, default=128)
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--out", help="also write the result to chiprun_out/<out>")
    a = p.parse_args()

    kind = jax.devices()[0].device_kind
    with open(os.path.join(HERE, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)[kind]
    result = {"shape": vars(a).copy(), "device": kind, "kernels": {},
              "against_jax_numpy": {
                  "float32": agreement(jnp.float32),
                  "bfloat16": agreement(jnp.bfloat16)}}
    print("against_jax_numpy", json.dumps(result["against_jax_numpy"]),
          flush=True)
    x, dt, decay, b, c, dy = operands(
        jax.random.PRNGKey(0), a.batch, a.rows, a.heads, a.width, a.groups,
        a.state, jnp.bfloat16)
    forward = jax.jit(lambda *ops: ssd._forward(*ops, a.chunk, False))
    y, states = jax.block_until_ready(forward(x, dt, decay, b, c))
    backward = jax.jit(lambda *ops: ssd._backward(*ops, a.chunk, False))
    calls = {"forward": (forward, (x, dt, decay, b, c)),
             "backward": (backward, (x, dt, decay, b, c, y, states, dy))}
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
