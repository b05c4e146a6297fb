"""The three flash kernels alone at one cell's shape: milliseconds a call and
microseconds a computed tile, with where XLA placed each operand.

    chiprun -- python3 scripts/time_flash_kernels.py --heads 32 --kv-heads 4 \\
        --rows 32768 --dk 128 --dv 128 --block 512 [--window 1024] [--key-bits]

GPT-2 medium's layer under ``attention="full"`` (batch 4 x 16 heads of 64 over
1,024 rows; the rule of ``full_attention_block`` gives it blocks of 512):

    chiprun -- python3 scripts/time_flash_kernels.py --heads 64 --kv-heads 64 \\
        --rows 1024 --dk 64 --dv 64 --block 512

One jitted kernel a time (forward, dQ, dK/dV as ``ops/flash_attention.py``
launches them, ``[heads, rows, width]`` bfloat16), ``--calls`` calls by the
host's clock between two ``block_until_ready``.  A kernel timed alone is
timed with whatever placement its little program gets (PERF.md section 7):
each operand of the kernel's call in the optimised HLO is printed with
``vmem`` where its layout says ``S(1)`` and ``hbm`` elsewhere; compare two
trees' times only where the placements agree.  ``--describe`` compiles for a
described v5e without a chip (placement only, no times).  The result is the
last line (JSON) and, with ``--out``, a file under ``chiprun_out/``.
"""
import argparse
import importlib
import json
import os
import re
import sys
import time

# --tree DIR (first, before anything of the program is imported): time the
# kernels of another tree inside this checkout, a ``git archive`` of the
# parent unpacked under the ignored ``_scratch/`` say; nothing outside the
# checkout is put on the path
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREE = os.path.abspath(sys.argv[sys.argv.index("--tree") + 1]
                       if "--tree" in sys.argv else HERE)
if os.path.commonpath([HERE, TREE]) != HERE:
    sys.exit("--tree {} lies outside this checkout ({})".format(TREE, HERE))
sys.path.insert(0, TREE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tensorflowonspark_tpu.ops import sparse_index  # noqa: E402

# the module: ``ops.flash_attention`` is the function
fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")


def placement(text):
    """``["bf16[4,32768,128] vmem", ...]``: the operands of the one kernel
    call in a compiled (scheduled) program's text, in order; an operand is a
    name there, and the instruction that made it carries the layout."""
    made = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\S+)", text, re.M))
    line = next(ln for ln in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln)
    names = re.findall(r"%[\w.-]+",
                       re.search(r" custom-call\(([^)]*)\)", line).group(1))
    return ["{} {}".format(made[name].split("{")[0],
                           "vmem" if "S(1)" in made[name] else "hbm")
            for name in names]


def kernels(a):
    """``{name: (function, arguments)}`` of the three launchers at the
    shape, arguments as ``ShapeDtypeStruct``s."""
    group = a.heads // a.kv_heads

    def arg(heads, width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((heads, a.rows, width), dtype)

    q, k, v, g = (arg(a.heads, a.dk), arg(a.kv_heads, a.dk),
                  arg(a.kv_heads, a.dv), arg(a.heads, a.dv))
    stat = jax.ShapeDtypeStruct((a.heads, 1, a.rows), jnp.float32)
    tail = (a.dk ** -0.5, a.causal, a.block, a.block, False, group)
    extra = ()
    if a.key_bits:
        extra = (jax.ShapeDtypeStruct(
            (1, sparse_index.key_groups(a.rows), a.rows, 128), jnp.int32),)
    return {
        "fwd": (lambda q, k, v, *bits: fa._flash_fwd(
            q, k, v, *tail, *(bits or (None,)), a.window), (q, k, v) + extra),
        "dq": (lambda *x: fa._flash_bwd_dq(
            *x[:6], *tail, *(x[6:] or (None,)), a.window),
            (q, k, v, g, stat, stat) + extra),
        "dkv": (lambda *x: fa._flash_bwd_dkv(
            *x[:6], *tail, *(x[6:] or (None,)), a.window),
            (q, k, v, g, stat, stat) + extra),
    }


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--kv-heads", type=int, default=4)
    p.add_argument("--rows", type=int, default=32768)
    p.add_argument("--dk", type=int, default=128)
    p.add_argument("--dv", type=int, default=128)
    p.add_argument("--block", type=int, default=512)
    p.add_argument("--window", type=int, default=None)
    p.add_argument("--key-bits", action="store_true",
                   help="every query's key set (all ones) through the kernels")
    p.add_argument("--full", dest="causal", action="store_false",
                   help="causal=False")
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--describe", action="store_true",
                   help="compile for a described v5e, no chip: placement only")
    p.add_argument("--tree", help="the tree whose kernels are timed: this "
                   "checkout (default) or a directory inside it")
    p.add_argument("--out", help="also write the result to chiprun_out/<out>")
    a = p.parse_args()

    blocks = a.rows // a.block
    tiles = (fa.band_tiles(a.rows, a.block, a.window)[0] if a.causal
             else blocks * blocks)
    result = {"shape": vars(a).copy(), "tree": TREE,
              "tiles_computed_a_head": tiles, "kernels": {}}
    if hasattr(fa, "grid_tiles"):   # a tree from before it steps the square
        result["grid_steps_a_head"] = fa.grid_tiles(
            a.rows, a.block, a.block, a.causal, a.window)[0]
    if a.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        result["device"] = "described v5e (nothing ran)"
    else:
        one = None
        result["device"] = jax.devices()[0].device_kind
    for name, (fn, shapes) in kernels(a).items():
        if one is not None:
            shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
                      for s in shapes]
        compiled = jax.jit(fn).lower(*shapes).compile()
        row = {"operands": placement(compiled.as_text())}
        if not a.describe:
            keys = jax.random.split(jax.random.PRNGKey(0), len(shapes))
            args = [jnp.full(s.shape, -1, s.dtype) if s.dtype == jnp.int32
                    else jax.random.normal(key, s.shape, s.dtype)
                    for key, s in zip(keys, shapes)]
            jax.block_until_ready(compiled(*args))
            start = time.perf_counter()
            for _ in range(a.calls):
                out = compiled(*args)
            jax.block_until_ready(out)
            row["ms_a_call"] = 1e3 * (time.perf_counter() - start) / a.calls
            row["us_a_tile"] = 1e3 * row["ms_a_call"] / (a.heads * tiles)
        result["kernels"][name] = row
        print(name, json.dumps(row), flush=True)
    if a.out:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", a.out), "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
