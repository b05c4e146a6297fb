"""One-shot hardware-evidence capture on the attached chip (run it there:
``chiprun -- python scripts/device_validate.py``).  Nothing below has been
measured on this code; ``chip_smoke.py`` is the maintained proof that the
main path starts on the chip.

Collects the raw numbers an MFU gap analysis needs:

1. Device roster through :mod:`tensorflowonspark_tpu.device_info` on the
   real chip.
2. ``pin_chips`` on the real host: pin worker 0 to chip 0 in a fresh
   subprocess and record whether device discovery still works and how many
   devices are visible (on this 1-chip host the meaningful assertion is
   "pinning does not break enumeration"; the env-var arithmetic itself has
   unit tests).
3. A ``jax.profiler`` trace captured through the framework's
   :class:`~tensorflowonspark_tpu.profiler.StepProfiler` path, asserting
   trace files actually land on disk.
4. Dispatch time (tiny jitted add, host readback per call) — the
   per-dispatch constant that K-steps-per-dispatch amortizes.
5. Raw sustained bf16 matmul throughput via ``lax.scan`` (dispatch
   amortized): the *achievable* ceiling for MFU, vs the v5e peak of 197
   bf16 TFLOP/s.

Timing discipline (both timed probes): every sample ends with a
device->host READBACK of a value data-dependent on the work (same rule as
``metrics.TimeHistory._sync``), so the host clock spans the device's work.

Writes one JSON blob to --out.  Each probe is isolated in a subprocess: one
process holds the chip at a time, and a failed probe loses one number.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROSTER = r"""
import json, sys
sys.path.insert(0, {root!r})
from tensorflowonspark_tpu import device_info
print(json.dumps({{"devices": device_info.device_summary(),
                   "local_chips": device_info.num_local_chips()}}))
"""

PIN = r"""
import json, os, sys
sys.path.insert(0, {root!r})
from tensorflowonspark_tpu import device_info
chips = device_info.pin_chips(0, 1, total_chips=1)
env = {{k: os.environ[k] for k in ("TPU_VISIBLE_CHIPS",
        "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")}}
import jax
print(json.dumps({{"pinned": chips, "env": env,
                   "visible_devices": len(jax.devices()),
                   "device_kind": jax.devices()[0].device_kind}}))
"""

PROFILE = r"""
import glob, json, os, sys, tempfile
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from tensorflowonspark_tpu.profiler import StepProfiler
log_dir = tempfile.mkdtemp(prefix="tfos_trace_")
f = jax.jit(lambda x: (x @ x).sum())
x = jnp.ones((512, 512), jnp.bfloat16)
prof = StepProfiler(log_dir, "1,3")
for _ in range(5):
    prof.on_step_begin()
    f(x).block_until_ready()
    prof.on_step_end()
prof.stop()
files = [p for p in glob.glob(os.path.join(log_dir, "**", "*"),
                              recursive=True) if os.path.isfile(p)]
print(json.dumps({{"log_dir": log_dir, "n_trace_files": len(files),
                   "sample": sorted(os.path.basename(p) for p in files)[:5]}}))
"""

DISPATCH = r"""
import json, time
import jax, jax.numpy as jnp
f = jax.jit(lambda x: (x + 1).sum())  # scalar out: readback is 4 bytes
x = jnp.zeros((8,), jnp.float32)
float(f(x))  # warm; float() = device->host readback, the real barrier
ts = []
for _ in range(20):
    t0 = time.perf_counter()
    float(f(x))
    ts.append(time.perf_counter() - t0)
ts.sort()
print(json.dumps({{"dispatch_rtt_ms_median": round(1e3 * ts[len(ts)//2], 2),
                   "dispatch_rtt_ms_min": round(1e3 * ts[0], 2)}}))
"""

MATMUL = r"""
import json, time
import jax, jax.numpy as jnp
from jax import lax
# K=512 scan steps per dispatch: the per-dispatch cost vanishes in the sample.
N, K = 4096, 512
def body(c, _):
    c = jnp.tanh(c @ c)  # tanh breaks trivial fusion/strength-reduction
    return c, ()
@jax.jit
def run(x):
    y, _ = lax.scan(body, x, None, length=K)
    return y.sum()  # scalar out: readback (the barrier) is 4 bytes
x = jnp.ones((N, N), jnp.bfloat16) * 0.001
float(run(x))  # warm + compile; float() forces real completion
best = None
for _ in range(3):
    t0 = time.perf_counter()
    float(run(x))
    dt = time.perf_counter() - t0
    best = dt if best is None else min(best, dt)
flops = 2 * N * N * N * K
tflops = flops / best / 1e12
print(json.dumps({{"matmul_n": N, "scan_len": K,
                   "sustained_bf16_tflops": round(tflops, 1),
                   "v5e_peak_tflops": 197,
                   "pct_of_peak": round(100 * tflops / 197, 1)}}))
"""

PROBES = {"roster": ROSTER, "pin_chips": PIN, "profiler": PROFILE,
          "dispatch": DISPATCH, "matmul": MATMUL}


def run_probe(name, code, timeout=600):
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code.format(root=ROOT)],
            capture_output=True, text=True, timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {"error": "timed out after %ds" % timeout}
    if proc.returncode != 0:
        return {"error": proc.stderr.strip()[-400:]}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "unparseable output: %r" % proc.stdout[-200:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        tempfile.gettempdir(), "device_validate.json"))
    args = ap.parse_args()
    out = {}
    for name, code in PROBES.items():
        out[name] = run_probe(name, code)
        print("%s: %s" % (name, json.dumps(out[name])[:300]), flush=True)
        # rewrite after every probe: a mid-run kill keeps what's done
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    print("wrote", args.out)


if __name__ == "__main__":
    main()
