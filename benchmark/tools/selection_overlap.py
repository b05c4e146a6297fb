#!/usr/bin/env python3
"""How far the keys that the program's learned index picks differ from the
plain reference's, layer by layer, at a configuration's own sizes: the
program's activations are bfloat16 and the reference's float32, so near a
row's ``topk``-th score the two keep different keys.  What a limits file
of a sparse-attention cell records beside its readings.

    python3 benchmark/tools/selection_overlap.py --config keye_vl2_30b_a3b_ep8 \
        --seed 2147480000 --out chiprun_out/<cell>.selection.json

One process, on the chip (or, at a tiny size, anywhere).  The program's side
is the program's own: its forward pass with the outputs of the index's three
projections captured (flax's ``capture_intermediates``), its RoPE, and its
selection kernel; the reference's side is ``references/<family>.py``'s
``index_scores`` and ``selection`` on its own float32 hidden states.  For each
layer: the share of the program's kept (query, key) pairs that the reference
does not keep."""

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _unpack(bits, start, block, seq):
    """bool [block, seq] of rows start .. start + block of one sequence's
    key_bits [groups, T, 128]."""
    import jax.numpy as jnp
    from jax import lax

    words = lax.dynamic_slice_in_dim(bits, start, block, axis=1)
    spread = (words[:, :, None, :] >> jnp.arange(32)[None, None, :, None]) & 1
    return spread.transpose(1, 0, 2, 3).reshape(block, -1)[:, :seq] == 1


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--configs-dir",
                        default=os.path.join(ROOT, "benchmark", "configs"))
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tensorflowonspark_tpu.models import transformer

    sparse_index = importlib.import_module(
        "tensorflowonspark_tpu.ops.sparse_index")
    with open(os.path.join(args.configs_dir, args.config + ".json")) as f:
        cfg = json.load(f)
    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    seq, topk = cfg["seq_len"], cfg["sa_config"]["topk"]
    layers = cfg["num_hidden_layers"]
    tokens = np.asarray(adapter.make_row(cfg, args.seed, 0)[1], np.int32)

    # the program: its own forward pass, the index's projections captured
    built = adapter.build(cfg, args.seed)
    model, params = built["model"], built["params"]
    if built["compute_dtype"] is not None:
        params = jax.tree_util.tree_map(
            lambda x: x.astype(built["compute_dtype"])
            if x.dtype == jnp.float32 else x, params)
    wanted = ("index_q", "index_k_norm", "index_w")

    @jax.jit
    def captured(params, tokens):
        _, state = model.apply(
            {"params": params}, tokens[None], mutable=["intermediates"],
            capture_intermediates=lambda module, _: module.name in wanted)
        out = []
        for i in range(layers):
            got = state["intermediates"]["block_%d" % i]["attention"]
            iq, ik, iw = (got[name]["__call__"][0] for name in wanted)
            inv, _ = transformer.rope_frequencies(iq.shape[-1],
                                                  float(cfg["rope_theta"]))
            iq = transformer.rope(iq, inv)
            ik = transformer.rope(ik[:, :, None], inv)[:, :, 0]
            iw = iw * (iq.shape[2] ** -0.5 * iq.shape[3] ** -0.5)
            out.append(sparse_index.select_keys(
                iq, ik, iw, topk, chunk=cfg["flash_block"])[0][0])
        return out

    program_bits = [np.asarray(b) for b in captured(params, tokens)]
    del built, params

    # the reference: its float32 hidden states, layer by layer
    weights = ref.init_weights(cfg, args.seed)
    block = min(seq, 512)

    p = "L0."       # every layer's leaves under one name: one program

    @jax.jit
    def differing(x, mine, bits):
        """(x after the layer, pairs the program keeps and the reference
        does not, pairs the program keeps)."""
        eps = cfg["rms_norm_eps"]
        h = ref._rms(x, mine[p + "op_norm"], eps)
        iq, ik, iw = ref.index_scores(h, mine, p, cfg)

        def rows(start):
            take = lambda a: lax.dynamic_slice_in_dim(  # noqa: E731
                a, start, block, axis=0)
            index = (take(iw).T[:, :, None] * jax.nn.relu(jnp.einsum(
                "qje,se->jqs", take(iq), ik,
                precision=lax.Precision.HIGHEST))).sum(axis=0)
            theirs = ref.selection(index, start, topk)
            ours = _unpack(bits, start, block, seq)
            return (ours & ~theirs).sum(), ours.sum()

        apart, kept = lax.map(rows, jnp.arange(0, seq, block))
        y, _ = ref._attention(h, mine, p, cfg, "float32")
        x = x + y
        x = x + ref._experts(ref._rms(x, mine[p + "ff_norm"], eps), mine, p,
                             cfg, "float32")
        return x, apart.sum(), kept.sum()

    x = weights["embed"][tokens]
    out = {"config": args.config, "seed": args.seed, "seq_len": seq,
           "topk": topk, "layers": []}
    for i in range(layers):
        mine = {p + k.split(".", 1)[1]: v for k, v in weights.items()
                if k.startswith("L%d." % i)}
        x, apart, kept = differing(x, mine, jnp.asarray(program_bits[i]))
        out["layers"].append({
            "layer": i, "kept_pairs": int(kept),
            "kept_by_the_program_alone": int(apart),
            "share": float(apart) / float(kept)})
        print("selection: " + json.dumps(out["layers"][-1]), flush=True)
    out["device"] = jax.devices()[0].device_kind
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
