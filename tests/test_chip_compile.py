"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's compiler is installed here and refuses what the chip
would refuse (block shapes off the tiling, too much VMEM, a kernel the
partitioner cannot split) — none of which interpret mode can show.

Nothing runs, so these say nothing about results or speed; the value parity
tests are in ``tests/test_ops.py``.  The whole training steps of the
benchmark's configurations are in ``tests/test_chip_compile_<family>.py``,
and what they share with this file in ``tests/chip_compile.py``.
``jax.default_backend()`` is still the CPU here, so code that picks interpret
mode from it is steered in the test.
"""

import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from chip_compile import (  # noqa: F401  (fixtures)
    _compile, _kernel_lines, _one_lane_arrays, fa, no_compile_cache, si, ssd,
    topo)

SHAPES = [(8, 1024, 16, 16, 64, 64, 128), (2, 4096, 8, 8, 128, 128, 128),
          (4, 8192, 16, 16, 192, 128, 512), (4, 8192, 32, 8, 64, 64, 512),
          (1, 32768, 32, 4, 128, 128, 512), (1, 32768, 8, 1, 128, 128, 128),
          (1, 131072, 2, 2, 128, 128, 128), (1, 80128, 1, 1, 128, 128, 128)]


@pytest.mark.parametrize("shape", SHAPES,
                         ids=["s1024_d64", "s4096_d128", "s8192_dk192_dv128",
                              "s8192_d64_group4", "s32768_d128_group8",
                              "s32768_block128_group8", "s131072_block128",
                              "s80128_block128"])
@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
def test_flash_kernel_compiles_for_v5e(topo, kernel, shape):
    """The causal kernels on the grid that lists the triangle's tiles (a
    table in SMEM: 2,080 steps a head at 32,768 / 512, and 16,640 a KV head
    in dK/dV at a group of 8), up to the longest list a launcher makes
    (``LISTED_STEPS``), and on the clamped rectangle where the list would be
    longer: no shape is refused."""
    batch, seq, heads, kv, dk, dv, block = shape
    one = SingleDeviceSharding(topo.devices[0])

    def arg(heads, width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((batch * heads, seq, width), dtype,
                                    sharding=one)

    q, k, v, g = arg(heads, dk), arg(kv, dk), arg(kv, dv), arg(heads, dv)
    stat = jax.ShapeDtypeStruct((batch * heads, 1, seq), jnp.float32,
                                sharding=one)
    # scale, causal, blocks, interpret, group
    tail = (dk ** -0.5, True, block, block, False, heads // kv)
    if kernel == "fwd":
        text = _compile(lambda q, k, v: fa._flash_fwd(q, k, v, *tail),
                        q, k, v)
    else:
        launch = fa._flash_bwd_dq if kernel == "bwd_dq" else fa._flash_bwd_dkv
        text = _compile(
            lambda q, k, v, g, lse, delta: launch(q, k, v, g, lse, delta,
                                                  *tail),
            q, k, v, g, stat, stat)
    assert text.count("tpu_custom_call") == 1
    n = seq // block
    steps = n * (n + 1) // 2 * (heads // kv if kernel == "bwd_dkv" else 1)
    assert ("s32[{}]".format(steps) in text) == (steps <= fa.LISTED_STEPS)
    assert (re.search(r"s32\[\d+\]", text) is None) == (
        steps > fa.LISTED_STEPS)
    assert not _one_lane_arrays("\n".join(_kernel_lines(text)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("width", [256, 512, 1024])
def test_the_rules_widest_operands_compile_for_v5e(topo, width, dtype):
    """The block ``row_block`` gives a row of 1,024 at the widest heads it
    serves (512 at 256, 256 at 512, 128 at 1,024: ``RULE_BLOCK_ELEMENTS``),
    forward and backward, in bfloat16 and in float32 (the default dtype of
    ``build_transformer``): what the rule hands the kernels, the chip's
    compiler takes."""
    block = fa.row_block(1024, width)
    assert block * width == fa.RULE_BLOCK_ELEMENTS
    assert fa.row_block(1024, 2 * width) == (block // 2 if block > 128
                                             else None)
    x = jax.ShapeDtypeStruct((2, 1024, 4, width), dtype,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, block_q=block, block_k=block,
                                  interpret=False).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, (0, 1, 2)), x, x, x)
    assert text.count("tpu_custom_call") == 3


def test_lm_block_with_flash_compiles_for_v5e(topo, monkeypatch):
    """One transformer block at chip_smoke's LM widths (d_model 1024, 16x64
    heads, seq 1024, batch 8, bf16), forward and backward: the kernel in
    its real surroundings (qkv projection layouts, the custom VJP)."""
    from tensorflowonspark_tpu.models import transformer

    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    block = transformer.Block(spec=transformer.gpt2_layer(16, 64),
                              attention="flash", dtype=jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((8, 1024, 1024), jnp.bfloat16, sharding=one)
    # parameters never depend on the attention kind: shape them without
    # tracing the kernel for the CPU
    shapes = jax.eval_shape(
        transformer.Block(spec=transformer.gpt2_layer(16, 64),
                          dtype=jnp.bfloat16).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 128, 1024), jnp.bfloat16))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        shapes)

    def loss(p, x):
        return block.apply(p, x).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss), params, x)
    assert text.count("tpu_custom_call") == 3  # forward, dQ, dK/dV


def test_flash_keeps_no_one_lane_residual(capsys):
    """What ``save_only_these_names(*KEPT)`` keeps of the op across a
    checkpoint, and what its backward rule is handed besides: no float32
    array whose last dimension is 1 (the logsumexp rows are kept as the
    kernel writes them, dense; the padded form cannot come back
    unnoticed).  Traced only, at latent attention's benchmark sizes."""
    from jax.ad_checkpoint import print_saved_residuals

    def arg(heads, width):
        return jax.ShapeDtypeStruct((4, 8192, heads, width), jnp.bfloat16)

    def op(q, k, v):
        out, lse = fa.flash_attention_lse(q, k, v, block_q=512, block_k=512,
                                          interpret=True)
        return out.astype(jnp.float32).sum() + lse.sum()

    print_saved_residuals(
        jax.checkpoint(op, policy=jax.checkpoint_policies
                       .save_only_these_names(*fa.KEPT)),
        arg(16, 192), arg(16, 192), arg(16, 128))
    kept = capsys.readouterr().out
    # the two named results (a name is a reduce_precision in the trace)
    assert "f32[64,1,8192] output of reduce_precision" in kept
    assert "bf16[64,8192,128] output of reduce_precision" in kept
    assert not _one_lane_arrays(kept), kept


def test_sharded_flash_compiles_for_v5e_2x2(topo, monkeypatch):
    """A Mosaic kernel cannot be partitioned by the compiler; on a mesh the
    op maps itself per shard (``mesh=``), and Ulysses attention calls it
    inside its own shard_map.  Both must compile across the four chips."""
    from tensorflowonspark_tpu.parallel import ring

    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "tensor"))
    x = jax.ShapeDtypeStruct(
        (8, 1024, 16, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, "tensor", None)))
    text = _compile(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True, mesh=mesh),
        x, x, x)
    assert "tpu_custom_call" in text

    seq_mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "seq"))
    xs = jax.ShapeDtypeStruct(
        (8, 4096, 16, 64), jnp.bfloat16,
        sharding=NamedSharding(seq_mesh, P("data", "seq", None, None)))
    text = _compile(
        lambda q, k, v: ring.ulysses_attention(q, k, v, seq_mesh, causal=True,
                                               impl="flash"),
        xs, xs, xs)
    assert "tpu_custom_call" in text and "all-to-all" in text


@pytest.mark.parametrize("axes, kernels", [
    ({"data": 2, "tensor": 2}, True), ({"data": 2, "seq": 2}, False)],
    ids=["data2_tensor2", "data2_seq2"])
def test_full_attention_on_a_mesh_compiles_for_v5e_2x2(topo, monkeypatch,
                                                       axes, kernels):
    """A GPT-2 block under ``attention="full"`` across the four chips,
    forward and backward: where the kernels' per-shard mapping fits (batch
    over ``data``, heads over ``tensor``) the rule hands it the three
    kernels; on a mesh with a ``seq`` axis the plain contraction stays under
    GSPMD, which compiles as it did."""
    from tensorflowonspark_tpu.models import transformer

    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), tuple(axes))
    block = transformer.Block(spec=transformer.gpt2_layer(16, 64),
                              mesh=mesh, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct(
        (8, 1024, 1024), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, None)))
    shapes = jax.eval_shape(
        transformer.Block(spec=transformer.gpt2_layer(16, 64),
                          dtype=jnp.bfloat16).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 128, 1024), jnp.bfloat16))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=NamedSharding(mesh, P())),
        shapes)

    def loss(p, x):
        return block.apply(p, x).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss), params, x)
    assert text.count("tpu_custom_call") == (3 if kernels else 0)


# the learned index at the benchmark's sizes: 32,768 positions, 32 query and
# 4 KV heads of 128, 16 index heads of 64, blocks of 512
KEYED = dict(batch=1, seq=32768, heads=32, kv=4, dim=128, index_heads=16,
             index_dim=64, block=512)


def _keyed_args(topo):
    one = SingleDeviceSharding(topo.devices[0])
    b, t = KEYED["batch"], KEYED["seq"]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return arg, arg((b, si.key_groups(t), t, 128), jnp.int32)


@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
def test_flash_kernel_with_key_bits_compiles_for_v5e(topo, kernel):
    """The three flash kernels reading each query's own keys as bits, at the
    sparse-attention cell's sizes (group 8, one ``[512, 128]`` tile of words
    for eight k blocks), on the grid that lists the triangle's tiles."""
    arg, bits = _keyed_args(topo)
    b, t, h, kv, d = (KEYED[k] for k in ("batch", "seq", "heads", "kv",
                                         "dim"))
    q, k, stat = arg((b * h, t, d)), arg((b * kv, t, d)), arg(
        (b * h, 1, t), jnp.float32)
    tail = (d ** -0.5, True, KEYED["block"], KEYED["block"], False, h // kv)
    if kernel == "fwd":
        text = _compile(lambda q, k, v, bits: fa._flash_fwd(
            q, k, v, *tail, bits), q, k, k, bits)
    else:
        launch = fa._flash_bwd_dq if kernel == "bwd_dq" else fa._flash_bwd_dkv
        text = _compile(
            lambda q, k, v, g, lse, delta, bits: launch(
                q, k, v, g, lse, delta, *tail, bits),
            q, k, k, q, stat, stat, bits)
    assert "s32[{}]".format(2080 * (8 if kernel == "bwd_dkv" else 1)) in text
    assert text.count("tpu_custom_call") == 1
    assert not _one_lane_arrays("\n".join(_kernel_lines(text)))


@pytest.mark.parametrize("kernel", ["select", "loss", "loss_grads"])
def test_index_kernels_compile_for_v5e(topo, kernel):
    """The selection kernel (a block of 256 queries' scores over all 32,768
    keys in VMEM: 32 MiB of its 100 MiB limit) and the kernel of the index's
    loss, without and with its gradients (every head's query block and the
    resident gradient of the index's keys in VMEM), at the cell's sizes."""
    arg, bits = _keyed_args(topo)
    b, t, h, kv, d, j, e = (KEYED[k] for k in (
        "batch", "seq", "heads", "kv", "dim", "index_heads", "index_dim"))
    index = (arg((b, t, j, e)), arg((b, t, e)), arg((b, t, j), jnp.float32))
    if kernel == "select":
        text = _compile(lambda *a: si.select_keys(*a, 2048, interpret=False),
                        *index)
    else:
        rest = (arg((b, t, h, d)), arg((b, t, kv, d)),
                arg((b, t, h), jnp.float32), arg((b, t), jnp.float32), bits)

        def loss(*a):
            return si.index_loss(*a, block=KEYED["block"],
                                 interpret=False).sum()

        text = _compile(loss if kernel == "loss"
                        else jax.grad(loss, (0, 1, 2)), *index, *rest)
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("block", [512, 256])
@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
def test_flash_kernel_with_a_window_compiles_for_v5e(topo, kernel, block):
    """The three flash kernels with a window of 1,024 keys at the window
    cell's sizes (32,768 positions, 32 query and 4 KV heads of 128), at
    blocks of 512 (three tiles a q block) and 256 (five): the inner grid
    extent is the band's, not ``seq / block``."""
    one = SingleDeviceSharding(topo.devices[0])
    t, h, kv, d = 32768, 32, 4, 128

    def arg(rows, width=d, dtype=jnp.bfloat16):
        shape = (rows, t, width) if width else (rows, 1, t)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    q, k, stat = arg(h), arg(kv), arg(h, 0, jnp.float32)
    tail = (d ** -0.5, True, block, block, False, h // kv, None, 1024)
    if kernel == "fwd":
        text = _compile(lambda q, k, v: fa._flash_fwd(q, k, v, *tail),
                        q, k, k)
    else:
        launch = fa._flash_bwd_dq if kernel == "bwd_dq" else fa._flash_bwd_dkv
        text = _compile(
            lambda q, k, v, g, lse, delta: launch(q, k, v, g, lse, delta,
                                                  *tail),
            q, k, k, q, stat, stat)
    assert text.count("tpu_custom_call") == 1
    assert not _one_lane_arrays("\n".join(_kernel_lines(text)))
    steps = 1024 // block + 1
    outer = t // block
    assert fa._k_run(outer, block, block, 1024)[0] == steps
    assert fa._q_run(outer, outer, block, block, 1024) == steps


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_ssd_scan_kernels_compile_for_v5e(topo, direction, dtype):
    """The chunked state-space scan at the benchmark's shape (2 rows of
    8,192 positions, 64 heads of 64 in 8 groups, a state of 128, chunks of
    128): the forward kernel with its carried state in VMEM and the reversed
    backward kernel, two heads to a 128-lane slab, every slice on a tile."""
    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    x, dt = arg((2, 8192, 64, 64)), arg((2, 8192, 64), jnp.float32)
    b = arg((2, 8192, 8, 128))

    def scan(*operands):
        return ssd.ssd_scan(*operands, chunk=128, impl="pallas")

    if direction == "forward":
        text = _compile(scan, x, dt, dt, b, b)
    else:
        text = _compile(
            jax.grad(lambda *a: scan(*a).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2, 3, 4)), x, dt, dt, b, b)
    calls = _kernel_lines(text)
    assert len(calls) == (1 if direction == "forward" else 2)
    # neither [T, T] nor a state a position
    assert "8192,8192" not in text and "8192,64,64,128" not in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_routed_rows_kernels_compile_v5e(topo, dtype):
    """The expert layer's row movement at the benchmark's sizes (32,768
    tokens of 2,048, four slots a token): the slab packing with its strided
    stores, the gather with a row DMA a fetched row, a scale and a dot
    product, and the gather-and-sum, for two-byte rows (two elements a
    32-bit word) and four-byte ones."""
    rr = importlib.import_module("tensorflowonspark_tpu.ops.routed_rows")
    one = SingleDeviceSharding(topo.devices[0])
    tokens, slots, d = 32768, 4, 2048
    pairs = tokens * slots

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    text = _compile(
        lambda x, src, n, scale, ys: rr.gather_rows(
            x, src, n, scale=scale, dot_with=ys, impl="pallas"),
        arg((tokens, d), dtype), arg((pairs,), jnp.int32),
        arg((), jnp.int32), arg((pairs,), jnp.float32),
        arg((pairs, d), dtype))
    assert text.count("tpu_custom_call") >= 2       # pack, gather
    text = _compile(
        lambda ys, idx, n, w: rr.gather_sum_rows(ys, idx, n, weights=w,
                                                 impl="pallas"),
        arg((pairs, d), dtype), arg((tokens, slots), jnp.int32),
        arg((), jnp.int32), arg((tokens, slots), jnp.float32))
    assert text.count("tpu_custom_call") >= 2       # pack, gather-and-sum


# (P, F, D, act) of the benchmark's five expert cells, in their order
EXPERT_CELLS = [(131072, 1792, 2048, "swiglu"), (196608, 1408, 2048, "swiglu"),
                (262144, 768, 2048, "swiglu"), (262144, 896, 2304, "swiglu"),
                (147456, 1856, 2688, "relu2")]


@pytest.mark.parametrize("rows,f,d,act", EXPERT_CELLS,
                         ids=["lfm2moe", "dsv2lite", "keyevl2", "mellum2",
                              "nemotron3nano"])
def test_expert_gate_kernels_compile_v5e(topo, rows, f, d, act):
    """The expert layer's row-wise passes at the sorted buffers of the
    benchmark's cells: the gate over ``[P, F]``, its backward (five arrays a
    tile, the gate's cotangent overwritten) and the sum of the two input
    gradients over ``[P, D]`` (one of them overwritten).  1,856 is no
    multiple of 128 lanes: its blocks are whole rows and its last piece half
    a lane tile."""
    from tensorflowonspark_tpu.ops import expert_gate as eg

    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    h, n = arg((rows, f)), arg((), jnp.int32)
    h3 = None if act == "relu2" else h
    assert eg.row_tile(rows, f, jnp.bfloat16) == 256
    text = _compile(lambda h1, h3, n: eg.gate(h1, h3, n, act, impl="pallas"),
                    h, h3, n)
    assert len(_kernel_lines(text)) == 1
    text = _compile(
        lambda h1, h3, g, n: eg.gate_grad(h1, h3, g, n, act, impl="pallas"),
        h, h3, h, n)
    (line,) = _kernel_lines(text)
    assert "output_to_operand_aliasing" in line
    text = _compile(lambda a, b, n: eg.add_rows(a, b, n, impl="pallas"),
                    arg((rows, d)), arg((rows, d)), n)
    (line,) = _kernel_lines(text)
    assert "output_to_operand_aliasing" in line
