"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's compiler is installed here and refuses what the chip
would refuse (block shapes off the tiling, too much VMEM, a kernel the
partitioner cannot split) — none of which interpret mode can show.

Nothing runs, so these say nothing about results or speed; the value parity
tests are in ``tests/test_ops.py``.  ``jax.default_backend()`` is still the
CPU here, so code that picks interpret mode from it is steered in the test.
"""

import importlib
import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
si = importlib.import_module("tensorflowonspark_tpu.ops.sparse_index")
ssd = importlib.import_module("tensorflowonspark_tpu.ops.ssd_scan")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("cannot describe a v5e:2x2 topology: {}".format(e))


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip (jax warns and recompiles)."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _kernel_lines(text):
    """The lines of a compiled program's text that call a pallas kernel."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _one_lane_arrays(text):
    """The float32 arrays with a last dimension of 1 in ``text`` (kernel
    calls' lines with their operands and results, or a list of residuals):
    the form the chip holds one number a 128-lane tile.  A flash kernel's
    statistics are dense rows."""
    return re.findall(r"f32\[[0-9,]*,1\]", text)


def _flash_calls(calls):
    return "\n".join(line for line in calls if "/attention/flash/" in line)


# (batch, seq, query heads, KV heads, q and k width, v width, block):
# chip_smoke's LM shape, one longer and wider point the model zoo allows, and
# the benchmark's cells without a key set: latent attention's (scores over
# 192, values of 128), 32 / 8 heads of 64 over 8,192 rows and 32 / 4 of 128
# over 32,768, blocks of 512 (the keyed cell's are further down); then the
# default blocks of 128 where the lists grow long: 32,768 rows and a group of
# 8 (32,896 steps a head, and 263,168 a KV head in dK/dV, more than SMEM
# holds: that kernel keeps the rectangle), a row of 131,072 as Ulysses hands
# one over whole (524,800: all three keep it), and the longest list there is
# (626 blocks, 196,251 steps of the 196,608 allowed)
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
    block = transformer.Block(num_heads=16, head_dim=64, attention="flash",
                              dtype=jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((8, 1024, 1024), jnp.bfloat16, sharding=one)
    # parameters never depend on the attention kind: shape them without
    # tracing the kernel for the CPU
    shapes = jax.eval_shape(
        transformer.Block(num_heads=16, head_dim=64, dtype=jnp.bfloat16).init,
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
    block = transformer.Block(num_heads=16, head_dim=64, mesh=mesh,
                              dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct(
        (8, 1024, 1024), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, None)))
    shapes = jax.eval_shape(
        transformer.Block(num_heads=16, head_dim=64, dtype=jnp.bfloat16).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 128, 1024), jnp.bfloat16))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=NamedSharding(mesh, P())),
        shapes)

    def loss(p, x):
        return block.apply(p, x).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss), params, x)
    assert text.count("tpu_custom_call") == (3 if kernels else 0)


def _benchmark_config(config_name, **overrides):
    """``benchmark/configs/<config_name>.json`` as a dict, with the checkout
    on the path for ``benchmark.adapters``."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    with open(os.path.join(root, "benchmark", "configs",
                           config_name + ".json")) as f:
        return dict(json.load(f), **overrides)


def _steer_to_kernels(monkeypatch):
    """Every op that picks its implementation from the process's platform
    takes the one it takes on a TPU."""
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    monkeypatch.setattr(si, "_default_interpret", lambda: False)
    monkeypatch.setattr(ssd, "_default_impl", lambda: "pallas")
    monkeypatch.setattr(
        importlib.import_module("tensorflowonspark_tpu.ops.grouped_matmul"),
        "_default_impl", lambda: "pallas")
    monkeypatch.setattr(
        importlib.import_module("tensorflowonspark_tpu.ops.routed_rows"),
        "_default_impl", lambda: ("pallas", False))


def _lowered_step(topo, model, cfg, seq):
    """The whole training step of ``model`` (the loss of
    ``transformer.loss_fn``, Adam at the configuration's learning rate, the
    configuration's batch of rows of ``seq`` tokens) lowered for one
    described v5e chip: ``(lowered, parameter count)``."""
    import optax

    from tensorflowonspark_tpu.models import transformer

    # parameters never depend on the row's length: shape them on a short one
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 128), jnp.int32))["params"]
    optimizer = optax.adam(cfg["optimizer"]["learning_rate"])
    loss = transformer.loss_fn(model)

    def step(params, opt_state, batch, mask):
        (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(
            params, batch, mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state, value,
                (aux, optax.global_norm(grads)))

    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    batch = cfg["batch_size"]
    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
        described(shapes), described(jax.eval_shape(optimizer.init, shapes)),
        {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                        sharding=one)},
        jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=one))
    return lowered, sum(x.size for x in jax.tree_util.tree_leaves(shapes))


def _needed(compiled):
    """XLA's memory analysis of a compiled program in bytes: arguments +
    outputs - aliased + temporaries."""
    memory = compiled.memory_analysis()
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)


def _compiled_step(topo, monkeypatch, family, config_name, **overrides):
    """The whole training step of a benchmark configuration of a
    ``TransformerLM`` family (``benchmark/configs/<config_name>.json``: its
    widths, batch and rows, bf16 compute, remat per block, Adam) compiled
    for one described v5e chip with every pallas kernel of the program in
    it: ``(compiled, parameter count, XLA's memory analysis in bytes:
    arguments + outputs - aliased + temporaries)``."""
    from tensorflowonspark_tpu.models import get_model

    cfg = _benchmark_config(config_name, **overrides)
    adapter = importlib.import_module("benchmark.adapters." + family)
    _steer_to_kernels(monkeypatch)
    model = get_model(family, config=adapter.program_config(cfg),
                      attention=cfg["attention"], remat=cfg["remat"],
                      dtype=cfg["dtype"])
    lowered, parameters = _lowered_step(topo, model, cfg, cfg["seq_len"])
    compiled = lowered.compile()
    return compiled, parameters, _needed(compiled)


def _kernel_calls(compiled):
    text = compiled.as_text()
    # none of XLA's nameless ragged-dot calls: every grouped product is a
    # pallas kernel that carries its scope
    assert "ragged-dot" not in text
    return _kernel_lines(text)


def test_gpt2_medium_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``gpt2_medium`` as the benchmark's cell 2
    builds it (``attention="full"``, the default; 24 like layers, 16 heads
    of 64, batch 4 of 1,024 tokens, bf16 compute, no remat, Adam) compiled
    for one described v5e chip: under the rule of
    ``flash_attention.full_attention_block`` every layer takes the three
    flash kernels at blocks of 512 (72 calls, all under
    ``block_i/Attention_0/flash``), no float32 ``[4, 16, 1024, 1024]`` score
    tensor is left anywhere in the program (the plain contraction's step
    mentions it 2,232 times and needs 8.38 GiB of temporaries), the 24
    layers share one lowered function of each kernel (three calls in the
    StableHLO where a launcher called bare lowers 72), and XLA's memory
    analysis of it (arguments + outputs - aliased + temporaries) may not
    outgrow the 8.16 GiB it is with Adam's state (8.77 GB, PR 44; 12.1 GB
    with the scores in HBM, the configuration's ``assumed.batch_size``)."""
    from tensorflowonspark_tpu.models import transformer

    cfg = _benchmark_config("gpt2_medium")
    _steer_to_kernels(monkeypatch)
    model = transformer.build_transformer(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], head_dim=cfg["n_embd"] // cfg["n_head"],
        max_seq_len=cfg["n_positions"], attention=cfg["attention"],
        dtype=cfg["dtype"])
    assert cfg["attention"] == "full"
    lowered, parameters = _lowered_step(topo, model, cfg, cfg["n_positions"])
    assert parameters == 354_823_168
    assert lowered.as_text().count("tpu_custom_call") == 3
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = _kernel_lines(text)
    assert len(calls) == 72
    assert sum("/Attention_0/flash/" in line for line in calls) == 72
    assert not _one_lane_arrays("\n".join(calls))
    assert "f32[4,16,1024,1024]" not in text
    assert _needed(compiled) <= 8.25 * 2 ** 30, _needed(compiled)


def test_lfm2_moe_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``lfm2_8b_a1b_ep4`` (the benchmark's
    configuration: published widths, the layer pattern, 8 of 32 experts,
    batch and 8,192-token rows as the file says, bf16 compute, remat per
    block, Adam) compiles for one described v5e chip, with the grouped-query
    flash kernels, the grouped expert products and the expert layer's row
    movement (pallas kernels all) in it, and XLA's memory analysis of it
    (arguments + outputs - aliased + temporaries) is no larger than the
    11.73 GiB it is with the attention layer's kernel output and logsumexp
    rows kept across the recomputed block and the flash kernels' statistics
    as dense rows (PR 40; 12.01 while they were ``[.., seq, 1]``; a v5e
    offers 15.75).  The numbers of PR 28 are in the configuration's
    ``assumed.batch_size``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "lfm2_moe", "lfm2_8b_a1b_ep4")
    assert parameters == 507_820_288
    assert needed <= 11.8 * 2 ** 30, needed
    # 4 expert layers x 3 grouped products x (forward, recomputed forward,
    # two gradients), and the flash kernels (forward once: the checkpoint
    # keeps its output and logsumexp; dQ, dK/dV): all pallas kernels that
    # carry their scope
    calls = _kernel_calls(compiled)
    assert sum("/attention/flash/" in line for line in calls) == 3
    assert not _one_lane_arrays(_flash_calls(calls))
    # ... and ten kernels of the row movement an expert layer, under the
    # scopes moe_route_ms_per_step reads: dispatch packs the tokens and
    # gathers them (forward and recomputed forward) and its gradient packs
    # and gather-sums; combine packs and gather-sums once (its recomputed
    # forward is dead code) and its gradient packs and gathers
    assert len(calls) >= 48 + 3 + 40
    for scope, kernel, count in (("dispatch", "gather", 8),
                                 ("dispatch", "sum", 4),
                                 ("combine", "sum", 4),
                                 ("combine", "gather", 4)):
        assert sum("/moe/{}/".format(scope) in line
                   and "/routed_rows_{}/pallas_call".format(kernel) in line
                   for line in calls) == count, (scope, kernel)


def test_deepseek_v2_lite_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``deepseek_v2_lite_ep8`` (published
    widths; one dense and four expert layers; latent attention through the
    flash kernels at 192 / 128; 8 of 64 experts by softmax top-6 beside the
    shared expert; an untied read-out over 12,800 rows; batch and
    8,192-token rows as the file says) compiles for one described v5e chip
    and fits its 15.75 GiB by XLA's memory analysis: 13.31 GiB at batch 4
    with five layers' kernel outputs and logsumexp rows kept across their
    recomputed blocks and the statistics as dense rows (PR 40; 13.56 while
    they were ``[.., seq, 1]``), which it may not outgrow.  The numbers of
    PR 32 are in the configuration's ``assumed.batch_size``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "deepseek_v2", "deepseek_v2_lite_ep8")
    assert parameters == 535_060_992
    assert needed <= 13.35 * 2 ** 30, needed
    calls = _kernel_calls(compiled)
    # five attention layers x (forward, dQ, dK/dV), all under
    # attention/flash: no forward kernel in the recomputed pass; four expert
    # layers x 3 grouped products x 4 passes, and their row movement
    assert sum("/attention/flash/" in line for line in calls) == 15
    assert not _one_lane_arrays(_flash_calls(calls))
    assert sum("/moe/experts/" in line for line in calls) == 48
    assert len(calls) >= 15 + 48 + 40


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


def test_keye_vl2_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``keye_vl2_30b_a3b_ep8`` (published
    widths; four layers; grouped-query attention over the 2,048 keys a
    learned index picks of a 32,768-token row; 16 of 128 experts by softmax
    top-8; an untied read-out over 18,992 rows; batch 1, as the file says)
    compiles for one described v5e chip with every kernel of the index in it
    and fits its 15.75 GiB by XLA's memory analysis: 13.66 GiB with four
    layers' kernel outputs, logsumexp rows, key bits and index logsumexp
    kept across their recomputed blocks, which it may not outgrow; no array
    of it is ``[T, T]``.  PR 40: 14.25 before it; 14.47 with the flash
    kernels' statistics as dense rows (the most that is live at once fell
    0.53 GB with the ``[.., seq, 1]`` arrays, and the block the compiler
    packs the temporaries into grew: a 0.39 GB hole in the expert layer's
    backward pass that its 0.40 GB buffers do not fit, and the analysis
    counts such a hole twice); 13.66 with the index's backward kernels run
    in their own layer's backward pass (``transformer._backward_together``:
    two layers' folded ``q``, ``k`` and index queries no longer lie over the
    third's expert layer).  The numbers of PR 37 are in the configuration's
    ``assumed.batch_size``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "keye_vl2", "keye_vl2_30b_a3b_ep8")
    assert parameters == 465_391_104
    assert needed <= 13.7 * 2 ** 30, needed
    assert "32768,32768" not in compiled.as_text()
    calls = _kernel_calls(compiled)

    def count(scope, kernel):
        return sum("/attention/{}/".format(scope) in line and kernel in line
                   for line in calls)

    # four layers x (forward, dQ, dK/dV) under attention/flash and one
    # selection each: the recomputed pass holds neither; the index's loss
    # once alone (forward) and once with its gradients (backward)
    assert count("flash", "pallas_call") == 12
    assert not _one_lane_arrays(_flash_calls(calls))
    assert count("select", "dsa_select/") == 4
    assert count("index_loss", "dsa_index_loss/") == 4
    assert count("index_loss", "dsa_index_loss_grads/") == 4
    assert sum("/moe/experts/" in line for line in calls) == 48


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


def test_mellum2_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``mellum2_12b_a2p5b_ep8`` (published
    widths; one period of three sliding layers, 1,024 keys, and one full
    layer under YaRN, over a 32,768-token row; 8 of 64 experts by softmax
    top-8; an untied read-out over 12,288 rows; batch 1, as the file says)
    compiles for one described v5e chip with the banded flash kernels in it
    and fits its 15.75 GiB by XLA's memory analysis, which it may not
    outgrow: 11.81 GiB (12.68 GB: 4.08 GB of parameters and Adam's moments
    as arguments, 8.59 GB temporaries, gradients among them); no array of it
    is ``[T, T]``."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "mellum2", "mellum2_12b_a2p5b_ep8")
    assert parameters == 340_350_208
    assert needed <= 11.9 * 2 ** 30, needed
    assert "32768,32768" not in compiled.as_text()
    calls = _kernel_calls(compiled)
    # three sliding layers x (forward, dQ, dK/dV) under attention/flash_window
    # and the full layer's three under attention/flash: the recomputed pass
    # holds no forward kernel of either
    assert sum("/attention/flash_window/" in line for line in calls) == 9
    assert sum("/attention/flash/" in line for line in calls) == 3
    assert not _one_lane_arrays("\n".join(
        line for line in calls if "/attention/flash" in line))
    assert sum("/moe/experts/" in line for line in calls) == 48


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


def test_nemotron3_nano_step_compiles_and_fits_v5e(topo, monkeypatch):
    """The whole training step of ``nemotron3_nano_30b_a3b_ep16`` (published
    widths; layers 35 to 43 of the pattern, ``MEMEMEM*E``: four Mamba-2
    layers under the chunked scan, four expert layers of two-matrix relu2
    experts, 8 of 128 held, beside a shared one, one attention layer of 32 /
    2 heads without positions; an untied read-out over 16,384 rows; rows of
    8,192 and the batch the file says) compiles for one described v5e chip
    and fits its 15.75 GiB by XLA's memory analysis, which it may not
    outgrow: 14.53 GiB at batch 3 (15.60 GB: 8.00 GB of parameters and Adam's
    moments as arguments, 7.60 GB temporaries, gradients among them; batch 4
    is refused at 16.29 GiB, batch 2 takes 13.31).  The scan kernels are in it once forward and once
    backward a layer: the checkpoint keeps their output and chunk states,
    so the recomputed pass holds none; hidden rows of 2,688 (1,344 words of
    bfloat16, ten slab rows and a half) pass through the expert layer's row
    movement, and an expert's width of 1,856, which no multiple of 128
    divides, is one tile of the grouped products."""
    compiled, parameters, needed = _compiled_step(
        topo, monkeypatch, "nemotron_h", "nemotron3_nano_30b_a3b_ep16")
    assert parameters == 666_963_456
    assert needed <= 14.6 * 2 ** 30, needed
    text = compiled.as_text()
    assert "8192,8192" not in text
    calls = _kernel_calls(compiled)
    assert sum("/mamba/scan/" in line for line in calls) == 8
    assert sum("/attention/flash/" in line for line in calls) == 3
    # 4 expert layers x 2 grouped products x (forward, recomputed forward,
    # two gradients)
    assert sum("/moe/experts/" in line for line in calls) == 32


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
