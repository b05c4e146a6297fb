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

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")


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


# (batch, seq, heads, head_dim): the bench LM's shape, and one longer and
# wider point the model zoo allows
SHAPES = [(8, 1024, 16, 64), (2, 4096, 8, 128)]


@pytest.mark.parametrize("shape", SHAPES, ids=["s1024_d64", "s4096_d128"])
@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
def test_flash_kernel_compiles_for_v5e(topo, kernel, shape):
    batch, seq, heads, dim = shape
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((batch * heads, seq, dim), jnp.bfloat16,
                             sharding=one)
    stat = jax.ShapeDtypeStruct((batch * heads, seq, 1), jnp.float32,
                                sharding=one)
    tail = (dim ** -0.5, True, 128, 128, False)  # scale, causal, blocks, interpret
    if kernel == "fwd":
        text = _compile(lambda q, k, v: fa._flash_fwd(q, k, v, *tail),
                        x, x, x)
    else:
        launch = fa._flash_bwd_dq if kernel == "bwd_dq" else fa._flash_bwd_dkv
        text = _compile(
            lambda q, k, v, g, lse, delta: launch(q, k, v, g, lse, delta,
                                                  *tail),
            x, x, x, x, stat, stat)
    assert text.count("tpu_custom_call") == 1


def test_lm_block_with_flash_compiles_for_v5e(topo, monkeypatch):
    """One transformer block at the bench LM's widths (d_model 1024, 16x64
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
