"""What the tests that compile for a described TPU v5e share
(``tests/test_chip_compile.py``: the kernels alone;
``tests/test_chip_compile_<family>.py``: a benchmark configuration's whole
training step, one file a family so that ``--dist loadfile`` can give each
a worker of its own): the described topology, the compile cache held off,
the steering of every op to the implementation it takes on a TPU, and the
readers of a compiled program's text."""

import importlib
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

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
