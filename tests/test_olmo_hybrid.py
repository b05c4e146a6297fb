"""The Olmo Hybrid description of ``models/transformer.py`` at tiny sizes on
the CPU: the whole model against the plain reference (logits, loss, the
gradient of every leaf), a block that norms a part's output and a QK-norm
over the whole projection each against the same layer without, the two head
shares of a tensor-parallel pair against the uncut Gated DeltaNet layer, the
family's spec from the config's keys, the parameter paths its adapter names
and those of the five families before it, the ``delta_*`` counters of
``Trainer``, and the tiny cell through ``benchmark/run.py`` with its fp8
control.  (``ops/gated_delta`` against the recurrence is
``tests/test_gated_delta.py``; the bf16 program against the reference under
the tiny cell's limits ``tests/benchmark/test_benchmark_references.py``.)"""

import functools
import hashlib
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import traverse_util

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests", "benchmark")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark.adapters import olmo_hybrid as adapter  # noqa: E402
from benchmark.references import olmo_hybrid as ref  # noqa: E402
from tensorflowonspark_tpu.models import get_model, transformer  # noqa: E402
from tensorflowonspark_tpu.models.families import olmo_hybrid as family  # noqa: E402

TINY = {"model_type": "olmo_hybrid", "vocab_size": 61, "hidden_size": 32,
        "intermediate_size": 48, "num_hidden_layers": 4,
        "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
        "hidden_act": "silu", "max_position_embeddings": 65536,
        "attention_bias": False, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False,
        "layer_types": ["linear_attention"] * 3 + ["full_attention"],
        "linear_num_key_heads": 3, "linear_num_value_heads": 3,
        "linear_key_head_dim": 8, "linear_value_head_dim": 16,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
        "seq_len": 64, "flash_block": 32, "linear_chunk_size": 16,
        "attention": "flash", "remat": False, "dtype": "float32",
        "optimizer": {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
                      "eps": 1e-8}}


def _tokens(batch=2, seq=64):
    return jnp.asarray(
        np.random.RandomState(0).randint(0, 61, (batch, seq)), jnp.int32)


# -- the whole model against the reference ------------------------------------

@functools.lru_cache(maxsize=None)
def _reference():
    weights = ref.init_weights(TINY, 3)
    tokens = _tokens()
    logits = jnp.stack([ref.forward(weights, row, TINY) for row in tokens])
    loss, grads = jax.jit(jax.value_and_grad(lambda w: sum(
        ref.loss_fn(w, row, TINY) for row in tokens) / 2))(weights)
    return logits, float(loss), grads


@pytest.mark.parametrize("attention,remat", [("flash", False),
                                             ("full", False),
                                             ("flash", True)])
def test_logits_loss_and_every_gradient_leaf_against_the_reference(
        attention, remat):
    """float32 on both sides.  The tolerances are those of the families
    before it: 2e-5 on logits and loss (float32 sums taken in another
    order: the chunked delta rule against the recurrence, flash against a
    plain softmax), 5e-5 of a leaf's largest element on every gradient
    leaf."""
    built = adapter.build(dict(TINY, attention=attention, remat=remat), 3)
    tokens = _tokens()
    want_logits, want_loss, want = _reference()
    logits = built["model"].apply({"params": built["params"]}, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=2e-5, rtol=2e-5)
    (loss, aux), grads = jax.value_and_grad(built["loss"], has_aux=True)(
        built["params"], {"tokens": tokens}, jnp.ones((2,)))
    assert float(loss) == pytest.approx(want_loss, rel=2e-5)
    assert int(aux["counters"]["delta_layers"]) == 3
    assert int(aux["counters"]["delta_chunks"]) == 3 * 2 * 64 // 16
    got = traverse_util.flatten_dict(grads, sep="/")
    assert set(got) == set(built["names"])
    for path, name in built["names"].items():
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(got[path]).reshape(want[name].shape) / scale,
            np.asarray(want[name]) / scale, atol=5e-5, err_msg=name)


@pytest.mark.parametrize("leaf,value", [("A_log", 8.0), ("dt_bias", 4.0)])
def test_the_decay_shows_in_the_model(leaf, value):
    """The case is what it says: with the state forgotten from a position to
    the next (``A`` or the step so large that nothing is carried) the same
    weights give other logits, a thousand times float32's rounding apart."""
    built = adapter.build(TINY, 3)
    tokens = _tokens(1)
    a = built["model"].apply({"params": built["params"]}, tokens)
    forgetful = traverse_util.unflatten_dict({
        k: (jnp.full_like(v, value) if k[-1] == leaf else v)
        for k, v in traverse_util.flatten_dict(built["params"]).items()})
    b = built["model"].apply({"params": forgetful}, tokens)
    assert float(jnp.abs(a - b).max()) > 1e-4


def test_the_delta_term_shows_in_the_model():
    """Write strengths in (0, 2) against (0, 1), the same weights: the
    correction ``v - S k`` weighs twice as much, and the logits move."""
    tokens = _tokens(1)
    built = adapter.build(TINY, 3)
    halved = adapter.build(dict(TINY, linear_allow_neg_eigval=False), 3)
    a = built["model"].apply({"params": built["params"]}, tokens)
    b = halved["model"].apply({"params": built["params"]}, tokens)
    assert float(jnp.abs(a - b).max()) > 1e-4


# -- the block's norm and the QK-norm -----------------------------------------

def _block(**spec):
    return transformer.Block(spec=transformer.LayerSpec(
        op="attention", ff="swiglu", norm="rmsnorm", positions="none",
        num_heads=2, head_dim=8, num_kv_heads=2, ff_size=24, **spec))


def test_a_block_norms_a_parts_output_where_the_description_says_so():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 16))
    after, before = _block(norm_place="output"), _block()
    params = after.init(jax.random.PRNGKey(1), x)["params"]
    assert sorted(params) == ["RMSNorm_0", "RMSNorm_1", "attention", "mlp"]
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(before.init(jax.random.PRNGKey(1),
                                                 x)["params"])
    # weights that are not 1, so that the norm's place shows in them too
    params = dict(params, **{name: {"scale": 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(n), (16,))} for n, name in enumerate(
            ("RMSNorm_0", "RMSNorm_1"))})

    def norm(name, t):
        return transformer._norm("rmsnorm", 1e-6, jnp.float32).apply(
            {"params": params[name]}, t)

    attention = lambda t: transformer.Attention(  # noqa: E731
        2, 8, "full", num_kv_heads=2).apply({"params": params["attention"]},
                                            t)
    mlp = lambda t: transformer.SwiGLU(24).apply(  # noqa: E731
        {"params": params["mlp"]}, t)
    h = x + norm("RMSNorm_0", attention(x))
    want = h + norm("RMSNorm_1", mlp(h))
    got = after.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    h = x + attention(norm("RMSNorm_0", x))
    np.testing.assert_allclose(
        np.asarray(before.apply({"params": params}, x)),
        np.asarray(h + mlp(norm("RMSNorm_1", h))), atol=1e-6)
    assert float(jnp.abs(got - before.apply({"params": params}, x)).max()) \
        > 1e-2
    with pytest.raises(ValueError, match="norm_place"):
        transformer.LayerSpec(norm_place="both")


def test_a_qk_norm_over_the_whole_projection():
    """``"whole"``: one statistic over all the heads' columns and a weight
    as wide; ``"head"`` (and ``True``, as the families before it say it): a
    head's own, one weight of ``head_dim``; the same q and k give other
    scores."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16))
    layers = {kind: transformer.Attention(2, 8, "full", num_kv_heads=2,
                                          qk_norm=kind)
              for kind in ("whole", "head", True, False)}
    params = {kind: layer.init(jax.random.PRNGKey(1), x)["params"]
              for kind, layer in layers.items()}
    assert params["whole"]["q_norm"]["scale"].shape == (16,)
    assert params["whole"]["k_norm"]["scale"].shape == (16,)
    assert params["head"]["q_norm"]["scale"].shape == (8,)
    assert "q_norm" not in params[False]
    out = {kind: layers[kind].apply({"params": params[kind]}, x)
           for kind in layers}
    np.testing.assert_array_equal(np.asarray(out["head"]),
                                  np.asarray(out[True]))
    for a, b in (("whole", "head"), ("whole", False), ("head", False)):
        assert float(jnp.abs(out[a] - out[b]).max()) > 1e-3, (a, b)
    # by hand: the projections, one RMSNorm over 16 columns, plain softmax
    p = params["whole"]
    q, k, v = (jnp.einsum("bsd,dhe->bshe", x, p[n]["kernel"]) for n in "qkv")

    def whole(t):
        flat = t.reshape(2, 16, 16)
        return (flat * jax.lax.rsqrt(jnp.square(flat).mean(-1, keepdims=True)
                                     + 1e-6)).reshape(t.shape)

    scores = jnp.einsum("bqhe,bkhe->bhqk", whole(q), whole(k)) * 8 ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), scores, -1e30)
    want = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, -1),
                      v).reshape(2, 16, 16) @ p["proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(out["whole"]), np.asarray(want),
                               atol=1e-5)
    with pytest.raises(ValueError, match="qk_norm"):
        transformer.LayerSpec(qk_norm="both")


# -- the chip's share ---------------------------------------------------------

def test_the_two_head_shares_add_up_to_the_uncut_layer():
    """30 is 6 here: a Gated DeltaNet layer of 6 heads in two shares of 3.
    Each chip's ``W_out`` partial sum, from its own columns of ``W_in``, its
    taps, its ``A_log`` and ``dt_bias`` (the head's norm weight is one for
    all), added up, is the uncut reference's whole layer; one share alone is
    the reference's same share."""
    d, heads, dk, dv, taps = 32, 6, 8, 16, 4
    cfg = dict(TINY, linear_num_key_heads=heads, linear_num_value_heads=heads)
    shapes = {k[3:]: s for k, (s, _) in ref.layer_leaves(cfg, 0).items()}
    ks = jax.random.split(jax.random.PRNGKey(5), 7)
    w = {"L0.in_proj": 0.3 * jax.random.normal(ks[0], shapes["in_proj"]),
         "L0.conv": jax.random.uniform(ks[1], shapes["conv"], minval=-0.5,
                                       maxval=0.5),
         "L0.A_log": jnp.log(jax.random.uniform(ks[2], (heads,), minval=1.0,
                                                maxval=16.0)),
         "L0.dt_bias": jax.random.normal(ks[3], (heads,)) - 3.0,
         "L0.gate_norm": 1.0 + 0.1 * jax.random.normal(ks[4], (dv,)),
         "L0.out_proj": 0.2 * jax.random.normal(ks[5], shapes["out_proj"])}
    assert shapes["in_proj"] == (d, heads * (2 * dk + 2 * dv + 2))
    x = jax.random.normal(ks[6], (2, 64, d))
    whole = jnp.stack([ref._delta(row, w, "L0.", cfg, "float32")
                       for row in x])

    def columns(first, count):
        """The columns of ``W_in`` (and of the taps) that are heads first ..
        first + count's, stream by stream."""
        streams = ((dk, True), (dk, True), (dv, True), (dv, False),
                   (1, False), (1, False))      # q k v z a b; the taps' three
        at, cols, tapped = 0, [], []
        for width, has_taps in streams:
            mine = np.arange(at + first * width,
                             at + (first + count) * width)
            cols.append(mine)
            if has_taps:
                tapped.append(mine)
            at += heads * width
        return np.concatenate(cols), np.concatenate(tapped)

    total = 0.0
    for first in (0, 3):
        cols, tapped = columns(first, 3)
        rows = np.arange(first * dv, (first + 3) * dv)
        mine = {"L0.in_proj": w["L0.in_proj"][:, cols],
                "L0.conv": w["L0.conv"][:, tapped],
                "L0.A_log": w["L0.A_log"][first:first + 3],
                "L0.dt_bias": w["L0.dt_bias"][first:first + 3],
                "L0.gate_norm": w["L0.gate_norm"],
                "L0.out_proj": w["L0.out_proj"][rows]}
        layer = transformer.GatedDelta(3, dk, dv, taps, True, 16)
        y = layer.apply({"params": {
            "in_proj": {"kernel": mine["L0.in_proj"]},
            "conv": mine["L0.conv"], "A_log": mine["L0.A_log"],
            "dt_bias": mine["L0.dt_bias"], "norm": mine["L0.gate_norm"],
            "out_proj": {"kernel": mine["L0.out_proj"]}}}, x)
        total = total + y
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(jnp.stack([ref._delta(
                row, mine, "L0.", TINY, "float32") for row in x])),
            atol=2e-5, rtol=2e-5)
        assert float(jnp.abs(y - whole).max()) > 1e-2     # a share is a part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5, rtol=5e-5)


# -- the description, its tree, its counters ----------------------------------

def test_olmo_hybrid_is_registered_and_follows_the_description():
    spec = family.olmo_hybrid_spec(TINY)
    assert len(spec.layers) == 4 and not spec.tied_readout
    assert (spec.norm, spec.norm_eps, spec.learned_positions) == (
        "rmsnorm", 1e-6, 0)
    assert [layer.op for layer in spec.layers] == ["gated_delta"] * 3 + [
        "attention"]
    for layer in spec.layers:
        assert (layer.ff, layer.ff_size, layer.norm, layer.norm_place,
                layer.positions, layer.qk_norm) == (
                    "swiglu", 48, "rmsnorm", "output", "none", "whole")
        assert (layer.delta_heads, layer.delta_key_dim, layer.delta_value_dim,
                layer.delta_neg_eigval, layer.delta_chunk,
                layer.conv_kernel) == (3, 8, 16, True, 16, 4)
        assert (layer.num_heads, layer.num_kv_heads, layer.head_dim,
                layer.flash_block) == (2, 2, 16, 32)
    assert spec.layers[0] is spec.layers[2]
    # the published keys alone: the heads' width is the hidden size's share
    published = {k: v for k, v in TINY.items() if k not in (
        "head_dim", "flash_block", "linear_chunk_size")}
    layer = family.olmo_hybrid_spec(published).layers[3]
    assert (layer.head_dim, layer.flash_block, layer.delta_chunk) == (
        16, 512, 64)
    for key, wrong in (
            ("layer_types", {"layer_types": ["sliding_attention"] * 4}),
            ("bias", {"attention_bias": True}),
            ("tie_word_embeddings", {"tie_word_embeddings": True}),
            ("rope_theta", {"rope_parameters": {"rope_theta": 500000.0}}),
            ("hidden_act", {"hidden_act": "gelu"}),
            ("linear_num_key_heads", {"linear_num_key_heads": 1}),
            ("sliding_window", {"sliding_window": 64})):
        with pytest.raises(ValueError, match=key):
            family.olmo_hybrid_spec(dict(TINY, **wrong))
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.olmo_hybrid_spec(dict(TINY, num_hidden_layers=5))


def test_the_description_yields_exactly_the_paths_its_adapter_names():
    model = get_model("olmo_hybrid", config=TINY)
    assert isinstance(model, transformer.TransformerLM)
    assert model.spec == family.olmo_hybrid_spec(TINY)
    assert model.attention == "flash"
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 64), jnp.int32))["params"]
    flat = traverse_util.flatten_dict(params, sep="/")
    assert set(flat) == {path for path, _ in adapter._paths(TINY).values()}
    assert "block_0/delta/A_log" in flat and "head" in flat
    assert "block_3/attention/q_norm/scale" in flat
    assert not [p for p in flat if "pos_embed" in p or "conv_bias" in p]
    assert flat["block_0/delta/in_proj/kernel"].shape == (
        32, 3 * (2 * 8 + 2 * 16 + 2))
    assert flat["block_0/delta/conv"].shape == (4, 3 * (2 * 8 + 16))
    assert flat["block_0/delta/norm"].shape == (16,)
    assert flat["block_3/attention/q_norm/scale"].shape == (32,)
    for leaf in ("A_log", "dt_bias"):           # float32 leaves, a head each
        assert flat["block_2/delta/" + leaf].shape == (3,)
        assert flat["block_2/delta/" + leaf].dtype == jnp.float32


def test_the_benchmarks_configuration_is_the_one_decoder_under_its_description():
    """``benchmark/configs/olmo_hybrid_7b_tp2.json`` as the adapter hands it
    over: every published width, the heads held, the counts the file
    states."""
    from chip_compile import _benchmark_config

    cfg = _benchmark_config("olmo_hybrid_7b_tp2")
    model = get_model("olmo_hybrid", config=cfg, attention="full", remat=True,
                      dtype="bfloat16")
    assert model.spec == family.olmo_hybrid_spec(cfg)
    delta, full = model.spec.layers[0], model.spec.layers[3]
    assert (delta.op, delta.delta_heads, delta.delta_key_dim,
            delta.delta_value_dim, delta.conv_kernel, delta.delta_neg_eigval,
            delta.ff_size) == ("gated_delta", 15, 96, 192, 4, True, 11008)
    assert (full.op, full.num_heads, full.num_kv_heads, full.head_dim,
            full.qk_norm, full.positions) == ("attention", 15, 15, 128,
                                              "whole", "none")
    assert model.spec.hidden_size == 3840 and model.spec.vocab_size == 12544
    leaves = ref.leaves(cfg)
    assert sum(int(np.prod(shape)) for shape, _ in leaves.values()) \
        == 766_241_946
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [c for c in json.load(f)["configs"]
                  if c["name"] == "olmo_hybrid_7b_tp2"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert key in cfg["published"], key


# (paths, shapes) of each family's tiny rehearsal configuration, as the
# parent of PR 47 built them (sha256 of the sorted list's repr, 16 digits):
# the norm's place, the QK-norm's second form and the new layer kind changed
# none of them
TREES = {"lfm2_moe": ("lfm2_moe_tiny", "9bb7c7bbdc7fb8a4", 33),
         "deepseek_v2": ("deepseek_v2_tiny", "b36e22b8382ae4b5", 41),
         "keye_vl2": ("keye_vl2_tiny", "96515ec82cae822f", 37),
         "mellum2": ("mellum2_tiny", "44f2fdb4b4d1f299", 51),
         "nemotron_h": ("nemotron_h_tiny", "0f6a054c90bff23b", 40)}


@pytest.mark.parametrize("name", sorted(TREES))
def test_the_families_before_it_keep_their_parameter_trees(name):
    config_name, digest, count = TREES[name]
    with open(os.path.join(ROOT, "tests", "benchmark", "tiny", "configs",
                           config_name + ".json")) as f:
        cfg = json.load(f)
    other = importlib.import_module("benchmark.adapters." + name)
    config = other.program_config(cfg) if hasattr(
        other, "program_config") else cfg
    model = get_model(name, config=config, attention=cfg["attention"],
                      remat=True, dtype=cfg["dtype"])
    for layer in model.spec.layers:     # as they said it before the field
        assert layer.norm_place == "input" and layer.qk_norm in (False, True)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((cfg["batch_size"], cfg["seq_len"]), jnp.int32))["params"]
    flat = traverse_util.flatten_dict(shapes, sep="/")
    paths = sorted((k, tuple(v.shape)) for k, v in flat.items())
    assert len(paths) == count
    assert hashlib.sha256(repr(paths).encode()).hexdigest()[:16] == digest


def test_trainer_counters_carry_the_delta_rules_chunks():
    from test_lfm2_moe import _fit

    snap = _fit(get_model("olmo_hybrid", config=TINY, attention="full"),
                seq=64)
    assert snap["delta_layers"] == 3 * 3            # 3 steps, 3 such layers
    assert snap["delta_chunks"] == 3 * 3 * 2 * 64 // 16         # batch 2
    # [batch, heads, chunks, dk, dv] float32 a layer and step
    assert snap["delta_state_bytes"] == 3 * 3 * (2 * 3 * 4 * 8 * 16 * 4)
    assert not [k for k in snap if k.startswith(("swa_", "dsa_", "ssd_",
                                                 "moe_", "flash_"))]


# -- the counts and the two readers -------------------------------------------

def _traced(by_scope):
    """A traced run's report, as far as a roofline reader looks."""
    from chip_compile import _benchmark_config

    from benchmark import flops

    cfg = _benchmark_config("olmo_hybrid_7b_tp2")
    return cfg, {"trace": {"by_scope": by_scope, "steps": 2},
                 "model": {"kernels": flops.kernels(cfg)},
                 "device": {"peaks": {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9}},
                 "window": {"chips": 1}}


def _reader(name):
    directory = os.path.join(ROOT, "benchmark", "layer_metrics")
    if directory not in sys.path:
        sys.path.insert(0, directory)
    return importlib.import_module(name).read


def test_the_counts_are_of_the_chunked_form():
    from benchmark import flops

    cfg, report = _traced({})
    kernels = report["model"]["kernels"]
    assert set(kernels) == {"delta/scan", "delta/in_proj", "delta/out_proj",
                            "attention/flash"}
    tokens, heads, dk, dv, chunk = 2 * 8192, 15, 96, 192, 64
    assert kernels["delta/scan"]["flops"] == 3 * 2 * tokens * heads * (
        chunk * (dk + dv) + 3 * dk * dv)
    # q, k, v, o in bfloat16, two float32 a head, the chunk states once
    assert kernels["delta/scan"]["bytes"] == 3 * tokens * heads * (
        2 * (2 * dk + 2 * dv) + 8 + 2 * dk * dv // chunk)
    assert kernels["delta/in_proj"]["flops"] == 3 * 2 * tokens * 3840 * (
        heads * (2 * dk + 2 * dv + 2))
    assert kernels["attention/flash"]["flops"] == 2 * 2 * (
        8192 * 8193 // 2) * 2 * 15 * 128
    # a step of the whole model: three passes of the forward products
    assert flops.train_flops_per_example(cfg) == pytest.approx(36.23e12,
                                                               rel=1e-3)


@pytest.mark.parametrize("name,scopes", [
    ("delta_scan_roofline", ("delta/scan",)),
    ("delta_proj_roofline", ("delta/in_proj", "delta/out_proj"))])
def test_a_reader_adds_up_the_layers_and_reads_nothing_where_nothing_is(
        name, scopes):
    read = _reader(name)
    by_scope = {"TransformerLM/block_%d/%s" % (i, scope): 0.004 * (i + 1)
                for i in range(3) for scope in scopes}
    cfg, report = _traced(dict(by_scope, **{
        "TransformerLM/block_3/attention/flash": 0.01}))
    kernels = report["model"]["kernels"]
    least = sum(max(kernels[s]["flops"] / 197e12, kernels[s]["bytes"] / 819e9)
                for s in scopes)
    seconds = sum(by_scope.values())
    assert read(report) == pytest.approx(100.0 * least * 2 / seconds)
    assert 0 < read(report) < 100
    # the parent's trace: no such scope, nothing read, nothing raised
    _, parent = _traced({"TransformerLM/block_3/attention/flash": 0.01})
    assert read(parent) is None
    assert read({}) is None and read({"trace": None}) is None


# -- the tiny cell through run.py ---------------------------------------------

def test_the_tiny_cell_through_run_py_and_its_control(tmp_path):
    """``olmo_hybrid_tiny_files`` of the merged rehearsal manifest, traced,
    with the control: the result line is ``correct`` under the cell's
    limits, reports the family's two per-layer entries' neighbours (a CPU
    trace carries no scopes: the rooflines themselves are the chip's), and
    the fp8 control fails at least one limit."""
    import _tiny

    details = tmp_path / "details.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PERFBENCH_REHEARSAL_PLATFORM="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", _tiny.manifest_path(tmp_path), "--workload",
         "olmo_hybrid_tiny_files", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--control", "1", "--details", str(details)],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, done.stdout[-2000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"compiles_in_window.train", "infeed_starved_pct",
            "setup_programs"} <= set(result["metrics"])
    assert result["metrics"]["compiles_in_window.train"]["value"] == 0
    with open(details) as f:
        report = json.load(f)
    trainer = report["window"]["counters1"]["trainer"]
    assert trainer["delta_layers"] > 0 and trainer["delta_chunks"] \
        == trainer["delta_layers"] * 2 * 128 // 32
    assert set(report["model"]["kernels"]) == {
        "delta/scan", "delta/in_proj", "delta/out_proj", "attention/flash"}
    from benchmark import correctness

    limits = _tiny.load(_tiny.TINY, "correctness",
                        "olmo_hybrid_tiny_files.json")["limits"]
    sound = correctness.judge(report["numbers"], limits)
    control = correctness.judge(report["control_numbers"], limits)
    assert all(ok for *_, ok in sound), sound
    assert not all(ok for *_, ok in control), control
