"""The Mellum 2 description of ``models/transformer.py`` at tiny sizes on the
CPU: the whole model against the plain reference (logits, loss, the gradient
of every leaf), the flash path against ``attention="full"`` with the band as
a mask, the two kinds of layer with their own rotary tables, the eight chips'
shares against the uncut layer, the parameter paths the family's adapter
names, the repairs in ``LayerSpec`` and ``Attention``, and the ``swa_*``
counters of ``Trainer``.  (The bf16 program against the reference under the
tiny cell's limits, and the fp8 control against them, is
``tests/benchmark/test_benchmark_references.py``; the kernels with a window
alone are in ``tests/test_ops.py``.)"""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import traverse_util

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.adapters import mellum2 as adapter  # noqa: E402
from benchmark.references import mellum2 as ref  # noqa: E402
from tensorflowonspark_tpu.models import get_model, transformer  # noqa: E402
from tensorflowonspark_tpu.models.families import mellum2 as family  # noqa: E402
from tensorflowonspark_tpu.ops.flash_attention import band_tiles  # noqa: E402

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
TINY = {"attention_bias": False, "head_dim": 16, "hidden_size": 32,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "sliding_attention", "full_attention"],
        "mlp_layer_types": ["sparse"] * 4, "max_window_layers": 0,
        "moe_intermediate_size": 16, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts": 4,
        "num_experts_per_tok": 3, "num_hidden_layers": 4,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_parameters": {
            "full_attention": YARN,
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 40, "tie_word_embeddings": False,
        "use_sliding_window": True, "vocab_size": 61, "router_experts": 8,
        "held_experts": [2, 4], "seq_len": 128, "flash_block": 32,
        "attention": "flash", "remat": False, "dtype": "float32",
        "optimizer": {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
                      "eps": 1e-8}}


def _tokens(batch=2, seq=128):
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


@pytest.mark.parametrize("attention", ["flash", "full"])
def test_logits_loss_and_every_gradient_leaf_against_the_reference(attention):
    built = adapter.build(dict(TINY, attention=attention), 3)
    tokens = _tokens()
    want_logits, want_loss, want = _reference()
    logits = built["model"].apply({"params": built["params"]}, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=2e-5, rtol=2e-5)
    (loss, aux), grads = jax.value_and_grad(built["loss"], has_aux=True)(
        built["params"], {"tokens": tokens}, jnp.ones((2,)))
    assert float(loss) == pytest.approx(want_loss, rel=2e-5)
    # the band's tiles are counted where the kernels run, and there alone
    assert ("swa_tiles_computed" in aux.get("counters", {})) == (
        attention == "flash")
    got = traverse_util.flatten_dict(grads, sep="/")
    assert set(got) == set(built["names"])
    for path, name in built["names"].items():
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(got[path]).reshape(want[name].shape) / scale,
            np.asarray(want[name]) / scale, atol=5e-5, err_msg=name)


def test_the_window_changes_the_model():
    """The case is what it says: with the window covering the row the same
    weights give other logits behind position 40, and the same before it."""
    built = adapter.build(TINY, 3)
    wide = adapter.build(dict(TINY, sliding_window=128), 3)
    tokens = _tokens(1)
    a = built["model"].apply({"params": built["params"]}, tokens)
    b = wide["model"].apply({"params": built["params"]}, tokens)
    np.testing.assert_allclose(np.asarray(a[:, :40]), np.asarray(b[:, :40]),
                               atol=2e-5)
    assert float(jnp.abs(a[:, 40:] - b[:, 40:]).max()) > 1e-3


# -- the two kinds of layer ---------------------------------------------------

def test_a_full_and_a_sliding_layer_get_their_own_rotary_tables():
    spec = family.mellum2_spec(adapter.program_config(TINY))
    sliding, full = spec.layers[0], spec.layers[3]
    assert [layer.window for layer in spec.layers] == [40, 40, 40, 0]
    assert sliding.rope_yarn is None and full.rope_yarn is not None
    assert full.rope_yarn[:4] == (16.0, 32.0, 32.0, 1.0)
    plain, one = transformer.rope_frequencies(16, sliding.rope_theta,
                                              sliding.rope_yarn)
    inv, factor = transformer.rope_frequencies(16, full.rope_theta,
                                               full.rope_yarn)
    assert one == 1.0
    assert factor == pytest.approx(1.2772588722239782, rel=1e-12)
    assert factor == pytest.approx(0.1 * np.log(16) + 1, rel=1e-12)
    # the fastest pair is kept, the slowest divided by the factor, and the
    # reference's own table says the same of every pair
    assert float(inv[0]) == float(plain[0])
    assert float(inv[-1]) == pytest.approx(float(plain[-1]) / 16, rel=1e-6)
    assert 0 < int((np.asarray(inv) != np.asarray(plain)).sum()) < 8
    want_inv, want_factor = ref.rotary_table(TINY, "full_attention")
    np.testing.assert_allclose(np.asarray(inv), np.asarray(want_inv),
                               rtol=1e-6)
    assert want_factor == factor
    np.testing.assert_allclose(
        np.asarray(plain),
        np.asarray(ref.rotary_table(TINY, "sliding_attention")[0]),
        rtol=1e-6)
    # a table without attention_factor: YaRN's own 0.1 ln(factor) + 1
    bare = {k: v for k, v in YARN.items() if k != "attention_factor"}
    again = family.mellum2_spec(dict(
        adapter.program_config(TINY),
        rope_parameters=dict(TINY["rope_parameters"], full_attention=bare)))
    assert again.layers[3].rope_yarn == full.rope_yarn[:4] + (1.0, 0.0)


def test_a_grouped_query_layer_turns_by_its_yarn_table():
    """``Attention``'s grouped-query path passes ``rope_yarn`` on (it used to
    drop it: a GQA layer with a YaRN tuple was silently plain RoPE)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32))

    def layer(**spec):
        return transformer.Attention(
            4, 16, "full", num_kv_heads=2, qk_norm=True, rope_theta=500000.0,
            **spec)

    params = layer().init(jax.random.PRNGKey(0), x)
    yarn = (16.0, 32.0, 32.0, 1.0, 1.0, 0.0)
    plain = layer().apply(params, x)
    turned = layer(rope_yarn=yarn).apply(params, x)
    assert float(jnp.abs(plain - turned).max()) > 1e-3
    # factor 1 stretches nothing: the plain frequencies, and a factor of 1
    same = layer(rope_yarn=(1.0, 32.0, 32.0, 1.0, 1.0, 0.0)).apply(params, x)
    np.testing.assert_allclose(np.asarray(same), np.asarray(plain),
                               atol=1e-6)


def test_a_window_wants_an_attention_layer_without_an_index():
    transformer.LayerSpec(window=8)
    assert transformer.LayerSpec().window == 0          # none: as before
    for wrong in (dict(op="conv"), dict(index_topk=4), dict(window=-1)):
        with pytest.raises(ValueError, match="window"):
            transformer.LayerSpec(**dict(dict(window=8), **wrong))


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_the_sequence_parallel_paths_refuse_a_window(attention):
    from tensorflowonspark_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"seq": 1}, devices=jax.devices()[:1])
    model = get_model("mellum2", config=adapter.program_config(TINY),
                      attention=attention, mesh=mesh)
    with pytest.raises(ValueError, match="has no window"):
        model.init(jax.random.PRNGKey(0), _tokens(1, 128))


# -- the chip's share ---------------------------------------------------------

def test_the_shares_of_the_eight_chips_add_up_to_the_uncut_layer(row_path):
    """64 is 16 here: 16 experts in 8 shares of 2, top-3 renormalised.  The
    routed partial sums of the eight chips equal the uncut reference's whole
    layer, and every (token, slot) pair is counted by one share."""
    d, f, e = 32, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    w = {"L0.router": 0.3 * jax.random.normal(ks[0], (d, e)),
         "L0.ew1": 0.2 * jax.random.normal(ks[1], (e, d, f)),
         "L0.ew3": 0.2 * jax.random.normal(ks[2], (e, d, f)),
         "L0.ew2": 0.2 * jax.random.normal(ks[3], (e, f, d))}
    x = jax.random.normal(ks[4], (2, 40, d))
    cfg = dict(TINY, router_experts=e, held_experts=[0, e])
    whole = jnp.stack([ref._experts(row, w, "L0.", cfg, "float32")
                       for row in x])
    total, local = 0.0, 0
    for first in range(0, e, 2):
        layer = transformer.TopKExperts(
            num_experts=e, experts_per_token=3, hidden=f, held=(first, 2),
            norm_topk=True, score="softmax", selection_bias=False)
        params = {"router": w["L0.router"],
                  **{k: w["L0.e" + k][first:first + 2]
                     for k in ("w1", "w3", "w2")}}
        y, state = layer.apply({"params": params}, x,
                               mutable=["intermediates"])
        counts = state["intermediates"]["counters"][0]
        assert int(counts["moe_slots_total"]) == 2 * 40 * 3
        total, local = total + y, local + int(counts["moe_slots_local"])
        if first == 6:      # one share alone is the reference's same share
            mine = dict(w, **{k: w[k][first:first + 2]
                              for k in ("L0.ew1", "L0.ew3", "L0.ew2")})
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(jnp.stack([ref._experts(
                    row, mine, "L0.", dict(cfg, held_experts=[first, 2]),
                    "float32") for row in x])), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5, rtol=5e-5)
    assert local == 2 * 40 * 3


# -- the description, its tree, its counters ----------------------------------

def test_mellum2_is_registered_and_follows_the_description():
    spec = family.mellum2_spec(adapter.program_config(TINY))
    assert len(spec.layers) == 4 and not spec.tied_readout
    for layer in spec.layers:
        assert (layer.op, layer.ff, layer.qk_norm) == ("attention",
                                                       "experts", True)
        assert (layer.num_heads, layer.num_kv_heads, layer.head_dim) == (
            4, 2, 16)
        assert (layer.router_score, layer.selection_bias, layer.norm_topk,
                layer.shared_size, layer.index_topk) == ("softmax", False,
                                                         True, 0, 0)
        assert layer.held_experts == (2, 4) and layer.num_experts == 8
    assert {layer.flash_block for layer in spec.layers} == {32}
    for key, value in (("attention_bias", True),
                       ("layer_types", ["conv"] * 4),
                       ("mlp_layer_types", ["dense"] * 4),
                       ("use_sliding_window", False)):
        with pytest.raises(ValueError, match=key):
            family.mellum2_spec(dict(adapter.program_config(TINY),
                                          **{key: value}))
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.mellum2_spec(dict(adapter.program_config(TINY),
                                      num_hidden_layers=5))


def test_the_description_yields_exactly_the_paths_its_adapter_names():
    model = get_model("mellum2", config=adapter.program_config(TINY))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 128), jnp.int32))["params"]
    program = set(traverse_util.flatten_dict(params, sep="/"))
    assert program == {path for path, _ in adapter._paths(TINY).values()}
    assert "block_3/attention/k_norm/scale" in program and "head" in program
    assert not [p for p in program
                if "expert_bias" in p or "shared" in p or "index" in p]


@pytest.mark.parametrize("seq,block,window", [(128, 32, 40), (128, 16, 32),
                                              (256, 32, 300)])
def test_trainer_counters_carry_the_band(seq, block, window):
    """``swa_tiles_computed / swa_tiles_causal`` is the band's share: a
    window that crosses a block, one of two whole blocks, and one that
    covers the row (every causal tile)."""
    from test_lfm2_moe import _fit

    snap = _fit(get_model("mellum2", config=dict(
        adapter.program_config(TINY), sliding_window=window,
        flash_block=block)), seq=seq)
    assert snap["swa_layers_steps"] == 3 * 3        # 3 steps, 3 sliding layers
    n = seq // block
    assert snap["swa_tiles_causal"] == 9 * 2 * n * (n + 1) // 2     # batch 2
    runs = [i - max(i * block - window + 1, 0) // block + 1 for i in range(n)]
    assert snap["swa_tiles_computed"] == 9 * 2 * sum(runs)
    assert band_tiles(seq, block, window) == (sum(runs), n * (n + 1) // 2)
    assert (window >= seq) == (
        snap["swa_tiles_computed"] == snap["swa_tiles_causal"])
    assert snap["moe_layers_steps"] == 3 * 4
    assert not [k for k in snap if k.startswith("dsa_")]
    # the flash kernels' grids, 3 steps x batch 2 x 4 heads: the full layer
    # steps over the triangle alone, a sliding one over its q blocks'
    # longest run (the causal grid where the window covers the row)
    triangle = n * (n + 1) // 2
    banded = (sum(runs), n * max(runs)) if window < seq else (triangle,) * 2
    assert snap["flash_tiles_computed"] == 24 * (3 * banded[0] + triangle)
    assert snap["flash_grid_steps"] == 24 * (3 * banded[1] + triangle)


def test_the_real_cells_band_is_a_tenth_of_the_triangle():
    assert band_tiles(32768, 512, 1024) == (189, 2080)
    assert band_tiles(32768, 256, 1024) == (630, 8256)
