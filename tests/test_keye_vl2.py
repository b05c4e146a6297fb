"""The Keye-VL-2.0 description of ``models/transformer.py`` at tiny sizes on
the CPU: the whole model against the plain reference (logits, the two losses,
the gradient of every leaf), which loss reaches which leaf, the selection
kernel against ``lax.top_k`` on a dense score matrix (ties, rows shorter than
``k``), an index that keeps every causal key against plain grouped-query
flash attention, the tiles the kept keys touch, the eight chips' shares
against the uncut layer, the parameter paths the family's adapter names, and the ``dsa_*`` counters of ``Trainer``.  (The
bf16 program against the reference under the tiny cell's limits, and the fp8
control against them, is ``tests/benchmark/test_benchmark_references.py``;
the kernels with ``key_bits`` alone are in ``tests/test_ops.py``.)"""

import functools
import importlib
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

from benchmark.adapters import keye_vl2 as adapter  # noqa: E402
from benchmark.references import keye_vl2 as ref  # noqa: E402
from tensorflowonspark_tpu.models import get_model, transformer  # noqa: E402
from tensorflowonspark_tpu.models.families import keye_vl2 as family  # noqa: E402
from tensorflowonspark_tpu.ops import (  # noqa: E402
    flash_attention, flash_attention_lse)

sparse_index = importlib.import_module(
    "tensorflowonspark_tpu.ops.sparse_index")

TINY = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
        "hidden_size": 32, "mlp_only_layers": [],
        "moe_intermediate_size": 16, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts": 4,
        "num_experts_per_tok": 3, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                      "indexer_num_kv_heads": 1, "topk": 40},
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 61, "router_experts": 8, "held_experts": [2, 4],
        "seq_len": 256, "flash_block": 128, "attention": "flash",
        "remat": False, "dtype": "float32",
        "optimizer": {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
                      "eps": 1e-8}}
INDEX_LEAVES = ("index_q", "index_k", "index_k_norm", "index_w")


def _tokens(batch=2, seq=256):
    return jnp.asarray(
        np.random.RandomState(0).randint(0, 61, (batch, seq)), jnp.int32)


def _program(seed=3, **changes):
    built = adapter.build(dict(TINY, **changes), seed)
    return built, ref.init_weights(dict(TINY, **changes), seed)


# -- the whole model against the reference ------------------------------------

@functools.lru_cache(maxsize=None)
def _reference_of_the_program():
    """The reference's logits, its two losses a row and its gradients: the
    same for both paths of the expert rows, so computed once."""
    weights = ref.init_weights(TINY, 3)
    tokens = _tokens()
    logits = jnp.stack([ref.forward(weights, row, TINY) for row in tokens])
    parts = [ref.losses(weights, row, TINY) for row in tokens]
    grads = jax.jit(jax.grad(lambda w: sum(
        ref.loss_fn(w, row, TINY) for row in tokens) / 2))(weights)
    return logits, parts, grads

def test_logits_losses_and_every_gradient_leaf_against_the_reference(
        row_path):
    built, _ = _program()
    tokens = _tokens()
    want_logits, parts, want = _reference_of_the_program()
    logits = built["model"].apply({"params": built["params"]}, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=2e-5, rtol=2e-5)
    (loss, aux), grads = jax.value_and_grad(built["loss"], has_aux=True)(
        built["params"], {"tokens": tokens}, jnp.ones((2,)))
    index_loss = float(np.mean([float(b) for _, b in parts]))
    assert index_loss > 0.01
    assert float(aux["dsa_index_loss"]) == pytest.approx(index_loss,
                                                         rel=2e-5)
    assert float(loss) == pytest.approx(
        float(np.mean([float(a) + float(b) for a, b in parts])), rel=2e-5)
    got = traverse_util.flatten_dict(grads, sep="/")
    assert set(got) == set(built["names"])
    for path, name in built["names"].items():
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(got[path]).reshape(want[name].shape) / scale,
            np.asarray(want[name]) / scale, atol=5e-5, err_msg=name)


def test_each_loss_reaches_its_own_leaves_alone():
    """The cross-entropy's gradient to the index's leaves is exactly zero,
    and the index's loss's to every other leaf too."""
    built, _ = _program()
    tokens = _tokens()
    model = built["model"]

    def both(params):
        logits, state = model.apply({"params": params}, tokens,
                                    mutable=["intermediates"])
        index = sum(transformer._sown(dict(state["intermediates"]),
                                      "dsa_index_loss")).mean()
        return logits.astype(jnp.float32).var(), index

    of_logits = traverse_util.flatten_dict(
        jax.grad(lambda p: both(p)[0])(built["params"]), sep="/")
    of_index = traverse_util.flatten_dict(
        jax.grad(lambda p: both(p)[1])(built["params"]), sep="/")
    def of_the_index(path):
        parts = path.split("/")
        return len(parts) > 2 and parts[2] in INDEX_LEAVES

    for path in of_logits:
        mine = of_the_index(path)
        alone, other = ((of_index, of_logits) if mine
                        else (of_logits, of_index))
        assert float(jnp.abs(other[path]).max()) == 0.0, path
        assert float(jnp.abs(alone[path]).max()) > 0.0, path
    assert sum(map(of_the_index, of_logits)) == 2 * 5


def test_the_indexs_gradient_is_made_before_its_layers_is_handed_on():
    """``_backward_together`` is the identity both ways, and its backward
    pass holds the two gradients behind one barrier (so that the index's
    backward, which nothing below waits for, runs in its own layer's
    backward pass and frees what it reads)."""
    main, side = jnp.arange(6.0).reshape(2, 3), (jnp.ones(4), jnp.ones(5))

    def loss(main, side):
        main, side = transformer._backward_together(main, side)
        return (main ** 2).sum() + 3 * side[0].sum() + 5 * side[1].sum()

    of_main, of_side = jax.grad(loss, (0, 1))(main, side)
    np.testing.assert_array_equal(np.asarray(of_main), 2 * np.asarray(main))
    np.testing.assert_array_equal(np.asarray(of_side[0]), np.full(4, 3.0))
    np.testing.assert_array_equal(np.asarray(of_side[1]), np.full(5, 5.0))
    backward = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(main, side))
    assert backward.count("optimization_barrier") == 1
    assert "optimization_barrier" not in str(jax.make_jaxpr(
        transformer._backward_together)(main, side))


# -- the selection ------------------------------------------------------------

def _index_inputs(seed, batch=2, seq=256, heads=4, dim=16, coarse=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    iq = jax.random.normal(ks[0], (batch, seq, heads, dim))
    ik = jax.random.normal(ks[1], (batch, seq, dim))
    iw = 0.2 * jax.random.normal(ks[2], (batch, seq, heads))
    if coarse:      # few distinct scores: many ties, exact zeros among them
        iq, ik, iw = (jnp.round(2 * iq) / 2, jnp.round(2 * ik) / 2,
                      jnp.round(8 * iw) / 8)
    return iq, ik, iw


def _dense_selection(iq, ik, iw, topk):
    scores = (iw.transpose(0, 2, 1)[..., None] * jax.nn.relu(
        jnp.einsum("btje,bse->bjts", iq, ik))).sum(axis=1)
    kept = jnp.stack([ref.selection(s, 0, topk) for s in scores])
    return scores, kept


def _unpack(bits, seq):
    """bool [B, T, S] of a key_bits array."""
    b = np.asarray(bits)
    s = np.arange(seq)
    words = b[:, s // 4096, :, s % 128]                 # [S, B, T]
    return ((words >> ((s % 4096) // 128)[:, None, None]) & 1).astype(
        bool).transpose(1, 2, 0)


@pytest.mark.parametrize("case", ["distinct", "ties", "k_over_rows",
                                  "one_block"])
def test_the_selection_is_top_k_of_the_dense_scores(case):
    topk = {"distinct": 40, "ties": 48, "k_over_rows": 300,
            "one_block": 17}[case]
    seq = 128 if case == "one_block" else 256
    iq, ik, iw = _index_inputs(1, seq=seq, coarse=case == "ties")
    bits, lse = sparse_index.select_keys(iq, ik, iw, topk, block_q=128,
                                         chunk=128)
    scores, kept = _dense_selection(iq, ik, iw, topk)
    got = _unpack(bits, seq)
    np.testing.assert_array_equal(got, np.asarray(kept))
    # a row shorter than k keeps every causal key, the others exactly k
    np.testing.assert_array_equal(
        got.sum(-1)[0], np.minimum(np.arange(seq) + 1, topk))
    if case == "ties":      # the case is what it says
        causal = np.tril(np.ones((seq, seq), bool))
        ranked = np.sort(np.where(causal, np.asarray(scores[0]), -np.inf))
        assert (ranked[:, -topk] == ranked[:, -topk - 1]).sum() > 20
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(
            jnp.where(kept, scores, -jnp.inf), axis=-1)), atol=2e-5)
    touched, causal_tiles = sparse_index.tiles_touched(bits, seq, 128)
    assert int(touched) == causal_tiles == 2 * (3 if seq == 256 else 1)


def test_an_index_that_keeps_every_key_is_plain_grouped_query_flash():
    """``index_topk >= T``: the same output and the same dq, dk, dv as the
    kernels without a key set."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (2, 256, 4, 16))
    k = jax.random.normal(ks[1], (2, 256, 2, 16))
    v = jax.random.normal(ks[2], (2, 256, 2, 16))
    g = jax.random.normal(ks[3], (2, 256, 4, 16))
    bits, _ = sparse_index.select_keys(*_index_inputs(4), 256, block_q=128,
                                       chunk=128)

    def keyed(q, k, v):
        return (flash_attention_lse(q, k, v, block_q=128, block_k=128,
                                    key_bits=bits)[0] * g).sum()

    def plain(q, k, v):
        return (flash_attention(q, k, v, block_q=128, block_k=128) * g).sum()

    np.testing.assert_allclose(
        np.asarray(flash_attention_lse(q, k, v, block_q=128, block_k=128,
                                       key_bits=bits)[0]),
        np.asarray(flash_attention(q, k, v, block_q=128, block_k=128)),
        atol=1e-6)
    for got, want in zip(jax.grad(keyed, (0, 1, 2))(q, k, v),
                         jax.grad(plain, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("scores", ["seeded", "first_keys"])
def test_the_tiles_counted_are_the_tiles_that_hold_a_kept_key(block, scores):
    """``tiles_touched`` against the kept pairs unpacked: seeded scores
    spread a row's keys over every causal tile, scores that fall with the
    position keep a row's first keys and leave every other tile empty."""
    seq, topk = 512, 24
    iq, ik, iw = _index_inputs(5, seq=seq)
    if scores == "first_keys":
        iq, iw = jnp.ones_like(iq), jnp.ones_like(iw)
        ik = jnp.broadcast_to(
            (1.0 - jnp.arange(seq) / seq)[None, :, None], ik.shape)
    bits, _ = sparse_index.select_keys(iq, ik, iw, topk, block_q=128,
                                       chunk=128)
    n = seq // block
    kept = _unpack(bits, seq).reshape(-1, n, block, n, block).any(
        axis=(2, 4))
    touched, causal = sparse_index.tiles_touched(bits, seq, block)
    assert causal == kept.shape[0] * n * (n + 1) // 2
    assert int(touched) == int(kept.sum()) <= causal
    if scores == "first_keys":                  # one tile a query block
        assert int(touched) == kept.shape[0] * n
    assert not np.triu(kept[0], 1).any()        # none above the diagonal


@pytest.mark.parametrize("attention", ["ring", "full"])
def test_an_index_wants_flash(attention):
    model = get_model("keye_vl2", config=adapter.program_config(TINY),
                      attention=attention)
    with pytest.raises(ValueError, match="index over the keys"):
        model.init(jax.random.PRNGKey(0), _tokens(1, 128))


# -- the chip's share ---------------------------------------------------------

def test_the_shares_of_the_eight_chips_add_up_to_the_uncut_layer(row_path):
    """128 is 16 here: 16 experts in 8 shares of 2, top-3 renormalised.  The
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

def test_keye_vl2_is_registered_and_follows_the_description():
    spec = family.keye_vl2_spec(adapter.program_config(TINY))
    assert len(spec.layers) == 2 and not spec.tied_readout
    layer = spec.layers[0]
    assert (layer.op, layer.ff, layer.qk_norm) == ("attention", "experts",
                                                   True)
    assert (layer.index_heads, layer.index_dim, layer.index_topk) == (4, 8,
                                                                      40)
    assert (layer.num_heads, layer.num_kv_heads, layer.head_dim) == (4, 2, 16)
    assert (layer.router_score, layer.selection_bias, layer.norm_topk,
            layer.shared_size) == ("softmax", False, True, 0)
    assert layer.held_experts == (2, 4) and layer.num_experts == 8
    assert transformer.LayerSpec().index_heads == 0     # none: as before
    with pytest.raises(ValueError, match="mlp_only_layers"):
        family.keye_vl2_spec(dict(TINY, mlp_only_layers=[0]))


def test_the_description_yields_exactly_the_paths_its_adapter_names():
    model = get_model("keye_vl2", config=adapter.program_config(TINY))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 128), jnp.int32))["params"]
    program = set(traverse_util.flatten_dict(params, sep="/"))
    assert program == {path for path, _ in adapter._paths(TINY).values()}
    assert "block_1/attention/index_k_norm/bias" in program
    assert not [p for p in program if "expert_bias" in p or "shared" in p]


def test_trainer_counters_carry_the_index():
    from test_lfm2_moe import _fit

    snap = _fit(get_model("keye_vl2", config=adapter.program_config(TINY)),
                seq=128)
    assert snap["dsa_layers_steps"] == 3 * 2            # 3 steps, 2 layers
    # rows of 128 tokens are one tile a row: batch 2, two layers, three steps
    assert snap["dsa_tiles_causal"] == snap["dsa_tiles_touched"] == 12
    assert snap["dsa_index_loss"] > 0.0
    assert snap["moe_layers_steps"] == 3 * 2
    # the keyed flash kernels' grid: a tile a row and head, 4 heads
    assert snap["flash_grid_steps"] == snap["flash_tiles_computed"] == 48
