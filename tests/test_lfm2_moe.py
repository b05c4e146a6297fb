"""The layer pattern of ``models/transformer.py`` and the top-k expert layer
(``parallel/ep.route_topk`` / ``experts_ffn``), at tiny sizes on the CPU:
the chip's share of an expert layer against the plain reference's whole
layer, a router that sends everything to one expert, the GPT-2 description's
parameter tree, and the router's load as ``Trainer`` counters.  (The whole
model against the reference, loss and every gradient leaf, is
``tests/benchmark/test_benchmark_references.py`` over the tiny cell.)"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from flax import traverse_util

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import lfm2_moe as ref  # noqa: E402
from tensorflowonspark_tpu.models import get_model, transformer  # noqa: E402
from tensorflowonspark_tpu.parallel import build_mesh  # noqa: E402
from tensorflowonspark_tpu.parallel.infeed import ShardedFeed  # noqa: E402
from tensorflowonspark_tpu.train import Trainer  # noqa: E402

# one expert layer of the reference's (layer 0), 8 experts, top-2
LAYER = {"hidden_size": 32, "moe_intermediate_size": 24, "router_experts": 8,
         "num_experts_per_tok": 2, "norm_topk_prob": True,
         "routed_scaling_factor": 1, "held_experts": [0, 8]}


def _layer_weights(seed, bias=None):
    d, f, e = 32, 24, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = {"L0.router": jax.random.normal(ks[0], (d, e)),
         "L0.expert_bias": 0.3 * jax.random.normal(ks[1], (e,)),
         "L0.ew1": 0.2 * jax.random.normal(ks[2], (e, d, f)),
         "L0.ew3": 0.2 * jax.random.normal(ks[3], (e, d, f)),
         "L0.ew2": 0.2 * jax.random.normal(ks[4], (e, f, d))}
    if bias is not None:
        w["L0.expert_bias"] = jnp.asarray(bias, jnp.float32)
    return w, jax.random.normal(ks[5], (3, 40, d))


def _program_share(w, x, first, count):
    """The program's expert layer holding experts first .. first+count-1."""
    layer = transformer.TopKExperts(num_experts=8, experts_per_token=2,
                                    hidden=24, held=(first, count))
    params = {"router": w["L0.router"], "expert_bias": w["L0.expert_bias"],
              **{k: w["L0.e" + k][first:first + count]
                 for k in ("w1", "w3", "w2")}}
    y, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    return y, state["intermediates"]["counters"][0]


def _reference_layer(w, x, held=(0, 8)):
    cfg = dict(LAYER, held_experts=list(held))
    first, count = held
    mine = dict(w, **{k: w[k][first:first + count]
                      for k in ("L0.ew1", "L0.ew3", "L0.ew2")})
    return jnp.stack([ref._experts(row, mine, "L0.", cfg, "float32")
                      for row in x])


def test_the_shares_add_up_to_the_whole_layer(row_path):
    """8 experts in 4 shares of 2: the four partial sums that the chips of an
    expert-parallel layer compute equal the uncut reference's whole layer,
    and every (token, slot) pair is counted by exactly one share."""
    w, x = _layer_weights(0)
    whole = _reference_layer(w, x)
    parts = [_program_share(w, x, first, 2) for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in parts)),
                               np.asarray(whole), atol=2e-5, rtol=2e-5)
    assert sum(int(c["moe_slots_local"]) for _, c in parts) == 3 * 40 * 2
    assert all(int(c["moe_slots_total"]) == 3 * 40 * 2 for _, c in parts)
    # the gate's passes visit a share's pairs rounded up to a row tile (240
    # rows are 15 tiles of 16), and every tile where every expert is held
    for _, c in parts + [_program_share(w, x, 0, 8)]:
        assert int(c["moe_gate_tiles_total"]) == 15
        assert int(c["moe_gate_tiles_live"]) == -(
            -int(c["moe_slots_local"]) // 16)
    assert all(int(c["moe_gate_tiles_live"]) < 15 for _, c in parts)
    # one share alone is the reference's same share, not a rescaled whole
    np.testing.assert_allclose(
        np.asarray(parts[1][0]), np.asarray(_reference_layer(w, x, (2, 2))),
        atol=2e-5, rtol=2e-5)


def test_nothing_is_dropped_when_every_token_goes_to_one_expert(row_path):
    """A selection bias that sends every token's first slot to expert 3: the
    share holding experts 3 and 4 gets all 120 tokens in one group (a
    capacity of 1.25 S / E would keep 18) and gives the reference's answer,
    values and the gradient of the input."""
    bias = np.zeros(8, np.float32)
    bias[3] = 10.0
    w, x = _layer_weights(1, bias)
    y, counts = _program_share(w, x, 3, 2)
    assert int(counts["moe_expert_load_max_sum"]) == 3 * 40
    assert int(counts["moe_slots_local"]) >= 3 * 40
    want = _reference_layer(w, x, (3, 2))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda x: (_program_share(w, x, 3, 2)[0] ** 2).sum())(x)
    g_ref = jax.grad(lambda x: (_reference_layer(w, x, (3, 2)) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


def test_a_share_that_nothing_is_routed_to_gives_zero(row_path):
    bias = np.zeros(8, np.float32)
    bias[:2] = 10.0        # both slots of every token go to experts 0 and 1
    w, x = _layer_weights(2, bias)
    y, counts = _program_share(w, x, 4, 4)
    assert int(counts["moe_slots_local"]) == 0
    assert int(counts["moe_gate_tiles_live"]) == 0
    assert not np.asarray(y).any()


def test_gpt2_description_keeps_the_parameter_tree():
    """The decoder under ``gpt2_spec`` has exactly the parameter paths
    ``benchmark/adapters/gpt2.py`` names, and ``build_transformer(...)`` and
    ``TransformerLM(spec=gpt2_spec(...))`` make the same parameters and the
    same logits."""
    from benchmark.adapters import gpt2 as adapter

    cfg = {"n_layer": 2, "n_head": 4, "n_embd": 64, "n_positions": 32,
           "vocab_size": 97}
    tokens = jnp.asarray(np.arange(64).reshape(2, 32) % 97, jnp.int32)
    built = transformer.build_transformer(
        vocab_size=97, num_layers=2, num_heads=4, head_dim=16, max_seq_len=32)
    params = built.init(jax.random.PRNGKey(0), tokens)["params"]
    paths = set(traverse_util.flatten_dict(params, sep="/"))
    assert paths == set(adapter.reference_names(cfg))
    explicit = transformer.TransformerLM(
        spec=transformer.gpt2_spec(97, 2, 4, 16, 32))
    assert explicit == built
    again = explicit.init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(traverse_util.flatten_dict(again, sep="/")) == paths
    for leaf, leaf_again in zip(jax.tree_util.tree_leaves(params),
                                jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(leaf_again))
    np.testing.assert_array_equal(
        np.asarray(built.apply({"params": params}, tokens)),
        np.asarray(explicit.apply({"params": params}, tokens)))


TINY = {"conv_L_cache": 3, "hidden_size": 32, "intermediate_size": 64,
        "layer_types": ["conv", "full_attention", "conv"],
        "moe_intermediate_size": 16, "norm_eps": 1e-5, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 3,
        "num_key_value_heads": 2, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "vocab_size": 61, "held_experts": [4, 4],
        "flash_block": 16}


def test_lfm2_moe_is_registered_and_follows_the_pattern():
    model = get_model("lfm2_moe", config=TINY, attention="full")
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(params["block_0"]) == {"RMSNorm_0", "short_conv", "RMSNorm_1",
                                      "mlp"}
    assert set(params["block_1"]) == {"RMSNorm_0", "attention", "RMSNorm_1",
                                      "moe"}
    assert set(params["block_2"]) == {"RMSNorm_0", "short_conv", "RMSNorm_1",
                                      "moe"}
    assert "pos_embed" not in params
    assert params["block_1"]["attention"]["k"]["kernel"].shape == (32, 2, 8)
    assert params["block_2"]["moe"]["w1"].shape == (4, 32, 16)   # held only
    assert params["block_2"]["moe"]["router"].shape == (32, 8)   # all 8
    with pytest.raises(ValueError, match="layer_types"):
        get_model("lfm2_moe", config=dict(TINY, num_hidden_layers=4))


def test_ep_param_shardings_cover_the_topk_leaves():
    """``ep_param_shardings``' pattern takes the new layer's expert-stacked
    leaves (w1, w3, w2) and leaves its router and bias replicated."""
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel import ep

    model = get_model("lfm2_moe", config=TINY, attention="full")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 16), jnp.int32))["params"]
    mesh = build_mesh({"data": 2, "expert": 4})
    flat = traverse_util.flatten_dict(
        ep.ep_param_shardings(params, mesh), sep="/")
    for leaf in ("w1", "w3", "w2"):
        assert flat["block_2/moe/" + leaf].spec == P("expert", None, None)
    for path in ("block_2/moe/router", "block_2/moe/expert_bias",
                 "block_0/mlp/w1/kernel", "embed/embedding"):
        assert not any(flat[path].spec)     # replicated


class _TokenSource(object):
    """A feed of seeded token rows, the columnar protocol ``ShardedFeed``
    drains (``next_batch_arrays``, ``should_stop``, ``interrupt``)."""

    def __init__(self, batches, batch, seq, vocab):
        rng = np.random.default_rng(3)
        self.rows = [rng.integers(0, vocab, (batch, seq), dtype=np.int32)
                     for _ in range(batches)]

    def next_batch_arrays(self, n):
        rows = self.rows.pop(0)
        assert len(rows) == n
        return {"tokens": rows}, n

    def should_stop(self):
        return not self.rows

    def interrupt(self):
        self.rows = []

    terminate = interrupt


def _fit(model, steps=3, seq=16):
    mesh = build_mesh({"data": 1}, devices=jax.devices()[:1])
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, seq), jnp.int32))["params"]
    trainer = Trainer(transformer.loss_fn(model), params, optax.adam(1e-3),
                      mesh=mesh, batch_size=2, log_steps=1,
                      step_flops_override=1.0)
    feed = ShardedFeed(_TokenSource(steps, 2, seq, 61), mesh, 2, prefetch=0)
    stats = trainer.fit_feed(feed)
    assert stats["global_steps"] == steps
    jax.block_until_ready(trainer.state.params)
    return trainer.counters_snapshot()


def test_trainer_counters_carry_the_routers_load():
    snap = _fit(get_model("lfm2_moe", config=TINY, attention="full"))
    pairs = 2 * 16 * 2      # batch x seq x experts a token
    assert snap["moe_layers_steps"] == 3 * 2            # 3 steps, 2 layers
    assert snap["moe_slots_total"] == 3 * 2 * pairs
    assert 0 < snap["moe_slots_local"] < snap["moe_slots_total"]
    assert snap["moe_expert_load_mean_sum"] == pytest.approx(
        snap["moe_slots_local"] / 4)                    # 4 experts held
    assert snap["moe_expert_load_max_sum"] >= snap["moe_expert_load_mean_sum"]


def test_a_model_without_experts_has_no_moe_counters():
    snap = _fit(transformer.build_transformer(
        vocab_size=61, num_layers=1, num_heads=2, head_dim=8, max_seq_len=16))
    assert not [k for k in snap if k.startswith(("moe_", "flash_"))]
    assert snap["dispatch_count"] == 3


def test_a_layer_kind_of_its_own_counts_without_an_edit_elsewhere():
    """A layer sows ``counters`` under keys that neither ``loss_fn`` nor the
    ``Trainer`` has heard of: they come out of ``counters_snapshot()`` under
    those keys, added up over the layers and the steps, an integer an
    integer and a float a float."""
    import flax.linen as nn

    class Counting(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.sow("intermediates", "counters", {
                "toy_rows": jnp.asarray(x.shape[0], jnp.int32),
                "toy_halves": jnp.asarray(0.5, jnp.float32)})
            return nn.Dense(x.shape[-1])(x)

    class ToyLM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(61, 8)(tokens)
            for _ in range(2):
                x = Counting()(x)
            return nn.Dense(61)(x)

    snap = _fit(ToyLM())
    assert snap["toy_rows"] == 3 * 2 * 2        # steps x layers x rows
    assert isinstance(snap["toy_rows"], int)
    assert snap["toy_halves"] == 3 * 2 * 0.5
    assert isinstance(snap["toy_halves"], float)


@pytest.mark.parametrize("seq, tiles, longest, steps", [
    (16, 1, None, 1), (256, 3, None, 3), (256, 3, 2, 4)],
    ids=["one_tile", "listed", "the_square"])
def test_trainer_counters_carry_the_flash_kernels_grid(
        monkeypatch, seq, tiles, longest, steps):
    """``flash_grid_steps / flash_tiles_computed`` is 1 for causal layers:
    the grid lists the triangle's tiles (3 steps x batch 2 x 2 heads x 2
    layers; blocks of 128, clamped to a short row); where the list would be
    longer than ``LISTED_STEPS`` the kernels step over the square and the
    counters say so (4 steps for 3 tiles)."""
    import importlib

    if longest is not None:
        monkeypatch.setattr(importlib.import_module(
            "tensorflowonspark_tpu.ops.flash_attention"), "LISTED_STEPS",
            longest)
    snap = _fit(transformer.build_transformer(
        vocab_size=61, num_layers=2, num_heads=2, head_dim=8, max_seq_len=seq,
        attention="flash"), seq=seq)
    assert snap["flash_tiles_computed"] == 24 * tiles
    assert snap["flash_grid_steps"] == 24 * steps
