"""The DeepSeek-V2 description of ``models/transformer.py`` at tiny sizes on
the CPU: latent attention's frequencies, pairing and scale against numbers
worked out here by hand, the softmax router's shares with the shared expert
counted once against the plain reference's whole layer, a router that sends
everything to one expert, the untied read-out, the parameter paths every
family's adapter names, and the router's load as ``Trainer`` counters.  (The
whole model against the reference, loss and every gradient leaf, is
``tests/benchmark/test_benchmark_references.py`` over the tiny cell; the
kernels with a value width of their own are in ``tests/test_ops.py``.)"""

import importlib
import math
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

from benchmark.references import deepseek_v2 as ref  # noqa: E402
from tensorflowonspark_tpu.models import get_model, transformer  # noqa: E402
from tensorflowonspark_tpu.models.families import deepseek_v2 as family  # noqa: E402

LITE_ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
             "mscale_all_dim": 0.707,
             "original_max_position_embeddings": 4096, "type": "yarn"}

TINY = {"attention_bias": False, "first_k_dense_replace": 1,
        "hidden_size": 32, "intermediate_size": 64, "kv_lora_rank": 16,
        "moe_intermediate_size": 16, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 8, "n_shared_experts": 2,
        "norm_topk_prob": False, "num_attention_heads": 4,
        "num_experts_per_tok": 3, "num_hidden_layers": 3,
        "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "rms_norm_eps": 1e-6, "rope_scaling": LITE_ROPE, "rope_theta": 10000,
        "routed_scaling_factor": 1, "scoring_func": "softmax",
        "tie_word_embeddings": False, "topk_method": "greedy",
        "v_head_dim": 16, "vocab_size": 61, "held_experts": [4, 4],
        "flash_block": 16}


# -- RoPE: YaRN's frequencies, the interleaved pairing, the scale -------------

def _lite_frequencies_by_hand():
    """DeepSeek-V2-Lite's 32 frequencies from its ``rope_scaling`` block,
    written out: base 10,000, 64 rotary dimensions, factor 40, original
    context 4,096, beta_fast 32, beta_slow 1."""
    extra = [10000.0 ** (-2.0 * i / 64) for i in range(32)]

    def cd(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(10000))

    low, high = math.floor(cd(32)), math.ceil(cd(1))
    assert (low, high) == (10, 23)
    out = []
    for i, f in enumerate(extra):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / 40 * ramp + f * (1 - ramp))
    return out


def test_yarn_frequencies_against_numbers_by_hand():
    want = _lite_frequencies_by_hand()
    yarn = (40.0, 4096.0, 32.0, 1.0, 0.707, 0.707)
    got, factor = transformer.rope_frequencies(64, 10000.0, yarn)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
    assert factor == 1.0        # m(mscale) / m(mscale_all_dim), both 0.707
    # pairs 0..10 keep the plain frequency, 23..31 take a fortieth of it
    np.testing.assert_allclose(np.asarray(got[:11]),
                               [10000.0 ** (-i / 32) for i in range(11)],
                               rtol=2e-6)
    np.testing.assert_allclose(np.asarray(got[23:]),
                               [10000.0 ** (-i / 32) / 40
                                for i in range(23, 32)], rtol=2e-6)
    # the reference computes its own (numpy float64) and agrees
    lite = {"qk_rope_head_dim": 64, "rope_theta": 10000,
            "rope_scaling": LITE_ROPE, "qk_nope_head_dim": 128}
    theirs, their_factor = ref.yarn_frequencies(lite)
    np.testing.assert_allclose(np.asarray(theirs), want, rtol=2e-6)
    assert their_factor == 1.0
    # plain frequencies are what rope() always used
    plain, one = transformer.rope_frequencies(64, 1000000.0)
    np.testing.assert_array_equal(
        np.asarray(plain),
        np.asarray(1000000.0 ** (-jnp.arange(32, dtype=jnp.float32) / 32)))
    assert one == 1.0


def test_softmax_scale_by_hand():
    """192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2 = 0.0721688 * 1.58963."""
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m * m == pytest.approx(1.58963, abs=1e-5)
    want = 192 ** -0.5 * m * m
    assert want == pytest.approx(0.114722, abs=1e-6)
    lite = dict(TINY, qk_nope_head_dim=128, qk_rope_head_dim=64)
    spec = family.deepseek_v2_spec(lite)
    assert spec.layers[0].attn_scale == pytest.approx(want, rel=1e-12)
    assert ref.softmax_scale(lite) == pytest.approx(want, rel=1e-12)
    assert spec.layers[0].rope_yarn == (40.0, 4096.0, 32.0, 1.0, 0.707, 0.707)


def test_interleaved_pairing_turns_neighbours():
    """Dimensions (2i, 2i + 1) turn by ``pos * f_i``: against complex
    numbers by hand.  The program hands the turned pairs out in the half
    layout (first members, then second: the family's code does the same),
    the reference in place; scores do not care, as long as q and k agree."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 5, 2, 6)).astype(np.float32)
    inv = jnp.asarray([1.0, 0.5, 0.01], jnp.float32)
    pos = np.arange(5)[None, :, None, None]
    turned = ((x[..., 0::2] + 1j * x[..., 1::2])
              * np.exp(1j * pos * np.asarray(inv)))
    got = np.asarray(transformer.rope(jnp.asarray(x), inv, "interleaved"))
    np.testing.assert_allclose(got[..., :3], turned.real, atol=1e-6)
    np.testing.assert_allclose(got[..., 3:], turned.imag, atol=1e-6)
    theirs = np.asarray(ref._rope(jnp.asarray(x[0]), inv, 1.0))
    np.testing.assert_allclose(theirs[..., 0::2], turned.real[0], atol=1e-6)
    np.testing.assert_allclose(theirs[..., 1::2], turned.imag[0], atol=1e-6)
    # rotate-half pairing is dimension i with i + D/2, as it always was
    half = np.asarray(transformer.rope(jnp.asarray(x), inv))
    paired = (x[..., :3] + 1j * x[..., 3:]) * np.exp(1j * pos
                                                     * np.asarray(inv))
    np.testing.assert_allclose(half[..., :3], paired.real, atol=1e-6)
    np.testing.assert_allclose(half[..., 3:], paired.imag, atol=1e-6)


# -- the expert layer: shares, the shared expert once, nothing dropped --------

# one expert layer of the reference's (layer 0): 8 experts, top-3 by softmax
LAYER = {"hidden_size": 32, "moe_intermediate_size": 24, "router_experts": 8,
         "num_experts_per_tok": 3, "norm_topk_prob": False,
         "routed_scaling_factor": 1, "n_shared_experts": 2,
         "held_experts": [0, 8]}


def _layer_weights(seed):
    d, f, e = 32, 24, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    w = {"L0.router": 0.2 * jax.random.normal(ks[0], (d, e)),
         "L0.ew1": 0.2 * jax.random.normal(ks[1], (e, d, f)),
         "L0.ew3": 0.2 * jax.random.normal(ks[2], (e, d, f)),
         "L0.ew2": 0.2 * jax.random.normal(ks[3], (e, f, d)),
         "L0.sw1": 0.2 * jax.random.normal(ks[4], (d, 2 * f)),
         "L0.sw3": 0.2 * jax.random.normal(ks[5], (d, 2 * f)),
         "L0.sw2": 0.2 * jax.random.normal(ks[6], (2 * f, d))}
    return w, jax.random.normal(ks[7], (3, 40, d))


def _program_share(w, x, first, count, shared=True):
    """The program's expert layer holding experts first .. first+count-1,
    with the shared expert (what every chip computes) or without."""
    layer = transformer.TopKExperts(
        num_experts=8, experts_per_token=3, hidden=24, held=(first, count),
        norm_topk=False, score="softmax", selection_bias=False,
        shared=48 if shared else 0)
    params = {"router": w["L0.router"],
              **{k: w["L0.e" + k][first:first + count]
                 for k in ("w1", "w3", "w2")}}
    if shared:
        params["shared"] = {k: {"kernel": w["L0.s" + k]}
                            for k in ("w1", "w3", "w2")}
    y, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    return y, state["intermediates"]["counters"][0]


def _reference_layer(w, x, held=(0, 8), shared=True):
    cfg = dict(LAYER, held_experts=list(held))
    first, count = held
    mine = dict(w, **{k: w[k][first:first + count]
                      for k in ("L0.ew1", "L0.ew3", "L0.ew2")})
    return jnp.stack([ref._experts(row, mine, "L0.", cfg, "float32",
                                   shared=shared) for row in x])


def test_the_shares_with_the_shared_expert_once_add_up(row_path):
    """8 experts in 4 shares of 2: the four routed partial sums that the
    chips of an expert-parallel layer compute, plus the shared expert
    **once** (every chip computes it whole), equal the uncut reference's
    whole layer; and every (token, slot) pair is counted by one share."""
    w, x = _layer_weights(0)
    whole = _reference_layer(w, x)
    routed = [_program_share(w, x, first, 2, shared=False)
              for first in (0, 2, 4, 6)]
    shared_alone = jnp.stack([ref._swiglu(
        row, w["L0.sw1"], w["L0.sw3"], w["L0.sw2"], "float32") for row in x])
    np.testing.assert_allclose(
        np.asarray(sum(y for y, _ in routed) + shared_alone),
        np.asarray(whole), atol=2e-5, rtol=2e-5)
    assert sum(int(c["moe_slots_local"]) for _, c in routed) == 3 * 40 * 3
    assert all(int(c["moe_slots_total"]) == 3 * 40 * 3 for _, c in routed)
    # what a chip really computes: its routed part and the shared expert
    # whole; four of those hold the shared expert four times
    chips = [_program_share(w, x, first, 2)[0] for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(
        np.asarray(chips[1]),
        np.asarray(routed[1][0] + shared_alone), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(sum(chips) - 3 * shared_alone), np.asarray(whole),
        atol=1e-4, rtol=1e-4)
    # one share alone is the reference's same share, not a rescaled whole
    np.testing.assert_allclose(
        np.asarray(chips[2]), np.asarray(_reference_layer(w, x, (4, 2))),
        atol=2e-5, rtol=2e-5)
    # softmax weights are not renormalised: a token's three weights sum to
    # less than one
    sel, weights = importlib.import_module(
        "tensorflowonspark_tpu.parallel.ep").route_topk(
            x.reshape(-1, 32), w["L0.router"], None, 3, norm_topk=False,
            score="softmax")
    assert float(weights.sum(-1).max()) < 0.99
    assert sel.shape == (120, 3)


def test_nothing_is_dropped_when_the_router_sends_all_to_one_expert(row_path):
    """No bias to push with: the tokens share a direction and expert 3's
    router column points along it, so every token's first choice is expert
    3.  The share holding experts 3 and 4 gets all 120 tokens in one group
    (a capacity of 1.25 S / E would keep 18) and gives the reference's
    answer, values and the gradient of the input."""
    w, x = _layer_weights(1)
    u = jnp.ones((32,)) / math.sqrt(32)
    x = x + 6.0 * u
    w["L0.router"] = w["L0.router"].at[:, 3].set(4.0 * u)
    y, counts = _program_share(w, x, 3, 2)
    assert int(counts["moe_expert_load_max_sum"]) == 3 * 40
    assert int(counts["moe_slots_local"]) >= 3 * 40
    want = _reference_layer(w, x, (3, 2))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda x: (_program_share(w, x, 3, 2)[0] ** 2).sum())(x)
    g_ref = jax.grad(lambda x: (_reference_layer(w, x, (3, 2)) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=2e-4, rtol=2e-4)


def test_a_layer_without_a_bias_leaf_has_none():
    w, x = _layer_weights(2)
    layer = transformer.TopKExperts(num_experts=8, experts_per_token=3,
                                    hidden=24, score="softmax",
                                    selection_bias=False, shared=48)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    assert set(params) == {"router", "w1", "w3", "w2", "shared"}
    assert set(params["shared"]) == {"w1", "w3", "w2"}
    with pytest.raises(ValueError, match="unknown router score"):
        transformer.TopKExperts(num_experts=8, experts_per_token=3,
                                hidden=24, score="tanh").init(
                                    jax.random.PRNGKey(0), x)


# -- the decoder's descriptions and their parameter trees ---------------------

def _paths(model, seq=16):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, seq), jnp.int32))["params"]
    return params, set(traverse_util.flatten_dict(params, sep="/"))


def test_deepseek_v2_is_registered_and_follows_the_description():
    model = get_model("deepseek_v2", config=TINY, attention="full")
    params, paths = _paths(model)
    assert set(params["block_0"]) == {"RMSNorm_0", "attention", "RMSNorm_1",
                                      "mlp"}
    assert set(params["block_1"]) == {"RMSNorm_0", "attention", "RMSNorm_1",
                                      "moe"}
    attention = params["block_2"]["attention"]
    assert set(attention) == {"q", "kv_a", "kv_norm", "kv_b", "proj"}
    assert attention["q"]["kernel"].shape == (32, 4, 24)        # 16 + 8
    assert attention["kv_a"]["kernel"].shape == (32, 24)        # 16 + 8
    assert attention["kv_norm"]["scale"].shape == (16,)
    assert attention["kv_b"]["kernel"].shape == (16, 4, 32)     # 16 + 16
    assert attention["proj"]["kernel"].shape == (64, 32)        # 4 x 16
    moe = params["block_2"]["moe"]
    assert "expert_bias" not in moe
    assert moe["w1"].shape == (4, 32, 16)                       # held only
    assert moe["router"].shape == (32, 8)                       # all 8
    assert moe["shared"]["w1"]["kernel"].shape == (32, 32)      # 2 x 16
    assert "pos_embed" not in params
    for key, value in (("q_lora_rank", 8), ("topk_method", "group_limited"),
                       ("n_group", 2)):
        with pytest.raises(ValueError, match=key):
            get_model("deepseek_v2", config=dict(TINY, **{key: value}))


def test_an_untied_model_has_a_head_leaf_and_a_tied_one_does_not():
    untied, _ = _paths(get_model("deepseek_v2", config=TINY,
                                 attention="full"))
    assert untied["head"].shape == (32, 61)
    tied, _ = _paths(get_model(
        "deepseek_v2", config=dict(TINY, tie_word_embeddings=True),
        attention="full"))
    assert "head" not in tied
    # the read-out reads the head and not the embedding
    model = get_model("deepseek_v2", config=TINY, attention="full")
    tokens = jnp.asarray(np.arange(32).reshape(2, 16) % 61, jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    doubled = dict(params, head=2.0 * params["head"])
    np.testing.assert_allclose(
        np.asarray(model.apply({"params": doubled}, tokens)),
        2.0 * np.asarray(model.apply({"params": params}, tokens)),
        rtol=1e-5, atol=1e-6)
    for name in ("transformer_lm", "lfm2_moe"):
        assert "head" not in _family_paths(name)[0]


def _family_paths(name):
    """(the program's parameter paths, the paths the family's adapter names)
    at the family's tiny configuration."""
    sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
    try:
        import _tiny
    finally:
        sys.path.pop(0)
    if name == "transformer_lm":
        from benchmark.adapters import gpt2 as adapter

        cfg = _tiny.config("gpt2_tiny")
        model = transformer.build_transformer(
            vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
            num_heads=cfg["n_head"], head_dim=cfg["n_embd"] // cfg["n_head"],
            max_seq_len=cfg["n_positions"])
        return _paths(model, cfg["n_positions"])[1], set(
            adapter.reference_names(cfg))
    tiny = {"lfm2_moe": "lfm2_moe_tiny", "deepseek_v2": "deepseek_v2_tiny"}
    cfg = _tiny.config(tiny[name])
    adapter = importlib.import_module("benchmark.adapters." + name)
    model = get_model(name, config=adapter.program_config(cfg),
                      attention="full")
    return _paths(model)[1], {path for path, _ in
                              adapter._paths(cfg).values()}


@pytest.mark.parametrize("name", ["transformer_lm", "lfm2_moe",
                                  "deepseek_v2"])
def test_each_description_yields_exactly_the_paths_its_adapter_names(name):
    """One decoder, three descriptions: the GPT-2 and LFM2 trees are what
    they were (their adapters are not edited), the new one is its own."""
    program, named = _family_paths(name)
    assert program == named


def test_flash_and_full_are_the_same_mathematics():
    """``attention="flash"`` (interpret mode here; values 16 wide under
    scores 24 wide) against ``"full"``: the same logits and the same
    gradient of every leaf."""
    full = get_model("deepseek_v2", config=TINY, attention="full")
    flash = get_model("deepseek_v2", config=TINY, attention="flash")
    tokens = jnp.asarray(np.arange(64).reshape(2, 32) % 61, jnp.int32)
    params = full.init(jax.random.PRNGKey(0), tokens)["params"]
    np.testing.assert_allclose(
        np.asarray(flash.apply({"params": params}, tokens)),
        np.asarray(full.apply({"params": params}, tokens)),
        atol=2e-5, rtol=2e-5)

    def grads(model):
        loss = transformer.loss_fn(model)
        return jax.grad(lambda p: loss(p, {"tokens": tokens},
                                       jnp.ones((2,)))[0])(params)

    got = traverse_util.flatten_dict(grads(flash), sep="/")
    want = traverse_util.flatten_dict(grads(full), sep="/")
    for path in want:
        np.testing.assert_allclose(np.asarray(got[path]),
                                   np.asarray(want[path]), atol=2e-5,
                                   rtol=2e-4, err_msg=path)


def test_trainer_counters_carry_the_softmax_routers_load():
    """The ``moe_*`` counters serve the new router as they are."""
    from test_lfm2_moe import _fit

    snap = _fit(get_model("deepseek_v2", config=TINY, attention="full"))
    pairs = 2 * 16 * 3      # batch x seq x experts a token
    assert snap["moe_layers_steps"] == 3 * 2            # 3 steps, 2 layers
    assert snap["moe_slots_total"] == 3 * 2 * pairs
    assert 0 < snap["moe_slots_local"] < snap["moe_slots_total"]
    assert snap["moe_expert_load_mean_sum"] == pytest.approx(
        snap["moe_slots_local"] / 4)                    # 4 experts held
