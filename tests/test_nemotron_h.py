"""The Nemotron-H description of ``models/transformer.py`` at tiny sizes on
the CPU: the whole model against the plain reference (logits, loss, the
gradient of every leaf), ``ops/ssd_scan`` against its ``jax.numpy`` form and
against the recurrence position by position (forward and ``jax.grad``, the
kernels in interpret mode), the sixteen chips' shares against the uncut
expert layer, a layer with one half, the parameter paths the family's
adapter names and those of the four families before it, and the ``ssd_*``
counters of ``Trainer``.  (The bf16 program against the reference under the
tiny cell's limits, and the fp8 control against them, is
``tests/benchmark/test_benchmark_references.py``.)"""

import functools
import hashlib
import importlib
import json
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

from benchmark.adapters import nemotron_h as adapter  # noqa: E402
from benchmark.references import nemotron_h as ref  # noqa: E402
from tensorflowonspark_tpu.models import get_model, transformer  # noqa: E402
from tensorflowonspark_tpu.models.families import nemotron_h as family  # noqa: E402
from tensorflowonspark_tpu.ops import ssd_scan as ssd  # noqa: E402

TINY = {"attention_bias": False, "chunk_size": 16, "conv_kernel": 4,
        "expand": 2, "head_dim": 16, "hidden_size": 32,
        "hybrid_override_pattern": "MEM*E", "intermediate_size": 24,
        "layer_norm_epsilon": 1e-5, "mamba_head_dim": 8,
        "mamba_num_heads": 4, "mlp_hidden_act": "relu2",
        "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 40, "n_group": 1,
        "n_groups": 2, "n_routed_experts": 4, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 4,
        "num_experts_per_tok": 3, "num_hidden_layers": 5,
        "num_key_value_heads": 2, "routed_scaling_factor": 2.5,
        "ssm_state_size": 16, "tie_word_embeddings": False,
        "time_step_floor": 1e-4, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_conv_bias": True,
        "vocab_size": 61, "router_experts": 8, "held_experts": [2, 4],
        "seq_len": 64, "flash_block": 32, "attention": "flash",
        "remat": False, "dtype": "float32",
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
    order: the chunked scan against the recurrence, flash against a plain
    softmax), 5e-5 of a leaf's largest element on every gradient leaf."""
    built = adapter.build(dict(TINY, attention=attention, remat=remat), 3)
    tokens = _tokens()
    want_logits, want_loss, want = _reference()
    logits = built["model"].apply({"params": built["params"]}, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=2e-5, rtol=2e-5)
    (loss, aux), grads = jax.value_and_grad(built["loss"], has_aux=True)(
        built["params"], {"tokens": tokens}, jnp.ones((2,)))
    assert float(loss) == pytest.approx(want_loss, rel=2e-5)
    assert int(aux["counters"]["ssd_layers"]) == 2
    assert int(aux["counters"]["ssd_chunks"]) == 2 * 2 * 64 // 16
    got = traverse_util.flatten_dict(grads, sep="/")
    assert set(got) == set(built["names"])
    for path, name in built["names"].items():
        scale = float(jnp.abs(want[name]).max())
        if name.endswith("expert_bias"):    # enters the choice alone
            assert scale == 0 and not np.asarray(got[path]).any()
            continue
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(got[path]).reshape(want[name].shape) / scale,
            np.asarray(want[name]) / scale, atol=5e-5, err_msg=name)


def test_the_scan_shows_in_the_model():
    """The case is what it says: with the state's part taken out (``A`` so
    negative that nothing is carried from a position to the next) the same
    weights give other logits, a thousand times float32's rounding apart."""
    built = adapter.build(TINY, 3)
    tokens = _tokens(1)
    a = built["model"].apply({"params": built["params"]}, tokens)
    forgetful = traverse_util.unflatten_dict({
        k: (jnp.full_like(v, 8.0) if k[-1] == "A_log" else v)
        for k, v in traverse_util.flatten_dict(built["params"]).items()})
    b = built["model"].apply({"params": forgetful}, tokens)
    assert float(jnp.abs(a - b).max()) > 1e-4


# -- the scan alone -----------------------------------------------------------

def _scan_operands(key, batch, seq, heads, width, groups, state):
    ks = jax.random.split(key, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    return (jax.random.normal(ks[0], (batch, seq, heads, width)), dt, dt * a,
            0.3 * jax.random.normal(ks[3], (batch, seq, groups, state)),
            0.3 * jax.random.normal(ks[4], (batch, seq, groups, state)))


def _position_by_position(x, dt, log_decay, b, c):
    """The reference's own recurrence, a row at a time."""
    return jnp.stack([ref.recurrence(x[i], dt[i], jnp.exp(log_decay[i]),
                                     b[i], c[i]) for i in range(x.shape[0])])


SCANS = {
    # the jax.numpy form at sizes off every tile: 3 heads a group, chunks of
    # 16 in rows of 80 (four carried boundaries)
    "xla": (dict(chunk=16, impl="xla"), (2, 80, 6, 8, 2, 12)),
    # the kernels in interpret mode: two heads of 64 to a 128-lane slab, two
    # groups, two chunks of 128
    "kernels_64": (dict(chunk=128, impl="pallas", interpret=True),
                   (2, 256, 4, 64, 2, 128)),
    # ... a head of 128 is a slab by itself; one group of two; three chunks
    # of 64
    "kernels_128": (dict(chunk=64, impl="pallas", interpret=True),
                    (1, 192, 2, 128, 1, 128)),
}


@pytest.mark.parametrize("case", sorted(SCANS))
def test_the_chunked_scan_is_the_recurrence(case):
    options, sizes = SCANS[case]
    operands = _scan_operands(jax.random.PRNGKey(1), *sizes)
    want = _position_by_position(*operands)
    got = ssd.ssd_scan(*operands, **options)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=1e-5)
    if case != "xla":       # and the kernels are the jax.numpy form
        plain = ssd.ssd_scan(*operands, chunk=options["chunk"], impl="xla")
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(plain) / scale, atol=1e-5)


@pytest.mark.parametrize("case", sorted(SCANS))
def test_the_chunked_backward_is_the_recurrences_gradient(case):
    """``jax.grad`` through the ``custom_vjp`` (the reversed kernel, the
    states as the forward wrote them) against ``jax.grad`` of the recurrence,
    every operand: x, dt, the decay's logarithm, B, C."""
    options, sizes = SCANS[case]
    operands = _scan_operands(jax.random.PRNGKey(2), *sizes)
    weigh = jax.random.normal(jax.random.PRNGKey(3), operands[0].shape)
    every = tuple(range(5))
    want = jax.grad(lambda *a: (_position_by_position(*a) * weigh).sum(),
                    argnums=every)(*operands)
    got = jax.grad(lambda *a: (ssd.ssd_scan(*a, **options) * weigh).sum(),
                   argnums=every)(*operands)
    for name, g, w in zip(("x", "dt", "log_decay", "b", "c"), got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5,
                                   err_msg=name)


def test_a_row_that_is_no_multiple_of_the_chunk_is_refused():
    operands = _scan_operands(jax.random.PRNGKey(1), 1, 40, 2, 8, 1, 8)
    for impl in ("xla", "pallas"):
        with pytest.raises(ValueError, match="40 positions .* chunks of 16"):
            ssd.ssd_scan(*operands, chunk=16, impl=impl)
    with pytest.raises(ValueError, match="128-lane tiles"):
        ssd.ssd_scan(*operands, chunk=8, impl="pallas", interpret=True)
    with pytest.raises(ValueError, match="unknown ssd_scan impl"):
        ssd.ssd_scan(*operands, chunk=8, impl="triton")


def test_the_checkpoint_policy_keeps_the_kernels_results():
    """Under ``save_only_these_names(*KEPT)`` the recomputed pass holds no
    scan kernel: the backward kernel reads the kept output and states."""
    operands = _scan_operands(jax.random.PRNGKey(1), 1, 256, 2, 64, 1, 128)

    def loss(*a):
        return ssd.ssd_scan(*a, chunk=128, impl="pallas",
                            interpret=True).sum()

    kept = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(*ssd.KEPT))
    text = str(jax.make_jaxpr(jax.grad(kept))(*operands))
    assert text.count("name=ssd_scan_fwd") == 1
    assert text.count("name=ssd_scan_bwd") == 1
    again = str(jax.make_jaxpr(jax.grad(jax.checkpoint(loss)))(*operands))
    assert again.count("name=ssd_scan_fwd") == 2


# -- the chip's share ---------------------------------------------------------

def test_the_shares_of_the_sixteen_chips_add_up_to_the_uncut_layer(row_path):
    """128 is 32 here: 32 experts in 16 shares of 2, top-3 by sigmoid scores
    with a selection bias, renormalised, times 2.5, beside a shared expert.
    The routed partial sums of the sixteen chips plus the shared expert once
    equal the uncut reference's whole layer, and every (token, slot) pair is
    counted by one share."""
    d, f, fs, e = 32, 24, 40, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 7)
    w = {"L0.router": 0.3 * jax.random.normal(ks[0], (d, e)),
         "L0.expert_bias": 0.1 * jax.random.normal(ks[1], (e,)),
         "L0.ew1": 0.2 * jax.random.normal(ks[2], (e, d, f)),
         "L0.ew2": 0.2 * jax.random.normal(ks[3], (e, f, d)),
         "L0.sw1": 0.2 * jax.random.normal(ks[4], (d, fs)),
         "L0.sw2": 0.2 * jax.random.normal(ks[5], (fs, d))}
    x = jax.random.normal(ks[6], (2, 40, d))
    cfg = dict(TINY, router_experts=e, held_experts=[0, e])
    whole = jnp.stack([ref._experts(row, w, "L0.", cfg, "float32")
                       for row in x])
    total, local = 0.0, 0
    for first in range(0, e, 2):
        layer = transformer.TopKExperts(
            num_experts=e, experts_per_token=3, hidden=f, held=(first, 2),
            norm_topk=True, routed_scaling=2.5, score="sigmoid",
            selection_bias=True, act="relu2")
        params = {"router": w["L0.router"],
                  "expert_bias": w["L0.expert_bias"],
                  "w1": w["L0.ew1"][first:first + 2],
                  "w2": w["L0.ew2"][first:first + 2]}
        y, state = layer.apply({"params": params}, x,
                               mutable=["intermediates"])
        counts = state["intermediates"]["counters"][0]
        assert int(counts["moe_slots_total"]) == 2 * 40 * 3
        total, local = total + y, local + int(counts["moe_slots_local"])
        if first == 6:      # one share alone is the reference's same share
            mine = dict(w, **{k: w[k][first:first + 2]
                              for k in ("L0.ew1", "L0.ew2")})
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(jnp.stack([ref._experts(
                    row, mine, "L0.", dict(cfg, held_experts=[first, 2]),
                    "float32", shared=False) for row in x])),
                atol=2e-5, rtol=2e-5)
    shared = transformer.Relu2(fs).apply(
        {"params": {"w1": {"kernel": w["L0.sw1"]},
                    "w2": {"kernel": w["L0.sw2"]}}}, x)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               atol=5e-5, rtol=5e-5)
    assert local == 2 * 40 * 3


# -- the description, its tree, its counters ----------------------------------

def test_nemotron_h_is_registered_and_follows_the_description():
    spec = family.nemotron_h_spec(adapter.program_config(TINY))
    assert len(spec.layers) == 5 and not spec.tied_readout
    assert [(layer.op, layer.ff) for layer in spec.layers] == [
        ("mamba2", "none"), ("none", "experts"), ("mamba2", "none"),
        ("attention", "none"), ("none", "experts")]
    for layer in spec.layers:
        assert (layer.positions, layer.qk_norm, layer.norm) == (
            "none", False, "rmsnorm")
        assert (layer.ssm_heads, layer.ssm_head_dim, layer.ssm_state,
                layer.ssm_groups, layer.ssm_chunk, layer.conv_kernel) == (
                    4, 8, 16, 2, 16, 4)
        assert (layer.router_score, layer.selection_bias, layer.norm_topk,
                layer.routed_scaling, layer.shared_size, layer.expert_act) \
            == ("sigmoid", True, True, 2.5, 40, "relu2")
        assert layer.held_experts == (2, 4) and layer.num_experts == 8
    assert spec.layers[0] is spec.layers[2]
    dense = family.nemotron_h_spec(dict(
        adapter.program_config(TINY), hybrid_override_pattern="M-M*E"))
    assert (dense.layers[1].op, dense.layers[1].ff) == ("none", "relu2")
    for key, value in (("hybrid_override_pattern", "MEMXE"),
                       ("n_group", 2), ("mlp_hidden_act", "silu"),
                       ("bias", None), ("use_conv_bias", False),
                       ("sliding_window", 64)):
        wrong = {"mlp_bias": True} if key == "bias" else {key: value}
        with pytest.raises(ValueError, match=key):
            family.nemotron_h_spec(dict(adapter.program_config(TINY),
                                             **wrong))
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.nemotron_h_spec(dict(adapter.program_config(TINY),
                                         num_hidden_layers=6))


def test_the_description_yields_exactly_the_paths_its_adapter_names():
    model = get_model("nemotron_h", config=adapter.program_config(TINY))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 64), jnp.int32))["params"]
    flat = traverse_util.flatten_dict(params, sep="/")
    assert set(flat) == {path for path, _ in adapter._paths(TINY).values()}
    assert "block_0/mamba/A_log" in flat and "head" in flat
    assert not [p for p in flat if "w3" in p or "pos_embed" in p
                or "q_norm" in p or "RMSNorm_1" in p]
    for leaf in ("A_log", "dt_bias", "D"):      # float32 leaves, a head each
        assert flat["block_2/mamba/" + leaf].shape == (4,)
        assert flat["block_2/mamba/" + leaf].dtype == jnp.float32


@pytest.mark.parametrize("halves", ["op", "ff", "both"])
def test_a_layer_with_one_half_has_one_norm_and_one_residual(halves):
    spec = transformer.LayerSpec(
        op="none" if halves == "ff" else "attention",
        ff="none" if halves == "op" else "swiglu", norm="rmsnorm",
        positions="none", num_heads=2, head_dim=8, num_kv_heads=1,
        ff_size=24)
    block = transformer.Block(spec=spec)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 16))
    params = block.init(jax.random.PRNGKey(1), x)["params"]
    assert sorted(params) == {
        "op": ["RMSNorm_0", "attention"], "ff": ["RMSNorm_0", "mlp"],
        "both": ["RMSNorm_0", "RMSNorm_1", "attention", "mlp"]}[halves]
    # one rsqrt a norm, and the block is its residual adds and nothing more
    text = str(jax.make_jaxpr(lambda p: block.apply({"params": p}, x))(
        params))
    assert text.count(" rsqrt ") == (2 if halves == "both" else 1)

    def norm(name, x):
        return transformer._norm("rmsnorm", 1e-6, jnp.float32).apply(
            {"params": params[name]}, x)

    want, norms = x, iter(("RMSNorm_0", "RMSNorm_1"))
    if halves != "ff":
        want = want + transformer.Attention(
            2, 8, "full", num_kv_heads=1).apply(
                {"params": params["attention"]}, norm(next(norms), want))
    if halves != "op":
        want = want + transformer.SwiGLU(24).apply(
            {"params": params["mlp"]}, norm(next(norms), want))
    np.testing.assert_allclose(np.asarray(block.apply({"params": params}, x)),
                               np.asarray(want), atol=1e-6)


def test_a_layer_needs_a_half():
    with pytest.raises(ValueError, match="neither op nor ff"):
        transformer.LayerSpec(op="none", ff="none")
    with pytest.raises(ValueError, match="window"):
        transformer.LayerSpec(op="mamba2", ff="none", window=8)


# (paths, shapes) of each family's tiny rehearsal configuration, as the
# parent of PR 43 built them (sha256 of the sorted list's repr, 16 digits):
# the one-half layers and the experts' second form changed none of them
TREES = {"lfm2_moe": ("lfm2_moe_tiny", "9bb7c7bbdc7fb8a4", 33),
         "deepseek_v2": ("deepseek_v2_tiny", "b36e22b8382ae4b5", 41),
         "keye_vl2": ("keye_vl2_tiny", "96515ec82cae822f", 37),
         "mellum2": ("mellum2_tiny", "44f2fdb4b4d1f299", 51)}


@pytest.mark.parametrize("family", sorted(TREES))
def test_the_families_before_it_keep_their_parameter_trees(family):
    name, digest, count = TREES[family]
    with open(os.path.join(ROOT, "tests", "benchmark", "tiny", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    other = importlib.import_module("benchmark.adapters." + family)
    config = other.program_config(cfg) if hasattr(
        other, "program_config") else cfg
    model = get_model(family, config=config, attention=cfg["attention"],
                      remat=True, dtype=cfg["dtype"])
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((cfg["batch_size"], cfg["seq_len"]), jnp.int32))["params"]
    flat = traverse_util.flatten_dict(shapes, sep="/")
    paths = sorted((k, tuple(v.shape)) for k, v in flat.items())
    assert len(paths) == count
    assert hashlib.sha256(repr(paths).encode()).hexdigest()[:16] == digest
    # a two-half layer keeps both norms under flax's own names
    assert "block_0/RMSNorm_1/scale" in flat
    assert set(flat) == {path for path, _ in other._paths(cfg).values()}


def test_trainer_counters_carry_the_scans_chunks():
    from test_lfm2_moe import _fit

    snap = _fit(get_model("nemotron_h", config=adapter.program_config(TINY),
                          attention="full"), seq=64)
    assert snap["ssd_layers"] == 3 * 2              # 3 steps, 2 Mamba layers
    assert snap["ssd_chunks"] == 3 * 2 * 2 * 64 // 16       # batch 2
    # [batch, heads, chunks, head_dim, state] float32 a layer and step
    assert snap["ssd_state_bytes"] == 3 * 2 * (2 * 4 * 4 * 8 * 16 * 4)
    assert snap["moe_layers_steps"] == 3 * 2
    assert not [k for k in snap if k.startswith(("swa_", "dsa_", "flash_"))]
