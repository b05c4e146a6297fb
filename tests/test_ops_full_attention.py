"""The flash kernels inside the decoder (interpret mode on the CPU mesh):
``attention="flash"`` against ``"full"``, under ulysses, mapped over a mesh's
shards, and the rule by which ``"full"`` takes the kernels
(``ops.flash_attention.full_attention_block``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops import flash_attention
from tensorflowonspark_tpu.parallel import ring

from test_ops import _qkv


def test_transformer_flash_mode_matches_full():
    """attention="flash" on the LM produces the same logits as "full"
    (checkpoints interchangeable across attention modes)."""
    from tensorflowonspark_tpu.models import transformer

    tokens = jnp.asarray(np.arange(2 * 64).reshape(2, 64) % 32, jnp.int32)
    full = transformer.build_transformer(
        vocab_size=32, num_layers=2, num_heads=2, head_dim=16,
        max_seq_len=64, attention="full")
    flash = transformer.build_transformer(
        vocab_size=32, num_layers=2, num_heads=2, head_dim=16,
        max_seq_len=64, attention="flash")
    params = full.init(jax.random.PRNGKey(0), tokens)["params"]
    base = full.apply({"params": params}, tokens)
    got = flash.apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_with_flash_inner(causal):
    """Sequence parallelism (Ulysses a2a) composed with the pallas kernel:
    per-device local attention runs flash, output matches the reference."""
    from tensorflowonspark_tpu.parallel import build_mesh

    q, k, v = _qkv(batch=2, seq=128, heads=4, dim=16, seed=2)
    mesh = build_mesh({"data": 2, "seq": 4})
    want = ring.reference_attention(q, k, v, causal=causal)
    got = ring.ulysses_attention(q, k, v, mesh, causal=causal, impl="flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_on_a_mesh_maps_itself_per_shard():
    """The compiler cannot partition a Mosaic kernel, so with ``mesh=`` the
    op runs per shard (batch over data, heads over tensor): same values and
    gradients as the reference."""
    from tensorflowonspark_tpu.parallel import build_mesh

    q, k, v = _qkv(batch=4, seq=64, heads=4, dim=16, seed=5)
    mesh = build_mesh({"data": 2, "tensor": 2},
                      devices=jax.devices()[:4])

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            mesh=mesh)
        return (o ** 2).sum(), o

    def loss_ref(q, k, v):
        o = ring.reference_attention(q, k, v, causal=True)
        return (o ** 2).sum(), o

    (_, got), g_flash = jax.jit(jax.value_and_grad(
        loss_flash, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, want), g_ref = jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg="d{} mismatch".format(name))


@pytest.mark.parametrize("platform, interpret", [("tpu", False),
                                                 ("cpu", True)])
def test_interpret_default_follows_the_platform(monkeypatch, platform,
                                                interpret):
    """A process whose platform is ``tpu`` never gets interpret mode
    unasked; interpreting is for the CPU tests."""
    import importlib

    fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")

    class Device:
        device_kind = "whatever it says"

    Device.platform = platform
    monkeypatch.setattr(jax, "devices", lambda *a: [Device()])
    assert fa._default_interpret() is interpret


class _Shape:
    """Stands in for an array where only ``shape`` is read."""

    def __init__(self, *shape):
        self.shape = shape


@pytest.mark.parametrize("platform, seq, width, block", [
    ("tpu", 1024, 64, 512), ("tpu", 8192, 192, 512), ("tpu", 32768, 128, 512),
    ("tpu", 768, 64, 256), ("tpu", 384, 64, 128), ("tpu", 1000, 64, None),
    ("tpu", 64, 64, None), ("tpu", 1024, 512, 256), ("tpu", 1024, 2048, None),
    ("cpu", 1024, 64, None), ("cpu", 384, 64, None), ("cpu", 64, 64, None)])
def test_full_attention_takes_the_kernels_where_a_row_tiles(
        monkeypatch, platform, seq, width, block):
    """The one rule: on a TPU the largest of 512, 256, 128 that divides the
    row (and whose operands the kernels were compiled with); no block, so
    the plain contraction, for a row that does not tile and for every row
    off the TPU."""
    import importlib

    fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_default_interpret", lambda: platform != "tpu")
    q = _Shape(4, seq, 16, width)
    assert fa.full_attention_block(q, q, _Shape(4, seq, 16, 64)) == block


@pytest.mark.parametrize("axes, batch, heads, kv_heads, block", [
    ({"data": 4}, 8, 16, 16, 512), ({"data": 2, "tensor": 2}, 8, 16, 4, 512),
    ({"fsdp": 2, "tensor": 2}, 8, 16, 2, 512),
    ({"data": 4}, 6, 16, 16, None),               # the batch does not divide
    ({"data": 2, "tensor": 2}, 8, 16, 1, None),   # nor one KV head over two
    ({"data": 2, "tensor": 2}, 8, 3, 3, None),
    ({"data": 2, "seq": 2}, 8, 16, 16, None),     # sequence parallel: GSPMD
    ({"data": 2, "expert": 2}, 8, 16, 16, None),
    ({"data": 1, "seq": 1}, 3, 5, 5, 512)])       # one device: no mapping
def test_full_attention_on_a_mesh_asks_what_the_mapping_asks(
        monkeypatch, axes, batch, heads, kv_heads, block):
    """On a mesh of more than one device the rule also asks what
    ``flash_attention(mesh=)`` maps by: batch over data/fsdp, both head
    counts over tensor, no other axis in use.  Never an error."""
    import importlib

    fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)

    class MeshLike:
        shape = axes
        size = int(np.prod(list(axes.values())))

    q, k = _Shape(batch, 1024, heads, 64), _Shape(batch, 1024, kv_heads, 64)
    assert fa.full_attention_block(q, k, k, MeshLike()) == block


def _decoder(layer, mesh=None):
    from tensorflowonspark_tpu.models import transformer

    spec = transformer.DecoderSpec(
        vocab_size=48, hidden_size=32, layers=(layer,) * 2,
        learned_positions=384 if layer.positions == "learned" else 0,
        norm=layer.norm)
    return transformer.TransformerLM(spec=spec, mesh=mesh)   # "full"


def _full_attention_layers():
    from tensorflowonspark_tpu.models import transformer

    grouped = dict(norm="rmsnorm", positions="rope", num_heads=4, head_dim=8,
                   num_kv_heads=2, qk_norm=True, ff="swiglu", ff_size=64)
    return {
        "gpt2": transformer.gpt2_layer(4, 8),
        "grouped_kv": transformer.LayerSpec(**grouped),
        "window": transformer.LayerSpec(window=100, **grouped),
        "latent": transformer.LayerSpec(
            op="mla", norm="rmsnorm", positions="rope", num_heads=2,
            head_dim=24, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=12,
            rope_pairing="interleaved", attn_scale=0.17, ff="swiglu",
            ff_size=64),
    }


@pytest.mark.parametrize("form", ["gpt2", "grouped_kv", "window", "latent",
                                  "gpt2_on_a_mesh"])
def test_full_attention_through_the_kernels_is_the_plain_contraction(
        monkeypatch, form):
    """``attention="full"`` with the rule steered on (the kernels in
    interpret mode, blocks of 128 over rows of 384: six tiles a head)
    against the plain contraction: the loss and every gradient leaf, for the
    fused GPT-2 form, grouped KV heads (handed over unrepeated), a window and
    the latent form with its scale and its two widths, and the GPT-2 form
    on a mesh (the kernels mapped per shard: batch over ``data``, heads over
    ``tensor``); the counters ``flash_*`` (and a window's ``swa_*``) come
    out exactly when the kernels ran."""
    import importlib

    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.parallel import build_mesh

    fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
    mesh = None
    if form == "gpt2_on_a_mesh":
        form, mesh = "gpt2", build_mesh({"data": 2, "tensor": 2},
                                        devices=jax.devices()[:4])
    model = _decoder(_full_attention_layers()[form], mesh)
    tokens = jnp.asarray(
        np.random.RandomState(3).randint(0, 48, (2, 384)), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    loss = jax.value_and_grad(transformer.loss_fn(model), has_aux=True)
    batch, mask = {"tokens": tokens}, jnp.ones((2,))

    (want, plain_aux), want_grads = loss(params, batch, mask)
    assert "counters" not in plain_aux      # nothing else here counts

    seen = []

    def steered(q, k, v, mesh=None):
        assert mesh is model.mesh
        seen.append((q.shape, k.shape, v.shape))
        return fa.row_block(q.shape[1], max(q.shape[3], v.shape[3]))

    monkeypatch.setattr(fa, "full_attention_block", steered)
    (got, aux), grads = loss(params, batch, mask)
    heads, kv_heads = {"gpt2": (4, 4), "latent": (2, 2)}.get(form, (4, 2))
    assert seen and all(q[2] == heads and k[2] == kv_heads == v[2]
                        for q, k, v in seen)
    # two layers x 2 rows x heads x the six causal tiles of three blocks (a
    # window of 100 keys keeps five of them, in three runs of two steps)
    tiles = 5 if form == "window" else 6
    counters = {k: int(v) for k, v in aux["counters"].items()}
    assert {k: v for k, v in counters.items() if k.startswith("flash_")} == {
        "flash_grid_steps": 2 * 2 * heads * 6,
        "flash_tiles_computed": 2 * 2 * heads * tiles}
    assert ("swa_tiles_computed" in counters) == (form == "window")
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        ref = np.asarray(flat_want[path])
        np.testing.assert_allclose(
            np.asarray(leaf), ref, rtol=2e-3,
            atol=2e-4 * max(float(np.abs(ref).max()), 1e-6),
            err_msg=jax.tree_util.keystr(path))
