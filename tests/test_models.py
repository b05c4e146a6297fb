"""Model zoo tests on the 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu import models
from tensorflowonspark_tpu.models import mnist, resnet, transformer, unet
from tensorflowonspark_tpu.parallel import build_mesh, batch_sharding
from tensorflowonspark_tpu.train import Trainer


def test_registry():
    assert set(models._REGISTRY) >= {
        "mnist_cnn", "resnet50", "resnet56_cifar", "unet", "transformer_lm"}
    with pytest.raises(KeyError, match="unknown model"):
        models.get_model("nope")


@pytest.mark.parametrize("family, config_name", [
    ("lfm2_moe", "lfm2_8b_a1b_ep4"), ("deepseek_v2", "deepseek_v2_lite_ep8"),
    ("keye_vl2", "keye_vl2_30b_a3b_ep8"),
    ("mellum2", "mellum2_12b_a2p5b_ep8"),
    ("nemotron_h", "nemotron3_nano_30b_a3b_ep16"),
    ("olmo_hybrid", "olmo_hybrid_7b_tp2")])
def test_a_registered_family_is_the_one_decoder_under_its_description(
        family, config_name):
    """``get_model(<family>, config=...)`` is a ``TransformerLM`` whose
    description is the family's spec function's of the same configuration
    (the benchmark's, as its adapter hands it over), at the run-time choices
    asked for."""
    import importlib

    from chip_compile import _benchmark_config

    cfg = _benchmark_config(config_name)
    config = importlib.import_module(
        "benchmark.adapters." + family).program_config(cfg)
    spec_fn = getattr(importlib.import_module(
        "tensorflowonspark_tpu.models.families." + family), family + "_spec")
    model = models.get_model(family, config=config, attention="full",
                             remat=True, dtype="bfloat16")
    assert isinstance(model, transformer.TransformerLM)
    assert model.spec == spec_fn(config)
    assert len(model.spec.layers) == cfg["num_hidden_layers"]
    assert (model.attention, model.remat, model.dtype, model.mesh) == (
        "full", True, jnp.bfloat16, None)
    assert models.get_model(family, config=config).attention == "flash"


class TestMnist:
    def test_forward_shapes(self):
        model = models.get_model("mnist_cnn")
        params = model.init(jax.random.PRNGKey(0),
                            jnp.ones((2, 28, 28, 1)))["params"]
        logits = model.apply({"params": params}, jnp.ones((2, 28, 28, 1)))
        assert logits.shape == (2, 10)

    def test_trains_on_synthetic_digits(self):
        """A couple of steps reduce loss on a fixed synthetic batch."""
        mesh = build_mesh()
        model = models.get_model("mnist_cnn")
        rng = np.random.RandomState(0)
        images = rng.rand(16, 28, 28, 1).astype(np.float32)
        labels = rng.randint(0, 10, size=(16,))
        sharding = batch_sharding(mesh)
        batch = {"image": jax.device_put(images, sharding),
                 "label": jax.device_put(labels, sharding)}
        params = model.init(jax.random.PRNGKey(0), images[:1])["params"]
        tr = Trainer(mnist.loss_fn(model), params, optax.adam(1e-3),
                     mesh=mesh, batch_size=16)
        first, _ = tr.step(batch)
        for _ in range(20):
            last, aux = tr.step(batch)
        assert float(last) < float(first)


class TestResNet:
    @pytest.mark.slow
    def test_resnet56_cifar_forward(self):
        model = models.get_model("resnet56_cifar")
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.ones((1, 32, 32, 3)))
        logits = model.apply(variables, jnp.ones((2, 32, 32, 3)))
        assert logits.shape == (2, 10)
        assert "batch_stats" in variables

    @pytest.mark.slow
    def test_resnet50_forward_tiny(self):
        model = models.get_model("resnet50", num_classes=5, dtype="float32")
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.ones((1, 64, 64, 3)))
        logits = model.apply(variables, jnp.ones((1, 64, 64, 3)))
        assert logits.shape == (1, 5)

    @pytest.mark.slow
    def test_train_step_updates_batch_stats(self):
        mesh = build_mesh()
        model = models.get_model("resnet56_cifar")
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.ones((1, 32, 32, 3)))
        rng = np.random.RandomState(0)
        sharding = batch_sharding(mesh)
        batch = {
            "image": jax.device_put(
                rng.rand(8, 32, 32, 3).astype(np.float32), sharding),
            "label": jax.device_put(rng.randint(0, 10, (8,)), sharding),
        }
        tr = Trainer(resnet.loss_fn(model), variables["params"],
                     optax.sgd(0.1), mesh=mesh,
                     extra_state=variables["batch_stats"], batch_size=8)
        before = np.asarray(jax.tree_util.tree_leaves(
            tr.state.extra)[0]).copy()
        tr.step(batch)
        after = np.asarray(jax.tree_util.tree_leaves(tr.state.extra)[0])
        assert not np.allclose(before, after)  # running stats moved


class TestUnet:
    @pytest.mark.slow
    def test_forward_and_loss(self):
        mesh = build_mesh()
        model = models.get_model("unet", num_classes=3)
        x = jnp.ones((2, 64, 64, 3))
        params = model.init(jax.random.PRNGKey(0), x)["params"]
        logits = model.apply({"params": params}, x)
        assert logits.shape == (2, 64, 64, 3)
        loss = unet.loss_fn(model)
        batch = {"image": x, "mask": jnp.zeros((2, 64, 64), jnp.int32)}
        val, aux = loss(params, batch, jnp.ones((2,)))
        assert np.isfinite(float(val))


class TestTransformer:
    @pytest.mark.parametrize("attention,mesh_spec", [
        ("full", None),
        ("ring", {"seq": 8}),
        ("ulysses", {"data": 2, "seq": 4}),
    ])
    def test_forward_modes_agree(self, attention, mesh_spec):
        mesh = build_mesh(mesh_spec) if mesh_spec else None
        kwargs = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                      max_seq_len=32)
        model = models.get_model("transformer_lm", attention=attention,
                                 mesh=mesh, **kwargs)
        ref = models.get_model("transformer_lm", attention="full", **kwargs)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 64, (2, 32)))
        params = ref.init(jax.random.PRNGKey(0), tokens)["params"]
        want = ref.apply({"params": params}, tokens)
        got = model.apply({"params": params}, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("attention,mesh_spec", [
        ("ring", {"seq": 8}),
        ("ulysses", {"data": 2, "seq": 4}),
    ])
    def test_sequence_parallel_training_step(self, attention, mesh_spec):
        """Training (loss+grad) must work in ring/ulysses mode: the loss keeps
        the full sequence length divisible by the seq axis."""
        mesh = build_mesh(mesh_spec)
        model = models.get_model("transformer_lm", vocab_size=32,
                                 num_layers=1, num_heads=4, head_dim=8,
                                 max_seq_len=32, attention=attention,
                                 mesh=mesh)
        tokens = np.random.RandomState(0).randint(0, 32, (4, 32))
        batch = {"tokens": jax.device_put(tokens, batch_sharding(mesh))}
        params = model.init(jax.random.PRNGKey(0),
                            jnp.asarray(tokens))["params"]
        tr = Trainer(transformer.loss_fn(model), params, optax.adam(1e-2),
                     mesh=mesh, batch_size=4)
        loss1, _ = tr.step(batch)
        loss2, _ = tr.step(batch)
        assert np.isfinite(float(loss1)) and float(loss2) < float(loss1)

    def test_remat_is_equivalent(self):
        """remat=True recomputes block activations in backward — outputs
        AND gradients must match the stored-activation model exactly
        (same math, different schedule)."""
        kwargs = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                      max_seq_len=32)
        base = models.get_model("transformer_lm", **kwargs)
        rem = models.get_model("transformer_lm", remat=True, **kwargs)
        tokens = jnp.asarray(
            np.random.RandomState(1).randint(0, 64, (2, 32)))
        params = base.init(jax.random.PRNGKey(0), tokens)["params"]
        np.testing.assert_allclose(
            np.asarray(rem.apply({"params": params}, tokens)),
            np.asarray(base.apply({"params": params}, tokens)),
            atol=1e-5, rtol=1e-5)
        mask = jnp.ones((2,), jnp.float32)
        g_base = jax.grad(
            lambda p: transformer.loss_fn(base)(p, {"tokens": tokens},
                                                mask)[0])(params)
        g_rem = jax.grad(
            lambda p: transformer.loss_fn(rem)(p, {"tokens": tokens},
                                               mask)[0])(params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
            g_base, g_rem)

    def test_lm_loss_decreases(self):
        mesh = build_mesh()
        model = models.get_model("transformer_lm", vocab_size=32,
                                 num_layers=1, num_heads=2, head_dim=8,
                                 max_seq_len=16)
        tokens = np.tile(np.arange(16, dtype=np.int32), (8, 1))
        batch = {"tokens": jax.device_put(tokens, batch_sharding(mesh))}
        params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
        tr = Trainer(transformer.loss_fn(model), params, optax.adam(1e-2),
                     mesh=mesh, batch_size=8)
        first, _ = tr.step(batch)
        for _ in range(30):
            last, _ = tr.step(batch)
        assert float(last) < float(first) * 0.5


class TestMoE:
    """Switch-style MoE FFN: dense one-hot dispatch/combine (no gathers),
    capacity drops ride the residual, load-balance aux folds into the loss,
    and expert weights shard over the mesh's expert axis."""

    def _model(self, **kw):
        from tensorflowonspark_tpu.models import transformer

        return transformer.build_transformer(
            vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
            max_seq_len=16, mlp="moe", num_experts=4, **kw)

    def test_forward_and_aux_loss(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from tensorflowonspark_tpu.models import transformer

        model = self._model()
        tokens = jnp.asarray(np.arange(4 * 16).reshape(4, 16) % 64, jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        # expert weights exist with the stacked [E, ...] layout
        w1 = params["block_0"]["moe"]["w1"]
        assert w1.shape[0] == 4
        loss = transformer.loss_fn(model)
        mask = jnp.ones((4,), jnp.float32)
        l, aux = jax.jit(lambda p: loss(p, {"tokens": tokens}, mask))(params)
        assert np.isfinite(float(l))
        # 2 MoE blocks each sow one aux term; folded value is finite
        assert np.isfinite(float(aux["moe_aux_loss"]))

    def test_training_step_decreases_loss(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from tensorflowonspark_tpu.models import transformer

        model = self._model()
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        loss = transformer.loss_fn(model)
        opt = optax.adam(1e-2)
        opt_state = opt.init(params)
        mask = jnp.ones((8,), jnp.float32)

        @jax.jit
        def step(params, opt_state):
            (l, _), g = jax.value_and_grad(loss, has_aux=True)(
                params, {"tokens": tokens}, mask)
            updates, opt_state = opt.update(g, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, l

        first = None
        for _ in range(15):
            params, opt_state, l = step(params, opt_state)
            first = first if first is not None else float(l)
        assert float(l) < first, (float(l), first)

    def test_expert_parallel_sharding_matches_replicated(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from tensorflowonspark_tpu.parallel import build_mesh, tp_param_shardings

        mesh = build_mesh({"data": 2, "expert": 4})
        model = self._model()
        tokens = jnp.asarray(np.arange(4 * 16).reshape(4, 16) % 64, jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]

        def fwd(p, t):
            return model.apply({"params": p}, t)

        base = jax.jit(fwd)(params, tokens)
        # shard ONLY the expert-stacked weights over the expert axis; the
        # axis-generic TP API + rules express expert parallelism directly
        shardings = tp_param_shardings(
            params, mesh, axis="expert",
            rules=[("moe/(w1|w2|b1|b2)", 0), ("", None)])
        ep_params = jax.device_put(params, shardings)
        specs = [str(s.spec) for s in jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda x: x.sharding, ep_params))]
        assert any("expert" in s for s in specs)
        with mesh:
            out = jax.jit(fwd)(ep_params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=2e-3, atol=2e-3)

    def test_ep_mode_shard_map_matches_gspmd(self):
        """ep_mode="shard_map" (the explicit all_to_all schedule inside the
        flax layer) must match the default GSPMD layer bit-for-bit-ish:
        same checkpoint layout, same forward, same folded aux loss."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from tensorflowonspark_tpu.models import transformer
        from tensorflowonspark_tpu.parallel import build_mesh

        mesh = build_mesh({"data": 4, "expert": 2})
        dense = self._model()
        ep = self._model(ep_mode="shard_map", mesh=mesh)
        tokens = jnp.asarray(np.arange(4 * 16).reshape(4, 16) % 64,
                             jnp.int32)
        params = dense.init(jax.random.PRNGKey(0), tokens)["params"]
        # identical param trees (checkpoints interchangeable)
        ep_params = ep.init(jax.random.PRNGKey(0), tokens)["params"]
        assert (jax.tree_util.tree_structure(params)
                == jax.tree_util.tree_structure(ep_params))
        loss = transformer.loss_fn(dense)
        ep_loss = transformer.loss_fn(ep)
        mask = jnp.ones((4,), jnp.float32)
        l0, aux0 = loss(params, {"tokens": tokens}, mask)
        with mesh:
            l1, aux1 = jax.jit(
                lambda p: ep_loss(p, {"tokens": tokens}, mask))(params)
        np.testing.assert_allclose(float(l1), float(l0), rtol=2e-5)
        np.testing.assert_allclose(float(aux1["moe_aux_loss"]),
                                   float(aux0["moe_aux_loss"]), rtol=2e-5)


class TestS2dStem:
    def test_stem_kernel_transform_exact(self):
        """The (4,4,12,F) s2d kernel must reproduce the 7x7/s2 SAME conv
        exactly (fp32, random input) — lone stem conv, no BN/pool."""
        import jax
        from jax import lax

        rng = np.random.RandomState(0)
        x = rng.rand(2, 32, 32, 3).astype(np.float32)
        k7 = rng.rand(7, 7, 3, 8).astype(np.float32) - 0.5

        ref = lax.conv_general_dilated(
            x, k7, window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        k4 = resnet.s2d_stem_kernel(k7)
        y = resnet.space_to_depth(jnp.asarray(x), 2)
        got = lax.conv_general_dilated(
            np.asarray(y), k4, window_strides=(1, 1),
            padding=((1, 2), (1, 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_s2d_model_matches_conv7_model(self):
        """Full ResNet forward: transplanting the transformed stem kernel
        into the s2d model reproduces the conv7 model's logits."""
        import jax

        m7 = models.get_model("resnet50", num_classes=5, dtype="float32",
                              blocks_per_stage=1)
        ms = models.get_model("resnet50", num_classes=5, dtype="float32",
                              blocks_per_stage=1, stem="s2d")
        x = np.random.RandomState(1).rand(2, 64, 64, 3).astype(np.float32)
        v7 = m7.init(jax.random.PRNGKey(0), x)
        vs_params = dict(v7["params"])
        stem7 = v7["params"]["Conv_0"]["kernel"]
        vs_params["Conv_0"] = {"kernel": jnp.asarray(
            resnet.s2d_stem_kernel(stem7))}
        out7 = m7.apply({"params": v7["params"],
                         "batch_stats": v7["batch_stats"]}, x)
        outs = ms.apply({"params": vs_params,
                         "batch_stats": v7["batch_stats"]}, x)
        np.testing.assert_allclose(np.asarray(outs), np.asarray(out7),
                                   rtol=1e-4, atol=1e-4)
