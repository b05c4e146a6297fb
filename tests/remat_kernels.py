"""A rematerialised block keeps its kernels' results: the check, shared by
``tests/test_remat_kernels.py`` and ``tests/test_remat_kernels_indexed.py``
(the case with a learned index takes as long as three of the others, so it
has a file, and under ``--dist loadfile`` a worker, of its own)."""

import numpy as np

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import models
from tensorflowonspark_tpu.models import transformer
from tensorflowonspark_tpu.parallel import build_mesh


def _flash_lm(case, remat):
    """``(model, tokens)``: a tiny decoder of each form of attention that
    runs the flash kernels, from the families' own test files."""
    seq = 256 if case == "indexed" else 32   # an index wants 128-key runs
    if case in ("flash", "sharded"):
        model = models.get_model(
            "transformer_lm", vocab_size=61, num_layers=2, num_heads=4,
            head_dim=8, max_seq_len=seq, attention="flash", remat=remat,
            mesh=(build_mesh({"data": 2, "tensor": 4}) if case == "sharded"
                  else None))
    elif case == "gqa":
        from test_lfm2_moe import TINY

        model = models.get_model("lfm2_moe", config=TINY, attention="flash",
                                 remat=remat)
    elif case == "latent":
        from test_deepseek_v2 import TINY

        model = models.get_model("deepseek_v2", config=TINY,
                                 attention="flash", remat=remat)
    else:
        from test_keye_vl2 import TINY, adapter

        model = models.get_model("keye_vl2",
                                 config=adapter.program_config(TINY),
                                 attention="flash", remat=remat)
    return model, jnp.asarray(
        np.random.RandomState(1).randint(0, 61, (2, seq)), jnp.int32)


def _kernels(jaxpr):
    """The name of every ``pallas_call`` of a jaxpr (its ``name=``, else the
    kernel function's), nested ones (the checkpoint's, the custom rules', a
    ``shard_map``'s) included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["jaxpr"].debug_info.func_name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _kernels(sub)
    return names


def remat_keeps_the_attention_kernels_results(case):
    """Under ``attention="flash"`` a recomputed block does not run its
    forward kernel again: the checkpoint keeps the kernel's output and
    logsumexp rows (and an indexed layer's key bits and index logsumexp,
    so the selection runs once too, and the three gradients of the index's
    loss, whose kernel runs once, with them, and never for the value alone).
    Loss and gradients are those of the stored-activation model, and the
    gradient's jaxpr holds three flash kernels a layer (forward, dQ, dK/dV),
    not four.  Cases: GPT-2's fused heads, grouped-query heads, the latent
    form (values narrower than keys), a learned index, and the kernel mapped
    over a mesh's shards (the names sit inside the ``shard_map``)."""
    (remat, tokens), (base, _) = (
        _flash_lm(case, flag) for flag in (True, False))
    params = base.init(jax.random.PRNGKey(0), tokens)["params"]
    mask = jnp.ones((tokens.shape[0],), jnp.float32)

    def value_and_grad(model):
        return jax.value_and_grad(
            lambda p: transformer.loss_fn(model)(
                p, {"tokens": tokens}, mask)[0])

    (loss_r, g_r), (loss_b, g_b) = (
        value_and_grad(m)(params) for m in (remat, base))
    np.testing.assert_allclose(float(loss_r), float(loss_b), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
        g_b, g_r)
    # the stored-activation model runs each kernel once a layer
    counts = [[_kernels(jax.make_jaxpr(value_and_grad(model))(
        params).jaxpr).count(name)
        for name in ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel",
                     "dsa_select", "dsa_index_loss_grads",
                     "dsa_index_loss")] for model in (remat, base)]
    assert counts[0] == counts[1], counts
    layers = counts[1][0]
    assert layers > 0 and counts[1] == [layers] * 3 + [
        layers if case == "indexed" else 0] * 2 + [0]
    if case == "indexed":
        # ... and without differentiation the kernel of the value alone
        value = _kernels(jax.make_jaxpr(lambda p: transformer.loss_fn(remat)(
            p, {"tokens": tokens}, mask)[0])(params).jaxpr)
        assert (value.count("dsa_index_loss"),
                value.count("dsa_index_loss_grads")) == (layers, 0)
