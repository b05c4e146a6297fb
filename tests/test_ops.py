"""Pallas kernel tests (interpret mode on the CPU mesh): flash attention
forward and backward against the reference contraction."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops import flash_attention, flash_attention_lse
from tensorflowonspark_tpu.parallel import ring


def _qkv(batch=2, seq=128, heads=2, dim=32, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, seq, heads, dim)
    return tuple(jax.random.normal(k, shape, dtype=dtype)
                 for k in (k1, k2, k3))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        want = ring.reference_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_multi_block_online_softmax(self):
        # 4 q blocks x 4 k blocks: the running (max, sum, acc) rescaling
        # across k iterations is what's under test
        q, k, v = _qkv(batch=1, seq=256, heads=1, dim=16, seed=3)
        want = ring.reference_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_reference(self, causal):
        q, k, v = _qkv(batch=1, seq=64, heads=2, dim=16, seed=1)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal,
                                block_q=32, block_k=32)
            return (o ** 2).sum()

        def loss_ref(q, k, v):
            return (ring.reference_attention(q, k, v, causal=causal) ** 2).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
                err_msg="d{} mismatch".format(name))

    def test_bf16_inputs(self):
        q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(seq=64, dim=16))
        want = ring.reference_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=3e-2)

    def test_under_jit(self):
        q, k, v = _qkv(batch=1, seq=64, heads=1, dim=16)
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=32,
                                                    block_k=32))
        got = f(q, k, v)
        want = ring.reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_seq_divisibility_enforced(self):
        q, k, v = _qkv(seq=48)
        with pytest.raises(ValueError, match="8.*divide.*32"):
            flash_attention(q, k, v, block_q=32, block_k=32)


@pytest.mark.parametrize("blocks", [(32, 32), (64, 32), (32, 64)],
                         ids=["q32k32", "q64k32", "q32k64"])
@pytest.mark.parametrize("causal", [False, True])
def test_grouped_query_flash_matches_reference(causal, blocks):
    """8 query heads over 2 KV heads: values and the three gradients against
    the reference contraction with each KV head repeated for its group (the
    reference's dK/dV then sum over the group by the chain rule); block
    sizes that differ exercise the causal index maps' clamping."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (2, 128, 8, 16))
    k = jax.random.normal(keys[1], (2, 128, 2, 16))
    v = jax.random.normal(keys[2], (2, 128, 2, 16))

    def flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=blocks[0],
                            block_k=blocks[1])
        return (o ** 2).sum(), o

    def ref(q, k, v):
        o = ring.reference_attention(q, jnp.repeat(k, 4, axis=2),
                                     jnp.repeat(v, 4, axis=2), causal=causal)
        return (o ** 2).sum(), o

    (_, got), g_flash = jax.value_and_grad(
        flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), g_ref = jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg="d{} mismatch".format(name))


@pytest.mark.parametrize("widths", [(192, 128), (24, 16), (16, 24),
                                    (16, 16)],
                         ids=["dk192_dv128", "dk24_dv16", "dk16_dv24",
                              "dk16_dv16"])
@pytest.mark.parametrize("group", [1, 4], ids=["mha", "group4"])
def test_flash_with_a_value_width_of_its_own(widths, group):
    """Latent attention's shapes: scores over ``dk`` (192 = 128 + 64 rotary),
    values ``dv`` wide (128), the caller's scale: the output is ``dv`` wide,
    and values and all three gradients agree with plain attention; V is
    never padded (dV has V's own shape).  ``dk == dv`` is the kernel as it
    was."""
    dk, dv = widths
    seq = 64 if dk > 64 else 128
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (2, seq, 4, dk))
    k = jax.random.normal(keys[1], (2, seq, 4 // group, dk))
    v = jax.random.normal(keys[2], (2, seq, 4 // group, dv))
    scale = 0.114722 * (192 / dk) ** 0.5

    def flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            scale=scale)
        return (o ** 2).sum(), o

    def ref(q, k, v):
        o = ring.reference_attention(q, jnp.repeat(k, group, axis=2),
                                     jnp.repeat(v, group, axis=2),
                                     causal=True, scale=scale)
        return (o ** 2).sum(), o

    (_, got), g_flash = jax.value_and_grad(
        flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), g_ref = jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert got.shape == (2, seq, 4, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg="d{} mismatch".format(name))


def _key_bits(kept):
    """bool ``[B, T, S]`` -> the ``[B, groups, T, 128]`` int32 words that
    ``flash_attention_lse(key_bits=)`` reads."""
    batch, rows, seq = kept.shape
    groups = -(-seq // 4096)
    padded = np.zeros((batch, rows, groups * 4096), np.int64)
    padded[:, :, :seq] = kept
    runs = padded.reshape(batch, rows, groups, 32, 128)
    words = (runs << np.arange(32)[None, None, None, :, None]).sum(axis=3)
    return jnp.asarray(words.transpose(0, 2, 1, 3).astype(np.uint32).view(
        np.int32))


@pytest.mark.parametrize("shape", [(256, 128, 128, 4, 16, 16),
                                   (256, 128, 128, 1, 24, 16),
                                   (512, 256, 128, 2, 16, 16),
                                   (512, 128, 256, 2, 16, 16)],
                         ids=["group4", "mha_dk24_dv16", "q256k128",
                              "q128k256"])
@pytest.mark.parametrize("density", [0.15, 1.0], ids=["sparse", "all"])
def test_flash_over_each_querys_own_keys(shape, density):
    """``key_bits``: every query reads its own keys inside the causal
    triangle (here a random set that always holds the query itself; some
    rows find none of theirs in the first k block).  Values, the logsumexp
    rows and the three gradients against plain attention under the same
    mask; a set that holds every causal key is the kernel without one."""
    seq, block_q, block_k, group, dk, dv = shape
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    q = jax.random.normal(keys[0], (2, seq, 4, dk))
    k = jax.random.normal(keys[1], (2, seq, 4 // group, dk))
    v = jax.random.normal(keys[2], (2, seq, 4 // group, dv))
    causal = np.tril(np.ones((seq, seq), bool))
    kept = (np.asarray(jax.random.uniform(keys[3], (2, seq, seq)))
            < density) & causal | np.eye(seq, dtype=bool)
    bits = _key_bits(kept)

    def flash(q, k, v):
        o, lse = flash_attention_lse(q, k, v, causal=True, block_q=block_q,
                                     block_k=block_k, key_bits=bits)
        return (o ** 2).sum(), (o, lse)

    def ref(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q,
                       jnp.repeat(k, group, axis=2)) * dk ** -0.5
        s = jnp.where(kept[:, None], s, -jnp.inf)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1),
                       jnp.repeat(v, group, axis=2))
        return (o ** 2).sum(), (o, jax.nn.logsumexp(s, axis=-1).transpose(
            0, 2, 1))

    (_, got), g_flash = jax.value_and_grad(
        flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), g_ref = jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg="d{} mismatch".format(name))
    if density == 1.0:
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k)),
            atol=1e-6)


def _selected_bits(batch, seq, topk, seed):
    """``key_bits`` as the model makes them: ``select_keys`` over seeded
    index scores, and the kept pairs unpacked (bool ``[B, T, S]``)."""
    from tensorflowonspark_tpu.ops import sparse_index

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bits, _ = sparse_index.select_keys(
        jax.random.normal(ks[0], (batch, seq, 2, 8)),
        jax.random.normal(ks[1], (batch, seq, 8)),
        jax.random.uniform(ks[2], (batch, seq, 2)), topk, block_q=128,
        chunk=128)
    words = np.asarray(bits)
    s = np.arange(seq)
    kept = ((words[:, s // 4096, :, s % 128]
             >> ((s % 4096) // 128)[:, None, None]) & 1).astype(bool)
    return bits, kept.transpose(1, 2, 0)


# the benchmark's three head shapes at test size: (q and k width, v width,
# query heads, group, rows, keyed), with the blocks each is run at: block_q
# != block_k both ways round, 16, 32 and 128 (key sets want a k block of 128)
HEAD_SHAPES = {
    "d64_group4": ((64, 64, 4, 4, 128, False),
                   [(16, 32), (32, 16), (32, 32), (128, 128)]),
    "d192_dv128_mha": ((192, 128, 2, 1, 128, False),
                       [(16, 32), (32, 16), (32, 32), (128, 128)]),
    "d128_group8_keyed": ((128, 128, 8, 8, 256, True),
                          [(128, 128), (256, 128), (128, 256)]),
    # ... and a value width that is no whole number of the lanes a statistic
    # is held on (48 over a k block's 32): the last form of ``_across``
    "d32_dv48_mha": ((32, 48, 2, 1, 128, False), [(32, 32)]),
}


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize(
    "shape, blocks",
    [(name, b) for name, (_, blocks) in HEAD_SHAPES.items() for b in blocks],
    ids=["{}-q{}k{}".format(name, *b)
         for name, (_, blocks) in HEAD_SHAPES.items() for b in blocks])
def test_flash_kernels_against_the_float32_reference(shape, blocks, causal):
    """Forward, dQ and dK/dV over the benchmark's head shapes: values, the
    logsumexp rows and the three gradients against plain float32 attention
    (under the same key sets where the shape has them: ``select_keys``'
    own, which lie inside the causal triangle)."""
    dk, dv, heads, group, seq, keyed = HEAD_SHAPES[shape][0]
    keys = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(keys[0], (2, seq, heads, dk))
    k = jax.random.normal(keys[1], (2, seq, heads // group, dk))
    v = jax.random.normal(keys[2], (2, seq, heads // group, dv))
    bits, allowed = None, np.ones((2, seq, seq), bool)
    if keyed:
        bits, allowed = _selected_bits(2, seq, 48, seed=23)
    if causal:
        allowed = allowed & np.tril(np.ones((seq, seq), bool))

    def flash(q, k, v):
        o, lse = flash_attention_lse(q, k, v, causal=causal,
                                     block_q=blocks[0], block_k=blocks[1],
                                     key_bits=bits)
        return (o ** 2).sum(), (o, lse)

    def ref(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q,
                       jnp.repeat(k, group, axis=2)) * dk ** -0.5
        s = jnp.where(allowed[:, None], s, -jnp.inf)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1),
                       jnp.repeat(v, group, axis=2))
        return (o ** 2).sum(), (o, jax.nn.logsumexp(s, axis=-1).transpose(
            0, 2, 1))

    (_, got), g_flash = jax.value_and_grad(
        flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), g_ref = jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert got[1].dtype == jnp.float32
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg="d{} mismatch".format(name))


@pytest.mark.parametrize("blocks", [(32, 16), (128, 128)],
                         ids=["q32k16", "q128k128"])
def test_flash_logsumexp_is_the_references(blocks):
    """The rows ``flash_attention_lse`` hands on (``[batch, seq, heads]``
    float32) are the natural-log sum of the scaled causal scores, to float32
    tolerance; no gradient passes through them."""
    q, k, v = _qkv(seq=128, heads=4, dim=32, seed=3)
    _, lse = flash_attention_lse(q, k, v, block_q=blocks[0],
                                 block_k=blocks[1])
    s = jnp.einsum("bthd,bshd->bhts", q, k) * 32 ** -0.5
    s = jnp.where(np.tril(np.ones((128, 128), bool)), s, -jnp.inf)
    assert lse.shape == (2, 128, 4) and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(lse),
        np.asarray(jax.nn.logsumexp(s, axis=-1).transpose(0, 2, 1)),
        atol=2e-6, rtol=2e-6)
    grads = jax.grad(lambda q: flash_attention_lse(
        q, k, v, block_q=blocks[0], block_k=blocks[1])[1].sum())(q)
    assert not np.asarray(grads).any()


def test_a_single_kept_key_has_probability_one():
    """A query whose key set holds one key (here itself) attends to it with
    probability exactly 1: its output is that key's value, its logsumexp
    that pair's scaled score, and every masked pair contributes exactly 0."""
    seq = 256
    q, k, v = _qkv(batch=1, seq=seq, heads=2, dim=16, seed=9)
    bits = _key_bits(np.eye(seq, dtype=bool)[None])
    out, lse = flash_attention_lse(q, k, v, block_q=128, block_k=128,
                                   key_bits=bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(v))
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray((q * k).sum(-1) * 16 ** -0.5),
        atol=1e-6, rtol=1e-6)


# -- the index's loss ---------------------------------------------------------

# (batch, rows, query heads, KV heads, head width, index heads, index width,
# dtype, the key sets): the model's form at test size (bfloat16 operands,
# ``select_keys``' own sets of 40), and rows of three blocks in float32 over
# a random causal set, index heads as wide as the cell's
INDEX_LOSS_CASES = {
    "selected_bf16": (2, 256, 4, 2, 16, 4, 8, jnp.bfloat16, 40),
    "random_f32": (1, 384, 2, 1, 32, 2, 64, jnp.float32, None),
}


def _index_loss_inputs(case):
    """``index_loss``'s eight operands as a layer makes them: the key sets,
    the logsumexp of the index scores over them, the attention kernel's
    logsumexp rows under them."""
    from tensorflowonspark_tpu.ops import sparse_index

    batch, seq, heads, kv, dim, j, e, dtype, topk = INDEX_LOSS_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(29), 7)
    q = jax.random.normal(ks[0], (batch, seq, heads, dim)).astype(dtype)
    k, v = (jax.random.normal(key, (batch, seq, kv, dim)).astype(dtype)
            for key in ks[1:3])
    iq = jax.random.normal(ks[3], (batch, seq, j, e)).astype(dtype)
    ik = jax.random.normal(ks[4], (batch, seq, e)).astype(dtype)
    iw = 0.2 * jax.random.normal(ks[5], (batch, seq, j))
    if topk:
        bits, index_lse = sparse_index.select_keys(iq, ik, iw, topk,
                                                   block_q=128, chunk=128)
    else:
        kept = (np.asarray(jax.random.uniform(ks[6], (batch, seq, seq)))
                < 0.3) & np.tril(np.ones((seq, seq), bool)) | np.eye(
                    seq, dtype=bool)
        bits = _key_bits(kept)
        scores = (iw.transpose(0, 2, 1)[..., None] * jax.nn.relu(
            jnp.einsum("btje,bse->bjts", iq, ik))).sum(axis=1)
        index_lse = jax.nn.logsumexp(jnp.where(kept, scores, -jnp.inf),
                                     axis=-1)
    _, lse = flash_attention_lse(q, k, v, causal=True, block_q=128,
                                 block_k=128, key_bits=bits)
    return iq, ik, iw, q, k, lse, index_lse, bits


@pytest.mark.parametrize("case", list(INDEX_LOSS_CASES))
def test_index_loss_takes_the_heads_scores_once(case):
    """Under differentiation ``index_loss`` runs its kernel once, with the
    gradients, and its backward rule none: value and the three gradients
    are, bit for bit, those of the two-kernel form (the value from the
    kernel without gradients, the gradients from the one with them, scaled
    by the cotangent over the rows' length and cast, as the backward rule
    once did itself)."""
    from tensorflowonspark_tpu.ops import sparse_index

    operands = _index_loss_inputs(case)
    iq, ik, iw, q, k, lse, index_lse, bits = operands
    batch, seq, j, e = iq.shape
    weight = 0.37 * jnp.arange(1.0, 1.0 + batch)      # the cotangent, a row

    def weighted(iq, ik, iw):
        return (sparse_index.index_loss(iq, ik, iw, *operands[3:],
                                        block=128) * weight).sum()

    got, got_grads = jax.value_and_grad(weighted, (0, 1, 2))(iq, ik, iw)

    def kernel(with_grads):
        return sparse_index._loss_call(
            q, k, lse, iq, ik, iw, index_lse, bits, q.shape[3] ** -0.5, 128,
            True, with_grads)

    want = (kernel(False).mean(axis=1) * weight).sum()
    _, (diq, dik, diw) = kernel(True)
    assert diq.shape == (batch, seq, j * e)           # a dense row a position
    unit = (diq.reshape(batch, seq, j, e),
            dik.transpose(0, 1, 3, 2).reshape(batch, seq, e), diw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for mine, d, x in zip(got_grads, unit, (iq, ik, iw)):
        assert mine.dtype == x.dtype and mine.shape == x.shape
        scaled = (d * (weight / seq).reshape((-1,) + (1,) * (d.ndim - 1))
                  ).astype(x.dtype)
        assert np.asarray(scaled, np.float32).any()
        np.testing.assert_array_equal(np.asarray(mine, np.float32),
                                      np.asarray(scaled, np.float32))
    # ... and the value alone, undifferentiated, is the same number
    np.testing.assert_array_equal(np.asarray(weighted(iq, ik, iw)),
                                  np.asarray(want))


def test_index_loss_keeps_its_gradients_and_no_one_lane_array(capsys):
    """What ``save_only_these_names(*KEPT)`` keeps of the op across a
    checkpoint is its kernel's three gradients, float32 and under their
    names, the index queries' as a dense row a position: none of the
    operands (the backward rule runs no kernel), and no array whose last
    dimension is 1.  Traced only, at the cell's widths."""
    from jax.ad_checkpoint import print_saved_residuals

    from tensorflowonspark_tpu.ops import sparse_index

    batch, seq, heads, kv, dim, j, e = 1, 1024, 32, 4, 128, 16, 64

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((batch, seq) + shape, dtype)

    def op(*operands):
        return sparse_index.index_loss(*operands, block=512,
                                       interpret=True).sum()

    print_saved_residuals(
        jax.checkpoint(op, policy=jax.checkpoint_policies
                       .save_only_these_names(*sparse_index.KEPT)),
        arg(j, e), arg(e), arg(j, dtype=jnp.float32), arg(heads, dim),
        arg(kv, dim), arg(heads, dtype=jnp.float32),
        jax.ShapeDtypeStruct((batch, seq), jnp.float32),
        jax.ShapeDtypeStruct((batch, 1, seq, 128), jnp.int32))
    kept = capsys.readouterr().out.splitlines()
    assert [line.split(" from ")[0] for line in kept] == [
        "f32[1,1024,1024] named 'dsa_index_loss_dq'",
        "f32[1,2,64,512] named 'dsa_index_loss_dk'",
        "f32[1,1024,16] named 'dsa_index_loss_dw'"], kept
    assert not any(line.split(" ")[0].endswith(",1]") for line in kept)


@pytest.mark.parametrize("sizes", [[40, 0, 100, 37], [0, 0, 0, 256],
                                   [64, 64, 64, 64]],
                         ids=["uneven", "one_group", "even"])
def test_grouped_matmul_kernels_match_ragged_dot(sizes):
    """The pallas grouped product (forward, and both gradients' kernels) in
    interpret mode against ``jax.lax.ragged_dot``: an empty group, every row
    in one group, and rows behind the last group, which the kernels leave
    unwritten and the caller masks."""
    from tensorflowonspark_tpu.ops import grouped_matmul

    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    lhs = jax.random.normal(keys[0], (256, 128))
    rhs = 0.1 * jax.random.normal(keys[1], (4, 128, 256))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(256) < group_sizes.sum())[:, None]

    def run(impl):
        def loss(lhs, rhs):
            out = grouped_matmul(jnp.where(valid, lhs, 0.0), rhs, group_sizes,
                                 impl=impl, interpret=True)
            out = jnp.where(valid, out, 0.0)
            return (out ** 2).sum(), out
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(lhs, rhs)

    (_, want), g_want = run("xla")
    (_, got), g_got = run("pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    for a, b, name in zip(g_got, g_want, ("lhs", "rhs")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3,
                                   rtol=1e-3, err_msg="d" + name)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("sizes", [[40, 0, 100, 37], [0, 0, 0, 256],
                                   [64, 64, 64, 64]],
                         ids=["uneven", "one_group", "even"])
def test_grouped_matmul_grads_are_what_differentiating_gives(sizes, impl):
    """``grouped_matmul_grads`` against ``jax.vjp`` of ``grouped_matmul``
    under the same ``impl`` (the kernels in interpret mode), on the rows in
    front of the last group: the same two cotangents, to the last bit."""
    import importlib

    gm = importlib.import_module("tensorflowonspark_tpu.ops.grouped_matmul")
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    lhs = jax.random.normal(keys[0], (256, 128))
    rhs = 0.1 * jax.random.normal(keys[1], (4, 128, 256))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(256) < group_sizes.sum())[:, None]
    grad = jnp.where(valid, jax.random.normal(keys[2], (256, 256)), 0.0)
    lhs = jnp.where(valid, lhs, 0.0)
    want = jax.vjp(lambda lhs, rhs: gm.grouped_matmul(
        lhs, rhs, group_sizes, impl=impl, interpret=True), lhs, rhs)[1](grad)
    got = gm.grouped_matmul_grads(lhs, rhs, group_sizes, grad, impl=impl,
                                  interpret=True)
    assert [g.shape for g in got] == [lhs.shape, rhs.shape]
    np.testing.assert_array_equal(np.asarray(jnp.where(valid, got[0], 0.0)),
                                  np.asarray(jnp.where(valid, want[0], 0.0)))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_grouped_matmul_refuses_an_unknown_impl():
    from tensorflowonspark_tpu.ops import grouped_matmul
    from tensorflowonspark_tpu.ops.grouped_matmul import grouped_matmul_grads

    with pytest.raises(ValueError, match="impl"):
        grouped_matmul(jnp.zeros((8, 8)), jnp.zeros((1, 8, 8)),
                       jnp.asarray([8], jnp.int32), impl="cuda")
    with pytest.raises(ValueError, match="impl"):
        grouped_matmul_grads(jnp.zeros((8, 8)), jnp.zeros((1, 8, 8)),
                             jnp.asarray([8], jnp.int32), jnp.zeros((8, 8)),
                             impl="cuda")


# the row movement of the expert layer: 64 tokens, 4 slots, rows of 256
ROWS_T, ROWS_K, ROWS_D = 64, 4, 256
ROWS_P = ROWS_T * ROWS_K


def _routed(dtype, seed=11):
    """Tokens, experts' rows, weights and a sorting of the pairs (a random
    permutation: the kernels take any), with cotangents for both outputs."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    order = jax.random.permutation(ks[0], ROWS_P).astype(jnp.int32)
    return {"x": jax.random.normal(ks[1], (ROWS_T, ROWS_D)).astype(dtype),
            "ys": jax.random.normal(ks[2], (ROWS_P, ROWS_D)).astype(dtype),
            "weights": jax.random.uniform(ks[3], (ROWS_T, ROWS_K)),
            "order": order,
            "idx": jnp.argsort(order).astype(jnp.int32).reshape(
                ROWS_T, ROWS_K),
            "c_xs": jax.random.normal(ks[4], (ROWS_P, ROWS_D)),
            "c_y": jax.random.normal(ks[5], (ROWS_T, ROWS_D))}


@pytest.fixture
def row_kernels(monkeypatch):
    """The kernels of ``ops/routed_rows`` in interpret mode as the default
    path, four row tiles in a sorted buffer and four token tiles."""
    import importlib

    rr = importlib.import_module("tensorflowonspark_tpu.ops.routed_rows")
    monkeypatch.setattr(rr, "_default_impl", lambda: ("pallas", True))
    monkeypatch.setattr(rr, "GATHER_TILE", 64)
    monkeypatch.setattr(rr, "SUM_TILE", 16)
    return rr


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_local", [0, 64, 100, ROWS_P],
                         ids=["none", "one_tile", "mid_tile", "all"])
def test_routed_rows_kernels_match_the_plain_formulation(row_kernels, n_local,
                                                         dtype):
    """Dispatch and combine through the kernels (interpret mode) against
    ``x[src]``, a mask and a weighted sum differentiated by jax: values, and
    the gradients of ``x``, ``ys`` and ``weights``, with nothing, one tile
    exactly, a tile and a part, and every row in front of ``n_local``.  The
    rows behind it are compared nowhere: they are unspecified."""
    from tensorflowonspark_tpu.parallel import ep

    a = _routed(dtype)
    n = jnp.int32(n_local)
    valid = (jnp.arange(ROWS_P) < n_local)[:, None]

    def f32(v):
        return np.asarray(v, np.float32)

    def plain_dispatch(x):
        return jnp.where(valid, x[a["order"] // ROWS_K], 0)

    def plain_combine(ys, weights):
        rows = ys[a["idx"]].astype(jnp.float32) * weights[..., None]
        return jnp.where((a["idx"] < n_local)[..., None], rows, 0.0).sum(
            axis=1).astype(dtype)

    def kernel_dispatch(x):
        xs = ep._dispatch(x, a["order"] // ROWS_K, a["idx"], n)
        return jnp.where(valid, xs, 0)

    def kernel_combine(ys, weights):
        return ep._combine(ys, weights, a["order"], a["idx"], n)

    def loss(fn, c):
        return lambda *v: (fn(*v).astype(jnp.float32) * c).sum()

    tol = dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 \
        else dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(f32(kernel_dispatch(a["x"])),
                                  f32(plain_dispatch(a["x"])))
    np.testing.assert_allclose(
        f32(jax.grad(loss(kernel_dispatch, a["c_xs"]))(a["x"])),
        f32(jax.grad(loss(plain_dispatch, a["c_xs"]))(a["x"])), **tol)
    np.testing.assert_allclose(
        f32(kernel_combine(a["ys"], a["weights"])),
        f32(plain_combine(a["ys"], a["weights"])), **tol)
    got = jax.grad(loss(kernel_combine, a["c_y"]), argnums=(0, 1))(
        a["ys"], a["weights"])
    want = jax.grad(loss(plain_combine, a["c_y"]), argnums=(0, 1))(
        a["ys"], a["weights"])
    np.testing.assert_allclose(f32(jnp.where(valid, got[0], 0)),
                               f32(want[0]), **tol)
    np.testing.assert_allclose(f32(got[1]), f32(want[1]), atol=1e-3,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_nothing_reads_a_sorted_buffer_behind_n_local(row_kernels,
                                                      monkeypatch):
    """``experts_ffn`` with every kernel in interpret mode and the rows
    behind ``n_local`` of every sorted buffer set to NaN, forward and
    backward (the tokens in expert order, the grouped products' inputs and
    outputs, and their cotangents, the "up" products' own backward among
    them): the layer's output and the gradients of
    tokens, weights and expert weights are finite and equal to the XLA
    path's, which is never poisoned."""
    import importlib

    from tensorflowonspark_tpu.parallel import ep

    gm = importlib.import_module("tensorflowonspark_tpu.ops.grouped_matmul")
    real, real_grads = gm.grouped_matmul, gm.grouped_matmul_grads

    @jax.custom_vjp
    def poison(rows, n):
        return jnp.where((jnp.arange(rows.shape[0]) < n)[:, None], rows,
                         jnp.nan)

    poison.defvjp(lambda rows, n: (poison(rows, n), n),
                  lambda n, g: (poison(g, n), None))

    calls = []

    def poisoned(lhs, rhs, group_sizes):
        calls.append(lhs.shape)
        n = group_sizes.sum()
        return poison(real(poison(lhs, n), rhs, group_sizes, impl="pallas",
                           interpret=True), n)

    def poisoned_grads(lhs, rhs, group_sizes, grad):
        n = group_sizes.sum()
        d_lhs, d_rhs = real_grads(poison(lhs, n), rhs, group_sizes,
                                  poison(grad, n), impl="pallas",
                                  interpret=True)
        return poison(d_lhs, n), d_rhs

    tokens, k, d, f, held = 128, 2, 128, 128, 3
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (tokens, d))
    sel = jax.random.randint(ks[1], (tokens, k), 0, 8, jnp.int32)
    weights = jax.random.uniform(ks[2], (tokens, k))
    ws = [0.1 * jax.random.normal(key, shape) for key, shape in zip(
        ks[3:], [(held, d, f), (held, d, f), (held, f, d)])]

    def loss(x, weights, *ws):
        y, load = ep.experts_ffn(x, sel, weights, *ws, first=2)
        return (y ** 2).sum(), (y, load["slots_local"])

    run = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    monkeypatch.setattr(gm, "grouped_matmul", poisoned)
    monkeypatch.setattr(gm, "grouped_matmul_grads", poisoned_grads)
    (_, (y, n_local)), grads = run(x, weights, *ws)
    monkeypatch.undo()
    assert len(calls) == 3
    (_, (y_want, _)), grads_want = run(x, weights, *ws)
    assert 0 < int(n_local) < tokens * k and int(n_local) % 64
    for got, want, name in zip((y,) + grads, (y_want,) + grads_want,
                               ("y", "d_x", "d_weights", "d_w1", "d_w3",
                                "d_w2")):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-3, rtol=1e-3, err_msg=name)


# the row-wise passes between the grouped products: four row tiles of 32
GATE_P, GATE_TILE = 128, 32


@pytest.fixture
def gate_kernels(row_kernels, monkeypatch):
    """``ops/expert_gate`` with four row tiles in ``GATE_P`` rows; its
    kernels are the default path wherever the row movement's are."""
    from tensorflowonspark_tpu.ops import expert_gate as eg

    monkeypatch.setattr(eg, "TILE", GATE_TILE)
    return eg


@pytest.mark.parametrize("width", [1792, 1856])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("n_local", [0, 1, 40, 64, GATE_P],
                         ids=["none", "one_row", "mid_tile", "tile_edge",
                              "all"])
def test_expert_gate_kernels_match_the_plain_formulation(gate_kernels,
                                                         n_local, act, dtype,
                                                         width):
    """The gate, its backward and the sum through the front-tile kernels
    (interpret mode) against the plain ``jax.numpy`` form and jax's
    derivative of it, on the rows in front of ``n_local``: no row, one, a
    tile and a part, two tiles exactly, every row; at the widths of the
    benchmark's cells 3 and 7 (1,856 is 14 lane tiles and a half).  The
    kernels agree to the last bit with the plain form computed in float32
    and rounded once, in float32 and in bfloat16; the plain form in
    bfloat16, which off the TPU rounds after every operation, lies within
    2 ** -6 of the largest value of them (terms of the gate's derivative
    cancel, so an element's own relative distance can be anything).  A tile
    wholly behind ``n_local`` keeps what the buffer held (NaN here, where a
    result is written over an operand)."""
    eg = gate_kernels
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    h1, h3, d_h, a, b = (jax.random.normal(key, (GATE_P, width)).astype(dtype)
                         for key in ks)
    h3 = None if act == "relu2" else h3
    n = jnp.int32(n_local)
    front = (jnp.arange(GATE_P) < n_local)[:, None]
    # rows behind the last tile in front of n_local, which no kernel touches
    behind = jnp.arange(GATE_P) >= max(-(-n_local // GATE_TILE), 1) * GATE_TILE

    def poisoned(v):
        return jnp.where(front, v, jnp.nan)

    def plain(fn, *arrays):
        """``fn`` by the plain form in float32, rounded once, and (bfloat16)
        in the arrays' dtype, rounded after every operation."""
        wide = fn(*[None if v is None else v.astype(jnp.float32)
                    for v in arrays])
        wide = jax.tree_util.tree_map(lambda v: v.astype(dtype), wide)
        return wide, fn(*arrays)

    def same(got, want):
        got, (once, each) = (jax.tree_util.tree_map(
            lambda v: np.asarray(jnp.where(front, v, 0), np.float32), tree)
            for tree in (got, want))
        for g, o, e in zip(*map(jax.tree_util.tree_leaves,
                                (got, once, each))):
            np.testing.assert_array_equal(g, o)
            np.testing.assert_allclose(g, e, rtol=0,
                                       atol=2 ** -6 * np.abs(e).max())

    same(eg.gate(h1, h3, n, act),
         plain(lambda h1, h3: eg.gate(h1, h3, n, act, impl="xla"), h1, h3))
    got = eg.gate_grad(h1, h3, poisoned(d_h), n, act)
    same(got, plain(lambda *v: eg.gate_grad(*v, n, act, impl="xla"),
                    h1, h3, d_h))
    assert (got[1] is None) == (act == "relu2")
    assert np.isnan(np.asarray(got[0], np.float32)[behind]).all()
    got = eg.add_rows(poisoned(a), poisoned(b), n)
    same(got, plain(lambda a, b: eg.add_rows(a, b, n, impl="xla"), a, b))
    assert np.isnan(np.asarray(got, np.float32)[behind]).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("first,held", [(8, 2), (2, 3), (0, 8)],
                         ids=["none_held", "some_held", "all_held"])
def test_experts_ffn_matches_the_products_differentiated_by_jax(
        gate_kernels, monkeypatch, first, held, act, dtype):
    """``experts_ffn`` against the formulation it had before its "up" half
    wrote its own backward (the plain gate between the grouped products,
    everything differentiated by jax), the same kernels under both in
    interpret mode: the output and the gradients in ``x``, ``weights``,
    ``w1``, ``w3`` and ``w2``, where the held experts get no pair, some
    (``n_local`` inside a row tile) and every one (every tile live)."""
    import functools
    import importlib

    from tensorflowonspark_tpu.parallel import ep

    gm = importlib.import_module("tensorflowonspark_tpu.ops.grouped_matmul")
    for name in ("grouped_matmul", "grouped_matmul_grads"):
        monkeypatch.setattr(gm, name, functools.partial(
            getattr(gm, name), impl="pallas", interpret=True))
    tokens, k, d, f = 64, 2, 128, 192
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (tokens, d))
    sel = jax.random.randint(ks[1], (tokens, k), 0, 8, jnp.int32)
    weights = jax.random.uniform(ks[2], (tokens, k))
    w1, w3, w2 = (0.1 * jax.random.normal(key, shape) for key, shape in zip(
        ks[3:], [(held, d, f), (held, d, f), (held, f, d)]))
    params = (x, weights, w1, w2) + ((w3,) if act == "swiglu" else ())

    def before(x, weights, w1, w2, w3=None):
        order, inverse, group_sizes, n_local = ep.sort_pairs(sel, first, held)
        idx = inverse.reshape(tokens, k)
        xs = ep._dispatch(x.astype(dtype), order // k, idx, n_local)
        h = gm.grouped_matmul(xs, w1.astype(dtype), group_sizes)
        if act == "relu2":
            h = jnp.square(jax.nn.relu(h))
        else:
            h = jax.nn.silu(h) * gm.grouped_matmul(xs, w3.astype(dtype),
                                                   group_sizes)
        ys = gm.grouped_matmul(h, w2.astype(dtype), group_sizes)
        return ep._combine(ys, weights, order, idx, n_local), n_local

    def now(x, weights, w1, w2, w3=None):
        y, load = ep.experts_ffn(x, sel, weights, w1, w3, w2, first,
                                 dtype=dtype, act=act)
        assert set(load) >= {"gate_tiles_live", "gate_tiles_total"}
        return y, (load["slots_local"], load["gate_tiles_live"],
                   load["gate_tiles_total"])

    def run(fn):
        def loss(*params):
            y, aux = fn(*params)
            return (y.astype(jnp.float32) ** 2).sum(), (y, aux)

        (_, (y, aux)), grads = jax.value_and_grad(
            loss, argnums=tuple(range(len(params))), has_aux=True)(*params)
        return (y,) + grads, aux

    got, (n_local, live, total) = run(now)
    want, n_want = run(before)
    assert int(n_local) == int(n_want)
    assert (int(n_local) == 0) == (held == 2)
    assert int(total) == tokens * k // GATE_TILE
    assert int(live) == -(-int(n_local) // GATE_TILE)
    if held == 8:
        assert int(live) == int(total)
    elif held == 3:
        assert int(n_local) % GATE_TILE and int(live) < int(total)
    for a, b, name in zip(got, want, ("y", "d_x", "d_weights", "d_w1", "d_w2",
                                      "d_w3")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        if dtype == jnp.float32:
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            np.testing.assert_allclose(a, b, rtol=3e-2,
                                       atol=3e-2 * np.abs(b).max(),
                                       err_msg=name)


def test_expert_gate_refuses_an_unknown_impl_and_form():
    from tensorflowonspark_tpu.ops import expert_gate

    h = jnp.zeros((8, 8))
    with pytest.raises(ValueError, match="impl"):
        expert_gate.gate(h, h, 8, impl="cuda")
    with pytest.raises(ValueError, match="form"):
        expert_gate.gate(h, h, 8, act="gelu", impl="xla")


def test_gather_sum_rows_takes_a_slot_count_that_is_no_power_of_two(
        row_kernels):
    """Three slots a token: the scalar copy of the positions is padded to
    four a token and the pad is never visited."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    ys = jax.random.normal(ks[0], (96, 128))
    idx = jax.random.permutation(ks[1], 96).astype(jnp.int32).reshape(32, 3)
    weights = jax.random.uniform(ks[2], (32, 3))
    want = row_kernels.gather_sum_rows(ys, idx, 50, weights=weights,
                                       impl="xla")
    got = row_kernels.gather_sum_rows(
        jnp.where((jnp.arange(96) < 50)[:, None], ys, jnp.nan), idx, 50,
        weights=weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_routed_rows_take_rows_of_an_odd_number_of_lane_tiles(row_kernels,
                                                              dtype):
    """Rows of 384 (2,688 in small): 192 words of bfloat16 are a slab row and
    a half, so the first 256 columns go to the low halves, the other 128 to
    the high halves of the first slab row, and the second row's high halves
    stay unused; gather (scaled, with its dot products) and gather-and-sum
    against XLA's take."""
    d, n = 384, 100
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (ROWS_T, d)).astype(dtype)
    ys = jax.random.normal(ks[1], (ROWS_P, d)).astype(dtype)
    src = jax.random.randint(ks[2], (ROWS_P,), 0, ROWS_T)
    scale = jax.random.uniform(ks[3], (ROWS_P,))
    idx = jax.random.permutation(ks[4], ROWS_P).reshape(ROWS_T, ROWS_K)
    weights = jax.random.uniform(ks[5], (ROWS_T, ROWS_K))
    assert row_kernels._slab(d, dtype)[:2] == (
        (2, 128) if dtype == jnp.bfloat16 else (3, 128))
    got, dots = row_kernels.gather_rows(x, src, n, scale=scale, dot_with=ys)
    want, want_dots = row_kernels.gather_rows(x, src, n, scale=scale,
                                              dot_with=ys, impl="xla")
    np.testing.assert_array_equal(np.asarray(got[:n], np.float32),
                                  np.asarray(want[:n], np.float32))
    np.testing.assert_allclose(np.asarray(dots[:n]),
                               np.asarray(want_dots[:n]), rtol=1e-5,
                               atol=1e-5)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(row_kernels.gather_sum_rows(ys, idx, n, weights=weights),
                   np.float32),
        np.asarray(row_kernels.gather_sum_rows(ys, idx, n, weights=weights,
                                               impl="xla"), np.float32),
        atol=tol, rtol=tol)


def test_routed_rows_refuse_an_unknown_impl():
    from tensorflowonspark_tpu.ops import gather_rows, gather_sum_rows

    with pytest.raises(ValueError, match="impl"):
        gather_rows(jnp.zeros((8, 8)), jnp.zeros((8,), jnp.int32), 8,
                    impl="cuda")
    with pytest.raises(ValueError, match="impl"):
        gather_sum_rows(jnp.zeros((8, 8)), jnp.zeros((4, 2), jnp.int32), 8,
                        impl="cuda")
