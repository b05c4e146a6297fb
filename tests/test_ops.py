"""Pallas kernel tests (interpret mode on the CPU mesh): flash attention
forward and backward against the reference contraction."""

import re

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


# (window, block_q, block_k, query heads, group): a window shorter than a
# block, one of two whole blocks (three tiles a q block), one that is no
# multiple of the block, unequal blocks both ways round, the benchmark's
# group of 8, a window of one key, and one that covers the row
WINDOWS = {
    "under_a_block": (20, 32, 32, 4, 2),
    "two_blocks": (64, 32, 32, 2, 1),
    "no_multiple": (50, 32, 32, 4, 4),
    "q16_k32": (40, 16, 32, 2, 1),
    "q32_k16": (33, 32, 16, 2, 2),
    "group8": (48, 32, 32, 8, 8),
    "one_key": (1, 32, 32, 2, 2),
    "the_row": (128, 32, 32, 4, 2),
    "over_the_row": (1000, 32, 32, 4, 2),
}


@pytest.mark.parametrize("kept", [False, True], ids=["stored", "kept"])
@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_flash_kernels_with_a_window_against_the_float32_reference(case,
                                                                    kept):
    """Forward, dQ and dK/dV with ``window``: values, the logsumexp rows and
    the three gradients against dense float32 attention under the band's
    mask; ``kept``: under a checkpoint that keeps the kernels' two residuals
    by name, as a recomputed block does.  A window that covers the row is
    the causal kernel, bit for bit."""
    from tensorflowonspark_tpu.ops.flash_attention import KEPT

    window, block_q, block_k, heads, group = WINDOWS[case]
    seq, dim = 128, 16
    keys = jax.random.split(jax.random.PRNGKey(29), 3)
    q = jax.random.normal(keys[0], (2, seq, heads, dim))
    k = jax.random.normal(keys[1], (2, seq, heads // group, dim))
    v = jax.random.normal(keys[2], (2, seq, heads // group, dim))
    t = np.arange(seq)
    allowed = (t[:, None] >= t[None]) & (t[:, None] - t[None] < window)

    def flash(q, k, v, window=window):
        o, lse = flash_attention_lse(q, k, v, block_q=block_q,
                                     block_k=block_k, window=window)
        return (o ** 2).sum(), (o, lse)

    def ref(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q,
                       jnp.repeat(k, group, axis=2)) * dim ** -0.5
        s = jnp.where(allowed, s, -jnp.inf)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1),
                       jnp.repeat(v, group, axis=2))
        return (o ** 2).sum(), (o, jax.nn.logsumexp(s, axis=-1).transpose(
            0, 2, 1))

    run = flash
    if kept:
        run = jax.checkpoint(
            flash, policy=jax.checkpoint_policies.save_only_these_names(
                *KEPT))
    (_, got), g_flash = jax.value_and_grad(
        run, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), g_ref = jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg="d{} mismatch".format(name))
    if window >= seq:
        (_, causal), g_causal = jax.value_and_grad(
            lambda q, k, v: flash(q, k, v, None), argnums=(0, 1, 2),
            has_aux=True)(q, k, v)
        for a, b in zip(got + g_flash, causal + g_causal):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _grids(fn, *args):
    """The grids of the kernels in ``fn``'s trace, as their jaxpr prints
    them, sorted."""
    return sorted(re.findall(r"grid=\(([\d, ]+)\)",
                             str(jax.make_jaxpr(fn)(*args))))


def test_a_windows_grid_follows_the_band():
    """The three kernels' inner grid extent is the band's longest run of
    blocks, not ``seq / block``, and without a window the grid is the 36
    tiles of the triangle of 8 blocks."""
    q, k, v = _qkv(batch=1, seq=256, heads=2, dim=16)

    def grids(window):
        return _grids(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, block_q=32, block_k=32, window=window).sum(),
            (0, 1, 2)), q, k, v)

    assert grids(None) == ["2, 36"] * 3
    assert grids(64) == ["2, 8, 3"] * 3        # two whole blocks: three tiles
    assert grids(34) == ["2, 8, 3"] * 3
    assert grids(33) == ["2, 8, 2"] * 3        # one key beyond one block
    assert grids(256) == grids(None)     # covers the row: the causal kernel


def _causal_tiles(seq, block_q, block_k):
    """The (q block, k block) tiles that hold a (query, key) pair with the
    key not after the query, counted pair by pair."""
    seen = np.arange(seq)[:, None] >= np.arange(seq)[None, :]
    return int(seen.reshape(seq // block_q, block_q, seq // block_k,
                            block_k).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("keyed", [False, True], ids=["plain", "key_bits"])
@pytest.mark.parametrize("seq, block_q, block_k, group", [
    (128, 128, 128, 1),     # one q block: one tile, the rectangle
    (512, 512, 128, 4),     # one q block over four k blocks: the rectangle
    (256, 128, 128, 4),     # two: three tiles
    (1024, 128, 128, 8),
    (512, 256, 128, 4),     # block_q != block_k, both ways round
    (512, 128, 256, 1),
    (1024, 128, 512, 8),
], ids=lambda x: str(x))
def test_a_causal_grid_has_a_step_for_every_tile_that_computes(
        seq, block_q, block_k, group, keyed):
    """Under ``causal`` without a window the three kernels' grids hold one
    step for every tile that the diagonal crosses or that lies below it, and
    none for the tiles above: forward and dQ ``(query heads, tiles)``, dK/dV
    ``(KV heads, group * tiles)``; ``grid_tiles`` says the same, and
    ``causal=False`` keeps the rectangle, as does one q block a head, whose
    tiles are the rectangle.  The steps come in the rectangle's own order
    (so every sum is taken in the order it was)."""
    import importlib

    fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
    grid_tiles = fa.grid_tiles
    heads = 8
    q = jnp.zeros((1, seq, heads, 128))
    k = v = jnp.zeros((1, seq, heads // group, 128))
    bits = jnp.zeros((1, 1, seq, 128), jnp.int32) if keyed else None

    def grids(causal):
        return _grids(jax.grad(lambda q, k, v: flash_attention_lse(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            key_bits=bits)[0].sum(), (0, 1, 2)), q, k, v)

    tiles = _causal_tiles(seq, block_q, block_k)
    n_q, n_k = seq // block_q, seq // block_k
    rectangle = sorted(
        ["{}, {}, {}".format(heads, n_q, n_k)] * 2
        + ["{}, {}, {}".format(heads // group, n_k, group * n_q)])
    assert grids(True) == (rectangle if tiles == n_q * n_k else sorted(
        ["{}, {}".format(heads, tiles)] * 2
        + ["{}, {}".format(heads // group, group * tiles)]))
    assert grid_tiles(seq, block_q, block_k) == (tiles, tiles)
    assert grids(False) == rectangle
    assert grid_tiles(seq, block_q, block_k, causal=False) == (
        n_q * n_k, n_q * n_k)

    def reaches(i, kk):     # the q block's last query, the k block's first key
        return i * block_q + block_q - 1 >= kk * block_k

    assert list(zip(*fa._causal_steps(n_q, block_q, block_k))) == [
        (i, kk) for i in range(n_q) for kk in range(n_k) if reaches(i, kk)]
    assert list(zip(*fa._causal_steps_by_keys(
        n_q, n_k, block_q, block_k, group))) == [
        (kk, head, i) for kk in range(n_k) for head in range(group)
        for i in range(n_q) if reaches(i, kk)]


@pytest.mark.parametrize("shape", ["d64_group4", "d192_dv128_mha",
                                   "d128_group8_keyed"])
def test_the_listed_grid_gives_the_rectangles_bits(shape):
    """A change of schedule, not of arithmetic: the three launchers on the
    grid that lists the triangle's tiles give, bit for bit, what they give
    on the rectangle with its steps above the diagonal left in and clamped
    (a window as long as the row is that grid: the band's, every k block
    long)."""
    import importlib

    fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
    (dk, dv, heads, group, seq, keyed), _ = HEAD_SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (2 * heads, seq, dk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2 * heads // group, seq, dk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2 * heads // group, seq, dv), jnp.bfloat16)
    g = jax.random.normal(ks[3], (2 * heads, seq, dv), jnp.bfloat16)
    bits = _selected_bits(2, seq, 40, seed=9)[0] if keyed else None
    block = seq // 2 if keyed else 32

    def run(window):
        tail = (dk ** -0.5, True, block, block, True, group, bits, window)
        out, lse = fa._flash_fwd(q, k, v, *tail)
        delta = fa._bwd_delta(out, g)
        return (out, lse, fa._flash_bwd_dq(q, k, v, g, lse, delta, *tail),
                *fa._flash_bwd_dkv(q, k, v, g, lse, delta, *tail))

    for listed, rectangle in zip(run(None), run(seq)):
        np.testing.assert_array_equal(np.asarray(listed, np.float32),
                                      np.asarray(rectangle, np.float32))


@pytest.mark.parametrize("keyed", [False, True], ids=["plain", "key_bits"])
@pytest.mark.parametrize("longest, listed", [(40, "forward and dQ"),
                                             (10, "none")])
def test_a_list_smem_would_not_hold_is_the_clamped_rectangle(
        monkeypatch, longest, listed, keyed):
    """A launcher whose list of steps would pass ``LISTED_STEPS`` words (36 a
    head here, 4 x 36 a KV head in dK/dV) takes the rectangle with its index
    maps clamped, as it did before there were lists: every shape has a
    grid, the results are the listed grid's bit for bit, and ``grid_tiles``
    counts the steps that compute nothing."""
    import importlib

    fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
    heads, group = 8, 4
    # eight blocks a row; key bits want whole runs of 128 keys
    seq, block = (1024, 128) if keyed else (256, 32)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, seq, heads, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, seq, heads // group, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, seq, heads // group, 128), jnp.bfloat16)
    bits = _selected_bits(1, seq, 40, seed=3)[0] if keyed else None
    n = seq // block
    tiles = n * (n + 1) // 2

    def run(q, k, v):
        out, lse = flash_attention_lse(q, k, v, block_q=block, block_k=block,
                                       key_bits=bits)
        return (out.astype(jnp.float32) ** 2).sum(), (out, lse)

    def results():
        (_, aux), grads = jax.value_and_grad(run, (0, 1, 2), has_aux=True)(
            q, k, v)
        return aux + grads, _grids(jax.grad(lambda *a: run(*a)[0], (0, 1, 2)),
                                   q, k, v)

    want, grids = results()
    assert grids == sorted(["{}, {}".format(heads, tiles)] * 2 + [
        "{}, {}".format(heads // group, group * tiles)])
    # the launchers are jitted: a trace made under one LISTED_STEPS (a
    # constant outside this test) would serve the other
    monkeypatch.setattr(fa, "LISTED_STEPS", longest)
    jax.clear_caches()
    try:
        got, grids = results()
        assert fa.grid_tiles(seq, block, block) == (
            (n * n if listed == "none" else tiles), tiles)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    square = "{}, {}, {}".format(heads, n, n)
    assert grids == sorted(
        ([square] if listed == "none" else ["{}, {}".format(heads, tiles)])
        * 2 + ["{}, {}, {}".format(heads // group, n, group * n)])
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_grid_tiles_at_the_benchmarks_sizes():
    """A row and head's grid steps and computed tiles at the cells' sizes:
    the triangle alone where the square grid took 1.969 and 1.882 steps a
    tile, a window's band with its three steps in 192 that compute nothing,
    blocks clamped to a short row, a window that covers the row."""
    from tensorflowonspark_tpu.ops.flash_attention import grid_tiles

    assert grid_tiles(32768, 512, 512) == (2080, 2080)      # of 4,096
    assert grid_tiles(8192, 512, 512) == (136, 136)         # of 256
    assert grid_tiles(32768, 512, 512, window=1024) == (192, 189)
    assert grid_tiles(64, 512, 512) == (1, 1)
    assert grid_tiles(1024, 512, 512, window=4096) == (3, 3)
    # a list of 524,800 steps is more than SMEM holds: the square's steps
    assert grid_tiles(131072, 128, 128) == (1024 * 1024, 524800)


def test_flash_refuses_a_window_it_cannot_run():
    q, k, v = _qkv(batch=1, seq=128, heads=2, dim=16)
    with pytest.raises(ValueError, match="wants causal=True"):
        flash_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="no key_bits"):
        flash_attention_lse(q, k, v, block_q=128, block_k=128, window=16,
                            key_bits=jnp.zeros((1, 1, 128, 128), jnp.int32))
    with pytest.raises(ValueError, match="window=0"):
        flash_attention(q, k, v, window=0)
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("seq",))
    for contraction in (ring.ring_attention, ring.ulysses_attention):
        with pytest.raises(ValueError, match="has no window"):
            contraction(q, k, v, mesh, causal=True, window=16)
    # the plain contraction takes the band as a mask
    np.testing.assert_allclose(
        np.asarray(ring.reference_attention(q, k, v, causal=True, window=16)),
        np.asarray(flash_attention(q, k, v, block_q=32, block_k=32,
                                   window=16)), atol=2e-5)


def test_key_bits_name_the_blocks_they_refuse():
    q = jnp.zeros((1, 128, 2, 8))
    bits = jnp.zeros((1, 1, 128, 128), jnp.int32)
    with pytest.raises(ValueError, match="key_bits want a k block"):
        flash_attention_lse(q, q, q, block_q=64, block_k=64, key_bits=bits)


def test_flash_names_the_shapes_it_refuses():
    q, k, v = _qkv(heads=4)
    with pytest.raises(ValueError, match=r"k \(2, 128, 4, 8\) differ in width"):
        flash_attention(q, k[..., :8], v)
    with pytest.raises(ValueError, match=r"4 query heads.*4 / 2 heads"):
        flash_attention(q, k, v[:, :, :2])


def test_flash_refuses_head_counts_that_do_not_group():
    q, k, v = _qkv(heads=6)
    with pytest.raises(ValueError, match="6 query heads"):
        flash_attention(q, k[:, :, :4], v[:, :, :4])


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
    ``tensor``); ``flash_counts`` (and a window's ``swa_counts``) come out
    exactly when the kernels ran."""
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
    assert "flash_counts" not in plain_aux and "swa_counts" not in plain_aux

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
    assert {k: int(v) for k, v in aux["flash_counts"].items()} == {
        "flash_grid_steps": 2 * 2 * heads * 6,
        "flash_tiles_computed": 2 * 2 * heads * tiles}
    assert ("swa_counts" in aux) == (form == "window")
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        ref = np.asarray(flat_want[path])
        np.testing.assert_allclose(
            np.asarray(leaf), ref, rtol=2e-3,
            atol=2e-4 * max(float(np.abs(ref).max()), 1e-6),
            err_msg=jax.tree_util.keystr(path))


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


def test_grouped_matmul_refuses_an_unknown_impl():
    from tensorflowonspark_tpu.ops import grouped_matmul

    with pytest.raises(ValueError, match="impl"):
        grouped_matmul(jnp.zeros((8, 8)), jnp.zeros((1, 8, 8)),
                       jnp.asarray([8], jnp.int32), impl="cuda")


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
    outputs, and their cotangents): the layer's output and the gradients of
    tokens, weights and expert weights are finite and equal to the XLA
    path's, which is never poisoned."""
    import importlib

    from tensorflowonspark_tpu.parallel import ep

    gm = importlib.import_module("tensorflowonspark_tpu.ops.grouped_matmul")
    real = gm.grouped_matmul

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
