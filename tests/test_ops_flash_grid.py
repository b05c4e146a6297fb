"""The flash kernels' grids (interpret mode on the CPU mesh): a window's band,
the triangle's listed tiles, the list SMEM would not hold, and the shapes the
kernels refuse.  The kernels against the reference contraction are in
``tests/test_ops.py``, whose rows and shapes these take."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops import flash_attention, flash_attention_lse
from tensorflowonspark_tpu.parallel import ring

from test_ops import HEAD_SHAPES, _qkv, _selected_bits




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
