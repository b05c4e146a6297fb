"""``ops/gated_delta`` at small sizes on the CPU: its ``jax.numpy`` form and,
under the interpreter, its pallas kernels against the recurrence position by
position (forward and ``jax.grad`` of all five operands), write strengths in
(1, 2), keys that repeat, the chunk's inverse alone, what is refused, what a
checkpoint keeps, and that no array is as long as the row twice."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.ops import gated_delta as gd


def _position_by_position(q, k, v, g, beta):
    """``S_t = exp(g_t) S_{t-1} + b_t k_t^T (v_t - exp(g_t) k_t S_{t-1})``,
    ``o_t = q_t S_t``, from a zero state ``[B, H, dk, dv]``."""
    def step(state, at):
        qt, kt, vt, gt, bt = at
        state = state * jnp.exp(gt)[..., None, None]
        answered = jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + kt[..., :, None] * ((vt - answered)
                                            * bt[..., None])[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    zero = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[3:], jnp.float32)
    _, o = lax.scan(step, zero, tuple(
        t.swapaxes(0, 1) for t in (q, k, v, g, beta)))
    return o.swapaxes(0, 1)


def _operands(seed, batch, seq, heads, dk, dv, low=0.0):
    """Unit keys, queries of ``dk ** -0.5``, decays of softplus steps, write
    strengths in ``(low, 2)``, and a cotangent for ``o``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key):
        x = jax.random.normal(key, (batch, seq, heads, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, heads)))
    beta = low + (2.0 - low) * jax.nn.sigmoid(
        jax.random.normal(ks[4], (batch, seq, heads)))
    return (unit(ks[0]) * dk ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (batch, seq, heads, dv)), g, beta,
            jax.random.normal(ks[5], (batch, seq, heads, dv)))


# name -> (batch, rows, heads, dk, dv, chunk, the least write strength)
CASES = {
    "one head, four chunks": (1, 64, 1, 16, 32, 16, 0.0),
    "three heads a step": (2, 128, 3, 12, 24, 32, 0.0),
    "chunks of 64": (1, 128, 2, 16, 32, 64, 0.0),
    "two steps of five heads": (1, 32, 10, 8, 16, 16, 0.0),
    "b in (1, 2)": (2, 96, 2, 12, 24, 32, 1.0),
}
IMPLS = {"xla": dict(impl="xla"),
         "pallas": dict(impl="pallas", interpret=True)}


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_rule_is_the_recurrence(case, impl):
    batch, seq, heads, dk, dv, chunk, low = CASES[case]
    q, k, v, g, beta, _ = _operands(0, batch, seq, heads, dk, dv, low)
    want = _position_by_position(q, k, v, g, beta)
    got = gd.gated_delta_rule(q, k, v, g, beta, chunk=chunk, **IMPLS[impl])
    assert got.shape == want.shape and got.dtype == v.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if low:
        assert float(beta.min()) > 1.0


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_backward_is_the_recurrences_gradient(case, impl):
    """Every operand's gradient within 2e-5 of its largest element."""
    batch, seq, heads, dk, dv, chunk, low = CASES[case]
    *operands, weigh = _operands(1, batch, seq, heads, dk, dv, low)

    def loss(fn):
        return lambda *a: (fn(*a) * weigh).sum()

    want = jax.grad(loss(_position_by_position), argnums=range(5))(*operands)
    got = jax.grad(loss(functools.partial(
        gd.gated_delta_rule, chunk=chunk, **IMPLS[impl])),
        argnums=range(5))(*operands)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        scale = float(jnp.abs(b).max())
        assert a.shape == b.shape and scale > 0
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_keys_that_repeat_under_b_near_two_stay_the_recurrence(impl):
    """One key for the whole row, no decay, b = 1.9: ``A`` is 1.9 below the
    diagonal, its powers reach ``10^17`` and its inverse's entries stay under
    2; block substitution keeps float32's digits where the series would
    not."""
    q, k, v, g, beta, _ = _operands(2, 1, 64, 1, 8, 16)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.zeros_like(g), jnp.full_like(beta, 1.9)
    want = _position_by_position(q, k, v, g, beta)
    got = gd.gated_delta_rule(q, k, v, g, beta, chunk=64, **IMPLS[impl])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("chunk", [2, 4, 16, 64])
def test_the_inverse_by_block_substitution(chunk):
    a = np.tril(np.random.RandomState(chunk).uniform(-2, 2, (chunk, chunk)),
                -1).astype(np.float32)
    got = gd._unit_lower_inverse(jnp.asarray(a))
    want = np.linalg.inv(np.eye(chunk) + a.astype(np.float64))
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=1e-5 * np.abs(want).max())


def test_bfloat16_kernels_are_the_bfloat16_jax_numpy_form():
    """The two forms run the same chunk functions: in bfloat16 they differ
    by the order of a sum at most."""
    operands = [t.astype(jnp.bfloat16) if t.ndim == 4 else t
                for t in _operands(3, 1, 64, 2, 16, 32)[:5]]
    a = gd.gated_delta_rule(*operands, chunk=32, impl="xla")
    b = gd.gated_delta_rule(*operands, chunk=32, impl="pallas",
                            interpret=True)
    assert a.dtype == b.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=2e-2)
    want = _position_by_position(*[t.astype(jnp.float32) for t in operands])
    assert float(jnp.abs(a.astype(jnp.float32) - want).max()) < 0.05 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("wrong,match", [
    (dict(chunk=48), "power of two"), (dict(chunk=128), "do not divide"),
    (dict(chunk=1), "power of two"), (dict(impl="triton"), "unknown"),
    (dict(beta=None), "not one layer's")])
def test_what_is_refused(wrong, match):
    """A row that is no multiple of the chunk, a chunk that is no power of
    two, another implementation, operands of different layers."""
    q, k, v, g, beta, _ = _operands(4, 1, 96, 2, 8, 16)
    if "beta" in wrong:
        beta, wrong = beta[:, :32], {}
    with pytest.raises(ValueError, match=match):
        gd.gated_delta_rule(q, k, v, g, beta, **dict(dict(chunk=32), **wrong))


def test_the_checkpoint_policy_keeps_the_kernels_results():
    """Under ``save_only_these_names(*KEPT)`` the recomputed pass holds no
    forward kernel: the backward kernel reads the kept output and states."""
    operands = _operands(5, 1, 64, 2, 16, 32)[:5]

    def loss(*a):
        return gd.gated_delta_rule(*a, chunk=32, impl="pallas",
                                   interpret=True).sum()

    kept = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(*gd.KEPT))
    text = str(jax.make_jaxpr(jax.grad(kept))(*operands))
    assert text.count("name=gated_delta_fwd") == 1
    assert text.count("name=gated_delta_bwd") == 1
    again = str(jax.make_jaxpr(jax.grad(jax.checkpoint(loss)))(*operands))
    assert again.count("name=gated_delta_fwd") == 2
    from tensorflowonspark_tpu import ops

    assert set(gd.KEPT) <= set(ops.KEPT)


def _shapes(jaxpr, found):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            found.add(tuple(getattr(var.aval, "shape", ())))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _shapes(inner, found)
    return found


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_no_array_is_the_row_twice_nor_a_state_a_position(impl):
    """Rows of 512 in chunks of 16: nothing in the forward or the backward
    pass has two dimensions of the row's length, and nothing has as many
    elements as a state a position; the chunk states are ``T / L`` of
    them."""
    seq, dk, dv = 512, 8, 16
    *operands, weigh = _operands(6, 1, seq, 2, dk, dv)

    def loss(*a):
        return (gd.gated_delta_rule(*a, chunk=16, **IMPLS[impl])
                * weigh).sum()

    shapes = _shapes(jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(
        *operands).jaxpr, set())
    assert (1, 2, seq // 16, dk, dv) in shapes          # the chunk states
    for shape in shapes:
        assert sum(n >= seq for n in shape) < 2, shape
        assert int(np.prod(shape)) < seq * dk * dv, shape


def test_chunk_counts():
    k = jnp.zeros((3, 128, 5, 8), jnp.bfloat16)
    v = jnp.zeros((3, 128, 5, 16), jnp.bfloat16)
    assert gd.chunk_counts(k, v, 32) == (12, 12 * 5 * 8 * 16 * 2)
    assert [gd._heads_a_step(n) for n in (1, 2, 6, 7, 10, 15, 30)] == [
        1, 2, 3, 1, 5, 5, 5]
