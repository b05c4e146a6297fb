"""Chunked gated delta rule (Gated DeltaNet, arXiv:2412.06464) as pallas TPU
kernels, forward and backward.

The function, a head with keys of width ``dk``, values of width ``dv`` and a
state ``S [dk, dv]`` that is zero before the row::

    S_t = a_t S_{t-1} + b_t k_t^T (v_t - a_t k_t S_{t-1}),   a_t = exp(g_t)
    o_t = q_t S_t

(the transpose of ``S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T``
with ``S [dv, dk]``: a position's write is corrected by what the decayed
state already answers for its key).  ``q, k [batch, T, H, dk]`` as the
caller made them (Gated DeltaNet: each head's L2-normalised, ``q`` times
``dk ** -0.5``), ``v [batch, T, H, dv]``, ``g`` (the log of the decay, ``<=
0``) and ``beta`` (``b``, in ``(0, 2)``) ``[batch, T, H]`` float32 -> ``o
[batch, T, H, dv]`` in ``v``'s dtype.

**The chunked form.**  With ``c_t`` the sum of ``g`` from the start of
``t``'s chunk of ``L`` positions up to ``t`` (float32), ``D = exp(c_t -
c_s)`` over the pairs ``t >= s`` and ``S_in`` the state that enters the
chunk, the chunk's writes ``U [L, dv]`` (``S_t = exp(c_t) S_in + sum_{s <=
t} exp(c_t - c_s) k_s^T u_s``) solve a unit lower-triangular system, and
everything else is a matrix product::

    A      = strict_lower(diag(b) (K K^T * D))              [L, L]   (1)
    R      = V - exp(c) * (K S_in)                          [L, dv]  (2)
    U      = (I + A)^-1 (b * R)                             [L, dv]  (3)
    o      = exp(c) * (Q S_in) + (Q K^T * D) U              [L, dv]  (4) (5)
    S_out  = exp(c_L) S_in + (exp(c_L - c) * K)^T U         [dk, dv] (6)

and the recurrence runs over ``T / L`` chunk states, not over ``T``
positions.  No array is ``[T, T]`` and none is ``[T, dk, dv]``: the only
state that reaches HBM is ``S_in`` of each chunk, ``[batch, H, T / L, dk,
dv]`` in ``v``'s dtype.  Every exponent is ``<= 0``: nothing is divided by a
decay.

**The inverse** ``(I + A)^-1`` is taken in float32 by block substitution
with matrix products alone: it is ``I - A`` on the diagonal blocks of two
positions, and two neighbouring diagonal blocks of ``s`` merge as ``[[T_1,
0], [-T_2 A_21 T_1, T_2]]``, for all of them at once ``T <- T - T A_off T``
with ``A_off`` the off-diagonal ``s``-blocks of the ``2 s``-blocks: ``2
(log2 L - 1)`` products of ``[L, L]``, which is why ``L`` is a power of two.
(The series ``sum (-A)^n`` in its doubling form costs as many and loses the
result in cancellation where keys repeat under ``b`` near 2, when ``A``'s
entries near 2 give powers of ``10^6`` for an inverse of entries near 1.)

**The kernels.**  One grid step is one chunk of a few heads (the largest
divisor of ``H`` up to ``HEADS_A_STEP``: a chunk of one head is 10 MFLOP,
less than a grid step's own cost, and the heads' products interleave); the
heads' states lie in a float32 VMEM scratch ``[heads, dk, dv]`` that the
grid carries from a chunk to the next (the chunk axis is the grid's last and
``arbitrary``, as in :mod:`~tensorflowonspark_tpu.ops.ssd_scan`).  ``q``,
``k``, ``v`` and ``o`` are taken head-major, ``[batch, H, T, width]``, so
that a head's block is whole in its last dimension whatever the width (96
and 192 are no multiples of the 128 lanes, and a slice of a lane-merged
``[T, H * dk]`` row would start inside a tile); the transposes are XLA's,
outside.  The decays' cumulative sums are taken in float32 outside the
kernel and come in twice, positions on sublanes and on lanes, because ``D``
needs a column and a row.  Products take operands of ``v``'s dtype and
accumulate in float32; the decays, ``A``, its inverse's products
(``Precision.HIGHEST``), the state and everything element-wise are float32.

**The backward** is chunked too, one kernel over the chunks in reverse that
carries ``dS`` as the forward carries ``S``, reads each chunk's ``S_in`` as
the forward wrote it (**the chunk states are kept, not recomputed**) and
makes ``A``'s inverse and ``U`` again.  With ``dU = (Q K^T * D)^T do +
(exp(c_L - c) * K) dS_out`` and ``dW = (I + A)^-T dU``::

    dv    = b * dW
    dA    = -strict_lower(dW U^T)        (d (I + A)^-1 = -T^T . T^T)
    db    = sum_v (dW * R) + sum_s (dA * K K^T * D)
    dq    = ((do U^T) * D) K + exp(c) * (do S_in^T)
    dk    = ((do U^T) * D)^T Q + (dK' + dK'^T) K,   dK' = dA * b * D
            + exp(c_L - c) * (U dS_out^T) - exp(c) * (dv S_in^T)
    dS_in = Q^T (exp(c) * do) - K^T (exp(c) * dv) + exp(c_L) dS_out
    d c_t = sum_v do o - sum_v dv v  +  [t = L] sum (dS_out * S_out)

(``o_t`` is proportional to ``exp(c_t)`` and ``v_s`` only ever appears as
``exp(-c_s) v_s``, which is where the last line comes from; its two sums
over ``dv`` are taken outside, as flash attention's ``delta`` is).  ``d g``
is the reversed cumulative sum of ``d c`` inside each chunk.

Under a checkpoint (``TransformerLM(remat=True)``): the forward rule names
the kernel's two results ``KEPT_OUT`` and ``KEPT_STATES``
(``jax.ad_checkpoint.checkpoint_name``), so a policy of
``save_only_these_names(*KEPT)`` keeps what the backward kernel reads and
the recomputed block holds no forward kernel.

``T`` must be a multiple of ``chunk``, and ``chunk`` a power of two: a
``ValueError`` names both where not (pad upstream).  Off the TPU the default
is ``impl="xla"``: the same chunk functions, forward and backward, under
``jax.vmap`` over the heads and ``lax.scan`` over the chunks, in the same
``custom_vjp``; ``impl="pallas", interpret=True`` runs the kernels in
interpret mode, which is what the tests compare with it and with the
recurrence position by position.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

NEG_INF = -1e30
HEADS_A_STEP = 5
# the residuals a checkpoint policy may keep by name (module docstring)
KEPT_OUT, KEPT_STATES = "delta_out", "delta_states"
KEPT = (KEPT_OUT, KEPT_STATES)

_AB = (((1,), (0,)), ((), ()))        # a @ b
_AB_T = (((1,), (1,)), ((), ()))      # a @ b.T
_AT_B = (((0,), (0,)), ((), ()))      # a.T @ b


def _default_impl():
    """The kernels exactly when the process's platform is ``tpu`` (as
    ``ssd_scan._default_impl``); the ``jax.numpy`` form elsewhere."""
    from tensorflowonspark_tpu.device_info import is_tpu_device

    return "pallas" if is_tpu_device() else "xla"


def chunk_counts(k, v, chunk):
    """``(chunks, state_bytes)`` of one call: the chunks of its rows, and
    the bytes of the chunk states that the forward kernel writes."""
    batch, seq, heads, dk = k.shape
    chunks = batch * (seq // chunk)
    return chunks, chunks * heads * dk * v.shape[3] * v.dtype.itemsize


def _local_cumsum(g, chunk):
    """``c [batch, T, H]``: float32 sums of ``g`` from the start of each
    position's chunk up to it."""
    batch, seq, heads = g.shape
    return jnp.cumsum(
        g.astype(jnp.float32).reshape(batch, seq // chunk, chunk, heads),
        axis=2).reshape(batch, seq, heads)


# ---------------------------------------------------------------------------
# one chunk of one head: what the kernels and the jax.numpy form both run
# ---------------------------------------------------------------------------

def _dot(a, b, dims=_AB, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _pairs(chunk):
    """Iotas over the rows and the columns of ``[chunk, chunk]``."""
    return (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0),
            lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular float32 ``a [L, L]``,
    ``L`` a power of two, by block substitution (module docstring)."""
    chunk = a.shape[0]
    row, col = _pairs(chunk)
    hi = lax.Precision.HIGHEST
    t = jnp.where(row == col, 1.0, 0.0) - jnp.where(
        (row >> 1) == (col >> 1), a, 0.0)
    bits = 1
    while (2 << bits) <= chunk:     # blocks of 2 ** bits merge two by two
        off = jnp.where(((row >> (bits + 1)) == (col >> (bits + 1)))
                        & ((row >> bits) != (col >> bits)), a, 0.0)
        t = t - _dot(_dot(t, off, precision=hi), t, precision=hi)
        bits += 1
    return t


def _chunk_terms(k, v, c_col, c_row, beta, s_in):
    """What both passes make of a chunk first: ``D`` (0 above the diagonal),
    ``K K^T``, the inverse ``T``, ``exp(c)``, ``exp(c_L - c)``, ``exp(c_L)``
    (a column ``[dk, 1]``), ``R`` and ``U`` (float32), and ``S_in`` in the
    operands' dtype."""
    dtype, f32 = v.dtype, jnp.float32
    chunk = k.shape[0]
    row, col = _pairs(chunk)
    decay = jnp.exp(jnp.where(row >= col, c_col - c_row, NEG_INF))
    kk = _dot(k, k, _AB_T)
    t = _unit_lower_inverse(jnp.where(row > col, beta * kk * decay, 0.0))
    grown = jnp.exp(c_col)
    # c_L as a sum: a slice from sublane L - 1 would not broadcast both ways
    last = jnp.sum(jnp.where(row[:, :1] == chunk - 1, c_col, 0.0), axis=0,
                   keepdims=True)
    s_c = s_in.astype(dtype)
    r = v.astype(f32) - grown * _dot(k, s_c)
    u = _dot(t.astype(dtype), (beta * r).astype(dtype))
    return (row > col, decay, kk, t, grown, jnp.exp(last - c_col),
            jnp.exp(jnp.broadcast_to(last, (s_in.shape[0], 1))), r, u, s_c)


def _chunk_fwd(q, k, v, c_col, c_row, beta, s_in):
    """``(o [L, dv] float32, S_out [dk, dv] float32)`` of one chunk of one
    head: ``q, k [L, dk]``, ``v [L, dv]``, the decays' sums as a column ``[L,
    1]`` and a row ``[1, L]``, ``beta [L, 1]``, ``S_in [dk, dv]`` float32."""
    dtype, f32 = v.dtype, jnp.float32
    _, decay, _, _, grown, reach, kept, _, u, s_c = _chunk_terms(
        k, v, c_col, c_row, beta, s_in)
    u = u.astype(dtype)
    inside = (_dot(q, k, _AB_T) * decay).astype(dtype)
    o = grown * _dot(q, s_c) + _dot(inside, u)
    into = (k.astype(f32) * reach).astype(dtype)
    return o, kept * s_in + _dot(into, u, _AT_B)


def _chunk_bwd(q, k, v, c_col, c_row, beta, s_in, do, ds_out, s_out):
    """``(dq, dk, dv, d beta [L, 1], dS_in, sum(dS_out * S_out) [1, 1])``,
    all float32, of one chunk of one head (the module docstring's
    backward); ``do [L, dv]``, ``dS_out`` and ``S_out [dk, dv]`` float32."""
    dtype, f32 = v.dtype, jnp.float32
    strict, decay, kk, t, grown, reach, kept, r, u, s_c = _chunk_terms(
        k, v, c_col, c_row, beta, s_in)
    u = u.astype(dtype)
    ds_c = ds_out.astype(dtype)
    inside = (_dot(q, k, _AB_T) * decay).astype(dtype)
    into = (k.astype(f32) * reach).astype(dtype)
    d_u = _dot(inside, do, _AT_B) + _dot(into, ds_c)
    d_w = _dot(t.astype(dtype), d_u.astype(dtype), _AT_B)
    d_v = beta * d_w
    d_a = jnp.where(strict, -_dot(d_w.astype(dtype), u, _AB_T), 0.0)
    d_beta = (jnp.sum(d_w * r, axis=1, keepdims=True)
              + jnp.sum(d_a * kk * decay, axis=1, keepdims=True))
    d_inside = (_dot(do, u, _AB_T) * decay).astype(dtype)
    d_kk = (d_a * beta * decay).astype(dtype)
    d_q = _dot(d_inside, k) + grown * _dot(do, s_c, _AB_T)
    d_k = (_dot(d_inside, q, _AT_B) + _dot(d_kk, k) + _dot(d_kk, k, _AT_B)
           + reach * _dot(u, ds_c, _AB_T)
           - grown * _dot(d_v.astype(dtype), s_c, _AB_T))
    d_s = (_dot(q, (grown * do.astype(f32)).astype(dtype), _AT_B)
           - _dot(k, (grown * d_v).astype(dtype), _AT_B) + kept * ds_out)
    at_end = jnp.sum(jnp.sum(ds_out * s_out, axis=1, keepdims=True),
                     axis=0, keepdims=True)
    return d_q, d_k, d_v, d_beta, d_s, at_end


# ---------------------------------------------------------------------------
# the operands' layout, shared by the two forms
# ---------------------------------------------------------------------------

def _heads_a_step(heads):
    return max(n for n in range(1, HEADS_A_STEP + 1) if heads % n == 0)


def _head_major(x):
    return x.transpose(0, 2, 1, 3)


def _columns(x, chunk, per):
    """``x [batch, T, H]`` float32 as ``[batch, H / per, T / chunk, chunk,
    per]``: a chunk's positions on sublanes, a step's heads on lanes."""
    batch, seq, heads = x.shape
    return x.astype(jnp.float32).reshape(
        batch, seq // chunk, chunk, heads // per, per).transpose(
            0, 3, 1, 2, 4)


def _positions(cols):
    """:func:`_columns`'s layout back to ``[batch, T, H]``."""
    batch, groups, n, chunk, per = cols.shape
    return cols.transpose(0, 2, 3, 1, 4).reshape(batch, n * chunk,
                                                 groups * per)


def _layouts(q, k, v, g, beta, chunk):
    """The operands of both forms: ``q``, ``k``, ``v`` head-major, the
    decays' local sums with the positions on sublanes and on lanes, ``beta``
    on sublanes (:func:`_columns`)."""
    per = _heads_a_step(k.shape[2])
    c_col = _columns(_local_cumsum(g, chunk), chunk, per)
    return (_head_major(q), _head_major(k), _head_major(v), c_col,
            c_col.swapaxes(3, 4), _columns(beta, chunk, per))


# ---------------------------------------------------------------------------
# the jax.numpy form
# ---------------------------------------------------------------------------

def _by_chunk(x, chunk):
    """``[batch, H, T, w]`` as ``[batch, H, T / chunk, chunk, w]``."""
    return x.reshape(x.shape[:2] + (x.shape[2] // chunk, chunk, x.shape[3]))


def _by_head(cols):
    """:func:`_columns`'s layout as ``[batch, H, T / chunk, chunk, 1]``."""
    batch, groups, n, chunk, per = cols.shape
    return cols.transpose(0, 1, 4, 2, 3).reshape(
        batch, groups * per, n, chunk, 1)


def _forward_xla(q, k, v, c_col, c_row, beta, chunk):
    def head(q, k, v, c, beta):
        def step(s_in, at):
            q, k, v, c, beta = at
            o, s_out = _chunk_fwd(q, k, v, c, c.T, beta, s_in)
            return s_out, (o.astype(v.dtype), s_in.astype(v.dtype))

        zero = jnp.zeros((k.shape[-1], v.shape[-1]), jnp.float32)
        return lax.scan(step, zero, (q, k, v, c, beta))[1]

    o, states = jax.vmap(jax.vmap(head))(
        _by_chunk(q, chunk), _by_chunk(k, chunk), _by_chunk(v, chunk),
        _by_head(c_col), _by_head(beta))
    return o.reshape(v.shape), states


def _backward_xla(q, k, v, c_col, c_row, beta, states, do, chunk):
    per = c_col.shape[4]

    def head(q, k, v, c, beta, states, do):
        def step(carried, at):
            ds_out, s_out = carried
            q, k, v, c, beta, s_in, do = at
            s_in = s_in.astype(jnp.float32)
            dq, dk, dv, db, ds_in, at_end = _chunk_bwd(
                q, k, v, c, c.T, beta, s_in, do, ds_out, s_out)
            end = jnp.zeros_like(db).at[-1:].set(at_end)
            return (ds_in, s_in), (dq.astype(q.dtype), dk.astype(k.dtype),
                                   dv.astype(v.dtype), db, end)

        zero = jnp.zeros(states.shape[1:], jnp.float32)
        return lax.scan(step, (zero, zero), (q, k, v, c, beta, states, do),
                        reverse=True)[1]

    dq, dk, dv, db, end = jax.vmap(jax.vmap(head))(
        _by_chunk(q, chunk), _by_chunk(k, chunk), _by_chunk(v, chunk),
        _by_head(c_col), _by_head(beta), states, _by_chunk(do, chunk))

    def columns(x):     # [batch, H, n, chunk, 1] back to _columns's layout
        batch, heads, n = x.shape[:3]
        return x.reshape(batch, heads // per, per, n, chunk).transpose(
            0, 1, 3, 4, 2)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            columns(db), columns(end))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _into_column(columns, j, value):
    """``columns [L, per]`` with column ``j`` set to ``value [L, 1]``."""
    head = lax.broadcasted_iota(jnp.int32, columns.shape, 1)
    return jnp.where(head == j, value, columns)


def _fwd_kernel(q_ref, k_ref, v_ref, ccol_ref, crow_ref, beta_ref, o_ref,
                states_ref, state):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    c_col, c_row, beta = ccol_ref[...], crow_ref[...], beta_ref[...]
    for j in range(q_ref.shape[0]):
        s_in = state[j]
        states_ref[j] = s_in.astype(states_ref.dtype)
        o, s_out = _chunk_fwd(q_ref[j], k_ref[j], v_ref[j],
                              c_col[:, j:j + 1], c_row[j:j + 1, :],
                              beta[:, j:j + 1], s_in)
        o_ref[j] = o.astype(o_ref.dtype)
        state[j] = s_out


def _bwd_kernel(q_ref, k_ref, v_ref, ccol_ref, crow_ref, beta_ref, states_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dbeta_ref, end_ref, d_state,
                state_out):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)     # the row's last chunk: nothing after
    def _():
        d_state[...] = jnp.zeros_like(d_state)
        state_out[...] = jnp.zeros_like(state_out)

    c_col, c_row, beta = ccol_ref[...], crow_ref[...], beta_ref[...]
    chunk = c_col.shape[0]
    is_last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    d_beta = jnp.zeros(c_col.shape, jnp.float32)
    end = jnp.zeros(c_col.shape, jnp.float32)
    for j in range(q_ref.shape[0]):
        s_in = states_ref[j].astype(jnp.float32)
        dq, dk, dv, db, ds_in, at_end = _chunk_bwd(
            q_ref[j], k_ref[j], v_ref[j], c_col[:, j:j + 1],
            c_row[j:j + 1, :], beta[:, j:j + 1], s_in, do_ref[j], d_state[j],
            state_out[j])
        dq_ref[j] = dq.astype(dq_ref.dtype)
        dk_ref[j] = dk.astype(dk_ref.dtype)
        dv_ref[j] = dv.astype(dv_ref.dtype)
        d_beta = _into_column(d_beta, j, db)
        end = _into_column(end, j, jnp.where(is_last, at_end, 0.0))
        d_state[j] = ds_in
        state_out[j] = s_in
    dbeta_ref[...] = d_beta
    end_ref[...] = end


def _specs(k, v, chunk, at):
    """BlockSpecs of one chunk of one step's heads; ``at(n)`` the chunk
    that grid step ``n`` of the last axis takes."""
    from jax.experimental import pallas as pl

    heads, dk = k.shape[1], k.shape[3]
    per, dv = _heads_a_step(heads), v.shape[3]

    def rows(width):
        return pl.BlockSpec((None, per, chunk, width),
                            lambda i, h, n: (i, h, at(n), 0))

    col = pl.BlockSpec((None, None, None, chunk, per),
                       lambda i, h, n: (i, h, at(n), 0, 0))
    row = pl.BlockSpec((None, None, None, per, chunk),
                       lambda i, h, n: (i, h, at(n), 0, 0))
    states = pl.BlockSpec((None, per, None, dk, dv),
                          lambda i, h, n: (i, h, at(n), 0, 0))
    return rows(dk), rows(dv), col, row, states


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward_pallas(q, k, v, c_col, c_row, beta, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq, dk = k.shape
    per, dv, n = _heads_a_step(heads), v.shape[3], seq // chunk
    narrow, wide, col, row, states = _specs(k, v, chunk, lambda n: n)
    return pl.pallas_call(
        _fwd_kernel, grid=(batch, heads // per, n),
        in_specs=[narrow, narrow, wide, col, row, col],
        out_specs=[wide, states],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((batch, heads, n, dk, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((per, dk, dv), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="gated_delta_fwd")(q, k, v, c_col, c_row, beta)


def _backward_pallas(q, k, v, c_col, c_row, beta, states, do, chunk,
                     interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq, dk = k.shape
    per, dv, n = _heads_a_step(heads), v.shape[3], seq // chunk
    narrow, wide, col, row, kept = _specs(k, v, chunk, lambda i: n - 1 - i)
    by_head = jax.ShapeDtypeStruct(c_col.shape, jnp.float32)
    return pl.pallas_call(
        _bwd_kernel, grid=(batch, heads // per, n),
        in_specs=[narrow, narrow, wide, col, row, col, kept, wide],
        out_specs=[narrow, narrow, wide, col, col],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), by_head, by_head],
        scratch_shapes=[pltpu.VMEM((per, dk, dv), jnp.float32),
                        pltpu.VMEM((per, dk, dv), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="gated_delta_bwd")(q, k, v, c_col, c_row, beta, states, do)


# ---------------------------------------------------------------------------
# the function
# ---------------------------------------------------------------------------

def _forward(q, k, v, g, beta, chunk, impl, interpret):
    """``(o [batch, H, T, dv], states [batch, H, T / chunk, dk, dv])``."""
    operands = _layouts(q, k, v, g, beta, chunk)
    if impl == "xla":
        return _forward_xla(*operands, chunk)
    return _forward_pallas(*operands, chunk, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _delta(q, k, v, g, beta, chunk, impl, interpret):
    return _head_major(_forward(q, k, v, g, beta, chunk, impl, interpret)[0])


def _delta_vjp_fwd(q, k, v, g, beta, chunk, impl, interpret):
    o, states = _forward(q, k, v, g, beta, chunk, impl, interpret)
    o = checkpoint_name(o, KEPT_OUT)
    states = checkpoint_name(states, KEPT_STATES)
    return _head_major(o), (q, k, v, g, beta, o, states)


def _delta_vjp_bwd(chunk, impl, interpret, residual, do):
    q, k, v, g, beta, o, states = residual
    operands = _layouts(q, k, v, g, beta, chunk)
    do = _head_major(do)
    if impl == "xla":
        dq, dk, dv, d_beta, end = _backward_xla(*operands, states, do, chunk)
    else:
        dq, dk, dv, d_beta, end = _backward_pallas(*operands, states, do,
                                                   chunk, interpret)
    f32 = jnp.float32
    d_c = _positions(end) + (
        do.astype(f32) * o.astype(f32)
        - dv.astype(f32) * operands[2].astype(f32)).sum(-1).swapaxes(1, 2)
    # d g: the sum of d c from each position to its chunk's end
    batch, seq, heads = g.shape
    d_g = jnp.flip(jnp.cumsum(jnp.flip(
        d_c.reshape(batch, seq // chunk, chunk, heads), 2), axis=2), 2)
    return (_head_major(dq), _head_major(dk), _head_major(dv),
            d_g.reshape(g.shape).astype(g.dtype),
            _positions(d_beta).astype(beta.dtype))


_delta.defvjp(_delta_vjp_fwd, _delta_vjp_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk=64, impl=None, interpret=False):
    """``o [batch, T, H, dv]`` of the gated delta rule in the module
    docstring, in ``v``'s dtype; differentiable in all five operands.

    ``impl``: ``"pallas"`` (the kernels; ``interpret=True`` off the TPU) or
    ``"xla"`` (the same chunk functions in ``jax.numpy``); None picks the
    kernels on a TPU and ``jax.numpy`` elsewhere.  Both take any widths and
    any number of heads; the row must be a multiple of ``chunk`` and
    ``chunk`` a power of two."""
    batch, seq, heads, _ = k.shape
    if chunk < 2 or chunk & (chunk - 1) or seq % chunk:
        raise ValueError(
            "gated_delta_rule: rows of {} positions do not divide into "
            "chunks of {} (a power of two): pad upstream".format(seq, chunk))
    if q.shape != k.shape or v.shape[:3] != k.shape[:3] \
            or g.shape != k.shape[:3] or beta.shape != g.shape:
        raise ValueError(
            "gated_delta_rule: q {} k {} v {} g {} beta {} are not one "
            "layer's".format(q.shape, k.shape, v.shape, g.shape, beta.shape))
    if impl is None:
        impl = _default_impl()
    if impl not in ("xla", "pallas"):
        raise ValueError("unknown gated_delta_rule impl {!r}".format(impl))
    q, k = q.astype(v.dtype), k.astype(v.dtype)
    return _delta(q, k, v, g, beta, chunk, impl, interpret)
