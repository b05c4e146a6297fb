"""Chunked state-space scan (Mamba-2's state-space duality, arXiv:2405.21060)
as pallas TPU kernels, forward and backward.

The function, a head ``h`` of width ``P`` with a state ``S [P, N]`` that is
zero before the row (``B`` and ``C`` of group ``h // (H / G)``)::

    S_t = exp(log_decay_t) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t

``x [batch, T, H, P]``, ``dt`` and ``log_decay [batch, T, H]`` float32
(Mamba-2: ``dt = softplus(.)``, ``log_decay = dt * A`` with ``A < 0``; they
are two arguments so that the caller's own arithmetic carries ``A``'s
gradient), ``b`` and ``c [batch, T, G, N]`` -> ``y [batch, T, H, P]`` in
``x``'s dtype.  The skip ``D x`` is the caller's.

**The chunked form.**  With ``a_t`` the sum of ``log_decay`` from the start
of ``t``'s chunk of ``L`` positions up to ``t`` (float32), a chunk's output
is four matrix products::

    G      = C B^T                                   [L, L]   (1) a group's
    y      = (G * exp(a_l - a_s)[l >= s] * dt_s) x   [L, P]   (2) inside
           + exp(a_l) * (C S_in^T)                   [L, P]   (3) the carried
    S_out  = exp(a_L) S_in + (x * exp(a_L - a_s) dt_s)^T B    (4) [P, N]

and the recurrence runs over ``T / L`` chunk states, not over ``T``
positions.  No array is ``[T, T]`` and none is ``[T, P, N]``: the only
state that reaches HBM is ``S_in`` of each chunk, ``[batch, H, T / L, P,
N]`` in ``x``'s dtype (as many elements as ``x`` at ``L = N``).

**The kernels.**  One grid step is one chunk of one group: its ``H / G``
heads share (1), and their states lie stacked ``[(H / G) P, N]`` in a VMEM
scratch that the grid carries from a chunk to the next (the chunk axis is
the grid's last and ``arbitrary``; the first kernels here whose grid carries
anything).  Heads narrower than the 128 lanes are taken ``128 // P`` at a
time as one 128-lane slab of ``x``: a head's ``[L, L]`` weights multiply
the whole slab and a lane mask keeps its own columns, which costs the MXU
nothing (its tiles are 128 wide either way) and keeps every slice on a tile
boundary.  The decays' cumulative sums are taken in float32 outside the
kernel (XLA, a pass over ``[batch, T, H]``) and come in twice, positions on
sublanes and on lanes, because a head's ``exp(a_l - a_s)`` needs a column
and a row.  Products take operands of ``x``'s dtype and accumulate in
float32; the decays, the state and everything element-wise are float32.

**The backward** is chunked too, one kernel over the chunks in reverse that
carries ``dS`` as the forward carries ``S`` and reads each chunk's ``S_in``
as the forward wrote it: **the chunk states are kept, not recomputed** (a
state-only forward pass would read ``x`` and ``B`` again for half the
states' bytes).  With ``E_s = exp(a_L - a_s)``::

    dx    = dt_s * ((G * Lambda)^T dy + E_s (B dS_out^T))
    dB    = dG^T C + (x E_s dt_s) dS_out,   dC = dG B + (exp(a_l) dy) S_in
            with dG = (dy x^T) * Lambda * dt_s summed over the group's heads
    dS_in = exp(a_L) dS_out + (exp(a_l) dy)^T C
    d dt_s = sum_p (dx / dt_s) x
    d a_l  = sum_p dy y  -  dt_l (d dt_l)  +  [l = L] sum (dS_out * S_out)

(``y_l`` is proportional to ``exp(a_l)`` and ``x_s`` only ever appears as
``exp(-a_s) dt_s x_s``, which is where the last line's first two terms come
from; ``sum_p dy y`` is taken outside, as flash attention's ``delta`` is).
``d log_decay`` is the reversed cumulative sum of ``d a`` inside each chunk.

Under a checkpoint (``TransformerLM(remat=True)``): the forward rule names
the kernel's two results ``KEPT_OUT`` and ``KEPT_STATES``
(``jax.ad_checkpoint.checkpoint_name``), so a policy of
``save_only_these_names(*KEPT)`` keeps what the backward kernel reads and
the recomputed block holds no scan kernel; what the kernel reads besides
(``x``, ``B``, ``C``, the decays) is made again from the block's input.

``T`` must be a multiple of ``chunk``: a ``ValueError`` names both where it
is not (pad upstream).  Off the TPU the default is ``impl="xla"``, the same
chunked function in plain ``jax.numpy`` under ``jax.grad``;
``impl="pallas", interpret=True`` runs the kernels in interpret mode, which
is what the tests compare with it and with the recurrence position by
position.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

LANES = 128
NEG_INF = -1e30
# the residuals a checkpoint policy may keep by name (module docstring)
KEPT_OUT, KEPT_STATES = "ssd_out", "ssd_states"
KEPT = (KEPT_OUT, KEPT_STATES)


def _default_impl():
    """The kernels exactly when the process's platform is ``tpu`` (as
    ``grouped_matmul._default_impl``); the ``jax.numpy`` form elsewhere."""
    from tensorflowonspark_tpu.device_info import is_tpu_device

    return "pallas" if is_tpu_device() else "xla"


def chunk_counts(x, b, chunk):
    """``(chunks, state_bytes)`` of one call: the chunks of its rows, and
    the bytes of the chunk states that the forward kernel writes."""
    batch, seq, heads, width = x.shape
    chunks = batch * (seq // chunk)
    return chunks, chunks * heads * width * b.shape[3] * x.dtype.itemsize


def _local_cumsum(log_decay, chunk):
    """``a [batch, T, H]``: float32 sums of ``log_decay`` from the start of
    each position's chunk up to it."""
    batch, seq, heads = log_decay.shape
    return jnp.cumsum(
        log_decay.astype(jnp.float32).reshape(batch, seq // chunk, chunk,
                                              heads),
        axis=2).reshape(batch, seq, heads)


# ---------------------------------------------------------------------------
# the jax.numpy form
# ---------------------------------------------------------------------------

def _scan_xla(x, dt, log_decay, b, c, chunk):
    batch, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    n, per = seq // chunk, heads // groups
    dtype, f32 = x.dtype, jnp.float32
    xs = x.reshape(batch, n, chunk, groups, per, width)
    bs = b.reshape(batch, n, chunk, groups, state)
    cs = c.reshape(batch, n, chunk, groups, state)
    dts = dt.astype(f32).reshape(batch, n, chunk, groups, per)
    a = _local_cumsum(log_decay, chunk).reshape(batch, n, chunk, groups, per)
    scores = jnp.einsum("bnlgk,bnsgk->bngls", cs, bs,
                        preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal[:, :, None, None],
        a[:, :, :, None] - a[:, :, None], NEG_INF))     # [b, n, l, s, g, j]
    weights = (scores.transpose(0, 1, 3, 4, 2)[..., None] * decay
               * dts[:, :, None]).astype(dtype)
    inside = jnp.einsum("bnlsgj,bnsgjp->bnlgjp", weights, xs,
                        preferred_element_type=f32)
    last = a[:, :, -1]                                   # [b, n, g, j]
    into = (xs.astype(f32) * (jnp.exp(last[:, :, None] - a) * dts)[..., None]
            ).astype(dtype)
    local = jnp.einsum("bnlgjp,bnlgk->bngjpk", into, bs,
                       preferred_element_type=f32)

    def carry(state_in, chunk_terms):
        decay_all, added = chunk_terms
        return decay_all[..., None, None] * state_in + added, state_in

    _, states = lax.scan(
        carry, jnp.zeros(local.shape[:1] + local.shape[2:], f32),
        (jnp.exp(last).swapaxes(0, 1), local.swapaxes(0, 1)))
    states = states.swapaxes(0, 1).astype(dtype)         # S_in of each chunk
    carried = jnp.einsum("bnlgk,bngjpk->bnlgjp", cs, states,
                         preferred_element_type=f32) * jnp.exp(a)[..., None]
    return (inside + carried).astype(dtype).reshape(x.shape)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _slab(width):
    """Lanes of ``x`` taken at once, and the heads in them."""
    return max(width, LANES), max(LANES // width, 1)


def _pick(columns, index, width):
    """One array out of one a head: ``columns[i]`` wherever ``index`` (an
    iota over the slab's lanes, or over the stacked state's rows) lies in
    head ``i``'s ``width``."""
    out = columns[0]
    for i in range(1, len(columns)):
        out = jnp.where(index >= i * width, columns[i], out)
    return out


def _sum_all(x):
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


_AB_T = (((1,), (1,)), ((), ()))      # a @ b.T
_AT_B = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _chunk_terms(b_ref, c_ref, chunk, slab):
    """What both kernels make of a chunk before its heads: ``B``, ``C``,
    their scores ``C B^T`` (the group's), the causal mask of ``[chunk,
    chunk]``, and iotas over a slab's lanes and a stacked state's rows."""
    bm, cm = b_ref[...], c_ref[...]
    causal = (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
              >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    return (bm, cm, _dot(cm, bm, _AB_T), causal,
            lax.broadcasted_iota(jnp.int32, (chunk, slab), 1),
            lax.broadcasted_iota(jnp.int32, (slab, 1), 0))


def _head_decay(acol, arow, j, causal):
    """Head ``j``'s sums as a column, and ``exp(a_l - a_s)`` over the causal
    pairs of its chunk (0 elsewhere)."""
    a_c = acol[:, j:j + 1]
    return a_c, jnp.exp(jnp.where(causal, a_c - arow[j:j + 1, :], NEG_INF))


def _fwd_kernel(x_ref, b_ref, c_ref, acol_ref, arow_ref, dtcol_ref, dtrow_ref,
                y_ref, states_ref, state, *, width):
    from jax.experimental import pallas as pl

    chunk, stacked = x_ref.shape
    dtype, f32 = x_ref.dtype, jnp.float32
    slab, per = _slab(width)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    bm, cm, scores, causal, lane, sub = _chunk_terms(b_ref, c_ref, chunk,
                                                      slab)
    acol, arow = acol_ref[...], arow_ref[...]
    dtcol, dtrow = dtcol_ref[...], dtrow_ref[...]
    states_ref[...] = state[...].astype(dtype)
    for k in range(stacked // slab):
        rows = slice(k * slab, (k + 1) * slab)
        xk = x_ref[:, rows]
        inside, grown, into, kept = [], [], [], []
        for j in range(k * per, (k + 1) * per):
            a_c, decay = _head_decay(acol, arow, j, causal)
            weights = (scores * decay * dtrow[j:j + 1, :]).astype(dtype)
            inside.append(_dot(weights, xk))
            a_last = a_c[chunk - 1:chunk, :]
            grown.append(jnp.exp(a_c))
            into.append(jnp.exp(a_last - a_c) * dtcol[:, j:j + 1])
            kept.append(jnp.exp(a_last))
        s_in = state[rows, :]
        carried = _dot(cm, s_in.astype(dtype), _AB_T)
        y_ref[:, rows] = (_pick(inside, lane, width) + carried
                          * _pick(grown, lane, width)).astype(dtype)
        xs = (xk.astype(f32) * _pick(into, lane, width)).astype(dtype)
        state[rows, :] = (s_in * _pick(kept, sub, width)
                          + _dot(xs, bm, _AT_B))


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, acol_ref, arow_ref, dtcol_ref,
                dtrow_ref, dyy_ref, states_ref, dx_ref, db_ref, dc_ref,
                ddt_ref, da_ref, d_state, state_out, *, width):
    from jax.experimental import pallas as pl

    chunk, stacked = x_ref.shape
    heads = acol_ref.shape[1]
    dtype, f32 = x_ref.dtype, jnp.float32
    slab, per = _slab(width)

    @pl.when(pl.program_id(2) == 0)     # the row's last chunk: nothing after
    def _():
        d_state[...] = jnp.zeros_like(d_state)
        state_out[...] = jnp.zeros_like(state_out)

    bm, cm, scores, causal, lane, sub = _chunk_terms(b_ref, c_ref, chunk,
                                                      slab)
    head = lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)
    is_last = lax.broadcasted_iota(jnp.int32, (chunk, heads), 0) == chunk - 1
    acol, arow = acol_ref[...], arow_ref[...]
    dtcol, dtrow = dtcol_ref[...], dtrow_ref[...]
    d_scores = jnp.zeros((chunk, chunk), f32)
    d_b = jnp.zeros(b_ref.shape, f32)
    d_c = jnp.zeros(c_ref.shape, f32)
    d_dt = jnp.zeros((chunk, heads), f32)
    d_a = dyy_ref[...]
    for k in range(stacked // slab):
        rows = slice(k * slab, (k + 1) * slab)
        xk, dyk = x_ref[:, rows], dy_ref[:, rows]
        s_in = states_ref[rows, :]
        ds_out, s_out = d_state[rows, :], state_out[rows, :]
        ds_out_c = ds_out.astype(dtype)
        inside, grown, reach, steps, kept = [], [], [], [], []
        for i, j in enumerate(range(k * per, (k + 1) * per)):
            a_c, decay = _head_decay(acol, arow, j, causal)
            mine = (lane >= i * width) & (lane < (i + 1) * width)
            d_weights = _dot(jnp.where(mine, dyk, jnp.zeros_like(dyk)), xk,
                             _AB_T)
            d_scores += d_weights * decay * dtrow[j:j + 1, :]
            inside.append(_dot((scores * decay).astype(dtype), dyk, _AT_B))
            a_last = a_c[chunk - 1:chunk, :]
            grown.append(jnp.exp(a_c))
            reach.append(jnp.exp(a_last - a_c))
            steps.append(dtcol[:, j:j + 1])
            kept.append(jnp.exp(a_last))
        reach_k, dt_k = _pick(reach, lane, width), _pick(steps, lane, width)
        dx_pre = (_pick(inside, lane, width)
                  + _dot(bm, ds_out_c, _AB_T) * reach_k)
        dx_ref[:, rows] = (dx_pre * dt_k).astype(dtype)
        by_x = dx_pre * xk.astype(f32)
        both = ds_out * s_out
        for i, j in enumerate(range(k * per, (k + 1) * per)):
            mine = (lane >= i * width) & (lane < (i + 1) * width)
            d_step = jnp.sum(jnp.where(mine, by_x, 0.0), axis=1,
                             keepdims=True)
            d_dt = jnp.where(head == j, d_step, d_dt)
            at_end = _sum_all(jnp.where(
                (sub >= i * width) & (sub < (i + 1) * width), both, 0.0))
            d_a += jnp.where(head == j, jnp.where(is_last, at_end, 0.0)
                             - d_step * dtcol[:, j:j + 1], 0.0)
        dy_grown = (dyk.astype(f32) * _pick(grown, lane, width)).astype(dtype)
        d_c += _dot(dy_grown, s_in)
        xs = (xk.astype(f32) * reach_k * dt_k).astype(dtype)
        d_b += _dot(xs, ds_out_c)
        d_state[rows, :] = (ds_out * _pick(kept, sub, width)
                            + _dot(dy_grown, cm, _AT_B))
        state_out[rows, :] = s_in.astype(f32)
    d_scores = d_scores.astype(dtype)
    dc_ref[...] = (d_c + _dot(d_scores, bm)).astype(dc_ref.dtype)
    db_ref[...] = (d_b + _dot(d_scores, cm, _AT_B)).astype(db_ref.dtype)
    ddt_ref[...] = d_dt
    da_ref[...] = d_a


def _check(x, b):
    heads, width = x.shape[2:]
    groups, state = b.shape[2:]
    per = _slab(width)[1]
    if (heads // groups) % per or max(width, LANES) % min(width, LANES) \
            or state % LANES:
        raise ValueError(
            "ssd_scan kernels: heads of {} in groups of {} with a state of "
            "{} do not lie on 128-lane tiles".format(width, heads // groups,
                                                     state))


def _layouts(x, dt, log_decay, b, c, chunk):
    """The kernels' operands: ``x``, ``b``, ``c`` with their heads side by
    side on the lanes, and the decays' local sums and ``dt`` a group, once
    with the positions on sublanes ``[batch, G, T, H / G]`` and once on
    lanes ``[batch, G, H / G, T]``."""
    batch, seq, heads, width = x.shape
    groups = b.shape[2]

    def by_group(t):
        col = t.astype(jnp.float32).reshape(
            batch, seq, groups, heads // groups).transpose(0, 2, 1, 3)
        return col, col.swapaxes(2, 3)

    return (x.reshape(batch, seq, heads * width),
            b.reshape(batch, seq, -1), c.reshape(batch, seq, -1),
            by_group(_local_cumsum(log_decay, chunk)), by_group(dt))


def _specs(x, b, chunk, at):
    """BlockSpecs of one chunk of one group; ``at(n)`` the chunk that grid
    step ``n`` of the last axis takes."""
    from jax.experimental import pallas as pl

    heads, width = x.shape[2:]
    groups, state = b.shape[2:]
    per_group = heads // groups
    stacked = per_group * width
    wide = pl.BlockSpec((None, chunk, stacked),
                        lambda i, g, n: (i, at(n), g))
    narrow = pl.BlockSpec((None, chunk, state), lambda i, g, n: (i, at(n), g))
    col = pl.BlockSpec((None, None, chunk, per_group),
                       lambda i, g, n: (i, g, at(n), 0))
    row = pl.BlockSpec((None, None, per_group, chunk),
                       lambda i, g, n: (i, g, 0, at(n)))
    states = pl.BlockSpec((None, None, None, stacked, state),
                          lambda i, g, n: (i, g, at(n), 0, 0))
    return wide, narrow, col, row, states


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(x, dt, log_decay, b, c, chunk, interpret):
    """``(y [batch, T, H * P], states [batch, G, T / chunk, (H / G) P,
    N])``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _check(x, b)
    batch, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    stacked = heads // groups * width
    x2, b2, c2, (acol, arow), (dtcol, dtrow) = _layouts(
        x, dt, log_decay, b, c, chunk)
    wide, narrow, col, row, states = _specs(x, b, chunk, lambda n: n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=width),
        grid=(batch, groups, seq // chunk),
        in_specs=[wide, narrow, narrow, col, row, col, row],
        out_specs=[wide, states],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct(
                (batch, groups, seq // chunk, stacked, state), x.dtype)],
        scratch_shapes=[pltpu.VMEM((stacked, state), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_fwd")(x2, b2, c2, acol, arow, dtcol, dtrow)


def _backward(x, dt, log_decay, b, c, y, states, dy, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    per_group, n = heads // groups, seq // chunk
    stacked = per_group * width
    x2, b2, c2, (acol, arow), (dtcol, dtrow) = _layouts(
        x, dt, log_decay, b, c, chunk)
    dy2 = dy.reshape(x2.shape)
    dyy = (dy.astype(jnp.float32) * y.reshape(x.shape).astype(jnp.float32)
           ).sum(-1).reshape(batch, seq, groups, per_group).transpose(
               0, 2, 1, 3)
    wide, narrow, col, row, kept = _specs(x, b, chunk, lambda i: n - 1 - i)
    by_head = jax.ShapeDtypeStruct(acol.shape, jnp.float32)
    dx, db, dc, d_dt, d_a = pl.pallas_call(
        functools.partial(_bwd_kernel, width=width),
        grid=(batch, groups, n),
        in_specs=[wide, wide, narrow, narrow, col, row, col, row, col, kept],
        out_specs=[wide, narrow, narrow, col, col],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(b2.shape, b.dtype),
                   jax.ShapeDtypeStruct(c2.shape, c.dtype), by_head, by_head],
        scratch_shapes=[pltpu.VMEM((stacked, state), jnp.float32),
                        pltpu.VMEM((stacked, state), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_bwd")(x2, dy2, b2, c2, acol, arow, dtcol, dtrow, dyy,
                             states)
    # d log_decay: the sum of d a from each position to its chunk's end
    d_decay = jnp.flip(jnp.cumsum(jnp.flip(
        d_a.reshape(batch, groups, n, chunk, per_group), 3), axis=3), 3)

    def by_position(t):
        return t.reshape(batch, groups, seq, per_group).transpose(
            0, 2, 1, 3).reshape(batch, seq, heads)

    return (dx.reshape(x.shape), by_position(d_dt).astype(dt.dtype),
            by_position(d_decay).astype(log_decay.dtype),
            db.reshape(b.shape), dc.reshape(c.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_pallas(x, dt, log_decay, b, c, chunk, interpret):
    return _forward(x, dt, log_decay, b, c, chunk,
                    interpret)[0].reshape(x.shape)


def _scan_vjp_fwd(x, dt, log_decay, b, c, chunk, interpret):
    y, states = _forward(x, dt, log_decay, b, c, chunk, interpret)
    y = checkpoint_name(y, KEPT_OUT)
    states = checkpoint_name(states, KEPT_STATES)
    return y.reshape(x.shape), (x, dt, log_decay, b, c, y, states)


def _scan_vjp_bwd(chunk, interpret, residual, dy):
    return _backward(*residual, dy, chunk, interpret)


_scan_pallas.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def ssd_scan(x, dt, log_decay, b, c, chunk=128, impl=None, interpret=False):
    """``y [batch, T, H, P]`` of the scan in the module docstring, in
    ``x``'s dtype; differentiable in all five operands.

    ``impl``: ``"pallas"`` (the kernels; ``interpret=True`` off the TPU) or
    ``"xla"`` (the chunked form in ``jax.numpy``); None picks the kernels on
    a TPU and ``jax.numpy`` elsewhere.  The kernels want ``128 // P`` heads
    (one for ``P`` of 128 or more) to divide a group's, and ``N`` a multiple
    of 128 lanes; the ``jax.numpy`` form takes any sizes."""
    batch, seq, heads, _ = x.shape
    if seq % chunk:
        raise ValueError(
            "ssd_scan: rows of {} positions do not divide into chunks of {}: "
            "pad upstream".format(seq, chunk))
    if heads % b.shape[2] or b.shape != c.shape:
        raise ValueError(
            "ssd_scan: {} heads do not divide into the groups of b {} / c {}"
            .format(heads, b.shape, c.shape))
    if impl is None:
        impl = _default_impl()
    if impl == "xla":
        return _scan_xla(x, dt, log_decay, b, c, chunk)
    if impl != "pallas":
        raise ValueError("unknown ssd_scan impl {!r}".format(impl))
    return _scan_pallas(x, dt, log_decay, b, c, chunk, interpret)
