"""FlashAttention-2 as pallas TPU kernels (forward + backward).

The attention contraction is the transformer's hot op; materializing the
[S, S] score matrix in HBM caps sequence length and burns bandwidth.  These
kernels stream K/V blocks through VMEM with an online softmax, so HBM
traffic is O(S·D) and the MXU sees back-to-back [block_q, D]x[D, block_k]
matmuls:

- forward: one kernel over grid (batch*heads, q_blocks, k_blocks) with
  running (max, sum, acc) scratch carried across the k dimension; also
  emits the logsumexp rows the backward needs.
- backward: the FlashAttention-2 split — one kernel accumulating dQ over k
  blocks, one accumulating dK/dV over q blocks — recomputing p = exp(qk -
  L) from the saved logsumexp instead of storing probabilities.

Off-TPU the same kernels run in pallas interpret mode (tests compare
against the reference attention, values and grads), so
``attention="flash"`` is portable; on TPU they compile to Mosaic
(``tests/test_chip_compile.py`` compiles them for a described v5e).

Grouped-query attention: K and V may carry fewer heads than Q
(``heads % kv_heads == 0``).  They stay ``[batch*kv_heads, S, D]``; the block
index maps send query head ``h`` to KV head ``h // group`` in the forward and
dQ kernels, and the dK/dV kernel's inner grid dimension runs over the
``group`` query heads of a KV head as well as over the q blocks, so their
contributions are summed in the scratch accumulators (nothing is repeated in
HBM).

Value width: ``v`` (and with it the output and dO) may be narrower or wider
than ``q`` and ``k`` (latent attention scores over 192 dimensions and sums
values of 128): every block, accumulator and output has its own array's
width, and nothing is padded to the other's.

Precision: matrix products take their operands in the inputs' dtype (bf16
inputs -> bf16 operands on the MXU, float32 inputs -> float32 as before) and
accumulate in float32; scores, softmax statistics and the accumulators are
float32 always.

Causal: tiles wholly above the diagonal are neither computed nor fetched (the
index maps clamp to the last tile a row block needs, and a block whose index
does not change is not copied again); the mask itself is applied on the tiles
the diagonal crosses only.

Layout contract: ``[batch, seq, heads, dim]`` like
:mod:`~tensorflowonspark_tpu.parallel.ring`; blocks default to 128 (MXU
tile) and the sequence length must divide by the block size: a
``ValueError`` names both where it does not (pad upstream — model code here
keeps S a power of two).
"""

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _default_interpret():
    """Interpret (pure-JAX emulation, for the CPU tests) exactly when the
    process's platform is not ``tpu``: a TPU process always gets the
    compiled Mosaic kernel unless the caller asks otherwise."""
    from tensorflowonspark_tpu.device_info import is_tpu_device

    return not is_tpu_device()


def _dot(a, b, contract):
    """``a`` x ``b`` over ``contract`` with float32 accumulation; operands as
    they come (the caller casts them to the inputs' dtype)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _scores(q_ref, k_ref, scale, masked, q_block_id, k_block_id, block_q,
            block_k):
    """float32 ``q k^T * scale`` of one (q block, k block) tile, the causal
    mask applied where ``masked`` (a tile the diagonal crosses)."""
    s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale       # [BQ, BK]
    if masked:
        rows = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                + q_block_id * block_q)
        cols = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                + k_block_id * block_k)
        s = jnp.where(rows >= cols, s, NEG_INF)
    return s


def _when_needed(causal, qi, kk, block_q, block_k, compute):
    """Run ``compute(masked)`` for the tile (qi, kk): always when not
    causal; when causal only for tiles that reach the diagonal or lie below
    it, masked only where the diagonal crosses the tile.  A tile wholly
    above the diagonal contributes p=0 / alpha=1 (exactly nothing)."""
    from jax.experimental import pallas as pl

    if not causal:
        compute(False)
        return
    needed = qi * block_q + block_q - 1 >= kk * block_k
    crossed = kk * block_k + block_k - 1 > qi * block_q
    pl.when(jnp.logical_and(needed, crossed))(lambda: compute(True))
    pl.when(jnp.logical_and(needed, jnp.logical_not(crossed)))(
        lambda: compute(False))


def _last_k_block(i, block_q, block_k):
    """The last k block a causal q block ``i`` needs."""
    return (i * block_q + block_q - 1) // block_k


def _first_q_block(kk, block_q, block_k):
    """The first q block a causal k block ``kk`` is seen by."""
    return (kk * block_k) // block_q


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, n_k):
    from jax.experimental import pallas as pl

    kk = pl.program_id(2)
    # program_id must be read OUTSIDE pl.when bodies (interpret mode can't
    # substitute it inside a cond branch); close over the values instead.
    qi = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked):
        s = _scores(q_ref, k_ref, scale, masked, qi, kk, block_q, block_k)
        m_prev = m_scr[:]                              # [BQ, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                         # [BQ, BK]
        alpha = jnp.exp(m_prev - m_new)                # [BQ, 1]
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1, keepdims=True)
        v = v_ref[0]                                   # [BK, DV]
        acc_scr[:] = acc_scr[:] * alpha + _dot(
            p.astype(v.dtype), v, ((1,), (0,)))
        m_scr[:] = m_new

    _when_needed(causal, qi, kk, block_q, block_k, _compute)

    @pl.when(kk == n_k - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _kv_maps(causal, block_q, block_k, group):
    """Block index maps of K and V on a (q head, q block, k block) grid:
    query head ``b`` reads KV head ``b // group``; a causal q block never
    moves past the last k block it needs."""
    def kv(b, i, kk):
        if causal:
            kk = jnp.minimum(kk, _last_k_block(i, block_q, block_k))
        return (b // group, kk, 0)
    return kv


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, group=1):
    """Returns ``(out [bh, seq, dv], logsumexp [bh, seq, 1])``; ``q`` is
    ``[bh, seq, d]``, ``k [bh // group, seq, d]`` and ``v [bh // group, seq,
    dv]``.  The softmax statistics keep a
    trailing unit dim: the TPU lowering wants a block's last two dims
    divisible by (8, 128) or equal to the array's, and a ``(1, block_q)``
    row block of a ``[bh, seq]`` array is neither."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s_len, d = q.shape
    dv = v.shape[-1]
    n_q = s_len // block_q
    n_k = s_len // block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k)
    kv = _kv_maps(causal, block_q, block_k, group)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_k, dv), kv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, kk: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, s_len, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k, n_k):
    from jax.experimental import pallas as pl

    kk = pl.program_id(2)
    qi = pl.program_id(1)  # read outside pl.when bodies (interpret mode)

    @pl.when(kk == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked):
        # p = exp(q k^T * scale - L), recomputed from the saved logsumexp
        p = jnp.exp(_scores(q_ref, k_ref, scale, masked, qi, kk, block_q,
                            block_k) - lse_ref[0])
        dp = _dot(do_ref[0], v_ref[0], ((1,), (1,)))   # [BQ, BK]
        ds = p * (dp - delta_ref[0])
        k = k_ref[0]
        dq_scr[:] += scale * _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    _when_needed(causal, qi, kk, block_q, block_k, _compute)

    @pl.when(kk == n_k - 1)
    def _emit():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, block_q, block_k, n_q, group):
    from jax.experimental import pallas as pl

    # the inner grid dimension runs over the KV head's ``group`` query heads
    # and, for each, over the q blocks: one accumulation for all of them
    j = pl.program_id(2)
    qi = j % n_q
    kk = pl.program_id(1)  # read outside pl.when bodies (interpret mode)

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked):
        p = jnp.exp(_scores(q_ref, k_ref, scale, masked, qi, kk, block_q,
                            block_k) - lse_ref[0])
        do = do_ref[0]                                 # [BQ, DV]
        dv_scr[:] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, v_ref[0], ((1,), (1,)))          # [BQ, BK]
        ds = p * (dp - delta_ref[0])
        q = q_ref[0]
        dk_scr[:] += scale * _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    _when_needed(causal, qi, kk, block_q, block_k, _compute)

    @pl.when(j == group * n_q - 1)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_delta(out, g):
    """D_i = rowsum(dO * O) — tiny elementwise pass, left to XLA; kept
    ``[bh, seq, 1]`` like the logsumexp rows (see :func:`_flash_fwd`)."""
    return (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(
        -1, keepdims=True)


def _flash_bwd_dq(q, k, v, g, lse, delta, scale, causal, block_q, block_k,
                  interpret, group=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s_len, d = q.shape
    dv = v.shape[-1]
    n_q = s_len // block_q
    n_k = s_len // block_k
    kv = _kv_maps(causal, block_q, block_k, group)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_k, dv), kv),
            pl.BlockSpec((1, block_q, dv), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, kk: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, kk: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)


def _flash_bwd_dkv(q, k, v, g, lse, delta, scale, causal, block_q, block_k,
                   interpret, group=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh_kv, s_len, d = k.shape
    dv = v.shape[-1]
    n_q = s_len // block_q
    n_k = s_len // block_k

    def rows(b, kk, j):
        """Block index of a per-query-head array: the ``j // n_q``-th query
        head of KV head ``b``, q block ``j % n_q`` (causal: never before the
        first q block that sees k block ``kk``)."""
        i = j % n_q
        if causal:
            i = jnp.maximum(i, _first_q_block(kk, block_q, block_k))
        return (b * group + j // n_q, i, 0)

    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          group=group),
        grid=(bh_kv, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), rows),
            pl.BlockSpec((1, block_k, d), lambda b, kk, j: (b, kk, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, kk, j: (b, kk, 0)),
            pl.BlockSpec((1, block_q, dv), rows),
            pl.BlockSpec((1, block_q, 1), rows),
            pl.BlockSpec((1, block_q, 1), rows),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, kk, j: (b, kk, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, kk, j: (b, kk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, delta)


def _flash_bwd(res, g, scale, causal, block_q, block_k, interpret, group):
    q, k, v, out, lse = res
    delta = _bwd_delta(out, g)
    dq = _flash_bwd_dq(q, k, v, g, lse, delta, scale, causal, block_q,
                       block_k, interpret, group)
    dk, dv = _flash_bwd_dkv(q, k, v, g, lse, delta, scale, causal, block_q,
                            block_k, interpret, group)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, block_q, block_k, interpret, scale, group):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                        group)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret, scale,
                   group):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                          group)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, scale, group, res, g):
    return _flash_bwd(res, g, scale, causal, block_q, block_k, interpret,
                      group)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                    interpret=None, scale=None, mesh=None):
    """Memory-linear attention over ``[batch, seq, heads, dim]`` inputs;
    ``k`` and ``v`` may carry fewer heads than ``q`` (grouped-query
    attention: query head ``h`` reads KV head ``h // (heads // kv_heads)``),
    and ``v`` may have a width of its own (``q, k [.., dk]``, ``v [.., dv]``
    -> ``[batch, seq, heads, dv]``; ``scale`` defaults to ``dk ** -0.5``).
    A ``ValueError`` names the shapes where ``q`` and ``k`` differ in width
    or ``k`` and ``v`` in heads.

    Differentiable (custom FlashAttention-2 backward kernels); softmax
    statistics live in fp32 regardless of input dtype.  ``block_q/k``
    default to the 128 MXU tile and are clamped to the sequence length;
    ``seq`` must divide by the clamped blocks (``ValueError`` otherwise).
    ``interpret`` defaults to True off-TPU so the same kernel runs (slowly)
    everywhere.

    ``mesh``: the compiler cannot partition a Mosaic kernel, so under a
    multi-device mesh the call is mapped per shard here — batch over the
    mesh's ``data``/``fsdp`` axes, heads over ``tensor`` (attention is
    independent per batch row and head, so no collective is needed; with
    grouped KV heads both head counts must divide by the ``tensor`` axis).
    """
    if interpret is None:
        interpret = _default_interpret()
    batch, s_len, heads, dim = q.shape
    kv_heads = k.shape[2]
    if k.shape[3] != dim:
        raise ValueError(
            "q {} and k {} differ in width: scores are taken over one"
            .format(q.shape, k.shape))
    if heads % kv_heads or v.shape[2] != kv_heads:
        raise ValueError(
            "{} query heads of q {} do not divide into the {} / {} heads of "
            "k {} / v {}".format(heads, q.shape, kv_heads, v.shape[2],
                                 k.shape, v.shape))
    if scale is None:
        scale = 1.0 / (dim ** 0.5)
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
        spec = P(batch_axes or None, None,
                 "tensor" if "tensor" in mesh.axis_names else None, None)
        local = functools.partial(
            flash_attention, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, scale=scale)
        # pallas_call's outputs carry no varying-axes annotation
        return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
    block_q = min(block_q, s_len)
    block_k = min(block_k, s_len)
    if s_len % block_q or s_len % block_k:
        raise ValueError(
            "sequence length {} does not divide by the block sizes (q {}, "
            "k {}): pad the sequence upstream or pass blocks that divide it"
            .format(s_len, block_q, block_k))

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, s_len, x.shape[3])

    out = _flash(fold(q), fold(k), fold(v), causal, block_q, block_k,
                 interpret, scale, heads // kv_heads)
    return out.reshape(batch, heads, s_len, v.shape[3]).transpose(0, 2, 1, 3)
