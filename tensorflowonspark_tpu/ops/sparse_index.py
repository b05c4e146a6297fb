"""A learned index that picks each query's keys (pallas TPU kernels).

Sparse attention by a learned index scores every causal (query, key) pair
with a small side network, ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] .
kI[s])`` over ``j`` index heads and one index key head, keeps for each query
the ``topk`` keys of largest score, and runs attention over those alone.  At
32,768 positions the scores are a ``[T, T]`` float32 array of 4.3 GB a
layer; nothing here ever holds one in HBM:

- :func:`select_keys` takes a block of queries at a time, computes their
  scores chunk by chunk into VMEM (as integers in the scores' order), finds
  each row's ``topk``-th largest score **exactly** by a search over the 32
  bits of the score (a pass over the block's scores in VMEM a bit), breaks
  ties towards the lower position by a second search over the position's
  bits, and writes the selection as **bits**: ``[batch, groups, T, 128]``
  int32, bit ``(s % 4096) // 128`` of word ``[b, s // 4096, t, s % 128]``
  says whether query ``t`` keeps key ``s`` (128 MB at 32k).  With it the
  natural logarithm of ``sum_{s in S_t} exp(I[t, s])``, which the loss
  below needs.  :func:`~tensorflowonspark_tpu.ops.flash_attention
  .flash_attention` reads the bits (``key_bits``).
- :func:`index_loss` is what trains the index: ``L[b] = mean_t sum_{s in
  S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t, .])[s])`` with ``p``
  the attention probabilities over the kept keys averaged over the query
  heads (a constant).  One kernel over the causal tiles recomputes the
  scores and the heads' probabilities from the saved logsumexp rows
  (``dsa_index_loss``: the value alone, which is all an undifferentiated
  call runs).  Under differentiation the forward pass runs the form that
  also accumulates the gradient of the loss's sum to ``qI``, ``kI`` and
  ``w`` (``dsa_index_loss_grads``: ``dL/dI = softmax(I) - p`` on the kept
  pairs) and no other: the value is that kernel's, the heads' scores are
  taken once a step, and the backward pass runs no kernel, it scales the
  three gradients by the cotangent over ``T``.  They are float32, as the
  kernel accumulates them, ``qI``'s a dense row a position (``[B, T, J
  E]``: 134 MB a layer at 32k, where ``[B J, T, E]`` would lie on half its
  lanes), and carry names (``KEPT_GRADS``) that a checkpoint policy keeps,
  so that a recomputed block does not run the kernel again.  Nothing else
  gets a gradient from it.

Off-TPU the kernels run in pallas interpret mode.  Precision: the index
products take bf16 (the inputs') operands and accumulate in float32; scores,
the search, softmax statistics and the loss are float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from tensorflowonspark_tpu.ops.flash_attention import (
    KEY_GROUP, KEY_LANES, NEG_INF, _default_interpret, _dot, key_mask)

_INT_MIN = -2 ** 31
# the kernels keep a block's scores (select) or a row block of every head
# (loss) in VMEM: more than the compiler's default share of the 128 MiB
_VMEM_LIMIT = 100 * 1024 * 1024
# select_keys' results and the loss kernel's three gradients (to index_q,
# index_k, index_w), by the names a checkpoint policy may keep them under
KEPT_BITS, KEPT_INDEX_LSE = "dsa_key_bits", "dsa_index_lse"
KEPT_GRADS = ("dsa_index_loss_dq", "dsa_index_loss_dk", "dsa_index_loss_dw")
KEPT = (KEPT_BITS, KEPT_INDEX_LSE) + KEPT_GRADS


def key_groups(seq):
    """Word groups of a ``key_bits`` array for ``seq`` keys."""
    return -(-seq // KEY_GROUP)


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' (its own
    inverse on the integers)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _unordered(i):
    return jax.lax.bitcast_convert_type(i ^ ((i >> 31) & 0x7FFFFFFF),
                                        jnp.float32)


def _index_scores(q_ref, k, w, heads):
    """float32 ``[rows, keys]``: ``sum_j w[:, j] relu(q_ref[j] k^T)``."""
    acc = None
    for j in range(heads):
        part = w[:, j:j + 1] * jnp.maximum(
            _dot(q_ref[j], k, ((1,), (1,))), 0.0)
        acc = part if acc is None else acc + part
    return acc


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

def _select_kernel(q_ref, k_ref, w_ref, bits_ref, lse_ref, keys_scr, *, topk,
                   block_q, chunk, n_chunks, heads, index_bits):
    from jax.experimental import pallas as pl

    row0 = pl.program_id(1) * block_q
    n_need = (row0 + block_q - 1) // chunk + 1   # chunks with a causal key
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    w = w_ref[0]                                             # [BQ, heads]

    def score(c, carry):
        k = k_ref[0, pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :]
        s = _index_scores(q_ref, k, w, heads)
        keys_scr[c] = _ordered(jnp.where(rows >= c * chunk + lanes, s,
                                         -jnp.inf))
        return carry

    jax.lax.fori_loop(0, n_need, score, 0)

    def count(test):
        """[BQ, 1]: how many of a row's causal chunks' keys pass ``test``."""
        def body(c, acc):
            hit = test(keys_scr[c], c * chunk + lanes).astype(jnp.int32)
            for r in range(chunk // KEY_LANES):
                acc = acc + hit[:, r * KEY_LANES:(r + 1) * KEY_LANES]
            return acc

        acc = jax.lax.fori_loop(
            0, n_need, body, jnp.zeros((block_q, KEY_LANES), jnp.int32))
        return acc.sum(axis=1, keepdims=True)

    keep = jnp.minimum(rows + 1, topk)
    # the keep-th largest score: its bits from the top, in the order where
    # an unsigned comparison is the scores' (the signed one after ^ INT_MIN)
    def score_bit(step, found):
        trial = found | jax.lax.shift_left(jnp.int32(1), 31 - step)
        enough = count(lambda keys, _: keys >= (trial ^ _INT_MIN)) >= keep
        return jnp.where(enough, trial, found)

    kth = jax.lax.fori_loop(0, 32, score_bit,
                            jnp.zeros((block_q, 1), jnp.int32)) ^ _INT_MIN
    # of the keys that tie with it, the lowest positions fill what is left
    spare = keep - count(lambda keys, _: keys > kth)

    def position_bit(step, found):
        trial = found | jax.lax.shift_left(jnp.int32(1),
                                           index_bits - 1 - step)
        before = count(lambda keys, cols: (keys == kth) & (cols < trial))
        return jnp.where(before < spare, trial, found)

    last_tie = jax.lax.fori_loop(0, index_bits, position_bit,
                                 jnp.zeros((block_q, 1), jnp.int32))

    per_group = min(n_chunks, KEY_GROUP // chunk)
    runs = chunk // KEY_LANES

    def pack(g, stats):
        m, l = stats
        word = jnp.zeros((block_q, KEY_LANES), jnp.int32)
        for cc in range(per_group):
            c = g * per_group + cc
            keys = keys_scr[jnp.minimum(c, n_chunks - 1)]
            kept = ((keys > kth) | ((keys == kth)
                                    & (c * chunk + lanes <= last_tie))) \
                & (c < n_need)
            for r in range(runs):
                word = word | jnp.where(
                    kept[:, r * KEY_LANES:(r + 1) * KEY_LANES],
                    jnp.int32(1) << (cc * runs + r), 0)
            s = jnp.where(kept, _unordered(keys), NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            l = l * jnp.exp(m - m_new) + jnp.exp(s - m_new).sum(
                axis=1, keepdims=True)
            m = m_new
        bits_ref[0, g] = word
        return m, l

    m, l = jax.lax.fori_loop(
        0, bits_ref.shape[1], pack,
        (jnp.full((block_q, 1), NEG_INF, jnp.float32),
         jnp.zeros((block_q, 1), jnp.float32)))
    lse_ref[0] = m + jnp.log(l)


def _fold_heads(x):
    """``[B, T, H, D] -> [B H, T, D]``."""
    return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])


def _chunk(seq, chunk):
    chunk = min(chunk, seq)
    if chunk % KEY_LANES or seq % chunk or KEY_GROUP % chunk:
        raise ValueError(
            "a key set wants positions in chunks that divide by {} and "
            "divide {}: {} positions, chunk {}".format(
                KEY_LANES, KEY_GROUP, seq, chunk))
    return chunk


def select_keys(index_q, index_k, index_w, topk, block_q=256, chunk=512,
                interpret=None):
    """Each query's ``min(t + 1, topk)`` causal keys of largest index score
    ``sum_j index_w[b, t, j] relu(index_q[b, t, j] . index_k[b, s])`` (ties to
    the lower position), exactly, without the scores in HBM.

    ``index_q [B, T, J, E]``, ``index_k [B, T, E]``, ``index_w [B, T, J]``
    float32.  Returns ``(key_bits [B, groups, T, 128] int32, logsumexp [B,
    T] float32 of the kept scores)`` (the module docstring has the layout).
    A constant of the step: no gradient passes.  Both results carry a name
    (``KEPT``) that a checkpoint policy may keep, so that a recomputed block
    does not search again."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _default_interpret()
    batch, seq, heads, dim = index_q.shape
    chunk = _chunk(seq, chunk)
    block_q = min(block_q, seq)
    if seq % block_q:
        raise ValueError("{} positions do not divide by the query block {}"
                         .format(seq, block_q))
    n_chunks, groups = seq // chunk, key_groups(seq)
    index_q, index_k, index_w = jax.lax.stop_gradient(
        (index_q, index_k, index_w))
    kernel = functools.partial(
        _select_kernel, topk=topk, block_q=block_q, chunk=chunk,
        n_chunks=n_chunks, heads=heads,
        index_bits=max((seq - 1).bit_length(), 1))
    bits, lse = pl.pallas_call(
        kernel,
        grid=(batch, seq // block_q),
        in_specs=[
            pl.BlockSpec((heads, block_q, dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq, dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, heads), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, groups, block_q, KEY_LANES),
                         lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, groups, seq, KEY_LANES), jnp.int32),
            jax.ShapeDtypeStruct((batch, seq, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_chunks, block_q, chunk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_select",
    )(_fold_heads(index_q), index_k, index_w.astype(jnp.float32))
    return (checkpoint_name(bits, KEPT_BITS),
            checkpoint_name(lse[..., 0], KEPT_INDEX_LSE))


def tiles_touched(key_bits, seq, block):
    """``(touched, causal)``: of the causal ``[block, block]`` tiles of
    (queries, keys), how many hold a kept key (int32 scalar), and how many
    there are.  What a kernel that skipped empty tiles could save."""
    batch, groups = key_bits.shape[:2]
    block = min(block, seq)
    n_blocks, runs = seq // block, block // KEY_LANES
    words = jax.lax.reduce(
        key_bits.reshape(batch, groups, n_blocks, block * KEY_LANES),
        np.int32(0), jax.lax.bitwise_or, (3,))       # [B, G, n_blocks]
    per_group = KEY_GROUP // block
    shifts = jnp.arange(per_group, dtype=jnp.int32) * runs
    run_mask = jnp.int32((1 << runs) - 1) if runs < 32 else jnp.int32(-1)
    hit = (jax.lax.shift_right_logical(
        words[..., None], shifts[None, None, None]) & run_mask) != 0
    return (hit.sum().astype(jnp.int32),
            batch * n_blocks * (n_blocks + 1) // 2)


# ---------------------------------------------------------------------------
# the index's loss
# ---------------------------------------------------------------------------

def _loss_kernel(*refs, scale, block, n_k, heads, group, index_heads,
                 with_grads):
    from jax.experimental import pallas as pl

    (q_ref, k_ref, lse_ref, iq_ref, ik_ref, iw_ref, ilse_ref, bits_ref,
     loss_ref) = refs[:9]
    i, kk = pl.program_id(1), pl.program_id(2)
    if with_grads:
        diq_ref, dik_ref, diw_ref, loss_scr, diq_scr, diw_scr = refs[9:]
    else:
        (loss_scr,) = refs[9:]

    @pl.when(kk == 0)
    def _init():
        loss_scr[:] = jnp.zeros_like(loss_scr)
        if with_grads:
            diq_scr[:] = jnp.zeros_like(diq_scr)
            diw_scr[:] = jnp.zeros_like(diw_scr)

    if with_grads:
        @pl.when(jnp.logical_and(i == 0, kk == 0))
        def _init_keys():
            dik_ref[:] = jnp.zeros_like(dik_ref)

    @pl.when(kk <= i)          # the causal tiles; the bits are causal too
    def _tile():
        kept = key_mask(bits_ref[0, 0], kk, block)
        lse = lse_ref[0]                                   # [BQ, heads]
        p = None
        for h in range(heads):
            s = _dot(q_ref[h], k_ref[h // group], ((1,), (1,))) * scale
            # a kept pair's score is under its row's logsumexp
            e = jnp.exp(jnp.minimum(s - lse[:, h:h + 1], 0.0))
            p = e if p is None else p + e
        p = jnp.where(kept, p * (1.0 / heads), 0.0)
        ik, iw = ik_ref[0], iw_ref[0]
        log_soft = _index_scores(iq_ref, ik, iw, index_heads) - ilse_ref[0]
        loss_scr[:] += jnp.where(
            kept, p * (jnp.log(jnp.maximum(p, 1e-37)) - log_soft),
            0.0).sum(axis=1, keepdims=True)
        if not with_grads:
            return
        # dL/dI on the kept pairs (the mean over positions is the caller's)
        g = jnp.where(kept, jnp.exp(jnp.minimum(log_soft, 0.0)) - p, 0.0)
        lane = jax.lax.broadcasted_iota(jnp.int32, diw_scr.shape, 1)
        dik = jnp.zeros(dik_ref.shape[2:], jnp.float32)     # [E, BK]
        for j in range(index_heads):
            iq = iq_ref[j]
            d = _dot(iq, ik, ((1,), (1,)))
            diw_scr[:] += jnp.where(
                lane == j,
                (g * jnp.maximum(d, 0.0)).sum(axis=1, keepdims=True), 0.0)
            a = jnp.where(d > 0.0, g * iw[:, j:j + 1], 0.0).astype(iq.dtype)
            diq_scr[j] += _dot(a, ik, ((1,), (0,)))
            dik = dik + _dot(iq, a, ((0,), (0,)))
        dik_ref[0, kk] += dik

    @pl.when(kk == n_k - 1)
    def _emit():
        loss_ref[0] = loss_scr[:]
        if with_grads:
            # the heads side by side on the lanes: a dense row a position
            dim = diq_scr.shape[2]
            for j in range(index_heads):
                diq_ref[0, :, j * dim:(j + 1) * dim] = diq_scr[j]
            diw_ref[0] = diw_scr[:, :index_heads]


def _loss_call(q, k, lse, index_q, index_k, index_w, index_lse, bits, scale,
               block, interpret, with_grads):
    """Per-position loss ``[B, T]`` (and the three gradients of its sum, as
    the kernel lays them out: :func:`_unfold_grads`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, heads, dim = q.shape
    kv_heads = k.shape[2]
    index_heads, index_dim = index_q.shape[2:]
    n = seq // block
    per_group = KEY_GROUP // block

    def at_q(b, i, kk):
        return (b, i, 0)

    def at_k(b, i, kk):
        return (b, jnp.minimum(kk, i), 0)

    in_specs = [
        pl.BlockSpec((heads, block, dim), at_q),
        pl.BlockSpec((kv_heads, block, dim), at_k),
        pl.BlockSpec((1, block, heads), at_q),
        pl.BlockSpec((index_heads, block, index_dim), at_q),
        pl.BlockSpec((1, block, index_dim), at_k),
        pl.BlockSpec((1, block, index_heads), at_q),
        pl.BlockSpec((1, block, 1), at_q),
        pl.BlockSpec((1, 1, block, KEY_LANES),
                     lambda b, i, kk: (b, jnp.minimum(kk, i) // per_group,
                                       i, 0)),
    ]
    out_specs = [pl.BlockSpec((1, block, 1), at_q)]
    out_shape = [jax.ShapeDtypeStruct((batch, seq, 1), jnp.float32)]
    scratch = [pltpu.VMEM((block, 1), jnp.float32)]
    if with_grads:
        out_specs += [
            pl.BlockSpec((1, block, index_heads * index_dim), at_q),
            pl.BlockSpec((1, n, index_dim, block),
                         lambda b, i, kk: (b, 0, 0, 0)),
            pl.BlockSpec((1, block, index_heads), at_q)]
        out_shape += [
            jax.ShapeDtypeStruct((batch, seq, index_heads * index_dim),
                                 jnp.float32),
            jax.ShapeDtypeStruct((batch, n, index_dim, block), jnp.float32),
            jax.ShapeDtypeStruct((batch, seq, index_heads), jnp.float32)]
        scratch += [pltpu.VMEM((index_heads, block, index_dim), jnp.float32),
                    pltpu.VMEM((block, KEY_LANES), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(
            _loss_kernel, scale=scale, block=block, n_k=n, heads=heads,
            group=heads // kv_heads, index_heads=index_heads,
            with_grads=with_grads),
        grid=(batch, n, n),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_index_loss_grads" if with_grads else "dsa_index_loss",
    )(_fold_heads(q), _fold_heads(k), lse, _fold_heads(index_q), index_k,
      index_w, index_lse[..., None], bits)
    loss = outs[0][..., 0]
    return (loss, tuple(outs[1:])) if with_grads else loss


def _unfold_grads(grads):
    """The kernel's three gradients in their inputs' shapes: ``index_q``'s
    ``[B, T, J E] -> [B, T, J, E]``, ``index_k``'s ``[B, T / block, E,
    block] -> [B, T, E]``, ``index_w``'s ``[B, T, J]`` as it is."""
    diq, dik, diw = grads
    batch, seq, index_heads = diw.shape
    return (diq.reshape(batch, seq, index_heads, -1),
            dik.transpose(0, 1, 3, 2).reshape(batch, seq, -1), diw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _index_loss(index_q, index_k, index_w, q, k, lse, index_lse, bits, scale,
                block, interpret, dtypes):
    # undifferentiated, the value alone
    return _loss_call(q, k, lse, index_q, index_k, index_w, index_lse, bits,
                      scale, block, interpret, False).mean(axis=1)


def _index_loss_fwd(index_q, index_k, index_w, q, k, lse, index_lse, bits,
                    scale, block, interpret, dtypes):
    # the value and the gradients of its sum from one pass over the heads'
    # scores; the names on the kernel's own results (one put on them outside
    # the rule would name a copy), so that a recomputed block keeps them and
    # its second run of the kernel has no reader and goes
    loss, grads = _loss_call(q, k, lse, index_q, index_k, index_w, index_lse,
                             bits, scale, block, interpret, True)
    return loss.mean(axis=1), tuple(
        checkpoint_name(d, name) for d, name in zip(grads, KEPT_GRADS))


def _index_loss_bwd(scale, block, interpret, dtypes, grads, g):
    # no kernel: the kept gradients times the cotangent, in the types of
    # what they are gradients to (``dtypes``)
    grads = _unfold_grads(grads)
    seq = grads[0].shape[1]
    scaled = tuple(
        (d * (g / seq).reshape((-1,) + (1,) * (d.ndim - 1))).astype(dtype)
        for d, dtype in zip(grads, dtypes))
    return scaled + (None,) * 5      # q, k, lse, index_lse, bits: constants


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(index_q, index_k, index_w, q, k, lse, index_lse, key_bits,
               scale=None, block=512, interpret=None):
    """``[B]``: for each row, the mean over its positions of ``KL(p ||
    softmax_{S_t}(I[t, .]))``, ``p`` the probabilities of attention over the
    kept keys (``q [B, T, H, D]``, ``k [B, T, KV, D]`` after norm and RoPE,
    ``lse [B, T, H]`` from ``flash_attention_lse(key_bits=)``) averaged over the
    heads and held constant, ``I`` the index scores of ``index_q``,
    ``index_k``, ``index_w`` (:func:`select_keys`, whose ``key_bits`` and
    ``index_lse`` come in too).  Differentiable in ``index_q``, ``index_k``
    and ``index_w`` only."""
    if interpret is None:
        interpret = _default_interpret()
    seq, dim = q.shape[1], q.shape[3]
    block = _chunk(seq, block)
    if scale is None:
        scale = dim ** -0.5
    q, k, lse, index_lse = jax.lax.stop_gradient((q, k, lse, index_lse))
    index_w = index_w.astype(jnp.float32)
    return _index_loss(index_q, index_k, index_w, q, k, lse, index_lse,
                       key_bits, scale, block, interpret,
                       (index_q.dtype, index_k.dtype, index_w.dtype))
