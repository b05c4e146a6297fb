"""FlashAttention-2 as pallas TPU kernels (forward + backward).

The attention contraction is the transformer's hot op; materializing the
[S, S] score matrix in HBM caps sequence length and burns bandwidth.  These
kernels stream K/V blocks through VMEM with an online softmax, so HBM
traffic is O(S·D) and the MXU sees back-to-back [block_q, D]x[D, block_k]
matmuls:

- forward: one kernel over the (batch*heads, q block, k block) tiles with
  running (max, sum, acc) scratch carried across a q block's k blocks; also
  emits the logsumexp rows the backward needs.
- backward: the FlashAttention-2 split — one kernel accumulating dQ over k
  blocks, one accumulating dK/dV over q blocks — recomputing p = exp(qk -
  L) from the saved logsumexp instead of storing probabilities.

Where a query's softmax statistics live: never one to a 128-lane tile.
In HBM the logsumexp and ``delta`` are ``[batch*heads, 1, seq]`` float32,
the query on the lane axis (a ``(1, 1, block_q)`` block is 2 KB where the
``[.., seq, 1]`` form was 256 KB, and nothing round the kernels relayouts
them).  In VMEM each kernel holds them the way its tile wants them applied:

- forward and dQ take the tile queries by keys; their statistics (running
  maximum and sum; logsumexp and ``delta``) are ``[block_q, 128]`` with a
  query's value on every lane, so that applying one to a ``[block_q,
  block_k]`` tile is a plain element-wise pass over each run of 128 keys and
  updating one is 64 whole vregs' work, not 64 vregs with one lane alive.
  The forward turns its logsumexp into the dense row once a q block, when it
  writes it; dQ turns the two dense rows into that form once a q block, when
  it starts on it.
- dK/dV takes the tile keys by queries (``k q^T``): the dense rows are
  applied by a broadcast along sublanes, and ``p^T dO`` and ``ds^T q`` are
  plain products (no transposed ``[block, block]`` operand).

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

Causal: the grid steps over the triangle alone.  Behind the heads it has one
dimension, a step for every tile that reaches the diagonal or lies below it
and none for the half of the square above: the tiles' coordinates
(:func:`_causal_steps`, :func:`_causal_steps_by_keys`; host ints from
``seq``, the blocks and ``group``), in the order the kernels accumulate, go
into SMEM in front of the kernel as one int32 a step
(``PrefetchScalarGridSpec``; bit fields, :func:`_listed_step`), the index
maps and the kernels read their (q block, k block) from it, and a step whose
tile opens a run initialises the scratch, one that closes it writes out.  A
tile above the diagonal is neither fetched, computed nor stepped over; the
mask itself is applied on the tiles the diagonal crosses only.  What it buys
and costs (a v5e, ``[512, 512]`` tiles): a step that computes nothing takes
about 0.2 us, and a listed step takes about 0.1 us more than a step of the
rectangle, because Mosaic evaluates every operand's index map three times a
step on the scalar core and a listed one starts with a load from SMEM; so
rows of many blocks gain (a tile 6 to 15% less at 64 blocks, 2,016 of 4,096
steps gone).  A launcher lists its steps where the list is the shorter grid
and SMEM holds it (:func:`_listed`), and takes the rectangle otherwise, the
tiles above the diagonal stepped over with the index maps clamped to the
last tile a run needs (a block whose index does not change is not copied
again) and nothing computed, as every causal grid was before there were
lists: so every shape has a grid.  One q block a head is the rectangle (its
tiles are all of it).  The list is ``(seq / block)^2 / 2`` words for the
forward and dQ kernels and ``group`` times that for dK/dV, and
``LISTED_STEPS`` (196,608, three quarters of a v5e's SMEM) is the longest:
blocks of 512 list 131,072 rows in the forward and dQ kernels and up to a
``group`` of 5 in dK/dV, 65,536 rows up to 23; blocks of 128 list 80,128
rows, and 28,288 at a group of 8.  Not causal, the grid is the rectangle: nothing in
it is empty.  :func:`grid_tiles` counts a grid's steps and the tiles among
them that compute.

Window: ``window`` (static, with ``causal``) keeps query ``t`` to the keys
``t - window < s <= t``, its own among them.  The band is the grid: a q block
``i`` needs the k blocks ``first..last`` (:func:`_first_k_block`,
:func:`_last_k_block`) and a k block is seen by the q blocks ``first..last``
(:func:`_first_q_block`, :func:`_last_q_block`); each kernel's inner grid
extent is the longest such run (three blocks for a window of two blocks),
not ``seq / block``, its index maps add the run's first block and clamp to
its last, and the mask (``t - s < window`` beside ``s <= t``) is applied on
the tiles that an edge of the band crosses.  A tile outside the band is
neither fetched, computed nor stepped over.  A query's first tile may hold
none of its keys (the band's lower edge crosses it): the running maximum
stays at ``NEG_INF`` there and the next tile's ``alpha`` is exactly 0.
Without a window nothing of it is traced.  :func:`band_tiles` counts the
tiles the forward grid computes.

Key sets: ``key_bits`` (``[batch, groups, seq, 128]`` int32, the layout of
:mod:`~tensorflowonspark_tpu.ops.sparse_index`: bit ``(s % 4096) // 128`` of
word ``[b, s // 4096, t, s % 128]`` says whether query ``t`` may read key
``s``) restricts every query to its own keys inside the causal triangle: the
three kernels read one ``[block_q, 128]`` tile of words for up to 32 k blocks
and mask the scores of the tiles they compute (dK/dV turns the words with
its tile, 64 vregs a grid step).  Every causal tile is still
computed (a masked pair's products are thrown away); without ``key_bits``
nothing of it is traced and the kernels are the ones they were.

Under a checkpoint (``jax.checkpoint``, ``TransformerLM(remat=True)``): of
the residuals the backward kernels read, two cost a kernel run to make again
and are small, the output (``[batch*heads, seq, dv]``, the inputs' dtype) and
the logsumexp rows (float32, ``[batch*heads, 1, seq]`` as the kernel writes
them: dense, 4 bytes a query).  The
forward rule of the ``custom_vjp`` (:func:`_flash_vjp_fwd`) names them
``KEPT_OUT`` and ``KEPT_LSE`` with ``jax.ad_checkpoint.checkpoint_name``, on
the kernel's own results, so that a policy of
``save_only_these_names(*KEPT)`` keeps the very arrays the backward rule
reads and the recomputed pass holds no forward kernel.  A name put on the
op's result outside the rule would name a copy.  Without a policy a name is
the identity.

Who takes the kernels: ``TransformerLM(attention="flash")`` always, at the
layer's block; ``attention="full"``, the default, by one rule
(:func:`full_attention_block`): where they would run compiled and the row
tiles, at the block the row gives (:func:`row_block`), else the plain
contraction of :func:`~tensorflowonspark_tpu.parallel.ring
.reference_attention`, which writes its ``[batch, heads, seq, seq]`` float32
scores to HBM (GPT-2 medium at batch 4: 268 MB a layer, about ten passes over
it forward and backward).  The three launchers are jitted functions
(``_one_trace``), so the like layers of a model share one trace of each.

Layout contract: ``[batch, seq, heads, dim]`` like
:mod:`~tensorflowonspark_tpu.parallel.ring`; blocks default to 128 (MXU
tile) and the sequence length must divide by the block size: a
``ValueError`` names both where it does not (pad upstream — model code here
keeps S a power of two).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

NEG_INF = -1e30
KEY_LANES = 128          # a word of key_bits holds one key a lane ...
KEY_GROUP = 32 * 128     # ... of each of 32 runs of 128 keys
# the residuals a checkpoint policy may keep by name (module docstring)
KEPT_OUT, KEPT_LSE = "flash_out", "flash_lse"
KEPT = (KEPT_OUT, KEPT_LSE)


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


def key_mask(words, k_block_id, block_k, keys_first=False):
    """bool ``[block_q, block_k]``: the keys of k block ``k_block_id`` that
    each query may read, from its ``[block_q, 128]`` tile of ``key_bits``
    words (the group's; ``block_k`` divides by 128 and divides 4096).
    ``keys_first``: the words come turned (``[128, block_q]``) and so does
    the mask (``[block_k, block_q]``)."""
    runs = block_k // KEY_LANES
    first = (k_block_id % (KEY_GROUP // block_k)) * runs
    return jnp.concatenate(
        [jax.lax.shift_right_logical(words, first + r) & 1
         for r in range(runs)], axis=0 if keys_first else 1) == 1


def _scores(q_ref, k_ref, scale, masked, q_block_id, k_block_id, block_q,
            block_k, bits_ref=None, keys_first=False, window=None):
    """float32 ``q k^T * scale`` of one (q block, k block) tile (``k q^T``,
    keys by queries, where ``keys_first``), the causal mask (and the
    window's) applied where ``masked`` (a tile that the diagonal or the
    band's lower edge crosses), and the queries' key sets where ``bits_ref``
    holds them."""
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    operands = (k_ref[0], q_ref[0]) if keys_first else (q_ref[0], k_ref[0])
    s = _dot(*operands, ((1,), (1,))) * scale
    if bits_ref is not None:
        words = bits_ref[0, 0]
        s = jnp.where(key_mask(words.T if keys_first else words, k_block_id,
                               block_k, keys_first), s, NEG_INF)
    if masked:
        queries = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
                   + q_block_id * block_q)
        keys = (jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
                + k_block_id * block_k)
        seen = queries >= keys
        if window is not None:
            seen = jnp.logical_and(seen, queries - keys < window)
        s = jnp.where(seen, s, NEG_INF)
    return s


def _stat_lanes(block_k):
    """How many lanes a query's statistic is held on beside a ``[block_q,
    block_k]`` tile: 128, one vreg's, wherever the tile is whole runs of
    them (a block below 128 is the tiny tests', in interpret mode)."""
    return KEY_LANES if block_k % KEY_LANES == 0 else block_k


def _across(stat, width):
    """A ``[block_q, lanes]`` statistic (a query's value on every lane)
    against ``width`` columns: the same vregs again, run after run."""
    lanes = stat.shape[1]
    if width == lanes:
        return stat
    if width < lanes:
        return stat[:, :width]
    if width % lanes == 0:
        return jnp.concatenate([stat] * (width // lanes), axis=1)
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], width))


def _on_lanes(row, lanes):
    """A dense ``[1, block_q]`` row of statistics turned to ``[block_q,
    lanes]``, a query's value on every lane."""
    return jnp.broadcast_to(row, (lanes, row.shape[1])).T


def _when_needed(causal, qi, kk, block_q, block_k, compute, window=None,
                 also=None, listed=False):
    """Run ``compute(masked)`` for the tile (qi, kk): always when not
    causal; when causal only for tiles that reach the diagonal or lie below
    it, masked only where the diagonal crosses the tile.  A tile wholly
    above the diagonal contributes p=0 / alpha=1 (exactly nothing).  Under a
    ``window`` a tile wholly below the band is not needed either, and one
    that the band's lower edge crosses is masked; ``also`` is a further
    condition of the caller's (a grid step past its run's end).  ``listed``:
    the grid steps over the causal tiles alone (:func:`_causal_steps`), so
    every tile it brings is needed."""
    from jax.experimental import pallas as pl

    if not causal:
        compute(False)
        return
    needed = None if listed else qi * block_q + block_q - 1 >= kk * block_k
    crossed = kk * block_k + block_k - 1 > qi * block_q
    if window is not None:
        # the block's first query still reaches the k block's last key
        needed = jnp.logical_and(
            needed, qi * block_q - (kk * block_k + block_k - 1) < window)
        # its last query no longer reaches the k block's first key
        crossed = jnp.logical_or(
            crossed, qi * block_q + block_q - 1 - kk * block_k >= window)
    if also is not None:
        needed = jnp.logical_and(needed, also)

    def when(kind, masked):
        pl.when(kind if needed is None else jnp.logical_and(needed, kind))(
            lambda: compute(masked))

    when(crossed, True)
    when(jnp.logical_not(crossed), False)


def _last_k_block(i, block_q, block_k):
    """The last k block a causal q block ``i`` needs."""
    return (i * block_q + block_q - 1) // block_k


def _first_k_block(i, block_q, block_k, window):
    """The first k block q block ``i`` needs under ``window``: the one that
    holds the earliest key of its first query."""
    return jnp.maximum(i * block_q - window + 1, 0) // block_k


def _first_q_block(kk, block_q, block_k):
    """The first q block a causal k block ``kk`` is seen by."""
    return (kk * block_k) // block_q


def _last_q_block(kk, block_q, block_k, window, n_q):
    """The last q block that sees k block ``kk`` under ``window``: the one
    that holds the latest query of its last key."""
    return jnp.minimum((kk * block_k + block_k + window - 2) // block_q,
                       n_q - 1)


def _k_run(n_q, block_q, block_k, window):
    """``(longest, total)`` of the runs of k blocks that the q blocks need
    under ``window`` (Python ints: the forward and dQ grids' inner extent,
    and the tiles a head's forward grid computes)."""
    runs = [_last_k_block(i, block_q, block_k) + 1
            - max(i * block_q - window + 1, 0) // block_k
            for i in range(n_q)]
    return max(runs), sum(runs)


def _q_run(n_q, n_k, block_q, block_k, window):
    """The longest run of q blocks that sees one k block under ``window``
    (the dK/dV grid's inner extent a query head)."""
    return max(min((kk * block_k + block_k + window - 2) // block_q, n_q - 1)
               + 1 - _first_q_block(kk, block_q, block_k)
               for kk in range(n_k))


def band_tiles(seq, block, window):
    """``(computed, causal)``: of the causal ``[block, block]`` tiles of
    (queries, keys) of one row and head, those that the forward kernel's
    grid computes under ``window`` (None, or one that covers the row: all of
    them), and how many there are."""
    n = seq // min(block, seq)
    return grid_tiles(seq, block, block, window=window)[1], n * (n + 1) // 2


def _causal_steps(n_q, block_q, block_k):
    """``(q blocks, k blocks)``, int arrays: the tiles that compute under
    ``causal``, in the order the forward and dQ kernels accumulate: q block
    ``i`` over its k blocks ``0.._last_k_block(i)``.  The rectangle's steps
    in their own order, without those whose tile lies above the diagonal."""
    runs = [_last_k_block(i, block_q, block_k) + 1 for i in range(n_q)]
    return (np.repeat(np.arange(n_q), runs),
            np.concatenate([np.arange(run) for run in runs]))


def _causal_steps_by_keys(n_q, n_k, block_q, block_k, group):
    """``(k blocks, query heads of the group, q blocks)``, int arrays: the
    tiles that compute under ``causal``, in the order the dK/dV kernel
    accumulates: k block ``kk`` over its ``group`` query heads and for each
    over the q blocks ``_first_q_block(kk)..n_q - 1``."""
    firsts = [_first_q_block(kk, block_q, block_k) for kk in range(n_k)]
    return (np.repeat(np.arange(n_k), [group * (n_q - f) for f in firsts]),
            np.concatenate([np.repeat(np.arange(group), n_q - f)
                            for f in firsts]),
            np.concatenate([np.tile(np.arange(f, n_q), group)
                            for f in firsts]))


# The longest list of steps a launch puts in SMEM, in int32 words: three
# quarters of a v5e's 1 MiB, which holds the kernel's other scalars and
# spills too (its compiler takes a list of 259,560 words and refuses one of
# 263,168 by name: "Allocation (size=1052672) would exceed memory
# (size=1048576) ... prefetched SMEM operand 0";
# tests/test_chip_compile.py compiles a launch on either side).  A row of
# 512 blocks lists 131,328 steps, so a power of two would cut at the wrong
# side of the rows there are
LISTED_STEPS = 3 << 16


def _listed(steps, extents):
    """``steps`` where the grid is to take them from a list, None where it
    takes the rectangle ``extents`` with its index maps clamped: a list
    serves where it leaves steps of the rectangle out (one q block's tiles
    are the rectangle) and where SMEM holds it (``LISTED_STEPS``)."""
    count = len(steps[0])
    return steps if count < np.prod(extents) and count <= LISTED_STEPS \
        else None


def _row_grid(s_len, block_q, block_k, causal, window):
    """``(extents, steps)`` of the forward and dQ kernels' grid behind the
    heads: the rectangle ``(n_q, n_k)``, under a window ``(n_q, the longest
    run of k blocks)`` counted from each run's first block; and, causal
    without a window, the :func:`_causal_steps` of it that the grid takes
    (the others' tiles lie above the diagonal) where it lists them
    (:func:`_listed`), else None: every one."""
    n_q, n_k = s_len // block_q, s_len // block_k
    if window is not None:
        return (n_q, _k_run(n_q, block_q, block_k, window)[0]), None
    return (n_q, n_k), (_listed(_causal_steps(n_q, block_q, block_k),
                                (n_q, n_k)) if causal else None)


def grid_tiles(seq, block_q, block_k, causal=True, window=None):
    """``(steps, computed)`` of one row and head: the steps of the forward
    kernel's grid (dQ's are the same, and dK/dV's a query head wherever it
    lists ``group`` times as many) and the tiles among them that compute.
    The same number where the causal kernel's grid lists its tiles and where
    nothing is masked away whole; a causal rectangle (a list SMEM would not
    hold, :func:`_listed`) steps over the square, and a window's grid over
    each q block's longest run (192 steps for 189 tiles at 32,768 / 512 /
    1,024)."""
    block_q, block_k = min(block_q, seq), min(block_k, seq)
    if window is not None and window >= seq:
        window = None
    (n_q, run), steps = _row_grid(seq, block_q, block_k, causal, window)
    if window is not None:
        return n_q * run, _k_run(n_q, block_q, block_k, window)[1]
    computed = (len(_causal_steps(n_q, block_q, block_k)[0]) if causal
                else n_q * run)
    return n_q * run if steps is None else len(steps[0]), computed


def _widths(extents):
    """The bits each coordinate of a listed step takes in its int32 word
    (``LISTED_STEPS`` keeps their sum far under 31: a list is at least half
    of its extents' product long)."""
    return tuple(max(int(e) - 1, 1).bit_length() for e in extents)


def _listed_step(steps_ref, t, widths):
    """The coordinates of grid step ``t`` of a grid that lists its steps:
    bit fields of one word, the last coordinate lowest (shifts and masks:
    an index map is evaluated for every operand and step, on the scalar
    core, in front of the step's copies)."""
    word = steps_ref[t]
    coords = []
    for width in reversed(widths):
        coords.append(word & ((1 << width) - 1))
        word = jax.lax.shift_right_logical(word, width)
    return tuple(reversed(coords))


def _launch(kernel, heads, extents, steps, in_specs, out_specs, out_shape,
            scratch_shapes, interpret, args):
    """``kernel(listed=None)`` over the grid ``(heads, *extents)``; or,
    where ``steps`` lists the coordinates to take (one int array a
    coordinate, each below its extent), ``kernel(listed=widths)`` over
    ``(heads, len(steps[0]))``: the list goes in front of the kernel's other
    references as one int32 a step (:func:`_listed_step`), prefetched into
    SMEM.  ``in_specs`` and ``out_specs`` are ``(block shape, index map)``,
    the maps over ``(head, *coordinates)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def block_specs(index_map):
        return jax.tree.map(
            lambda s: pl.BlockSpec(s[0], index_map(s[1])),
            (in_specs, out_specs),
            is_leaf=lambda s: isinstance(s, tuple) and callable(s[1]))

    if steps is None:
        in_specs, out_specs = block_specs(lambda where: where)
        return pl.pallas_call(
            functools.partial(kernel, listed=None), grid=(heads,) + extents,
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch_shapes, interpret=interpret)(*args)
    widths = _widths(extents)
    in_specs, out_specs = block_specs(
        lambda where: lambda b, t, listed: where(
            b, *_listed_step(listed, t, widths)))
    words = np.zeros(len(steps[0]), np.int64)
    for coordinate, width in zip(steps, widths):
        words = words << width | coordinate
    return pl.pallas_call(
        functools.partial(kernel, listed=widths),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads, len(words)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape, interpret=interpret)(
            words.astype(np.int32), *args)


def _steps_first(refs, listed):
    """``(steps_ref, the others)`` of a kernel's references: the prefetched
    list of the grid's steps comes first where there is one."""
    return (refs[0], refs[1:]) if listed else (None, refs)


def _row_step(steps_ref, block_q, block_k, n_k, window, listed):
    """``(qi, kk, step, end)`` of a forward or dQ grid step: the tile, and
    where it stands in its q block's run (``step == 0`` starts the run,
    ``step == end`` closes it).  ``n_k`` is the rectangle's inner extent:
    every k block, or under a window the longest run a q block needs,
    counted from the run's first block; ``listed`` the widths of a listed
    step's coordinates."""
    from jax.experimental import pallas as pl

    # program_id (and the list) must be read OUTSIDE pl.when bodies
    # (interpret mode can't substitute it inside a cond branch); the kernels
    # close over the values instead.
    if listed:
        qi, kk = _listed_step(steps_ref, pl.program_id(1), listed)
        return qi, kk, kk, _last_k_block(qi, block_q, block_k)
    step = pl.program_id(2)
    qi = pl.program_id(1)
    kk = step if window is None else step + _first_k_block(
        qi, block_q, block_k, window)
    return qi, kk, step, n_k - 1


# A launcher under ``jax.jit``, everything but its arrays static (``bits`` is
# an operand or None): the like layers of a model share one trace and one
# lowered function of each kernel, where a launcher called bare is traced and
# lowered again in every layer (GPT-2 medium's 72 kernels: 13 s of a warm
# set-up's tracing and lowering on the chip's host against 1.5 s; PERF.md,
# PR 44).  XLA inlines the calls, so the compiled step is the same; a module
# constant a launcher reads (``LISTED_STEPS``) is part of no trace's key.
# Below the ``custom_vjp`` and below ``checkpoint_name``: the names a
# checkpoint policy keeps stay on the kernel's own results.
_one_trace = functools.partial(
    jax.jit, static_argnames=("scale", "causal", "block_q", "block_k",
                              "interpret", "group", "window"))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, block_q, block_k, n_k, keyed,
                listed, window=None):
    from jax.experimental import pallas as pl

    steps_ref, (q_ref, k_ref, v_ref, *refs) = _steps_first(refs, listed)
    bits_ref, (o_ref, lse_ref, m_scr, l_scr, acc_scr) = _bits_first(
        refs, keyed)
    qi, kk, step, end = _row_step(steps_ref, block_q, block_k, n_k, window,
                                  listed)

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked):
        s = _scores(q_ref, k_ref, scale, masked, qi, kk, block_q, block_k,
                    bits_ref, window=window)
        m_prev = m_scr[:]                              # [BQ, lanes]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _across(m_new, block_k))       # [BQ, BK]
        alpha = jnp.exp(m_prev - m_new)                # [BQ, lanes]
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1, keepdims=True)
        v = v_ref[0]                                   # [BK, DV]
        acc_scr[:] = acc_scr[:] * _across(alpha, v.shape[1]) + _dot(
            p.astype(v.dtype), v, ((1,), (0,)))
        m_scr[:] = m_new

    _when_needed(causal, qi, kk, block_q, block_k, _compute, window,
                 listed=bool(listed))

    @pl.when(step == end)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / _across(l, acc_scr.shape[1])).astype(
            o_ref.dtype)
        # the dense row: every lane holds the query's value, one is written
        lse_ref[0] = (m_scr[:] + jnp.log(l)).T[:1]


def _kv_maps(clamped, block_q, block_k, group, window=None):
    """Block index maps of K and V on a (q head, q block, k block) grid:
    query head ``b`` reads KV head ``b // group``; under a window a q
    block's run starts at the first k block it needs; ``clamped`` (a causal
    rectangle, a window's band: a grid with steps past a run's end) it never
    moves past the last, and a block whose index does not change is not
    copied again."""
    def kv(b, i, kk):
        if window is not None:
            kk = kk + _first_k_block(i, block_q, block_k, window)
        if clamped:
            kk = jnp.minimum(kk, _last_k_block(i, block_q, block_k))
        return (b // group, kk, 0)
    return kv


def _bits_first(refs, keyed):
    """``(bits_ref, the others)`` of the references a kernel gets behind its
    fixed inputs: ``key_bits`` come first among them where there are any."""
    return (refs[0], refs[1:]) if keyed else (None, refs)


def _bits_spec(rows, block_q, block_k, q_block, k_block):
    """Block shape and index map (:func:`_launch`'s form) of ``key_bits`` on
    a launcher's grid: a step reads the words of q block ``q_block(*ids)``
    in the group of k block ``k_block(*ids)``; the grid's first index counts
    ``rows`` (heads) a batch row."""
    if block_k % KEY_LANES or KEY_GROUP % block_k:
        raise ValueError(
            "key_bits want a k block that divides by {} and divides {}: "
            "{}".format(KEY_LANES, KEY_GROUP, block_k))
    per_group = KEY_GROUP // block_k
    return ((1, 1, block_q, KEY_LANES),
            lambda b, *ids: (b // rows, k_block(*ids) // per_group,
                             q_block(*ids), 0))


@_one_trace
def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, group=1,
               bits=None, window=None):
    """Returns ``(out [bh, seq, dv], logsumexp [bh, 1, seq])``; ``q`` is
    ``[bh, seq, d]``, ``k [bh // group, seq, d]`` and ``v [bh // group, seq,
    dv]``.  The logsumexp rows are dense, the query on the lanes; the unit
    dim in the middle is for the TPU lowering, which wants a block's last
    two dims divisible by (8, 128) or equal to the array's: a ``(1, 1,
    block_q)`` block is, a ``(1, block_q)`` row block of ``[bh, seq]`` is
    not."""
    from jax.experimental.pallas import tpu as pltpu

    bh, s_len, d = q.shape
    dv = v.shape[-1]
    extents, steps = _row_grid(s_len, block_q, block_k, causal, window)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=extents[1], keyed=bits is not None,
        window=window)
    kv = _kv_maps(causal and steps is None, block_q, block_k, group,
                  window)
    in_specs = [
        ((1, block_q, d), lambda b, i, kk: (b, i, 0)),
        ((1, block_k, d), kv),
        ((1, block_k, dv), kv),
    ]
    args = (q, k, v)
    if bits is not None:
        in_specs.append(_bits_spec(
            bh // bits.shape[0], block_q, block_k,
            lambda i, kk: i, lambda i, kk: kv(0, i, kk)[1]))
        args += (bits,)
    out, lse = _launch(
        kernel, bh, extents, steps, in_specs,
        out_specs=[
            ((1, block_q, dv), lambda b, i, kk: (b, i, 0)),
            ((1, 1, block_q), lambda b, i, kk: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s_len), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _stat_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, _stat_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret, args=args)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, n_k, keyed,
                   listed, window=None):
    from jax.experimental import pallas as pl

    steps_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *refs) = _steps_first(refs, listed)
    bits_ref, (dq_ref, dq_scr, lse_scr, delta_scr) = _bits_first(refs, keyed)
    # the forward kernel's grid, see there
    qi, kk, step, end = _row_step(steps_ref, block_q, block_k, n_k, window,
                                  listed)

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # the q block's dense rows, turned once for all of its tiles
        lse_scr[:] = _on_lanes(lse_ref[0], lse_scr.shape[1])
        delta_scr[:] = _on_lanes(delta_ref[0], delta_scr.shape[1])

    def _compute(masked):
        # p = exp(q k^T * scale - L), recomputed from the saved logsumexp
        p = jnp.exp(_scores(q_ref, k_ref, scale, masked, qi, kk, block_q,
                            block_k, bits_ref, window=window)
                    - _across(lse_scr[:], block_k))
        dp = _dot(do_ref[0], v_ref[0], ((1,), (1,)))   # [BQ, BK]
        ds = p * (dp - _across(delta_scr[:], block_k))
        k = k_ref[0]
        dq_scr[:] += scale * _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    _when_needed(causal, qi, kk, block_q, block_k, _compute, window,
                 listed=bool(listed))

    @pl.when(step == end)
    def _emit():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, n_q, group, keyed,
                    listed, window=None, rows=None):
    from jax.experimental import pallas as pl

    steps_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *refs) = _steps_first(refs, listed)
    bits_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = _bits_first(refs, keyed)
    # the inner grid dimension runs over the KV head's ``group`` query heads
    # and, for each, over the q blocks (``n_q``: all of them, or under a
    # window the longest run that sees a k block, counted from the run's
    # first block; ``rows`` is then how many q blocks there are): one
    # accumulation for all of them.  A grid that lists its steps
    # (:func:`_causal_steps_by_keys`) brings the same (k block, head of the
    # group, q block) without those whose tile no query sees: a k block's
    # first is then its first q block's of the group's first head
    inside = None
    if listed:   # read outside pl.when bodies (interpret mode)
        kk, head, qi = _listed_step(steps_ref, pl.program_id(1), listed)
        j = head * n_q + qi
        start = _first_q_block(kk, block_q, block_k)
    else:
        j = pl.program_id(2)
        qi = j % n_q
        kk = pl.program_id(1)  # read outside pl.when bodies (interpret mode)
        start = 0
        if window is not None:
            qi = qi + _first_q_block(kk, block_q, block_k)
            inside = qi < rows

    @pl.when(j == start)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked):
        # the tile keys by queries: the dense rows go along the lanes
        p = jnp.exp(_scores(q_ref, k_ref, scale, masked, qi, kk, block_q,
                            block_k, bits_ref, keys_first=True, window=window)
                    - lse_ref[0])                      # [BK, BQ] - [1, BQ]
        do = do_ref[0]                                 # [BQ, DV]
        dv_scr[:] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
        dp = _dot(v_ref[0], do, ((1,), (1,)))          # [BK, BQ]
        ds = p * (dp - delta_ref[0])
        q = q_ref[0]
        dk_scr[:] += scale * _dot(ds.astype(q.dtype), q, ((1,), (0,)))

    _when_needed(causal, qi, kk, block_q, block_k, _compute, window, inside,
                 bool(listed))

    @pl.when(j == group * n_q - 1)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_delta(out, g):
    """D_i = rowsum(dO * O) — tiny elementwise pass, left to XLA; dense
    ``[bh, 1, seq]`` like the logsumexp rows (see :func:`_flash_fwd`)."""
    return (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(
        -1)[:, None, :]


@_one_trace
def _flash_bwd_dq(q, k, v, g, lse, delta, scale, causal, block_q, block_k,
                  interpret, group=1, bits=None, window=None):
    from jax.experimental.pallas import tpu as pltpu

    bh, s_len, d = q.shape
    dv = v.shape[-1]
    # the forward kernel's grid, see there
    extents, steps = _row_grid(s_len, block_q, block_k, causal, window)
    kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               n_k=extents[1], keyed=bits is not None,
                               window=window)
    kv = _kv_maps(causal and steps is None, block_q, block_k, group,
                  window)
    in_specs = [
        ((1, block_q, d), lambda b, i, kk: (b, i, 0)),
        ((1, block_k, d), kv),
        ((1, block_k, dv), kv),
        ((1, block_q, dv), lambda b, i, kk: (b, i, 0)),
        ((1, 1, block_q), lambda b, i, kk: (b, 0, i)),
        ((1, 1, block_q), lambda b, i, kk: (b, 0, i)),
    ]
    args = (q, k, v, g, lse, delta)
    if bits is not None:
        in_specs.append(_bits_spec(
            bh // bits.shape[0], block_q, block_k,
            lambda i, kk: i, lambda i, kk: kv(0, i, kk)[1]))
        args += (bits,)
    return _launch(
        kernel, bh, extents, steps, in_specs,
        out_specs=((1, block_q, d), lambda b, i, kk: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _stat_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, _stat_lanes(block_k)), jnp.float32),
        ],
        interpret=interpret, args=args)


@_one_trace
def _flash_bwd_dkv(q, k, v, g, lse, delta, scale, causal, block_q, block_k,
                   interpret, group=1, bits=None, window=None):
    from jax.experimental.pallas import tpu as pltpu

    bh_kv, s_len, d = k.shape
    dv = v.shape[-1]
    n_q = q_blocks = s_len // block_q
    n_k = s_len // block_k
    steps = None
    if window is not None:      # a query head's steps: the longest run
        n_q = _q_run(q_blocks, n_k, block_q, block_k, window)
    elif causal:                # a step for every tile that computes
        steps = _listed(
            _causal_steps_by_keys(n_q, n_k, block_q, block_k, group),
            (n_k, group, n_q))

    if steps is None:
        extents = (n_k, group * n_q)

        def rows(b, kk, j):
            """Block index of a per-query-head array: the ``j // n_q``-th
            query head of KV head ``b``, q block ``j % n_q`` (causal: never
            before the first q block that sees k block ``kk``; under a
            window counted from it, and never past the last)."""
            i = j % n_q
            if window is not None:
                i = jnp.minimum(
                    i + _first_q_block(kk, block_q, block_k),
                    _last_q_block(kk, block_q, block_k, window, q_blocks))
            elif causal:
                i = jnp.maximum(i, _first_q_block(kk, block_q, block_k))
            return (b * group + j // n_q, i, 0)
    else:
        extents = (n_k, group, n_q)

        def rows(b, kk, head, i):   # a listed step names all three
            return (b * group + head, i, 0)

    def stats(*ids):
        head, i, _ = rows(*ids)
        return (head, 0, i)

    def keys(b, kk, *_):
        return (b, kk, 0)

    kernel = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_q=n_q,
                               group=group, keyed=bits is not None,
                               window=window, rows=q_blocks)
    in_specs = [
        ((1, block_q, d), rows),
        ((1, block_k, d), keys),
        ((1, block_k, dv), keys),
        ((1, block_q, dv), rows),
        ((1, 1, block_q), stats),
        ((1, 1, block_q), stats),
    ]
    args = (q, k, v, g, lse, delta)
    if bits is not None:
        in_specs.append(_bits_spec(
            bh_kv // bits.shape[0], block_q, block_k,
            lambda *ids: rows(0, *ids)[1], lambda kk, *_: kk))
        args += (bits,)
    return _launch(
        kernel, bh_kv, extents, steps, in_specs,
        out_specs=[((1, block_k, d), keys), ((1, block_k, dv), keys)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret, args=args)


def _flash_bwd(res, g, scale, causal, block_q, block_k, interpret, group,
               bits=None, window=None):
    q, k, v, out, lse = res
    delta = _bwd_delta(out, g)
    dq = _flash_bwd_dq(q, k, v, g, lse, delta, scale, causal, block_q,
                       block_k, interpret, group, bits, window)
    dk, dv = _flash_bwd_dkv(q, k, v, g, lse, delta, scale, causal, block_q,
                            block_k, interpret, group, bits, window)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, bits, causal, block_q, block_k, interpret, scale, group,
           window):
    """``(out, logsumexp)``, over each query's own keys where ``bits`` holds
    them (None: every causal key), or over its last ``window`` keys.  No
    gradient is taken through the logsumexp rows, nor to ``bits``."""
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                      group, bits, window)


def _flash_vjp_fwd(q, k, v, bits, causal, block_q, block_k, interpret, scale,
                   group, window):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                          group, bits, window)
    out = checkpoint_name(out, KEPT_OUT)
    lse = checkpoint_name(lse, KEPT_LSE)
    return (out, lse), (q, k, v, out, lse, bits)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, scale, group, window,
                   res, g):
    *res, bits = res
    return _flash_bwd(res, g[0], scale, causal, block_q, block_k, interpret,
                      group, bits, window) + (
                          None if bits is None else
                          np.zeros(bits.shape, jax.dtypes.float0),)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _checked(q, k, v, scale, interpret):
    """``(scale, interpret)`` with their defaults in, once the shapes fit."""
    dim, kv_heads = q.shape[3], k.shape[2]
    if k.shape[3] != dim:
        raise ValueError(
            "q {} and k {} differ in width: scores are taken over one"
            .format(q.shape, k.shape))
    if q.shape[2] % kv_heads or v.shape[2] != kv_heads:
        raise ValueError(
            "{} query heads of q {} do not divide into the {} / {} heads of "
            "k {} / v {}".format(q.shape[2], q.shape, kv_heads, v.shape[2],
                                 k.shape, v.shape))
    return (1.0 / (dim ** 0.5) if scale is None else scale,
            _default_interpret() if interpret is None else interpret)


# the blocks the rule below chooses among, largest first, and the widest
# ``[block, width]`` operand a kernel has been compiled with for a v5e
# (tests/test_chip_compile.py)
RULE_BLOCKS = (512, 256, 128)
RULE_BLOCK_ELEMENTS = 512 * 256


def row_block(seq, width):
    """The q and k block that exact attention over rows of ``seq`` positions
    takes where nobody states one: the largest of ``RULE_BLOCKS`` that
    divides the row and whose ``[block, width]`` operands (``width`` the
    wider of q/k's and v's) the kernels have been compiled with; None where
    there is none (a row of 64 or 1,000 positions).  From the shapes alone."""
    return next((block for block in RULE_BLOCKS
                 if seq % block == 0
                 and block * width <= RULE_BLOCK_ELEMENTS), None)


def full_attention_block(q, k, v, mesh=None):
    """The one rule by which ``attention="full"`` takes these kernels: the
    block to run them with over ``q, k [batch, seq, heads, dk]`` and ``v [..,
    dv]`` (:func:`row_block`), or None where the plain contraction runs.

    The kernels where they would run compiled (the process's device is a
    TPU: :func:`_default_interpret`) and the row tiles; on a mesh of more
    than one device also only where :func:`flash_attention`'s per-shard
    mapping fits as it is: the batch divides over ``data``/``fsdp``, both head
    counts over ``tensor``, and no other axis (``seq``, ``expert``) has more
    than one device.  Anywhere else (every CPU process, a row that does not
    tile, a sequence-parallel mesh) the contraction that XLA partitions by
    itself, never an error."""
    if _default_interpret():
        return None
    if mesh is not None and mesh.size > 1:
        sizes = dict(mesh.shape)
        tensor = sizes.pop("tensor", 1)
        rows = sizes.pop("data", 1) * sizes.pop("fsdp", 1)
        if (any(size > 1 for size in sizes.values()) or q.shape[0] % rows
                or q.shape[2] % tensor or k.shape[2] % tensor):
            return None
    return row_block(q.shape[1], max(q.shape[3], v.shape[3]))


def flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                    interpret=None, scale=None, mesh=None, window=None):
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
    everywhere.  ``window`` (static; needs ``causal``): query ``t`` reads the
    keys ``t - window < s <= t`` (module docstring); one that covers the row
    is the causal kernel.

    ``mesh``: the compiler cannot partition a Mosaic kernel, so under a
    multi-device mesh the call is mapped per shard here — batch over the
    mesh's ``data``/``fsdp`` axes, heads over ``tensor`` (attention is
    independent per batch row and head, so no collective is needed; with
    grouped KV heads both head counts must divide by the ``tensor`` axis).
    """
    scale, interpret = _checked(q, k, v, scale, interpret)
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
        spec = P(batch_axes or None, None,
                 "tensor" if "tensor" in mesh.axis_names else None, None)
        local = functools.partial(
            flash_attention, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, scale=scale, window=window)
        # pallas_call's outputs carry no varying-axes annotation
        return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
    return flash_attention_lse(q, k, v, causal, block_q, block_k, interpret,
                               scale, window=window)[0]


def flash_attention_lse(q, k, v, causal=True, block_q=128, block_k=128,
                        interpret=None, scale=None, key_bits=None,
                        window=None):
    """:func:`flash_attention` on one device with the softmax's statistics
    beside the output: ``(out, logsumexp [batch, seq, heads])`` (float32,
    natural log, of the scaled scores over the query's keys; no gradient
    passes through it), for a caller that needs the probabilities again.

    ``key_bits`` (``[batch, groups, seq, 128]`` int32, made by
    :func:`tensorflowonspark_tpu.ops.sparse_index.select_keys`; see the
    module docstring) keeps every query to its own keys; ``window`` keeps it
    to its last ``window`` keys, and is refused beside ``key_bits`` or
    without ``causal`` (``ValueError``)."""
    scale, interpret = _checked(q, k, v, scale, interpret)
    batch, s_len, heads, _ = q.shape
    if window is not None:
        if not causal or key_bits is not None or window < 1:
            raise ValueError(
                "window={!r} wants causal=True and no key_bits (causal={}, "
                "key_bits {})".format(window, causal,
                                      "given" if key_bits is not None
                                      else "none"))
        if window >= s_len:     # every causal key: the causal kernel
            window = None
    block_q = min(block_q, s_len)
    block_k = min(block_k, s_len)
    if s_len % block_q or s_len % block_k:
        raise ValueError(
            "sequence length {} does not divide by the block sizes (q {}, "
            "k {}): pad the sequence upstream or pass blocks that divide it"
            .format(s_len, block_q, block_k))

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, s_len, x.shape[3])

    def unfold(x):
        return x.reshape(batch, heads, s_len, x.shape[2]).transpose(0, 2, 1, 3)

    out, lse = _flash(fold(q), fold(k), fold(v), key_bits, causal, block_q,
                      block_k, interpret, scale, heads // k.shape[2], window)
    lse = lse.reshape(batch, heads, s_len).transpose(0, 2, 1)
    return unfold(out), jax.lax.stop_gradient(lse)
