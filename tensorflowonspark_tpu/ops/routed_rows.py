"""Row movement of an expert layer as pallas TPU kernels that stop at the
rows routed here: a gather into expert order and a gather-and-sum back to
token order.

The sorted buffers of
:func:`~tensorflowonspark_tpu.parallel.ep.experts_ffn` have the static
worst-case number of rows (every (token, slot) pair routed to the held
experts); the pairs that are lie in front of ``n_rows``.  XLA's gather moves
every row of the buffer whatever ``n_rows`` says.  These kernels fetch a row
only where its sorted position lies in front of ``n_rows`` (one DMA a row,
a tile's DMAs all in flight together), so their work follows the routed
pairs as the grouped products' does
(:mod:`~tensorflowonspark_tpu.ops.grouped_matmul`) and that of the row-wise
passes between those (:mod:`~tensorflowonspark_tpu.ops.expert_gate`, which
takes its row tiles through this module's ``_front_tile``):

- :func:`gather_rows`: ``out[i] = scale[i] * x[src[i]]`` for ``i < n_rows``
  (dispatch; the gradient of combine with respect to the experts' output,
  which also wants the rows' dot products with that output, ``dot_with``);
- :func:`gather_sum_rows`: ``out[t] = sum_j [idx[t, j] < n_rows] *
  weights[t, j] * ys[idx[t, j]]`` accumulated in float32 and written once
  (combine; the gradient of dispatch).  A token tile's present slots are
  listed first (a sort inside each tile, by XLA), so the kernel's scalar
  loop runs over those and not over every slot: a turn of that loop costs
  as much as the DMA it starts.

**Rows behind ``n_rows`` are not written by** :func:`gather_rows` **and not
read by** :func:`gather_sum_rows`: they may hold anything.

A single row of an array in HBM is no DMA: the array is tiled, (8, 128)
32-bit words a tile, and a copy takes whole tiles of rows.  So a source is
first **packed into slabs** (``routed_rows_pack``, one pass over the row
tiles in front of ``n_rows`` and none behind): a row's 32-bit words, 128 to a
slab row, so that each row is a run of whole tiles of its own (4 KB for 2,048
bfloat16) and its fetch is one contiguous DMA.  A 16-bit row is packed two
elements a word, the first half of its columns in the low halves and the
second in the high, so that a word's halves widen to float32 by a shift and
a mask and land 128 lanes apart.  The kernels turn the slabs they fetched
back into rows with strided sublane loads.

Off the TPU the default is XLA's take with a mask (the plain formulation);
``impl="pallas", interpret=True`` runs the kernels in interpret mode, which
is what the tests compare with it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.ops.grouped_matmul import _row_tile

# rows of the sorted buffer a grid step of gather_rows fills, and tokens a
# grid step of gather_sum_rows sums (k fetched rows each)
GATHER_TILE = 256
SUM_TILE = 128
# turns of a kernel's scalar loops (start a copy, wait for one) unrolled
UNROLL = 4


def _default_impl():
    """``(impl, interpret)``: the compiled kernels exactly when the process's
    platform is ``tpu`` (as ``grouped_matmul._default_impl``); XLA's take
    elsewhere."""
    from tensorflowonspark_tpu.device_info import is_tpu_device

    return ("pallas", False) if is_tpu_device() else ("xla", False)


def _pallas():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def _each(count, body):
    """``body(i)`` for ``i`` in ``range(count)``, ``count`` a traced scalar,
    ``UNROLL`` a turn of the loop: a turn that starts one DMA costs 37 ns on a
    v5e and a quarter of a turn that starts four costs 23."""
    groups = count // UNROLL

    def group(g, carry):
        for u in range(UNROLL):
            body(g * UNROLL + u)
        return carry

    def one(i, carry):
        body(i)
        return carry

    lax.fori_loop(0, groups, group, 0)
    lax.fori_loop(groups * UNROLL, count, one, 0)


def _eye(n):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _column(row):
    """A lane row ``[1, n]`` (float32) as a sublane column ``[n, 1]``: the
    diagonal of its broadcast, summed along the lanes."""
    return jnp.where(_eye(row.shape[1]), row, 0.0).sum(axis=1, keepdims=True)


def _lane_row(column):
    """The inverse of :func:`_column`: ``[n, 1]`` -> ``[1, n]``."""
    return jnp.where(_eye(column.shape[0]), column, 0.0).sum(
        axis=0, keepdims=True)


def _slab(d, dtype):
    """``(sublanes, lanes, packed)`` of one row of ``d`` elements as a slab
    of 32-bit words: 128 lanes wide where the words divide so, or where the
    elements do (a 16-bit row of an odd number of 128s: the last slab row is
    half used), one sublane otherwise (sizes only the interpreter sees).
    ``packed``: a 16-bit dtype, two elements a word, columns ``sublanes *
    lanes`` apart."""
    packed = jnp.dtype(dtype).itemsize == 2
    if jnp.dtype(dtype).itemsize not in (2, 4) or (packed and d % 2):
        raise ValueError("routed_rows: rows of {} x {} are not 32-bit "
                         "words".format(d, jnp.dtype(dtype).name))
    words = d // 2 if packed else d
    lanes = 128 if d % 128 == 0 else words
    return -(-words // lanes), lanes, packed


def _to_words(lo, hi):
    """Two bfloat16 (or float16) blocks as one block of uint32 words: ``lo``
    in the low halves, ``hi`` in the high."""
    def bits(a):
        return lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.uint32)

    return bits(lo) | (bits(hi) << jnp.uint32(16))


def _from_words(words, dtype):
    """The inverse of :func:`_to_words`, each half widened to float32."""
    if dtype == jnp.bfloat16:       # a bfloat16 is the top half of a float32
        return (lax.bitcast_convert_type(words << jnp.uint32(16),
                                         jnp.float32),
                lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                         jnp.float32))

    def half(w):
        return lax.bitcast_convert_type(w.astype(jnp.uint16), dtype).astype(
            jnp.float32)

    return half(words & jnp.uint32(0xFFFF)), half(words >> jnp.uint32(16))


def _pack_kernel(n_ref, x_ref, out_ref, *, tile, sublanes, lanes, packed):
    """Rows ``[tile, D]`` as slabs ``[tile * sublanes, lanes]``: slab row
    ``c`` of a packed source holds columns ``c * lanes ..`` of the row's
    first half in the low halves of its words and the same columns of the
    second half in the high halves."""
    pl, _ = _pallas()

    @pl.when(pl.program_id(0) * tile < n_ref[0])
    def _():
        half = sublanes * lanes
        for c in range(sublanes):
            cols = slice(c * lanes, (c + 1) * lanes)
            if packed:
                lo = x_ref[:, cols]
                words = _to_words(
                    lo, x_ref[:, slice(half + cols.start, half + cols.stop)]
                    if half + cols.start < x_ref.shape[1]
                    else jnp.zeros_like(lo))
            else:
                words = x_ref[:, cols]
            out_ref[pl.ds(c, tile, stride=sublanes), :] = words


def _front_tile(tile, tail=1):
    """Index map of a buffer read or written by row tiles: a tile wholly
    behind ``n_rows`` maps to the last one in front of it, and a block whose
    index does not change is neither fetched nor written again."""
    def index(i, n_ref):
        last = jnp.maximum(n_ref[0] - 1, 0) // tile
        return (jnp.minimum(i, last),) + (0,) * tail

    return index


def _smem_spec(total, step):
    """``(BlockSpec, offset)`` that bring a 1-D int32 operand of ``total``
    entries to SMEM ``step`` (a power of two) a grid step.  XLA lays such an
    operand out in tiles of 1,024, so a block is ``step`` where that is a
    multiple, else 1,024 entries holding several steps, else the whole
    (small) array; ``offset(i)`` is where grid step ``i``'s entries begin
    inside its block."""
    pl, pltpu = _pallas()
    if step % 1024 == 0:
        block = step
    else:
        block = 1024 if total % 1024 == 0 else total
    steps = block // step
    spec = pl.BlockSpec((block,), lambda i, *_: (i // steps,),
                        memory_space=pltpu.SMEM)
    return spec, lambda i: (i % steps) * step


def _as_n(n_rows):
    return jnp.reshape(n_rows, (1,)).astype(jnp.int32)


# jitted, as the two kernels' wrappers below are: an expert layer calls each
# several times a step at the same shapes, and a step lowers the kernel of a
# jitted function once, not once a call (40 kernels a step otherwise, and
# 3 s more of every lowering of the step)
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _pack(x, n_rows, tile, interpret):
    """``x [rows, D]`` -> ``[rows * sublanes, lanes]`` 32-bit words, the row
    tiles in front of ``n_rows`` only (the others are not written)."""
    pl, pltpu = _pallas()
    rows, d = x.shape
    sublanes, lanes, packed = _slab(d, x.dtype)
    tile = _row_tile(rows, tile)
    front = _front_tile(tile)
    return pl.pallas_call(
        functools.partial(_pack_kernel, tile=tile, sublanes=sublanes,
                          lanes=lanes, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tile,),
            in_specs=[pl.BlockSpec((tile, d), front)],
            out_specs=pl.BlockSpec((tile * sublanes, lanes), front)),
        out_shape=jax.ShapeDtypeStruct(
            (rows * sublanes, lanes), jnp.uint32 if packed else x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="routed_rows_pack",
    )(_as_n(n_rows), x)


def _row_copy(source, row, buf, slot, sem, sublanes):
    """The copy of slab ``row`` of ``source [rows * sublanes, lanes]`` (HBM)
    into slab ``slot`` of ``buf`` (VMEM)."""
    pl, pltpu = _pallas()

    def at(i):
        return pl.ds(pl.multiple_of(i * sublanes, sublanes), sublanes)

    return pltpu.make_async_copy(source.at[at(row)], buf.at[at(slot)], sem)


def _wait_rows(source, buf, sem, sublanes, count):
    """Wait for ``count`` row copies on ``sem`` (each wait takes one slab's
    worth off the semaphore, whichever slab it names)."""
    _each(count,
          lambda _: _row_copy(source, 0, buf, 0, sem, sublanes).wait())


def _chunks(buf, first, tile, sublanes, lanes, packed, dtype, d):
    """The ``tile`` slabs of ``buf`` from slab ``first`` on as ``(columns,
    float32 [tile, lanes])`` pieces of the rows ``[tile, d]`` they hold: the
    inverse of :func:`_pack_kernel`, a slab row at a time."""
    pl, _ = _pallas()
    half = sublanes * lanes
    for c in range(sublanes):
        cols = slice(c * lanes, (c + 1) * lanes)
        words = buf[pl.ds(first * sublanes + c, tile, stride=sublanes), :]
        if packed:
            lo, hi = _from_words(words, dtype)
            yield cols, lo
            if half + cols.start < d:
                yield slice(half + cols.start, half + cols.stop), hi
        else:
            yield cols, words.astype(jnp.float32)


def _gather_kernel(n_ref, src_ref, *refs, tile, offset, sublanes, lanes,
                   packed, scaled, dotted):
    pl, _ = _pallas()
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    with_ref = refs.pop(0) if dotted else None
    source, out_ref = refs[0], refs[1]
    dots_ref = refs[2] if dotted else None
    buf, sem = refs[-2], refs[-1]
    step = pl.program_id(0)
    rows = jnp.clip(n_ref[0] - step * tile, 0, tile)

    @pl.when(rows > 0)
    def _():
        base = offset(step)

        def start(r):
            _row_copy(source, src_ref[base + r], buf, r, sem,
                      sublanes).start()

        _each(rows, start)
        _wait_rows(source, buf, sem, sublanes, rows)
        scale = _column(scale_ref[...]) if scaled else None
        dots = jnp.zeros((tile, 1), jnp.float32)
        for cols, value in _chunks(buf, 0, tile, sublanes, lanes, packed,
                                   out_ref.dtype, out_ref.shape[1]):
            if dotted:
                dots += (value * with_ref[:, cols].astype(jnp.float32)).sum(
                    axis=1, keepdims=True)
            if scaled:
                value = value * scale
            out_ref[:, cols] = value.astype(out_ref.dtype)
        if dotted:
            dots_ref[...] = _lane_row(dots)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gather_pallas(x, src, n_rows, scale, dot_with, tile, interpret):
    pl, pltpu = _pallas()
    m, d = src.shape[0], x.shape[1]
    sublanes, lanes, packed = _slab(d, x.dtype)
    source = _pack(x, x.shape[0], tile, interpret)
    tile = _row_tile(m, tile)
    tiles = m // tile
    lane_spec = pl.BlockSpec((None, 1, tile), lambda i, n: (i, 0, 0))
    front = _front_tile(tile)
    src_spec, offset = _smem_spec(m, tile)
    operands = [src]
    in_specs = [src_spec]
    if scale is not None:
        operands.append(scale.astype(jnp.float32).reshape(tiles, 1, tile))
        in_specs.append(lane_spec)
    if dot_with is not None:
        operands.append(dot_with)
        in_specs.append(pl.BlockSpec((tile, d), front))
    operands.append(source)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out_shape = [jax.ShapeDtypeStruct((m, d), x.dtype)]
    out_specs = [pl.BlockSpec((tile, d), front)]
    if dot_with is not None:
        out_shape.append(jax.ShapeDtypeStruct((tiles, 1, tile), jnp.float32))
        out_specs.append(pl.BlockSpec((None, 1, tile), _front_tile(tile, 2)))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tile=tile, offset=offset,
                          sublanes=sublanes, lanes=lanes, packed=packed,
                          scaled=scale is not None,
                          dotted=dot_with is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tile * sublanes, lanes),
                                       source.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="routed_rows_gather",
    )(_as_n(n_rows), *operands)
    if dot_with is None:
        return out[0]
    return out[0], out[1].reshape(m)


def gather_rows(x, src, n_rows, scale=None, dot_with=None, impl=None,
                interpret=False):
    """``x [rows, D]``, ``src [m]`` int32, ``n_rows`` int32 scalar ->
    ``out [m, D]`` in ``x``'s dtype with ``out[i] = x[src[i]]`` for ``i <
    n_rows``, times ``scale[i]`` (float32 ``[m]``) where given.  **Rows from
    ``n_rows`` on are unspecified** (the kernel does not write them).

    With ``dot_with [m, D]`` also returns ``dots [m]`` float32, ``dots[i] =
    <dot_with[i], x[src[i]]>`` (unscaled), unspecified from ``n_rows`` on
    likewise; ``dot_with`` is not read there.

    ``impl``: ``"pallas"`` (the kernel; ``interpret=True`` off the TPU) or
    ``"xla"`` (a take of all ``m`` rows); None picks the kernel on a TPU and
    XLA elsewhere.  Not differentiable: the expert layer gives both
    directions (:func:`~tensorflowonspark_tpu.parallel.ep.experts_ffn`)."""
    if impl is None:
        impl, interpret = _default_impl()
    if impl == "pallas":
        return _gather_pallas(x, src.astype(jnp.int32), n_rows, scale,
                              dot_with, tile=GATHER_TILE, interpret=interpret)
    if impl != "xla":
        raise ValueError("unknown gather_rows impl {!r}".format(impl))
    rows = x[src]
    out = rows
    if scale is not None:
        out = (rows.astype(jnp.float32) * scale[:, None]).astype(x.dtype)
    if dot_with is None:
        return out
    return out, (rows.astype(jnp.float32)
                 * dot_with.astype(jnp.float32)).sum(axis=-1)


def _sum_kernel(counts_ref, rows_ref, slabs_ref, w_ref, source, out_ref, buf,
                sem, *, tile, slots, offset, sublanes, lanes, packed):
    pl, _ = _pallas()
    step = pl.program_id(0)
    base = offset(step)
    count = counts_ref[step]

    def start(i):
        _row_copy(source, rows_ref[base + i], buf, slabs_ref[base + i], sem,
                  sublanes).start()

    _each(count, start)
    _wait_rows(source, buf, sem, sublanes, count)
    # an absent slot's slab holds whatever was there: its weight, 0, selects
    columns = [_column(w_ref[j:j + 1, :]) for j in range(slots)]
    for pieces in zip(*[_chunks(buf, j * tile, tile, sublanes, lanes, packed,
                                out_ref.dtype, out_ref.shape[1])
                        for j in range(slots)]):
        cols = pieces[0][0]
        acc = jnp.zeros((tile, cols.stop - cols.start), jnp.float32)
        for column, (_, value) in zip(columns, pieces):
            acc += jnp.where(column != 0.0, value * column, 0.0)
        out_ref[:, cols] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tile", "pack_tile", "interpret"))
def _sum_pallas(ys, idx, n_rows, weights, tile, pack_tile, interpret):
    pl, pltpu = _pallas()
    tokens, slots = idx.shape
    d = ys.shape[1]
    sublanes, lanes, packed = _slab(d, ys.dtype)
    source = _pack(ys, n_rows, pack_tile, interpret)
    tile = _row_tile(tokens, tile)
    tiles = tokens // tile
    present = idx < n_rows
    if weights is None:
        weights = jnp.ones(idx.shape, jnp.float32)

    def by_tile(a):     # slot-major inside a token tile: [tiles, slots, tile]
        return a.reshape(tiles, tile, slots).swapaxes(1, 2)

    # a tile's present slots first (by position in ys: absent ones sort
    # last), each with the slab of the tile's buffer it fills: the kernel
    # loops over a tile's count of them, not over its slots
    behind = jnp.iinfo(jnp.int32).max
    rows, slabs = lax.sort(
        (by_tile(jnp.where(present, idx, behind)).reshape(tiles, -1),
         lax.broadcasted_iota(jnp.int32, (tiles, slots * tile), 1)),
        dimension=1, num_keys=1)
    # a power of two of scalars a tile, as the blocks of SMEM want
    step = tile * (1 << (slots - 1).bit_length())
    pad = ((0, 0), (0, step - slots * tile))
    spec, offset = _smem_spec(tiles * step, step)
    return pl.pallas_call(
        functools.partial(_sum_kernel, tile=tile, slots=slots, offset=offset,
                          sublanes=sublanes, lanes=lanes, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,),
            in_specs=[spec, spec,
                      pl.BlockSpec((None, slots, tile),
                                   lambda i, c: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, c: (i, 0)),
            scratch_shapes=[pltpu.VMEM((slots * tile * sublanes, lanes),
                                       source.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), ys.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="routed_rows_sum",
    )(by_tile(present).sum(axis=(1, 2), dtype=jnp.int32),
      jnp.pad(rows, pad).reshape(-1), jnp.pad(slabs, pad).reshape(-1),
      by_tile(jnp.where(present, weights.astype(jnp.float32), 0.0)), source)


def gather_sum_rows(ys, idx, n_rows, weights=None, impl=None,
                    interpret=False):
    """``ys [m, D]``, ``idx [tokens, k]`` int32 positions in ``ys``,
    ``n_rows`` int32 scalar -> ``out [tokens, D]`` in ``ys``'s dtype:
    ``out[t] = sum_j [idx[t, j] < n_rows] * weights[t, j] * ys[idx[t, j]]``
    (``weights`` float32 ``[tokens, k]``, 1 where absent), summed in
    float32.  **Rows of ``ys`` from ``n_rows`` on are not read.**

    ``impl`` as :func:`gather_rows`.  Not differentiable."""
    if impl is None:
        impl, interpret = _default_impl()
    if impl == "pallas":
        return _sum_pallas(ys, idx.astype(jnp.int32), n_rows, weights,
                           tile=SUM_TILE, pack_tile=GATHER_TILE,
                           interpret=interpret)
    if impl != "xla":
        raise ValueError("unknown gather_sum_rows impl {!r}".format(impl))
    rows = ys[idx].astype(jnp.float32)                       # [tokens, k, D]
    if weights is not None:
        rows = rows * weights[..., None]
    return jnp.where((idx < n_rows)[..., None], rows, 0.0).sum(
        axis=1).astype(ys.dtype)
