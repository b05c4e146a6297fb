"""The row-wise passes between an expert layer's grouped products as pallas
TPU kernels that stop at the rows routed here: the gate, its backward, and
the sum of the two input gradients.

The sorted buffers of
:func:`~tensorflowonspark_tpu.parallel.ep.experts_ffn` have the static
worst-case number of rows; the pairs routed to the held experts lie in front
of ``n_rows``.  An XLA fusion between two grouped products passes over every
row of the buffer whatever ``n_rows`` says.  These kernels visit the row
tiles in front of ``n_rows`` and no other (``routed_rows._front_tile``: a
tile wholly behind maps to the last one in front, so it is neither fetched
nor written, and the body runs under ``pl.when``), as the grouped products
(:mod:`~tensorflowonspark_tpu.ops.grouped_matmul`) and the row movement
(:mod:`~tensorflowonspark_tpu.ops.routed_rows`) do:

- :func:`gate`: ``silu(h1) * h3`` (``act="swiglu"``) or ``relu(h1) ** 2``
  (``"relu2"``, no ``h3``);
- :func:`gate_grad`: ``(d_h1, d_h3)`` from ``(h1, h3, d_h)`` in one pass,
  ``d_h1`` written over ``d_h``;
- :func:`add_rows`: ``a + b``, written over ``a``.

**Rows behind ``n_rows`` are not written**; a tile that straddles it is
computed whole.  The arithmetic is the plain form's and jax's own derivative
of it, in float32 on a piece of a tile at a time, rounded once to the arrays'
dtype (what XLA's fusion of the plain form does on a TPU, which keeps the
excess precision between its operations).

Off the TPU the default is the plain ``jax.numpy`` form over every row;
``impl="pallas", interpret=True`` runs the kernels in interpret mode, which
is what the tests compare with it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.ops import routed_rows
from tensorflowonspark_tpu.ops.grouped_matmul import VMEM_BUDGET, _row_tile
from tensorflowonspark_tpu.ops.routed_rows import (_as_n, _front_tile,
                                                    _pallas)

# rows of the sorted buffer a grid step takes
TILE = 256
# the piece of a tile the body computes at a time: a value of it is 4
# float32 registers, so a backward's eight live values stay in the 64
PIECE_ROWS, PIECE_LANES = 32, 128

_ACTS = {"swiglu": lambda h1, h3: jax.nn.silu(h1) * h3,
         "relu2": lambda h1: jnp.square(jax.nn.relu(h1))}


def _act(act):
    if act not in _ACTS:
        raise ValueError("unknown expert form {!r}".format(act))
    return _ACTS[act]


def _ups(h1, h3):
    """The "up" products a form has: ``h1`` alone where ``h3`` is None."""
    return [h1] if h3 is None else [h1, h3]


def row_tile(rows, width, dtype, most=None):
    """Rows a grid step of these kernels takes of ``[rows, width]`` arrays:
    ``TILE``, halved while ``rows`` does not divide or five arrays a tile
    (the backward's), double buffered at the full width (a width that is no
    multiple of 128 lanes, 1,856, cannot be blocked), pass
    ``VMEM_BUDGET``."""
    tile = _row_tile(rows, most or TILE)
    while tile > 8 and 2 * 5 * tile * width * jnp.dtype(
            dtype).itemsize > VMEM_BUDGET:
        tile //= 2
    return tile


def _kernel(n_ref, *refs, fn, inputs, tile):
    """``fn`` (float32 pieces in, a tuple of float32 pieces out) over a row
    tile ``[tile, width]`` of every operand, where the tile begins in front
    of ``n_rows``."""
    pl, _ = _pallas()
    ins, outs = refs[:inputs], refs[inputs:]

    @pl.when(pl.program_id(0) * tile < n_ref[0])
    def _():
        width = ins[0].shape[1]
        rows = min(PIECE_ROWS, tile)

        def group(g, carry):
            at = pl.ds(pl.multiple_of(g * rows, rows), rows)
            for c in range(0, width, PIECE_LANES):
                piece = (at, slice(c, min(c + PIECE_LANES, width)))
                values = fn(*[ref[piece].astype(jnp.float32) for ref in ins])
                for ref, value in zip(outs, values):
                    ref[piece] = value.astype(ref.dtype)
            return carry

        lax.fori_loop(0, tile // rows, group, 0)


def _front_rows(fn, name, n_rows, arrays, outputs, over, tile, interpret):
    """``outputs`` arrays like ``arrays[0]``, ``fn`` of the ``arrays``' rows
    on the row tiles in front of ``n_rows``; the first output is written
    over ``arrays[over]``."""
    pl, pltpu = _pallas()
    rows, width = arrays[0].shape
    tile = row_tile(rows, width, arrays[0].dtype, tile)
    spec = pl.BlockSpec((tile, width), _front_tile(tile))
    like = jax.ShapeDtypeStruct((rows, width), arrays[0].dtype)
    return pl.pallas_call(
        functools.partial(_kernel, fn=fn, inputs=len(arrays), tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tile,),
            in_specs=[spec] * len(arrays), out_specs=[spec] * outputs),
        out_shape=[like] * outputs,
        # operand 0 is n_rows
        input_output_aliases={} if over is None else {1 + over: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(_as_n(n_rows), *arrays)


# jitted, as the wrappers of routed_rows are and for their reason: four like
# layers and three passes (forward, recomputed, backward) lower each kernel
# once a step
@functools.partial(jax.jit, static_argnames=("act", "tile", "interpret"))
def _gate_pallas(h1, h3, n_rows, act, tile, interpret):
    fn = _act(act)
    return _front_rows(lambda *v: (fn(*v),), "expert_gate", n_rows,
                       _ups(h1, h3), 1, None, tile, interpret)[0]


@functools.partial(jax.jit, static_argnames=("act", "tile", "interpret"))
def _gate_grad_pallas(h1, h3, d_h, n_rows, act, tile, interpret):
    fn = _act(act)
    ups = _ups(h1, h3)
    out = _front_rows(lambda *v: jax.vjp(fn, *v[:-1])[1](v[-1]),
                      "expert_gate_grad", n_rows, ups + [d_h], len(ups),
                      len(ups), tile, interpret)
    return out[0], (None if h3 is None else out[1])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _add_pallas(a, b, n_rows, tile, interpret):
    return _front_rows(lambda a, b: (a + b,), "expert_gate_sum", n_rows,
                       [a, b], 1, 0, tile, interpret)[0]


def _impl(impl, interpret):
    if impl is None:    # one choice for the layer's row passes
        return routed_rows._default_impl()
    if impl not in ("pallas", "xla"):
        raise ValueError("unknown expert_gate impl {!r}".format(impl))
    return impl, interpret


def gate(h1, h3, n_rows, act="swiglu", impl=None, interpret=False):
    """``h1``/``h3 [rows, F]`` (``h3`` None for ``act="relu2"``), ``n_rows``
    int32 scalar -> ``[rows, F]`` in their dtype: ``silu(h1) * h3`` or
    ``relu(h1) ** 2`` on the rows in front of ``n_rows``.  **Rows behind the
    tile that holds row ``n_rows - 1`` are not written** (unspecified).

    ``impl``: ``"pallas"`` (the kernel; ``interpret=True`` off the TPU) or
    ``"xla"`` (the plain form over every row); None picks the kernel on a
    TPU and XLA elsewhere.  Not differentiable: the expert layer gives both
    directions (:func:`~tensorflowonspark_tpu.parallel.ep.experts_ffn`)."""
    impl, interpret = _impl(impl, interpret)
    if impl == "pallas":
        return _gate_pallas(h1, h3, n_rows, act=act, tile=TILE,
                            interpret=interpret)
    return _act(act)(*_ups(h1, h3))


def gate_grad(h1, h3, d_h, n_rows, act="swiglu", impl=None, interpret=False):
    """The cotangents ``(d_h1, d_h3)`` of :func:`gate`'s operands from the
    result's, ``d_h [rows, F]``, in one pass (``d_h3`` None for
    ``"relu2"``); rows and ``impl`` as :func:`gate`.  The kernel writes
    ``d_h1`` over ``d_h``."""
    impl, interpret = _impl(impl, interpret)
    if impl == "pallas":
        return _gate_grad_pallas(h1, h3, d_h, n_rows, act=act, tile=TILE,
                                 interpret=interpret)
    out = jax.vjp(_act(act), *_ups(h1, h3))[1](d_h)
    return out[0], (None if h3 is None else out[1])


def add_rows(a, b, n_rows, impl=None, interpret=False):
    """``a + b`` (``[rows, D]`` each) on the rows in front of ``n_rows``,
    summed in float32; rows and ``impl`` as :func:`gate`.  The kernel
    writes the sum over ``a``."""
    impl, interpret = _impl(impl, interpret)
    if impl == "pallas":
        return _add_pallas(a, b, n_rows, tile=TILE, interpret=interpret)
    return a + b
