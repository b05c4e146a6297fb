"""Grouped matrix product as pallas TPU kernels: ``out[rows of group g] =
lhs[rows of group g] @ rhs[g]`` for row groups given by their sizes.

The product an expert layer without dropped tokens needs
(:func:`~tensorflowonspark_tpu.parallel.ep.experts_ffn`): the rows are the
(token, slot) pairs sorted by expert, ``rhs`` the stacked weights of the
experts held, ``group_sizes`` what the router sent to each.  The buffer has
the static worst-case number of rows; the kernels visit only the row tiles
that lie inside a group (a scalar-prefetched tile -> group map; a tile that
straddles a boundary is visited once a group, masked), so the work follows
``sum(group_sizes)``, not the buffer.  **Rows behind the last group are not
written** and, where a product sums over rows (the gradient of ``rhs``), not
read: the expert layer leaves them as they are and reads none of them (what
it does to a product's rows between two products, the gate and the sum of two
input gradients, visits the row tiles in front of the last group only:
:mod:`~tensorflowonspark_tpu.ops.expert_gate`).

The kernels are the ``megablox`` grouped products that ship with jax
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the forward
product and the gradient of ``lhs``, ``tgmm`` for the gradient of ``rhs``),
each called here with tiles chosen for its own shapes.  XLA's own lowering
of ``jax.lax.ragged_dot`` for the TPU is a kernel of the same kind, but its
custom calls carry no ``op_name`` (``ragged-dot-none``), so a device trace
cannot say which layer or scope their time belongs to; these carry the
``jax.named_scope`` path like any operation.

Off the TPU the default is ``jax.lax.ragged_dot`` (the same mathematics by
XLA); ``impl="pallas", interpret=True`` runs the kernels in interpret mode,
which is what the tests compare with it.
"""

import functools
import importlib

import jax
import jax.numpy as jnp


def _default_impl():
    """The kernels exactly when the process's platform is ``tpu`` (as
    ``flash_attention._default_interpret``); XLA's ``ragged_dot`` elsewhere."""
    from tensorflowonspark_tpu.device_info import is_tpu_device

    return "pallas" if is_tpu_device() else "xla"


def _backend():
    # the package's ``gmm`` attribute is its custom-VJP function, which
    # shadows the module of the kernels
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tile(size, most, unit=128):
    """The largest multiple of ``unit`` that divides ``size`` and is at most
    ``most``; ``size`` itself where none does (a small or odd dimension:
    one tile)."""
    for tile in range(min(most, size) // unit * unit, 0, -unit):
        if size % tile == 0:
            return tile
    return size


def _row_tile(m, most=512):
    tile = most
    while tile > 8 and m % tile:
        tile //= 2
    if m % tile:
        raise ValueError(
            "grouped_matmul: {} rows do not divide into tiles of 8".format(m))
    return tile


# what a kernel's tiles may take of VMEM (operands and result double
# buffered, the float32 accumulator once): under the 16 MiB a kernel gets
VMEM_BUDGET = 13 * 2 ** 20


def _smaller(size, tile):
    """The next multiple of 128 under ``tile`` that divides ``size``; None
    where there is none (``tile`` is the whole of an odd dimension, or 128)."""
    if tile % 128:
        return None
    return next((t for t in range(tile - 128, 0, -128) if size % t == 0),
                None)


def _tiling(m, k, n, itemsize=2, out_rows="m"):
    """``(tm, tk, tn)``: 512 rows and the largest tiles of ``k`` and ``n`` up
    to 1,024, shrunk where the kernel's buffers would pass ``VMEM_BUDGET``:
    a width with no divisor that is a multiple of 128 (1,856) is one tile,
    and the other dimensions make room for it, ``k`` first (more steps over
    an accumulator that stays in VMEM), then ``n``, then the rows (the
    weights are read once more a row tile).  ``out_rows="k"``: the
    transposed product, ``[tk, tm] @ [tm, tn]`` summed over the rows."""
    tiles = {"m": _row_tile(m), "k": _tile(k, 1024), "n": _tile(n, 1024)}

    def need(m, k, n):
        operands = k * m + m * n if out_rows == "k" else m * k + k * n
        result = (k if out_rows == "k" else m) * n
        return 2 * itemsize * (operands + result) + 4 * result

    while need(**tiles) > VMEM_BUDGET:
        smaller = {"k": _smaller(k, tiles["k"]), "n": _smaller(n, tiles["n"]),
                   "m": tiles["m"] // 2 if tiles["m"] > 128 else None}
        axis = next((a for a in "knm" if smaller[a]), None)
        if axis is None:
            break
        tiles[axis] = smaller[axis]
    return tiles["m"], tiles["k"], tiles["n"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, interpret):
    m, k = lhs.shape
    return _backend().gmm(lhs, rhs, group_sizes, lhs.dtype,
                          _tiling(m, k, rhs.shape[2], lhs.dtype.itemsize),
                          interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _gmm_grads(lhs, rhs, group_sizes, grad, interpret):
    backend = _backend()
    m, k = lhs.shape
    n = rhs.shape[2]
    size = lhs.dtype.itemsize
    grad = grad.astype(lhs.dtype)
    d_lhs = backend.gmm(grad, rhs, group_sizes, lhs.dtype,
                        _tiling(m, n, k, size), transpose_rhs=True,
                        interpret=interpret)
    d_rhs = backend.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                         _tiling(m, k, n, size, out_rows="k"),
                         interpret=interpret)
    return d_lhs, d_rhs


def _gmm_bwd(interpret, residual, grad):
    return _gmm_grads(*residual, grad, interpret) + (None,)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, impl=None, interpret=False):
    """``lhs [m, k]``, ``rhs [groups, k, n]``, ``group_sizes [groups]`` int32
    with ``sum <= m`` -> ``[m, n]`` in ``lhs``'s dtype: row ``i`` of group
    ``g`` (the groups lie one after another from row 0) is ``lhs[i] @
    rhs[g]``.  Rows behind the last group are unspecified: do not read them.
    Differentiable in ``lhs`` and ``rhs``.

    ``impl``: ``"pallas"`` (the kernels; ``interpret=True`` off the TPU) or
    ``"xla"`` (``jax.lax.ragged_dot``); None picks the kernels on a TPU and
    XLA elsewhere."""
    if impl is None:
        impl = _default_impl()
    if impl == "xla":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    if impl != "pallas":
        raise ValueError("unknown grouped_matmul impl {!r}".format(impl))
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
                interpret)


def grouped_matmul_grads(lhs, rhs, group_sizes, grad, impl=None,
                         interpret=False):
    """The cotangents ``(d_lhs [m, k], d_rhs [groups, k, n])`` of
    :func:`grouped_matmul`'s operands from the result's, ``grad [m, n]``: what
    differentiating it gives, for a caller that writes the backward of
    several products itself (an expert's two "up" products share their
    ``lhs``, and
    :func:`~tensorflowonspark_tpu.parallel.ep.experts_ffn` sums their two
    ``d_lhs`` in front of the last group only).  Rows of ``d_lhs`` behind
    the last group are unspecified and rows of ``grad`` there are not read;
    ``impl`` as :func:`grouped_matmul`."""
    if impl is None:
        impl = _default_impl()
    if impl == "xla":
        return jax.vjp(lambda lhs, rhs: jax.lax.ragged_dot(
            lhs, rhs, group_sizes), lhs, rhs)[1](grad)
    if impl != "pallas":
        raise ValueError("unknown grouped_matmul impl {!r}".format(impl))
    return _gmm_grads(lhs, rhs.astype(lhs.dtype),
                      group_sizes.astype(jnp.int32), grad, interpret)
