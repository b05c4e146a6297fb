"""What the plain references share: the seed's key, the fp8 rounding of the
control, and a memo of jitted functions.  Nothing of the program."""

import json

import jax
import jax.numpy as jnp
from jax import lax

_compiled = {}


def key(seed):
    """A PRNG key for any whole-number seed (the driver's exceed 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def memo(cfg, *purpose):
    """One jitted function a (configuration, purpose): a second seed finds
    the first one's program."""
    def wrap(build):
        k = (json.dumps(cfg, sort_keys=True),) + purpose
        if k not in _compiled:
            _compiled[k] = jax.jit(build())
        return _compiled[k]
    return wrap


def operand(x, precision):
    """A matrix product's operand in ``precision``: float32 as it is; fp8
    (the control) rounded to float8_e4m3 under a per-tensor scale, the
    gradient passing straight through the rounding."""
    if precision == "float32":
        return x
    if precision != "fp8":
        raise ValueError("unknown precision {!r}".format(precision))
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale
    return x + lax.stop_gradient(q - x)
