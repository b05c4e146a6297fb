"""Plain reference of the worked example's family (``benchmark/README.md``,
"Adding things"): a two-layer MLP that predicts a row's next token from its
current one.  Straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``; nothing of the program.

    x = wte[tokens[:, :-1]];  h = gelu_tanh(x @ w1 + b1);  logits = h @ w2 + b2
    loss = mean over positions and rows of the cross-entropy with tokens[:, 1:]

``precision="fp8"`` is the **control**: both operands of the two matrix
products rounded to float8_e4m3 under a per-tensor scale.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references._common import key as _key, memo as _memo, \
    operand as _operand

_HI = lax.Precision.HIGHEST


def init_weights(cfg, seed):
    """dict name -> float32 array, made on the device in one jitted call;
    small seeded biases, so that no leaf's gradient is lost in a zero."""
    v, d, h = cfg["vocab_size"], cfg["n_embd"], cfg["n_inner"]

    def make(key):
        ks = jax.random.split(key, 5)

        def n(k, shape, std):
            return std * jax.random.normal(k, shape, jnp.float32)

        return {"wte": n(ks[0], (v, d), 1.0),
                "w1": n(ks[1], (d, h), 1.0 / np.sqrt(d)),
                "b1": n(ks[2], (h,), 0.01),
                "w2": n(ks[3], (h, v), 1.0 / np.sqrt(h)),
                "b2": n(ks[4], (v,), 0.01)}

    return _memo(cfg, "init")(lambda: make)(_key(seed))


def _mm(a, b, precision):
    return jnp.einsum("bsd,de->bse", _operand(a, precision),
                      _operand(b, precision), precision=_HI)


def loss_fn(weights, tokens, precision="float32"):
    x = weights["wte"][tokens[:, :-1]]
    h = jax.nn.gelu(_mm(x, weights["w1"], precision) + weights["b1"],
                    approximate=True)
    logits = _mm(h, weights["w2"], precision) + weights["b2"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean(-1).mean()


def train_steps(cfg, seed, batches, precision="float32"):
    """Follow the first ``len(batches)`` Adam steps from the seeded weights:
    {"losses", "first_gradient" (name -> array), "delta_norms" (name ->
    norm of the parameters' change over the steps)}."""
    weights = init_weights(cfg, seed)
    opt = cfg["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["learning_rate"]
    tm = jax.tree_util.tree_map

    def step(weights, mu, nu, tokens, t):
        loss, g = jax.value_and_grad(loss_fn)(weights, tokens, precision)
        mu = tm(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = tm(lambda n, x: b2 * n + (1 - b2) * x * x, nu, g)
        new = tm(lambda w, m, n: w - lr * (m / (1 - b1 ** t))
                 / (jnp.sqrt(n / (1 - b2 ** t)) + eps), weights, mu, nu)
        return loss, g, new, mu, nu

    step = _memo(cfg, "step", precision)(lambda: step)
    start = weights
    mu = nu = tm(jnp.zeros_like, weights)
    losses, first = [], None
    for t, batch in enumerate(batches, 1):
        loss, g, weights, mu, nu = step(
            weights, mu, nu, jnp.asarray(batch["tokens"], jnp.int32),
            jnp.float32(t))
        losses.append(float(loss))
        if first is None:
            first = {k: np.asarray(v) for k, v in g.items()}
    return {"losses": losses, "first_gradient": first,
            "delta_norms": {k: float(jnp.linalg.norm(
                (weights[k] - start[k]).ravel())) for k in weights}}
