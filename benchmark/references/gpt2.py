"""Plain reference for the GPT-2 configurations: forward, next-token loss,
gradients and Adam in straightforward ``jax.numpy``, float32, every
contraction at ``Precision.HIGHEST``.  No kernels, no flax, nothing of the
program.

Follows Radford et al. 2019 / ``openai-community/gpt2-*`` ``config.json``:
pre-LayerNorm blocks, learned positions, ``gelu_new`` (tanh), tied read-out.
Departures, each because the configuration as run says so:

- ``layer_norm_epsilon`` is the configuration's (1e-6, the program's
  LayerNorm default), not the source's 1e-5;
- no dropout (the source trains with 0.1; a benchmark step is deterministic);
- the loss is the mean over the first ``S - 1`` positions of every row (the
  last position has no target).

Layers are held stacked (leading dimension = layer) and the forward pass is a
``lax.scan`` over them with each layer recomputed in the backward pass, rows
taken in blocks: that is how float32 at 24 layers x 1024 positions fits.  A
leaf, wherever leaves are compared, is one layer's slice of a stacked array.

``precision="fp8"`` is the **control**: both operands of every matrix product
rounded to float8_e4m3 under a per-tensor scale (straight-through backward).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references._common import key as _key, memo as _memo, \
    operand as _operand

_HI = lax.Precision.HIGHEST
STACKED = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
           "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def init_weights(cfg, seed):
    """dict name -> float32 array, made on the device in one jitted call.
    GPT-2's own initialisation: normal(0.02), residual projections scaled by
    1/sqrt(2 L), positions normal(0.01); biases and LayerNorm offsets get a
    small normal instead of zero so that no leaf's gradient is lost in one."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    v, p = cfg["vocab_size"], cfg["n_positions"]

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def n(shape, std):
            return std * jax.random.normal(next(ks), shape, jnp.float32)

        res = 0.02 / np.sqrt(2.0 * layers)
        return {
            "wte": n((v, d), 0.02), "wpe": n((p, d), 0.01),
            "ln1_g": jnp.ones((layers, d)), "ln1_b": n((layers, d), 0.01),
            "qkv_w": n((layers, d, 3 * d), 0.02),
            "qkv_b": n((layers, 3 * d), 0.01),
            "proj_w": n((layers, d, d), res), "proj_b": n((layers, d), 0.01),
            "ln2_g": jnp.ones((layers, d)), "ln2_b": n((layers, d), 0.01),
            "fc_w": n((layers, d, 4 * d), 0.02),
            "fc_b": n((layers, 4 * d), 0.01),
            "out_w": n((layers, 4 * d, d), res),
            "out_b": n((layers, d), 0.01),
            "lnf_g": jnp.ones((d,)), "lnf_b": n((d,), 0.01),
        }

    return _memo(cfg, "init")(lambda: make)(_key(seed))


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=_HI)


def _ln(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, w, cfg, precision):
    b, s, d = x.shape
    heads = cfg["n_head"]
    hd = d // heads
    h = _ln(x, w["ln1_g"], w["ln1_b"], cfg["layer_norm_epsilon"])
    qkv = _mm("bsd,de->bse", h, w["qkv_w"], precision) + w["qkv_b"]
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(b, s, heads, hd)
               for i in range(3))
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    att = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, d)
    x = x + _mm("bsd,de->bse", att, w["proj_w"], precision) + w["proj_b"]
    h = _ln(x, w["ln2_g"], w["ln2_b"], cfg["layer_norm_epsilon"])
    h = _gelu_new(_mm("bsd,de->bse", h, w["fc_w"], precision) + w["fc_b"])
    return x + _mm("bse,ed->bsd", h, w["out_w"], precision) + w["out_b"]


def forward(weights, tokens, cfg, precision="float32"):
    """float32 logits [B, S, V] for int tokens [B, S]."""
    s = tokens.shape[1]
    x = weights["wte"][tokens] + weights["wpe"][:s]
    stacked = {k: weights[k] for k in STACKED}

    @jax.checkpoint
    def body(x, w):
        return _layer(x, w, cfg, precision), None

    x, _ = lax.scan(body, x, stacked)
    x = _ln(x, weights["lnf_g"], weights["lnf_b"], cfg["layer_norm_epsilon"])
    return _mm("bsd,vd->bsv", x, weights["wte"], precision)


def loss_fn(weights, tokens, cfg, precision="float32"):
    logits = forward(weights, tokens, cfg, precision)[:, :-1]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean(-1).mean()


def leaf_norms(tree):
    """name -> norm for plain leaves, ``name/<layer>`` -> norm for each
    layer's slice of a stacked one."""
    out = {}
    for k, v in tree.items():
        if k in STACKED:
            norms = jnp.sqrt(jnp.sum(jnp.square(v).reshape(v.shape[0], -1),
                                     axis=1))
            for i in range(v.shape[0]):
                out["%s/%d" % (k, i)] = norms[i]
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


def unstack(tree):
    """name -> array for plain leaves, ``name/<layer>`` -> that layer's slice
    of a stacked one: the leaves that are compared."""
    out = {}
    for k, v in tree.items():
        if k in STACKED:
            for i in range(v.shape[0]):
                out["%s/%d" % (k, i)] = v[i]
        else:
            out[k] = v
    return out


def train_steps(cfg, seed, batches, precision="float32", rows_per_block=2):
    """Follow the first ``len(batches)`` Adam steps from the seeded weights;
    see ``resnet50.train_steps`` for what comes back."""
    weights = init_weights(cfg, seed)
    opt = cfg["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["learning_rate"]

    def grads_of(weights, tokens):
        blocks = tokens.reshape(-1, rows_per_block, tokens.shape[-1])

        def one(acc, block):
            loss, g = jax.value_and_grad(
                lambda w: loss_fn(w, block, cfg, precision))(weights)
            return jax.tree_util.tree_map(jnp.add, acc, (loss, g)), None

        zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like,
                                                      weights))
        (loss, g), _ = lax.scan(one, zero, blocks)
        n = blocks.shape[0]
        return loss / n, jax.tree_util.tree_map(lambda x: x / n, g)

    def adam(weights, mu, nu, g, t):
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(
            lambda n, x: b2 * n + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        new = jax.tree_util.tree_map(
            lambda w, m, n: w - lr * (m / c1) / (jnp.sqrt(n / c2) + eps),
            weights, mu, nu)
        return new, mu, nu

    grads_of = _memo(cfg, "grads", precision, rows_per_block)(
        lambda: grads_of)
    adam = _memo(cfg, "adam")(lambda: adam)
    start = weights
    mu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    nu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    losses, first = [], None
    for t, batch in enumerate(batches, 1):
        loss, g = grads_of(weights, jnp.asarray(batch["tokens"], jnp.int32))
        losses.append(float(loss))
        if first is None:
            first = unstack({k: np.asarray(v) for k, v in g.items()})
        weights, mu, nu = adam(weights, mu, nu, g, jnp.float32(t))
        del g
    delta = _memo(cfg, "delta")(lambda: lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(weights, start)
    return {"losses": losses, "first_gradient": first,
            "delta_norms": {k: float(v) for k, v in delta.items()}}
