"""Plain reference for the Olmo Hybrid configurations (``model_type``
``olmo_hybrid``: Olmo-Hybrid-7B): forward, next-token loss, gradients and
Adam in straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``.  No kernels, no flax, nothing of the program; the
Gated DeltaNet layer is **the recurrence itself, position by position**, not
the chunked algorithm the program runs.

The layer equations, from the keys of ``allenai/Olmo-Hybrid-7B``'s
``config.json`` (what the configuration's ``assumed`` lists is what Olmo 2's
and Olmo 3's block and the ``linear_*`` keys' modelling code, Qwen3-Next's
Gated DeltaNet, do and the config has no key for):

- model: ``h0 = E[tokens]``; the layers; ``out = RMSNorm(h_L)``
  (``rms_norm_eps``, the weight multiplies, no bias); logits ``= out
  W_head``, a matrix of its own (``tie_word_embeddings`` false).  No
  positions anywhere: ``rope_parameters.rope_theta`` is null.
- a layer **norms a part's output**: ``h = x + RMSNorm(mixer(x))``, ``out =
  h + RMSNorm(mlp(h))``; the mixer by ``layer_types[i]``.
- ``linear_attention``, Gated DeltaNet (arXiv:2412.06464), ``H =
  linear_num_value_heads = linear_num_key_heads`` heads, ``dk =
  linear_key_head_dim``, ``dv = linear_value_head_dim``: ``[q | k | v | z |
  a | b] = x W_in`` of ``H dk | H dk | H dv | H dv | H | H`` columns (the
  six matrices ``W_q .. W_b`` side by side), no bias; ``q, k, v = silu(c)``
  with ``c_t = sum_j w_j u_{t-j}`` a channel (``linear_conv_kernel_dim``
  taps, no bias, zeros before the row; none on the gate ``z``); a head's
  ``q^ = q / sqrt(sum q^2 + 1e-6) * dk^-0.5``, ``k^ = k / sqrt(sum k^2 +
  1e-6)``; ``b_t = sigmoid(b)`` (times 2: ``linear_allow_neg_eigval``);
  ``a_t = exp(-exp(A_log) softplus(a + dt_bias))`` a head; ``S_t = a_t
  S_{t-1} + b_t (v_t - a_t S_{t-1} k^_t) k^_t^T``, ``S [dv, dk]`` a head and
  zero before the row; ``o_t = S_t q^_t``; ``y = RMSNorm(o) * w * silu(z)``
  a head (``w [dv]``, one for all heads); out ``y W_out``.
- ``full_attention``: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)``, each
  norm over the **whole** projection (``num_attention_heads * head_dim``
  columns, a weight as wide), then split into heads of ``head_dim``; ``v = x
  W_v``; no biases, no rotation; head ``j`` reads KV head ``j // (heads /
  kv_heads)``; scores ``q_t . k_s / sqrt(head_dim)`` over ``s <= t``;
  softmax; ``y = concat(o) W_o``.
- feed-forward: ``(silu(x W_1) * (x W_3)) W_2`` of ``intermediate_size``.

**The chip's share.**  The configuration's head counts are those held (two
chips share each layer's heads): the columns of ``W_in`` and the rows of
``W_out`` (``W_q, W_k, W_v`` and ``W_o`` in an attention layer) that are
theirs.  What the other heads would add to the output projection's sum is
left out, and that partial sum goes on to the norm and the next layer (the
program does the same); the attention layers' QK-norm takes its statistic
over the held columns.  The vocabulary is the configuration's (a slice is a
smaller one).

Departures: the loss is the mean cross-entropy over the first ``S - 1``
positions of every row.  Rows are taken one at a time and the gradients
added, each layer recomputed in the backward pass, the recurrence
checkpointed every 128 positions (its backward pass holds 128 states, not
``S``), attention in blocks of 512 queries with the heads in turn, the
read-out and its cross-entropy in blocks of positions: that is how float32
at 8,192 positions fits.

``precision="fp8"`` is the **control**: both operands of every matrix product
rounded to float8_e4m3 under a per-tensor scale (straight-through backward);
the recurrence, which has none, stays as it is.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references._common import key as _key, memo as _memo, \
    operand as _operand

_HI = lax.Precision.HIGHEST
_QUERY_BLOCK = 512
_TOKEN_BLOCK = 4096
_STATE_BLOCK = 128


def _delta_sizes(cfg):
    """heads, key width, value width, q's (and k's) columns, v's columns."""
    heads = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return heads, dk, dv, heads * dk, heads * dv


def _attention_sizes(cfg):
    """heads, KV heads, head width."""
    heads = cfg["num_attention_heads"]
    return (heads, cfg["num_key_value_heads"],
            cfg.get("head_dim") or cfg["hidden_size"] // heads)


def layer_leaves(cfg, i):
    """name -> (shape, kind) of layer ``i``'s leaves; kind is ``matrix``
    (normal 0.02), ``residual`` (a part's output matrix: scaled down by the
    root of twice the number of layers), ``one`` (a norm's weight), ``tap``,
    ``a_log`` or ``dt_bias`` (:func:`init_weights`)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    p = "L%d." % i
    out = {p + "op_norm": ((d,), "one"), p + "ff_norm": ((d,), "one"),
           p + "w1": ((d, f), "matrix"), p + "w3": ((d, f), "matrix"),
           p + "w2": ((f, d), "residual")}
    kind = cfg["layer_types"][i]
    if kind == "linear_attention":
        heads, _, dv, keys, values = _delta_sizes(cfg)
        out.update({
            p + "in_proj": ((d, 2 * keys + 2 * values + 2 * heads), "matrix"),
            p + "conv": ((cfg["linear_conv_kernel_dim"], 2 * keys + values),
                         "tap"),
            p + "A_log": ((heads,), "a_log"),
            p + "dt_bias": ((heads,), "dt_bias"),
            p + "gate_norm": ((dv,), "one"),
            p + "out_proj": ((values, d), "residual")})
    elif kind == "full_attention":
        heads, kv, dim = _attention_sizes(cfg)
        out.update({
            p + "wq": ((d, heads * dim), "matrix"),
            p + "wk": ((d, kv * dim), "matrix"),
            p + "wv": ((d, kv * dim), "matrix"),
            p + "q_norm": ((heads * dim,), "one"),
            p + "k_norm": ((kv * dim,), "one"),
            p + "wo": ((heads * dim, d), "residual")})
    else:
        raise ValueError("no layer {!r} in this reference".format(kind))
    return out


def leaves(cfg):
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": ((vocab, d), "embedding"),
           "head": ((d, vocab), "matrix"), "norm_f": ((d,), "one")}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_leaves(cfg, i))
    return out


def init_weights(cfg, seed):
    """dict name -> float32 array, made on the device in one jitted call.
    ``nemotron3_nano_30b_a3b_ep16``'s recipe for what the two share:
    matrices normal(0.02) (the head too), norm weights 1, the embedding
    normal(1), a part's output matrix (``out_proj``, ``W_o``, ``W_2``)
    scaled by ``1 / sqrt(2 L)`` for two residual adds a layer.  The Gated
    DeltaNet layer's own, by Mamba-2's rule, which its modelling code shares:
    ``A_log = log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly in ``[0.001, 0.1]`` and floored at 1e-4, the
    taps uniform in ``+-1 / sqrt(linear_conv_kernel_dim)`` (what the code
    leaves a depthwise convolution at): decays from 0.2 to 0.999 a position
    and write strengths all over (0, 2), so that the decay and the delta
    term both show in a gradient."""
    table = leaves(cfg)
    std = {"matrix": 0.02, "embedding": 1.0,
           "residual": 0.02 / np.sqrt(2.0 * cfg["num_hidden_layers"])}
    tap = 1.0 / math.sqrt(cfg["linear_conv_kernel_dim"])
    low, high, floor = math.log(0.001), math.log(0.1), 1e-4

    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(sorted(table.items())):
            k = jax.random.fold_in(key, n)
            if kind == "one":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "tap":
                out[name] = jax.random.uniform(k, shape, jnp.float32, -tap,
                                               tap)
            elif kind == "a_log":
                out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                       1.0, 16.0))
            elif kind == "dt_bias":
                step = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, low, high)), floor)
                out[name] = step + jnp.log(-jnp.expm1(-step))
            else:
                out[name] = std[kind] * jax.random.normal(k, shape,
                                                          jnp.float32)
        return out

    return _memo(cfg, "init")(lambda: make)(_key(seed))


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=_HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * g


def recurrence(q, k, v, decay, beta):
    """``o [S, H, dv]`` of ``S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t)
    k_t^T``, ``o_t = S_t q_t``, one position after another from a zero
    state ``[H, dv, dk]``: ``q, k [S, H, dk]``, ``v [S, H, dv]``, ``decay``
    (``a``) and ``beta`` (``b``) ``[S, H]``."""
    seq, heads, dk = k.shape
    block = math.gcd(seq, _STATE_BLOCK)

    def step(state, at):
        qt, kt, vt, a, b = at
        state = a[:, None, None] * state
        answered = (state * kt[:, None, :]).sum(-1)             # S k
        state = state + (b[:, None] * (vt - answered))[:, :, None] \
            * kt[:, None, :]
        return state, (state * qt[:, None, :]).sum(-1)

    @jax.checkpoint
    def some(state, positions):
        return lax.scan(step, state, positions)

    blocks = jax.tree_util.tree_map(
        lambda t: t.reshape((seq // block, block) + t.shape[1:]),
        (q, k, v, decay, beta))
    _, o = lax.scan(some, jnp.zeros((heads, v.shape[2], dk), v.dtype), blocks)
    return o.reshape(v.shape)


def _delta(x, w, p, cfg, precision):
    """``y [S, d]`` of one Gated DeltaNet layer."""
    heads, dk, dv, keys, values = _delta_sizes(cfg)
    seq, taps = x.shape[0], cfg["linear_conv_kernel_dim"]
    qkv, z, a, b = jnp.split(
        _mm("sd,de->se", x, w[p + "in_proj"], precision),
        [2 * keys + values, 2 * keys + 2 * values,
         2 * keys + 2 * values + heads], axis=-1)
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        w[p + "conv"][j] * padded[taps - 1 - j:taps - 1 - j + seq]
        for j in range(taps)))
    q = qkv[:, :keys].reshape(seq, heads, dk)
    k = qkv[:, keys:2 * keys].reshape(seq, heads, dk)
    v = qkv[:, 2 * keys:].reshape(seq, heads, dv)
    q = q * lax.rsqrt(jnp.square(q).sum(-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * lax.rsqrt(jnp.square(k).sum(-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(b) * (2.0 if cfg["linear_allow_neg_eigval"] else 1.0)
    decay = jnp.exp(-jnp.exp(w[p + "A_log"])
                    * jax.nn.softplus(a + w[p + "dt_bias"]))
    o = recurrence(q, k, v, decay, beta)
    y = _rms(o, w[p + "gate_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.silu(z.reshape(seq, heads, dv))
    return _mm("se,ed->sd", y.reshape(seq, values), w[p + "out_proj"],
               precision)


def _attention(x, w, p, cfg, precision):
    """``y [S, d]`` of one attention layer: every causal key, no positions,
    q and k each normed over the whole projection."""
    heads, kv, dim = _attention_sizes(cfg)
    seq, eps = x.shape[0], cfg["rms_norm_eps"]
    q = _rms(_mm("sd,de->se", x, w[p + "wq"], precision), w[p + "q_norm"],
             eps).reshape(seq, heads, dim)
    k = _rms(_mm("sd,de->se", x, w[p + "wk"], precision), w[p + "k_norm"],
             eps).reshape(seq, kv, dim)
    v = _mm("sd,de->se", x, w[p + "wv"], precision).reshape(seq, kv, dim)
    block = min(seq, _QUERY_BLOCK)
    qh, kh, vh = (t.transpose(1, 0, 2) for t in (q, k, v))    # [H, S, D]
    of_head = jnp.arange(heads) // (heads // kv)              # its KV head

    @jax.checkpoint
    def rows(start):
        """The heads' outputs ``[H, block, D]`` for the queries start ..
        start + block."""
        seen = jnp.arange(seq)[None] <= (start + jnp.arange(block))[:, None]

        @jax.checkpoint
        def head(q_kv):
            qi, kv_head = q_kv
            scores = _mm("qd,sd->qs", qi, kh[kv_head], precision) \
                * dim ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            return _mm("qs,sd->qd", probs, vh[kv_head], precision)

        return lax.map(head, (lax.dynamic_slice_in_dim(qh, start, block,
                                                       axis=1), of_head))

    out = lax.map(rows, jnp.arange(0, seq, block))      # [n, H, block, D]
    out = out.transpose(0, 2, 1, 3).reshape(seq, heads * dim)
    return _mm("se,ed->sd", out, w[p + "wo"], precision)


def _swiglu(x, w, p, precision):
    return _mm("sf,fd->sd",
               jax.nn.silu(_mm("sd,df->sf", x, w[p + "w1"], precision))
               * _mm("sd,df->sf", x, w[p + "w3"], precision),
               w[p + "w2"], precision)


_MIXERS = {"linear_attention": _delta, "full_attention": _attention}


def hidden(weights, tokens, cfg, precision="float32"):
    """``out [S, d]`` after the final norm."""
    x = weights["embed"][tokens]
    eps = cfg["rms_norm_eps"]
    for i, kind in enumerate(cfg["layer_types"]):
        p = "L%d." % i
        mine = {k: v for k, v in weights.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(x, w, p=p, kind=kind):
            h = x + _rms(_MIXERS[kind](x, w, p, cfg, precision),
                         w[p + "op_norm"], eps)
            return h + _rms(_swiglu(h, w, p, precision), w[p + "ff_norm"],
                            eps)

        x = layer(x, mine)
    return _rms(x, weights["norm_f"], eps)


def forward(weights, tokens, cfg, precision="float32"):
    """float32 logits [S, V] for one sequence of int tokens [S]."""
    return _mm("sd,dv->sv", hidden(weights, tokens, cfg, precision),
               weights["head"], precision)


def loss_fn(weights, tokens, cfg, precision="float32"):
    """Mean cross-entropy over the first ``S - 1`` positions of one
    sequence, the read-out taken in blocks of positions."""
    out = hidden(weights, tokens, cfg, precision)
    seq = tokens.shape[0]
    block = min(seq, _TOKEN_BLOCK)
    targets = jnp.roll(tokens, -1)
    counted = jnp.arange(seq) < seq - 1        # the last has no target

    @jax.checkpoint
    def some(start):
        take = lambda x: lax.dynamic_slice_in_dim(  # noqa: E731
            x, start, block, axis=0)
        logp = jax.nn.log_softmax(
            _mm("sd,dv->sv", take(out), weights["head"], precision))
        picked = jnp.take_along_axis(logp, take(targets)[:, None], axis=-1)
        return -(picked[:, 0] * take(counted)).sum()

    return lax.map(some, jnp.arange(0, seq, block)).sum() / (seq - 1)


def train_steps(cfg, seed, batches, precision="float32"):
    """Follow the first ``len(batches)`` Adam steps from the seeded weights:
    ``{"losses", "first_gradient" (leaf -> array), "delta_norms" (leaf ->
    norm of the parameters' change over the steps)}``."""
    weights = init_weights(cfg, seed)
    opt = cfg["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["learning_rate"]
    warmup = opt.get("warmup_steps", 0)     # linear, from lr / warmup

    def grads_of(weights, tokens):
        if tokens.shape[0] == 1:    # no second tree of gradients to add to
            return jax.value_and_grad(
                lambda w: loss_fn(w, tokens[0], cfg, precision))(weights)

        def one(acc, row):
            got = jax.value_and_grad(
                lambda w: loss_fn(w, row, cfg, precision))(weights)
            return jax.tree_util.tree_map(jnp.add, acc, got), None

        zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like,
                                                      weights))
        (loss, g), _ = lax.scan(one, zero, tokens)
        n = tokens.shape[0]
        return loss / n, jax.tree_util.tree_map(lambda x: x / n, g)

    def adam(w, m, n, g, t):
        """One leaf's update (leaf by leaf the old buffers go as the new
        ones come: a whole-tree update would hold both for a moment)."""
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        rate = lr * jnp.minimum(1.0, t / warmup) if warmup else lr
        return w - rate * (m / c1) / (jnp.sqrt(n / c2) + eps), m, n

    grads_of = _memo(cfg, "grads", precision)(lambda: grads_of)
    adam = _memo(cfg, "adam")(lambda: adam)
    # Adam's moments wait on the host between updates and pass through the
    # device a leaf at a time: beside the weights the chip then holds the
    # gradient program alone (at 766 M parameters the two moments are 6.1 GB
    # that it has no room for while the rows' gradients are added up)
    mu = {k: np.zeros(v.shape, np.float32) for k, v in weights.items()}
    nu = {k: np.zeros(v.shape, np.float32) for k, v in weights.items()}
    step_losses, first = [], None
    for t, batch in enumerate(batches, 1):
        loss, g = grads_of(weights, jnp.asarray(batch["tokens"], jnp.int32))
        step_losses.append(float(loss))
        if first is None:
            first = {k: np.asarray(v) for k, v in g.items()}
        for k in sorted(weights):
            weights[k], m, n = adam(weights[k], mu[k], nu[k], g.pop(k),
                                    jnp.float32(t))
            mu[k], nu[k] = np.asarray(m), np.asarray(n)
    # the seeded weights again (the same jitted call gives the same bits):
    # the steps did not have to keep them
    delta = _memo(cfg, "delta")(lambda: lambda a, b: {
        k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})(
            weights, init_weights(cfg, seed))
    return {"losses": step_losses, "first_gradient": first,
            "delta_norms": {k: float(v) for k, v in delta.items()}}
