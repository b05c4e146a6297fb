"""Plain reference for the Mellum 2 mixture configurations (``model_type``
``mellum``: Mellum2-12B-A2.5B-Instruct): forward, next-token loss, gradients
and Adam in straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``.  No kernels, no flax, nothing of the program.

The layer equations, from the keys of
``JetBrains/Mellum2-12B-A2.5B-Instruct``'s ``config.json`` (what the
configuration's ``assumed`` lists is what the family's modelling code does
and the config has no key for):

- model: ``h0 = E[tokens]``; the layers; ``out = RMSNorm(h_L)``
  (``rms_norm_eps``, the weight multiplies, no bias); logits ``= out W_head``,
  a matrix of its own (``tie_word_embeddings`` false).  No learned positions.
- layer: ``x = x + Attn(RMSNorm(x))``, then ``x = x + Experts(RMSNorm(x))``
  (``mlp_layer_types`` all ``sparse``).
- projections, no biases: ``q = h W_q`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = h W_k``, ``v = h W_v`` (``num_key_value_heads``);
  RMSNorm over each head of ``q`` and of ``k``, a weight of ``head_dim`` each
  (assumed); RoPE on both, rotate-half pairing, all of ``head_dim``,
  positions 0..T-1, by the layer's own table of ``rope_parameters``:
  ``default``: ``inv_i = theta^(-2i/D)``;
  ``yarn``: with ``r(n) = D ln(original / (2 pi n)) / (2 ln theta)`` the pair
  that turns ``n`` times over ``original_max_position_embeddings``, ``low =
  floor(r(beta_fast))``, ``high = ceil(r(beta_slow))`` (kept inside 0..D-1),
  ``g_i = clip((i - low) / (high - low), 0, 1)``: ``inv_i = (1 - g_i)
  theta^(-2i/D) + g_i theta^(-2i/D) / factor``, and cos and sin times
  ``attention_factor``.
- attention: head ``j`` reads KV head ``j // (heads / kv_heads)``; scores
  ``q_t . k_s / sqrt(head_dim)``; a ``full_attention`` layer's query ``t``
  reads the keys ``s <= t``, a ``sliding_attention`` layer's ``t -
  sliding_window < s <= t`` (its own key among the ``sliding_window``);
  softmax; ``o = sum_s a v``; ``y = concat(o) W_o``.
- experts: ``s = softmax(h W_r)`` over the ``router_experts`` outputs in
  float32, top ``num_experts_per_tok``, weights divided by their sum (+1e-6)
  (``norm_topk_prob``); SwiGLU experts ``(silu(x W_1) * (x W_3)) W_2`` of
  ``moe_intermediate_size``; no bias, no shared expert, nothing dropped, no
  auxiliary loss.

**The chip's share.**  ``held_experts = [first, count]`` are the experts this
configuration holds of every layer; every held expert runs on every token,
times a mask of the selection; what the absent experts would add is left
out, and that partial sum goes on to the next layer (the program does the
same).  The vocabulary is the configuration's (a slice is a smaller one).

Departures: the loss is the mean cross-entropy over the first ``S - 1``
positions of every row.  Rows are taken one at a time and the gradients
added, each layer recomputed in the backward pass, attention taken in blocks
of 512 queries with the heads in turn (a sliding layer's block multiplies
only the ``512 + sliding_window - 1`` keys its band reaches), the read-out
and its cross-entropy in blocks of positions: that is how float32 at 32,768
positions fits, and no ``[T, T]`` array exists.

``precision="fp8"`` is the **control**: both operands of every matrix product
rounded to float8_e4m3 under a per-tensor scale (straight-through backward).
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


def _sizes(cfg):
    """hidden, heads, KV heads, head width."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def layer_leaves(cfg, i):
    """name -> (shape, kind) of layer ``i``'s leaves; kind is ``matrix``
    (normal 0.02), ``residual`` (a residual branch's output: scaled by
    1/sqrt(2 L)) or ``one`` (a norm's weight); the embedding's is
    ``embedding`` (:func:`init_weights`)."""
    d, heads, kv, dim = _sizes(cfg)
    f, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
    p = "L%d." % i
    return {
        p + "op_norm": ((d,), "one"), p + "ff_norm": ((d,), "one"),
        p + "wq": ((d, heads * dim), "matrix"),
        p + "wk": ((d, kv * dim), "matrix"),
        p + "wv": ((d, kv * dim), "matrix"),
        p + "q_norm": ((dim,), "one"), p + "k_norm": ((dim,), "one"),
        p + "wo": ((heads * dim, d), "residual"),
        p + "router": ((d, cfg["router_experts"]), "matrix"),
        p + "ew1": ((held, d, f), "matrix"),
        p + "ew3": ((held, d, f), "matrix"),
        p + "ew2": ((held, f, d), "residual")}


def leaves(cfg):
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": ((vocab, d), "embedding"),
           "head": ((d, vocab), "matrix"), "norm_f": ((d,), "one")}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_leaves(cfg, i))
    return out


def init_weights(cfg, seed):
    """dict name -> float32 array, made on the device in one jitted call:
    matrices normal(0.02), residual outputs (``wo`` and every ``ew2``) scaled
    by 1/sqrt(2 L), norm weights 1, **the embedding normal(1)** (the
    ``keye_vl2`` reference's rule and reason: at 0.02 the attention branch
    outweighs a token's own row, the hidden states of a few layers that have
    learned nothing collapse onto one direction, and the router's load, and
    the step's time with it, swings by seed)."""
    table = leaves(cfg)
    std = {"matrix": 0.02, "embedding": 1.0,
           "residual": 0.02 / np.sqrt(2.0 * cfg["num_hidden_layers"])}

    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(sorted(table.items())):
            if kind == "one":
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = std[kind] * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
        return out

    return _memo(cfg, "init")(lambda: make)(_key(seed))


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=_HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * g


def rotary_table(cfg, kind):
    """``(inv [D / 2] float32, what cos and sin are multiplied by)`` of the
    layers of ``kind``, from that kind's table of ``rope_parameters``."""
    table = cfg["rope_parameters"][kind]
    dim, theta = cfg["head_dim"], float(table["rope_theta"])
    pair = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * pair / dim)
    if table.get("rope_type", "default") == "default":
        return jnp.asarray(plain, jnp.float32), 1.0
    factor = float(table["factor"])
    original = float(table["original_max_position_embeddings"])

    def turning(times):     # the pair that turns so often over the original
        return dim * math.log(original / (times * 2.0 * math.pi)) / (
            2.0 * math.log(theta))

    low = max(math.floor(turning(float(table["beta_fast"]))), 0)
    high = min(math.ceil(turning(float(table["beta_slow"]))), dim - 1)
    ramp = np.clip((pair - low) / max(high - low, 0.001), 0.0, 1.0)
    inv = (1.0 - ramp) * plain + ramp * plain / factor
    return (jnp.asarray(inv, jnp.float32),
            float(table.get("attention_factor",
                            0.1 * math.log(factor) + 1.0)))


def _rope(x, inv, factor):
    """x [S, H, D], positions 0..S-1, dimension i turned with i + D/2 by
    ``pos * inv_i``; cos and sin times ``factor``."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(angle)[:, None] * factor
    sin = jnp.sin(angle)[:, None] * factor
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, w, p, cfg, kind, precision):
    """``y [S, d]`` of one layer of ``kind``."""
    d, heads, kv, dim = _sizes(cfg)
    seq, eps = h.shape[0], cfg["rms_norm_eps"]
    group = heads // kv
    inv, factor = rotary_table(cfg, kind)
    q = _mm("sd,de->se", h, w[p + "wq"], precision).reshape(seq, heads, dim)
    k = _mm("sd,de->se", h, w[p + "wk"], precision).reshape(seq, kv, dim)
    v = _mm("sd,de->se", h, w[p + "wv"], precision).reshape(seq, kv, dim)
    q = _rope(_rms(q, w[p + "q_norm"], eps), inv, factor)
    k = _rope(_rms(k, w[p + "k_norm"], eps), inv, factor)
    window = cfg["sliding_window"] if kind == "sliding_attention" else seq
    block = min(seq, _QUERY_BLOCK)
    # the keys a block of queries can reach: all of a full layer's row, of a
    # sliding layer's the block's own and the window - 1 before its first
    span = min(seq, block + window - 1)
    qh, kh, vh = (x.transpose(1, 0, 2) for x in (q, k, v))    # [H, S, D]
    of_head = jnp.arange(heads) // group                      # its KV head

    @jax.checkpoint
    def rows(start):
        """The heads' outputs ``[H, block, D]`` for the queries start ..
        start + block."""
        first = jnp.clip(start - (window - 1), 0, seq - span)
        t = (start + jnp.arange(block))[:, None]
        s = (first + jnp.arange(span))[None]
        seen = (s <= t) & (t - s < window)

        @jax.checkpoint
        def head(q_kv):
            qi, kv_head = q_kv
            ki = lax.dynamic_slice_in_dim(kh[kv_head], first, span, axis=0)
            vi = lax.dynamic_slice_in_dim(vh[kv_head], first, span, axis=0)
            scores = _mm("qd,sd->qs", qi, ki, precision) * dim ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            return _mm("qs,sd->qd", probs, vi, precision)

        return lax.map(head, (lax.dynamic_slice_in_dim(qh, start, block,
                                                       axis=1), of_head))

    out = lax.map(rows, jnp.arange(0, seq, block))      # [n, H, block, D]
    out = out.transpose(0, 2, 1, 3).reshape(seq, heads * dim)
    return _mm("se,ed->sd", out, w[p + "wo"], precision)


def _swiglu(h, w1, w3, w2, precision):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, w1, precision))
               * _mm("sd,df->sf", h, w3, precision), w2, precision)


def _experts(h, w, p, cfg, precision):
    """Every held expert on every token, times a mask of the selection; the
    tokens in blocks, so that the experts' running sum is a block's."""
    first, held = cfg["held_experts"]
    scores = jax.nn.softmax(
        _mm("sd,de->se", h, w[p + "router"], precision), axis=-1)
    weight, sel = lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    # gate[t, e]: the token's weight for held expert e, 0 where not selected
    gate = (weight[:, :, None] * (sel[:, :, None] == first + jnp.arange(
        held))).sum(axis=1)
    seq = h.shape[0]
    block = min(seq, _TOKEN_BLOCK)

    @jax.checkpoint
    def some(start):
        take = lambda x: lax.dynamic_slice_in_dim(  # noqa: E731
            x, start, block, axis=0)
        hb = take(h)

        @jax.checkpoint
        def one(y, expert):
            w1, w3, w2, g = expert
            return y + g[:, None] * _swiglu(hb, w1, w3, w2, precision), None

        return lax.scan(one, jnp.zeros_like(hb), (
            w[p + "ew1"], w[p + "ew3"], w[p + "ew2"], take(gate).T))[0]

    return lax.map(some, jnp.arange(0, seq, block)).reshape(h.shape)


def hidden(weights, tokens, cfg, precision="float32"):
    """``out [S, d]`` after the final norm."""
    x = weights["embed"][tokens]
    eps = cfg["rms_norm_eps"]
    for i, kind in enumerate(cfg["layer_types"]):
        p = "L%d." % i
        mine = {k: v for k, v in weights.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(x, w, p=p, kind=kind):
            x = x + _attention(_rms(x, w[p + "op_norm"], eps), w, p, cfg,
                               kind, precision)
            return x + _experts(_rms(x, w[p + "ff_norm"], eps), w, p, cfg,
                                precision)

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
    mu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    nu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    step_losses, first = [], None
    for t, batch in enumerate(batches, 1):
        loss, g = grads_of(weights, jnp.asarray(batch["tokens"], jnp.int32))
        step_losses.append(float(loss))
        if first is None:
            first = {k: np.asarray(v) for k, v in g.items()}
        for k in sorted(weights):
            weights[k], mu[k], nu[k] = adam(weights[k], mu[k], nu[k],
                                            g.pop(k), jnp.float32(t))
    # the seeded weights again (the same jitted call gives the same bits):
    # the steps did not have to keep them
    delta = _memo(cfg, "delta")(lambda: lambda a, b: {
        k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})(
            weights, init_weights(cfg, seed))
    return {"losses": step_losses, "first_gradient": first,
            "delta_norms": {k: float(v) for k, v in delta.items()}}
