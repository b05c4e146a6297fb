"""Plain reference for the LFM2 mixture configurations (``model_type``
``lfm2_moe``): forward, next-token loss, gradients and Adam in
straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``.  No kernels, no flax, nothing of the program.

The layer equations, from the keys of ``LiquidAI/LFM2-8B-A1B``'s
``config.json`` (what the configuration's ``assumed`` lists is what the
family's published modelling code does and the config has no key for):

- model: ``h0 = E[tokens]``; the layers; ``out = RMSNorm(h_L)`` (``norm_eps``,
  the weight multiplies, no bias); logits ``= out @ E^T`` (tied read-out,
  assumed).  No learned positions.
- layer ``l``: ``x = x + Op_l(RMSNorm(x))``, then ``x = x + FF_l(RMSNorm(x))``;
  ``Op_l`` by ``layer_types[l]``; ``FF_l`` the dense SwiGLU of width
  ``intermediate_size`` for ``l < num_dense_layers``, else the expert layer.
- ``conv``: ``[B, C, u] = split3(x W_in)``, no bias; ``z = B * u``; ``c_t =
  sum_{j<L} w_j * z_{t-j}`` (depthwise, causal, ``conv_L_cache`` taps a
  channel, zeros before the sequence); ``y = (C * c) W_out`` (the order gate,
  convolve, gate is assumed).
- ``full_attention``: ``q = x W_q`` (``num_attention_heads`` heads), ``k = x
  W_k``, ``v = x W_v`` (``num_key_value_heads`` heads), no biases; per-head
  RMSNorm on q and on k over the head's dimensions (assumed); RoPE,
  ``rope_theta``, rotate-half pairing (assumed), positions 0..T-1; causal
  softmax attention, scale 1/sqrt(head size), query head i reading KV head
  ``i // group``; ``y = concat(heads) W_o``.
- SwiGLU: ``(silu(x W_1) * (x W_3)) W_2``.
- expert layer: ``s = sigmoid(x W_r)``, ``W_r`` to ``router_experts`` outputs,
  no bias (sigmoid assumed: ``use_expert_bias``, ``norm_topk_prob`` and
  ``routed_scaling_factor`` are the keys of that router family); ``sel =
  top_k(s + b)`` with ``b`` the ``expert_bias`` values, which enter the
  selection only; ``w = s[sel] / (sum(s[sel]) + 1e-6)`` (``norm_topk_prob``)
  times ``routed_scaling_factor``; ``y = sum_{e in sel} w_e SwiGLU_e(x)``,
  width ``moe_intermediate_size``.  No shared expert, no auxiliary loss, no capacity.
  ``expert_bias`` receives no gradient (its balancing update is not in the
  config): it stays at its seeded value.

**The chip's share.**  ``held_experts = [first, count]`` are the experts this
configuration holds of every expert layer.  The expert layer here is the
obvious one: every held expert on every token, times a mask of the selection;
what the absent experts would add is left out, and that partial sum goes on to
the next layer (the program does the same; ``model-configs`` guide, section
4).  The vocabulary is the configuration's (a slice is a smaller vocabulary).

The optimizer is Adam with a linear warm-up (``optimizer.warmup_steps``: step
``t`` uses ``learning_rate * min(1, t / warmup_steps)``).

Departures: the loss is the mean over the first ``S - 1`` positions of every
row (the last position has no target).  The steps are taken one sequence at a
time and the gradients added (routing is per token, so the sum over sequences
is exact), each layer recomputed in the backward pass and attention taken in
blocks of queries: that is how float32 at 8,192 positions fits.

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
_QUERY_BLOCK = 512


def _sizes(cfg):
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, heads, kv, d // heads


def layer_leaves(cfg, i):
    """name -> (shape, kind) of layer ``i``'s leaves; kind is ``matrix``
    (normal 0.02), ``residual`` (a residual branch's output: scaled by
    1/sqrt(2 L)), ``one`` (a norm's weight), ``tap`` or ``bias``."""
    d, heads, kv, hd = _sizes(cfg)
    p = "L%d." % i
    leaves = {p + "op_norm": ((d,), "one"), p + "ff_norm": ((d,), "one")}
    if cfg["layer_types"][i] == "conv":
        leaves.update({
            p + "in_proj": ((d, 3 * d), "matrix"),
            p + "conv": ((cfg["conv_L_cache"], d), "tap"),
            p + "out_proj": ((d, d), "residual")})
    else:
        leaves.update({
            p + "wq": ((d, heads * hd), "matrix"),
            p + "wk": ((d, kv * hd), "matrix"),
            p + "wv": ((d, kv * hd), "matrix"),
            p + "q_norm": ((hd,), "one"), p + "k_norm": ((hd,), "one"),
            p + "wo": ((heads * hd, d), "residual")})
    if i < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        leaves.update({p + "w1": ((d, f), "matrix"),
                       p + "w3": ((d, f), "matrix"),
                       p + "w2": ((f, d), "residual")})
    else:
        f, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
        leaves.update({
            p + "router": ((d, cfg["router_experts"]), "matrix"),
            p + "expert_bias": ((cfg["router_experts"],), "bias"),
            p + "ew1": ((held, d, f), "matrix"),
            p + "ew3": ((held, d, f), "matrix"),
            p + "ew2": ((held, f, d), "residual")})
    return leaves


def leaves(cfg):
    out = {"embed": ((cfg["vocab_size"], cfg["hidden_size"]), "matrix"),
           "norm_f": ((cfg["hidden_size"],), "one")}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_leaves(cfg, i))
    return out


def _expert_bias(cfg, key):
    """One layer's ``expert_bias``: ``expert_bias_std`` times the mid-quantiles
    of a standard normal, one a router output, dealt so that no seed changes
    the work (as the benchmark's generator deals sizes and arrivals): the
    experts held here take every ``E / held``-th quantile, symmetric about 0
    (their sum is 0, so the share of the pairs routed here stays at ``held /
    E`` and the heaviest held expert's load over the mean's stays where
    ``expert_bias_std`` puts it), the others take the rest; the seed permutes
    the values within each of the two sets."""
    experts = cfg["router_experts"]
    first, held = cfg["held_experts"]
    stride = experts // held
    mine = stride * np.arange(held) + stride // 2 - (
        (np.arange(held) % 2 == 0) if stride > 1 else 0)
    rest = np.setdiff1d(np.arange(experts), mine)
    here = np.arange(first, first + held)
    away = np.setdiff1d(np.arange(experts), here)
    quantile = jax.scipy.special.ndtri(
        (jnp.arange(experts, dtype=jnp.float32) + 0.5) / experts)
    k_here, k_away = jax.random.split(key)
    return cfg["expert_bias_std"] * jnp.zeros(experts, jnp.float32).at[
        here].set(quantile[jax.random.permutation(k_here, mine)]).at[
        away].set(quantile[jax.random.permutation(k_away, rest)])


def init_weights(cfg, seed):
    """dict name -> float32 array, made on the device in one jitted call:
    matrices normal(0.02), residual outputs scaled by 1/sqrt(2 L), taps
    normal(0.02) (no identity tap is planted), norms 1, ``expert_bias`` the
    quantiles of a normal of the configuration's ``expert_bias_std``
    (:func:`_expert_bias`)."""
    table = leaves(cfg)
    std = {"matrix": 0.02, "tap": 0.02,
           "residual": 0.02 / np.sqrt(2.0 * cfg["num_hidden_layers"])}

    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(sorted(table.items())):
            if kind == "one":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "bias":
                out[name] = _expert_bias(cfg, jax.random.fold_in(key, n))
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


def _rope(x, theta):
    """x [S, H, D], positions 0..S-1, rotate-half pairing."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _conv(h, w, p, cfg, precision):
    seq, taps = h.shape[0], cfg["conv_L_cache"]
    gate_b, gate_c, u = jnp.split(
        _mm("sd,de->se", h, w[p + "in_proj"], precision), 3, axis=-1)
    z = jnp.pad(gate_b * u, ((taps - 1, 0), (0, 0)))
    c = sum(w[p + "conv"][j] * z[taps - 1 - j:taps - 1 - j + seq]
            for j in range(taps))
    return _mm("sd,de->se", gate_c * c, w[p + "out_proj"], precision)


def _attention(h, w, p, cfg, precision):
    d, heads, kv, hd = _sizes(cfg)
    seq, group = h.shape[0], heads // kv
    q = _mm("sd,de->se", h, w[p + "wq"], precision).reshape(seq, heads, hd)
    k = _mm("sd,de->se", h, w[p + "wk"], precision).reshape(seq, kv, hd)
    v = _mm("sd,de->se", h, w[p + "wv"], precision).reshape(seq, kv, hd)
    q = _rope(_rms(q, w[p + "q_norm"], cfg["norm_eps"]), cfg["rope_theta"])
    k = _rope(_rms(k, w[p + "k_norm"], cfg["norm_eps"]), cfg["rope_theta"])
    q = q.reshape(seq, kv, group, hd)
    block = min(seq, _QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        """The attention output of the queries start .. start + block."""
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = _mm("qkgd,skd->kgqs", qb, k, precision) / np.sqrt(hd)
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        probs = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        return _mm("kgqs,skd->qkgd", probs, v, precision)

    out = lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, heads * hd)
    return _mm("se,ed->sd", out, w[p + "wo"], precision)


def _swiglu(h, w1, w3, w2, precision):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, w1, precision))
               * _mm("sd,df->sf", h, w3, precision), w2, precision)


def _experts(h, w, p, cfg, precision):
    """Every held expert on every token, times a mask of the selection."""
    first, held = cfg["held_experts"]
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, w[p + "router"], precision))
    _, sel = lax.top_k(scores + w[p + "expert_bias"],
                       cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    weight = weight * cfg["routed_scaling_factor"]
    # gate[t, e]: the token's weight for held expert e, 0 where not selected
    gate = (weight[:, :, None] * (sel[:, :, None] == first + jnp.arange(
        held))).sum(axis=1)

    @jax.checkpoint
    def one(y, expert):
        w1, w3, w2, g = expert
        return y + g[:, None] * _swiglu(h, w1, w3, w2, precision), None

    y, _ = lax.scan(one, jnp.zeros_like(h),
                    (w[p + "ew1"], w[p + "ew3"], w[p + "ew2"], gate.T))
    return y


def forward(weights, tokens, cfg, precision="float32"):
    """float32 logits [S, V] for one sequence of int tokens [S]."""
    x = weights["embed"][tokens]
    for i, kind in enumerate(cfg["layer_types"]):
        p = "L%d." % i
        mine = {k: v for k, v in weights.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(x, w, p=p, i=i, kind=kind):
            h = _rms(x, w[p + "op_norm"], cfg["norm_eps"])
            op = _conv if kind == "conv" else _attention
            x = x + op(h, w, p, cfg, precision)
            h = _rms(x, w[p + "ff_norm"], cfg["norm_eps"])
            if i < cfg["num_dense_layers"]:
                return x + _swiglu(h, w[p + "w1"], w[p + "w3"], w[p + "w2"],
                                   precision)
            return x + _experts(h, w, p, cfg, precision)

        x = layer(x, mine)
    x = _rms(x, weights["norm_f"], cfg["norm_eps"])
    return _mm("sd,vd->sv", x, weights["embed"], precision)


def loss_fn(weights, tokens, cfg, precision="float32"):
    logits = forward(weights, tokens, cfg, precision)[:-1]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


def train_steps(cfg, seed, batches, precision="float32"):
    """Follow the first ``len(batches)`` Adam steps from the seeded weights:
    ``{"losses", "first_gradient" (leaf -> array), "delta_norms" (leaf ->
    norm of the parameters' change over the steps)}``."""
    weights = init_weights(cfg, seed)
    opt = cfg["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["learning_rate"]
    warmup = opt.get("warmup_steps", 0)     # linear, from lr / warmup

    def grads_of(weights, tokens):
        def one(acc, row):
            got = jax.value_and_grad(
                lambda w: loss_fn(w, row, cfg, precision))(weights)
            return jax.tree_util.tree_map(jnp.add, acc, got), None

        zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like,
                                                      weights))
        (loss, g), _ = lax.scan(one, zero, tokens)
        n = tokens.shape[0]
        return loss / n, jax.tree_util.tree_map(lambda x: x / n, g)

    def adam(weights, mu, nu, g, t):
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(
            lambda n, x: b2 * n + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        rate = lr * jnp.minimum(1.0, t / warmup) if warmup else lr
        new = jax.tree_util.tree_map(
            lambda w, m, n: w - rate * (m / c1) / (jnp.sqrt(n / c2) + eps),
            weights, mu, nu)
        return new, mu, nu

    grads_of = _memo(cfg, "grads", precision)(lambda: grads_of)
    adam = _memo(cfg, "adam")(lambda: adam)
    start = weights
    mu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    nu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    losses, first = [], None
    for t, batch in enumerate(batches, 1):
        loss, g = grads_of(weights, jnp.asarray(batch["tokens"], jnp.int32))
        losses.append(float(loss))
        if first is None:
            first = {k: np.asarray(v) for k, v in g.items()}
        weights, mu, nu = adam(weights, mu, nu, g, jnp.float32(t))
        del g
    delta = _memo(cfg, "delta")(lambda: lambda a, b: {
        k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})(
            weights, start)
    return {"losses": losses, "first_gradient": first,
            "delta_norms": {k: float(v) for k, v in delta.items()}}
