"""Plain reference for the DeepSeek-V2 configurations without a query latent
(``model_type`` ``deepseek_v2``, ``q_lora_rank`` null: DeepSeek-V2-Lite):
forward, next-token loss, gradients and Adam in straightforward
``jax.numpy``, float32, every contraction at ``Precision.HIGHEST``.  No
kernels, no flax, nothing of the program.

The layer equations, from the keys of ``deepseek-ai/DeepSeek-V2-Lite``'s
``config.json`` (what the configuration's ``assumed`` lists is what the
family's published modelling code does and the config has no key for):

- model: ``h0 = E[tokens]``; the layers; ``out = RMSNorm(h_L)``
  (``rms_norm_eps``, the weight multiplies, no bias); logits ``= out W_head``,
  ``W_head`` a matrix of its own (``tie_word_embeddings`` false).  No learned
  positions.
- layer ``l``: ``x = x + MLA(RMSNorm(x))``, then ``x = x + FF_l(RMSNorm(x))``;
  ``FF_l`` the dense SwiGLU of width ``intermediate_size`` for ``l <
  first_k_dense_replace``, else the expert layer (``moe_layer_freq`` 1).
- MLA (latent attention), ``num_attention_heads`` heads, no biases, no query
  latent: ``q = x W_q``, a head ``[q_nope (qk_nope_head_dim) | q_pe
  (qk_rope_head_dim)]``; ``[c | k_pe] = x W_kva`` with ``c`` the latent
  (``kv_lora_rank``) and ``k_pe`` **one** rotary key a position, shared by
  all heads; ``[k_nope | v] = RMSNorm(c) W_kvb``, a head ``qk_nope_head_dim +
  v_head_dim``, the norm with a weight of its own; RoPE on ``q_pe`` and
  ``k_pe`` only, positions 0..T-1, interleaved pairing (dimensions ``2i``
  and ``2i + 1`` turn by ``pos * f_i``; assumed), the ``f_i`` YaRN's
  (``rope_scaling``: :func:`yarn_frequencies`); ``k = [k_nope | k_pe]``;
  scores ``q k^T * scale`` with ``scale = (nope + rope) ** -0.5 *
  m(mscale_all_dim) ** 2``, ``m(s) = 0.1 s ln(factor) + 1`` (assumed); causal
  softmax; ``o = softmax(.) v``, heads of ``v_head_dim``; ``y = concat(o)
  W_o``.
- SwiGLU: ``(silu(x W_1) * (x W_3)) W_2``.
- expert layer: ``s = softmax(x W_r)`` over the ``router_experts`` outputs, no
  bias (``scoring_func`` softmax); ``sel = top_k(s)`` (``topk_method``
  greedy, one group); ``w = s[sel]``, **not renormalised** (``norm_topk_prob``
  false), times ``routed_scaling_factor``; ``y = sum_{e in sel} w_e
  SwiGLU_e(x) + SwiGLU_shared(x)``, experts of width
  ``moe_intermediate_size``, the shared SwiGLU of width ``n_shared_experts *
  moe_intermediate_size``.  No selection bias, no capacity, nothing dropped.

**The chip's share.**  ``held_experts = [first, count]`` are the experts this
configuration holds of every expert layer.  The expert layer here is the
obvious one: every held expert on every token, times a mask of the selection,
plus the shared expert; what the absent experts would add is left out, and
that partial sum goes on to the next layer (the program does the same;
``model-configs`` guide, section 4).  ``shared=False`` leaves the shared
expert out too (the test that adds the shares up counts it once).  The
vocabulary is the configuration's (a slice is a smaller vocabulary).

The optimizer is Adam with a linear warm-up (``optimizer.warmup_steps``: step
``t`` uses ``learning_rate * min(1, t / warmup_steps)``).

Departures: ``seq_aux`` names a sequence-wise balance loss whose coefficient
is not among the config's keys: the loss is the cross-entropy alone, the mean
over the first ``S - 1`` positions of every row (the last position has no
target).  The steps are taken one sequence at a time and the gradients added
(routing is per token, so the sum over sequences is exact), each layer
recomputed in the backward pass and attention taken in blocks of queries:
that is how float32 at 8,192 positions fits.

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


def _sizes(cfg):
    """hidden, heads, nope, rope, value width, latent."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"])


def _is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def layer_leaves(cfg, i):
    """name -> (shape, kind) of layer ``i``'s leaves; kind is ``matrix``
    (normal 0.02), ``residual`` (a residual branch's output: scaled by
    1/sqrt(2 L)) or ``one`` (a norm's weight)."""
    d, heads, nope, rot, vd, rank = _sizes(cfg)
    p = "L%d." % i
    leaves = {
        p + "op_norm": ((d,), "one"), p + "ff_norm": ((d,), "one"),
        p + "wq": ((d, heads * (nope + rot)), "matrix"),
        p + "wkva": ((d, rank + rot), "matrix"),
        p + "kv_norm": ((rank,), "one"),
        p + "wkvb": ((rank, heads * (nope + vd)), "matrix"),
        p + "wo": ((heads * vd, d), "residual")}
    if _is_dense(cfg, i):
        f = cfg["intermediate_size"]
        leaves.update({p + "w1": ((d, f), "matrix"),
                       p + "w3": ((d, f), "matrix"),
                       p + "w2": ((f, d), "residual")})
    else:
        f, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
        fs = cfg["n_shared_experts"] * f
        leaves.update({
            p + "router": ((d, cfg["router_experts"]), "matrix"),
            p + "ew1": ((held, d, f), "matrix"),
            p + "ew3": ((held, d, f), "matrix"),
            p + "ew2": ((held, f, d), "residual"),
            p + "sw1": ((d, fs), "matrix"), p + "sw3": ((d, fs), "matrix"),
            p + "sw2": ((fs, d), "residual")})
    return leaves


def leaves(cfg):
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": ((vocab, d), "matrix"), "head": ((d, vocab), "matrix"),
           "norm_f": ((d,), "one")}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_leaves(cfg, i))
    return out


def init_weights(cfg, seed):
    """dict name -> float32 array, made on the device in one jitted call:
    matrices normal(0.02), residual outputs (``wo`` and every ``w2``) scaled
    by 1/sqrt(2 L), norms 1."""
    table = leaves(cfg)
    std = {"matrix": 0.02,
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


def _m(factor, mscale):
    """YaRN's attention factor."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(cfg):
    """``(f [rope / 2], cos-and-sin factor)`` from ``rope_theta`` and
    ``rope_scaling``: with ``b`` the base, ``D`` the rotary width, ``L0`` the
    original context: ``f_extra_i = b^(-2i/D)``, ``f_inter_i = f_extra_i /
    factor``; ``cd(n) = D ln(L0 / (2 pi n)) / (2 ln b)``; ``low =
    floor(cd(beta_fast))``, ``high = ceil(cd(beta_slow))``; ``ramp_i =
    clip((i - low) / (high - low), 0, 1)``; ``f_i = f_inter_i ramp_i +
    f_extra_i (1 - ramp_i)``.  In numpy float64, rounded to float32 once."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = np.arange(dim // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / dim)
    scaling = cfg.get("rope_scaling")
    if not scaling:
        return jnp.asarray(extra, jnp.float32), 1.0
    factor = scaling["factor"]

    def cd(n):
        return dim * math.log(scaling["original_max_position_embeddings"]
                              / (2 * math.pi * n)) / (2 * math.log(base))

    low = max(math.floor(cd(scaling["beta_fast"])), 0)
    high = min(math.ceil(cd(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    f = extra / factor * ramp + extra * (1.0 - ramp)
    return jnp.asarray(f, jnp.float32), (
        _m(factor, scaling["mscale"]) / _m(factor, scaling["mscale_all_dim"]))


def softmax_scale(cfg):
    scaling = cfg.get("rope_scaling")
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if scaling:
        scale *= _m(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def _rope(x, freqs, factor):
    """x [S, H, D], positions 0..S-1, dimensions 2i and 2i + 1 turned in
    place."""
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos = (jnp.cos(angle) * factor)[:, None]
    sin = (jnp.sin(angle) * factor)[:, None]
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(h, w, p, cfg, precision):
    d, heads, nope, rot, vd, rank = _sizes(cfg)
    seq, eps = h.shape[0], cfg["rms_norm_eps"]
    freqs, factor = yarn_frequencies(cfg)
    q = _mm("sd,de->se", h, w[p + "wq"], precision).reshape(
        seq, heads, nope + rot)
    kva = _mm("sd,de->se", h, w[p + "wkva"], precision)
    latent, k_pe = kva[:, :rank], kva[:, rank:]
    kv = _mm("sr,re->se", _rms(latent, w[p + "kv_norm"], eps),
             w[p + "wkvb"], precision).reshape(seq, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope(q[..., nope:], freqs, factor)
    k_pe = _rope(k_pe[:, None, :], freqs, factor)            # [S, 1, rot]
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (seq, heads, rot))], axis=-1)
    scale = softmax_scale(cfg)
    block = min(seq, _QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        """The attention output of the queries start .. start + block."""
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = _mm("qhd,shd->hqs", qb, k, precision) * scale
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        probs = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        return _mm("hqs,shd->qhd", probs, v, precision)

    out = lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, heads * vd)
    return _mm("se,ed->sd", out, w[p + "wo"], precision)


def _swiglu(h, w1, w3, w2, precision):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, w1, precision))
               * _mm("sd,df->sf", h, w3, precision), w2, precision)


def _experts(h, w, p, cfg, precision, shared=True):
    """Every held expert on every token, times a mask of the selection, plus
    (``shared``) the shared expert."""
    first, held = cfg["held_experts"]
    scores = jax.nn.softmax(
        _mm("sd,de->se", h, w[p + "router"], precision), axis=-1)
    weight, sel = lax.top_k(scores, cfg["num_experts_per_tok"])
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
    if shared:
        y = y + _swiglu(h, w[p + "sw1"], w[p + "sw3"], w[p + "sw2"],
                        precision)
    return y


def forward(weights, tokens, cfg, precision="float32"):
    """float32 logits [S, V] for one sequence of int tokens [S]."""
    x = weights["embed"][tokens]
    eps = cfg["rms_norm_eps"]
    for i in range(cfg["num_hidden_layers"]):
        p = "L%d." % i
        mine = {k: v for k, v in weights.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(x, w, p=p, i=i):
            h = _rms(x, w[p + "op_norm"], eps)
            x = x + _attention(h, w, p, cfg, precision)
            h = _rms(x, w[p + "ff_norm"], eps)
            if _is_dense(cfg, i):
                return x + _swiglu(h, w[p + "w1"], w[p + "w3"], w[p + "w2"],
                                   precision)
            return x + _experts(h, w, p, cfg, precision)

        x = layer(x, mine)
    x = _rms(x, weights["norm_f"], eps)
    return _mm("sd,dv->sv", x, weights["head"], precision)


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

    def adam(w, m, n, g, t):
        """One leaf's update: 535 M float32 parameters with their two
        moments and a gradient are 8.6 GB, and a whole-tree update would
        hold the new 6.4 GB beside them for a moment, more than the chip has
        left; leaf by leaf the old buffers go as the new ones come."""
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        rate = lr * jnp.minimum(1.0, t / warmup) if warmup else lr
        return w - rate * (m / c1) / (jnp.sqrt(n / c2) + eps), m, n

    grads_of = _memo(cfg, "grads", precision)(lambda: grads_of)
    adam = _memo(cfg, "adam")(lambda: adam)
    mu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    nu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    losses, first = [], None
    for t, batch in enumerate(batches, 1):
        loss, g = grads_of(weights, jnp.asarray(batch["tokens"], jnp.int32))
        losses.append(float(loss))
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
    return {"losses": losses, "first_gradient": first,
            "delta_norms": {k: float(v) for k, v in delta.items()}}
