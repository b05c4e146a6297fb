"""Plain reference for the language model of the Keye-VL-2.0 mixture
configurations (``model_type`` ``KeyeVL2``: Keye-VL-2.0-30B-A3B): forward,
next-token loss with the index's loss, gradients and Adam in straightforward
``jax.numpy``, float32, every contraction at ``Precision.HIGHEST``.  No
kernels, no flax, nothing of the program.

The layer equations, from the keys of ``Kwai-Keye/Keye-VL-2.0-30B-A3B``'s
``config.json`` (what the configuration's ``assumed`` lists is what the
family's modelling code, or the published DeepSeek-V3.2-Exp indexer that the
catalog's ``described_as`` names, does and the config has no key for):

- model: ``h0 = E[tokens]``; the layers, all alike; ``out = RMSNorm(h_L)``
  (``rms_norm_eps``, the weight multiplies, no bias); logits ``= out W_head``,
  a matrix of its own (``tie_word_embeddings`` false).  No learned positions.
- layer: ``x = x + Attn(RMSNorm(x))``, then ``x = x + Experts(RMSNorm(x))``
  (``decoder_sparse_step`` 1, ``mlp_only_layers`` empty).
- main projections, no biases: ``q = h W_q`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = h W_k``, ``v = h W_v`` (``num_key_value_heads``);
  RMSNorm over each head of ``q`` and of ``k``, a weight of ``head_dim`` each
  (assumed); RoPE on both, ``rope_theta``, rotate-half pairing, all of
  ``head_dim``, positions 0..T-1 (a text row: the three ``mrope_section``
  positions are equal, which is plain RoPE).
- index scores, on ``sg(h)`` (``sa_config``; assumed from the published
  indexer): ``qI = sg(h) W_qI`` (``indexer_num_heads`` heads of
  ``indexer_head_dim``), ``kI = LayerNorm(sg(h) W_kI)`` (one head; weight and
  bias), RoPE on both (all dimensions, the main theta), ``w = sg(h) W_w *
  J^-1/2 * E^-1/2``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for
  ``s <= t``.  (``q_chunk_size`` and ``kv_chunk_size`` are tiles and change
  no result.)
- selection: ``S_t`` = the ``min(t + 1, topk)`` positions ``s <= t`` of
  largest ``I[t, s]``, ties to the lower position (``lax.top_k``).  A
  constant of the step.
- attention: head ``i`` reads KV head ``i // (heads / kv_heads)``; ``a[t, s,
  i] = softmax over s in S_t of q[t, i] . k[s, .] / sqrt(head_dim)``; ``o =
  sum_s a v``; ``y = concat(o) W_o``.
- the index's loss (assumed: the sparse stage of the same publication):
  ``p[t, s] = sg(sum_i a[t, s, i]) / heads``; ``L_I = mean_t sum_{s in S_t}
  p[t, s] (log p[t, s] - log softmax_{S_t}(I[t, .])[s])``, added over the
  layers to the cross-entropy.  With the two detachments the index's leaves
  get ``L_I``'s gradient alone and every other leaf the cross-entropy's.
- experts: ``s = softmax(h W_r)`` over the ``router_experts`` outputs in
  float32, top ``num_experts_per_tok``, weights divided by their sum (+1e-6)
  (``norm_topk_prob``); SwiGLU experts ``(silu(x W_1) * (x W_3)) W_2`` of
  ``moe_intermediate_size``; no bias, no shared expert, nothing dropped.

**The chip's share.**  ``held_experts = [first, count]`` are the experts this
configuration holds of every layer; every held expert runs on every token,
times a mask of the selection; what the absent experts would add is left
out, and that partial sum goes on to the next layer (the program does the
same).  The vocabulary is the configuration's (a slice is a smaller one).

Departures: the vision tower is left out; the loss's cross-entropy is the
mean over the first ``S - 1`` positions of every row.  Rows are taken one at
a time and the gradients added, each layer recomputed in the backward pass,
attention taken in blocks of 512 queries with the heads in turn, the
read-out and its cross-entropy in blocks of positions: that is how float32
at 32,768 positions fits.

``precision="fp8"`` is the **control**: both operands of every matrix product
(the index's too, so its selection is coarser) rounded to float8_e4m3 under a
per-tensor scale (straight-through backward).
"""

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
    """hidden, heads, KV heads, head width, index heads, index width."""
    sparse = cfg["sa_config"]
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            sparse["indexer_num_heads"], sparse["indexer_head_dim"])


def layer_leaves(cfg, i):
    """name -> (shape, kind) of layer ``i``'s leaves; kind is ``matrix``
    (normal 0.02), ``residual`` (a residual branch's output: scaled by
    1/sqrt(2 L)), ``one`` (a norm's weight) or ``zero`` (a norm's bias);
    the embedding's is ``embedding`` (:func:`init_weights`)."""
    d, heads, kv, dim, ih, idim = _sizes(cfg)
    f, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
    p = "L%d." % i
    return {
        p + "op_norm": ((d,), "one"), p + "ff_norm": ((d,), "one"),
        p + "wq": ((d, heads * dim), "matrix"),
        p + "wk": ((d, kv * dim), "matrix"),
        p + "wv": ((d, kv * dim), "matrix"),
        p + "q_norm": ((dim,), "one"), p + "k_norm": ((dim,), "one"),
        p + "wo": ((heads * dim, d), "residual"),
        p + "iwq": ((d, ih * idim), "matrix"),
        p + "iwk": ((d, idim), "matrix"),
        p + "ik_norm": ((idim,), "one"), p + "ik_bias": ((idim,), "zero"),
        p + "iww": ((d, ih), "matrix"),
        p + "router": ((d, cfg["router_experts"]), "matrix"),
        p + "ew1": ((held, d, f), "matrix"),
        p + "ew3": ((held, d, f), "matrix"),
        p + "ew2": ((held, f, d), "residual")}


def index_leaves(cfg):
    """The leaves that the index's loss alone trains."""
    return sorted(n for n in leaves(cfg)
                  if n.split(".")[-1] in ("iwq", "iwk", "ik_norm", "ik_bias",
                                          "iww"))


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
    by 1/sqrt(2 L), norm weights 1, norm biases 0, **the embedding normal(1)**.
    With 0.02 there too the attention branch (0.06 rms) outweighs a token's
    own row (0.02), and as that branch is an average over thousands of keys
    the hidden states of a net that has learned nothing collapse onto one
    direction layer by layer: the router's logits then share 19%, 61%, 77%,
    80% of their spread across tokens (layers 0 to 3; 2,048 positions, CPU),
    one expert in 128 draws up to 16 times its share, and what a chip's 16
    hold swings between 9% and 21% of the pairs by layer and by seed, and the
    step's time with it.  A trained model's states are its tokens'; a unit
    embedding keeps them so (common share 3-5%, heaviest expert 1.3-1.5
    times the mean, 12.5-13.1% held)."""
    table = leaves(cfg)
    std = {"matrix": 0.02, "embedding": 1.0,
           "residual": 0.02 / np.sqrt(2.0 * cfg["num_hidden_layers"])}

    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(sorted(table.items())):
            if kind in ("one", "zero"):
                out[name] = jnp.full(shape, float(kind == "one"), jnp.float32)
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


def _layer_norm(x, g, b, eps):
    centred = x - x.mean(-1, keepdims=True)
    return centred * lax.rsqrt(
        jnp.square(centred).mean(-1, keepdims=True) + eps) * g + b


def _rope(x, theta):
    """x [S, H, D], positions 0..S-1, dimension i turned with i + D/2 by
    ``pos * theta^(-2i/D)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_scores(h, w, p, cfg, precision="float32"):
    """``(qI [S, J, E], kI [S, E], w [S, J])`` of the detached ``h``."""
    _, _, _, _, ih, idim = _sizes(cfg)
    seq, theta = h.shape[0], float(cfg["rope_theta"])
    h = lax.stop_gradient(h)
    iq = _rope(_mm("sd,de->se", h, w[p + "iwq"], precision).reshape(
        seq, ih, idim), theta)
    ik = _layer_norm(_mm("sd,de->se", h, w[p + "iwk"], precision),
                     w[p + "ik_norm"], w[p + "ik_bias"],
                     cfg["rms_norm_eps"])
    ik = _rope(ik[:, None], theta)[:, 0]
    iw = _mm("sd,dj->sj", h, w[p + "iww"], precision) * (
        ih ** -0.5 * idim ** -0.5)
    return iq, ik, iw


def selection(scores, start, topk):
    """bool ``[block, S]``: for the queries ``start .. start + block`` the
    ``min(t + 1, topk)`` causal keys of largest score, ties to the lower
    position."""
    block, seq = scores.shape
    visible = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
    _, picked = lax.top_k(jnp.where(visible, scores, -jnp.inf),
                          min(topk, seq))
    kept = jnp.zeros((block, seq), bool).at[
        jnp.arange(block)[:, None], picked].set(True)
    return kept & visible


def _attention(h, w, p, cfg, precision):
    """``(y [S, d], L_I)`` of one layer."""
    d, heads, kv, dim, ih, idim = _sizes(cfg)
    seq, eps, theta = h.shape[0], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    group = heads // kv
    q = _mm("sd,de->se", h, w[p + "wq"], precision).reshape(seq, heads, dim)
    k = _mm("sd,de->se", h, w[p + "wk"], precision).reshape(seq, kv, dim)
    v = _mm("sd,de->se", h, w[p + "wv"], precision).reshape(seq, kv, dim)
    q = _rope(_rms(q, w[p + "q_norm"], eps), theta)
    k = _rope(_rms(k, w[p + "k_norm"], eps), theta)
    iq, ik, iw = index_scores(h, w, p, cfg, precision)
    topk = cfg["sa_config"]["topk"]
    block = min(seq, _QUERY_BLOCK)
    qh, kh, vh = (x.transpose(1, 0, 2) for x in (q, k, v))    # [H, S, D]
    of_head = jnp.arange(heads) // group                      # its KV head

    @jax.checkpoint
    def rows(start):
        """The heads' outputs ``[H, block, D]`` and the index's loss summed
        over the queries start .. start + block."""
        take = lambda x, axis: lax.dynamic_slice_in_dim(  # noqa: E731
            x, start, block, axis=axis)
        @jax.checkpoint
        def add_heads(total, part):
            iq_part, iw_part = part
            return total + (iw_part.T[:, :, None] * jax.nn.relu(_mm(
                "qje,se->jqs", iq_part, ik, precision))).sum(axis=0), None

        # a few index heads at a time: [J, block, S] is 1 GB at 32k
        few = min(ih, 4)
        index = lax.scan(add_heads, jnp.zeros((block, seq)), (
            take(iq, 0).reshape(block, ih // few, few, idim).transpose(
                1, 0, 2, 3),
            take(iw, 0).reshape(block, ih // few, few).transpose(1, 0, 2)))[0]
        kept = selection(lax.stop_gradient(index), start, topk)

        def probs(qi, ki):
            scores = _mm("qd,sd->qs", qi, ki, precision) * dim ** -0.5
            return jax.nn.softmax(jnp.where(kept, scores, -1e30), axis=-1)

        @jax.checkpoint
        def head(q_kv):
            qi, kv_head = q_kv
            return _mm("qs,sd->qd", probs(qi, kh[kv_head]), vh[kv_head],
                       precision)

        out = lax.map(head, (take(qh, 1), of_head))

        def add(total, q_kv):
            return total + probs(q_kv[0], lax.stop_gradient(kh)[q_kv[1]]), None

        # the heads' probabilities averaged: a constant (nothing is kept of
        # it for a backward pass)
        p_mean = lax.scan(add, jnp.zeros((block, seq)), (
            lax.stop_gradient(take(qh, 1)), of_head))[0] / heads
        log_soft = jax.nn.log_softmax(jnp.where(kept, index, -1e30), axis=-1)
        loss = jnp.where(
            kept, p_mean * (jnp.log(jnp.maximum(p_mean, 1e-37)) - log_soft),
            0.0).sum()
        return out, loss

    out, loss = lax.map(rows, jnp.arange(0, seq, block))
    out = out.transpose(0, 2, 1, 3).reshape(seq, heads * dim)
    return _mm("se,ed->sd", out, w[p + "wo"], precision), loss.sum() / seq


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
    """``(out [S, d] after the final norm, the layers' L_I added up)``."""
    x = weights["embed"][tokens]
    eps = cfg["rms_norm_eps"]
    index_loss = jnp.zeros(())
    for i in range(cfg["num_hidden_layers"]):
        p = "L%d." % i
        mine = {k: v for k, v in weights.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(x, w, p=p):
            y, loss = _attention(_rms(x, w[p + "op_norm"], eps), w, p, cfg,
                                 precision)
            x = x + y
            return x + _experts(_rms(x, w[p + "ff_norm"], eps), w, p, cfg,
                                precision), loss

        x, loss = layer(x, mine)
        index_loss = index_loss + loss
    return _rms(x, weights["norm_f"], eps), index_loss


def forward(weights, tokens, cfg, precision="float32"):
    """float32 logits [S, V] for one sequence of int tokens [S]."""
    return _mm("sd,dv->sv", hidden(weights, tokens, cfg, precision)[0],
               weights["head"], precision)


def losses(weights, tokens, cfg, precision="float32"):
    """``(cross-entropy, L_I)`` of one sequence; the step's loss is their
    sum."""
    out, index_loss = hidden(weights, tokens, cfg, precision)
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

    ce = lax.map(some, jnp.arange(0, seq, block)).sum() / (seq - 1)
    return ce, index_loss


def loss_fn(weights, tokens, cfg, precision="float32"):
    return sum(losses(weights, tokens, cfg, precision))


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
