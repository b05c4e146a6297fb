"""Plain reference for the Nemotron-H configurations (``model_type``
``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B): forward, next-token loss,
gradients and Adam in straightforward ``jax.numpy``, float32, every
contraction at ``Precision.HIGHEST``.  No kernels, no flax, nothing of the
program; the state-space layer is **the recurrence itself, position by
position**, not the chunked algorithm the program runs.

The layer equations, from the keys of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s ``config.json`` (what the
configuration's ``assumed`` lists is what the family's modelling code does
and the config has no key for, or has a key that says otherwise):

- model: ``h0 = E[tokens]``; the layers; ``out = RMSNorm(h_L)``
  (``layer_norm_epsilon``, the weight multiplies, no bias); logits ``= out
  W_head``, a matrix of its own (``tie_word_embeddings`` false).  No
  positions anywhere: no table, no rotary embedding (assumed).
- a layer is **one part alone**, ``x = x + part(RMSNorm(x))``, by its
  character of ``hybrid_override_pattern``: ``M``, ``E`` or ``*``.
- ``M``, Mamba-2 (arXiv:2405.21060): ``[z | xBC | dt] = u W_in`` of
  ``inner | inner + 2 n_groups ssm_state_size | mamba_num_heads`` columns,
  ``inner = mamba_num_heads * mamba_head_dim``, no bias; ``xBC = silu(c +
  b)`` with ``c_t = sum_j w_j xBC_{t-j}`` a channel (``conv_kernel`` taps,
  zeros before the row, one convolution over x, B and C together); ``x [T,
  heads, head_dim]``, ``B, C [T, n_groups, state]``, head ``h`` reads group
  ``h // (heads / n_groups)``; ``dt = softplus(dt + dt_bias)`` (no clamp),
  ``A = -exp(A_log)`` a head; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
  B_t``, ``S [head_dim, state]`` a head and zero before the row; ``y_t = S_t
  C_t + D x_t``; ``y = RMSNorm_group(y * silu(z)) * w`` over groups of
  ``inner / n_groups`` (the gate before the norm); out ``y W_out``.
- ``E``: ``s = sigmoid(x W_r)`` over the ``router_experts`` outputs in
  float32; ``sel = top_k(s + b)`` with ``b`` the correction bias, which
  enters the choice only and gets no gradient; the chosen ``s`` divided by
  their sum (+1e-6) (``norm_topk_prob``) and times
  ``routed_scaling_factor``; an expert is two matrices, ``relu(x W_1)**2
  W_2`` of ``moe_intermediate_size`` (``mlp_hidden_act`` ``relu2``); one
  shared expert of the same form of ``moe_shared_expert_intermediate_size``
  for every token; nothing dropped, no auxiliary loss.
- ``*``: ``q = h W_q`` (``num_attention_heads`` heads of ``head_dim``), ``k``,
  ``v`` (``num_key_value_heads``), no biases, no norm, no rotation; head
  ``j`` reads KV head ``j // (heads / kv_heads)``; scores ``q_t . k_s /
  sqrt(head_dim)`` over ``s <= t``; softmax; ``y = concat(o) W_o``.

**The chip's share.**  ``held_experts = [first, count]`` are the experts this
configuration holds of every expert layer; every held expert runs on every
token, times a mask of the selection; what the absent experts would add is
left out, and that partial sum goes on to the next layer (the program does
the same).  The shared expert is whole (``shared=False`` leaves it out: the
tests add up the sixteen shares with it once).  The vocabulary is the
configuration's (a slice is a smaller one).

Departures: the loss is the mean cross-entropy over the first ``S - 1``
positions of every row.  Rows are taken one at a time and the gradients
added, each layer recomputed in the backward pass, the recurrence
checkpointed every 128 positions (its backward pass holds 128 states, not
``S``), attention in blocks of 512 queries with the heads in turn, the
experts, the read-out and its cross-entropy in blocks of positions: that is
how float32 at 8,192 positions fits.

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


def _mamba_sizes(cfg):
    """heads, head width, state, groups, inner width, B's (and C's) width."""
    heads, dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return (heads, dim, cfg["ssm_state_size"], cfg["n_groups"], heads * dim,
            cfg["n_groups"] * cfg["ssm_state_size"])


def layer_leaves(cfg, i):
    """name -> (shape, kind) of layer ``i``'s leaves; kind is ``matrix``
    (normal 0.02), ``residual`` (a layer's output: scaled down by the root
    of the number of layers), ``one`` (a norm's weight, ``D``), ``tap``,
    ``bias``, ``a_log`` or ``dt_bias`` (:func:`init_weights`)."""
    d, kind = cfg["hidden_size"], cfg["hybrid_override_pattern"][i]
    p = "L%d." % i
    out = {p + "norm": ((d,), "one")}
    if kind == "M":
        heads, _, _, _, inner, bc = _mamba_sizes(cfg)
        out.update({
            p + "in_proj": ((d, 2 * inner + 2 * bc + heads), "matrix"),
            p + "conv": ((cfg["conv_kernel"], inner + 2 * bc), "tap"),
            p + "conv_bias": ((inner + 2 * bc,), "tap"),
            p + "A_log": ((heads,), "a_log"),
            p + "dt_bias": ((heads,), "dt_bias"), p + "D": ((heads,), "one"),
            p + "gate_norm": ((inner,), "one"),
            p + "out_proj": ((inner, d), "residual")})
    elif kind == "E":
        f, held = cfg["moe_intermediate_size"], cfg["held_experts"][1]
        fs = cfg["n_shared_experts"] * \
            cfg["moe_shared_expert_intermediate_size"]
        out.update({
            p + "router": ((d, cfg["router_experts"]), "matrix"),
            p + "expert_bias": ((cfg["router_experts"],), "bias"),
            p + "ew1": ((held, d, f), "matrix"),
            p + "ew2": ((held, f, d), "residual"),
            p + "sw1": ((d, fs), "matrix"), p + "sw2": ((fs, d), "residual")})
    elif kind == "*":
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        dim = cfg["head_dim"]
        out.update({
            p + "wq": ((d, heads * dim), "matrix"),
            p + "wk": ((d, kv * dim), "matrix"),
            p + "wv": ((d, kv * dim), "matrix"),
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
    ``mellum2_12b_a2p5b_ep8``'s recipe for what the two share: matrices
    normal(0.02) (router and head too), norm weights 1, **the embedding
    normal(1)** (at 0.02 a few untrained layers collapse the router's input
    and the rate swings by seed: ``keye_vl2_30b_a3b_ep8``'s file has the
    measurements), a layer's output matrix scaled by ``1 / sqrt(L)``: there
    it is ``1 / sqrt(2 L)`` for two residual adds a layer, here a layer has
    one (the family's ``rescale_prenorm_residual`` divides by the same
    root).  Mamba-2's own: ``A_log = log(uniform(1, 16))``, ``dt_bias`` the
    inverse softplus of a step drawn log-uniformly in ``[time_step_min,
    time_step_max]`` and floored at ``time_step_floor``, ``D = 1``, the taps
    and their bias uniform in ``+-1 / sqrt(conv_kernel)`` (what the family's
    code leaves a depthwise convolution at; at normal(0.02) x, B and C
    would be a hundredth of ``D x`` and the scan would not show in any
    gradient).  The router's correction bias normal(0.02): at zero nothing
    would show that it enters the choice alone."""
    table = leaves(cfg)
    std = {"matrix": 0.02, "embedding": 1.0, "bias": 0.02,
           "residual": 0.02 / np.sqrt(float(cfg["num_hidden_layers"]))}
    tap = 1.0 / math.sqrt(cfg["conv_kernel"])
    low, high = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])

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
                    k, shape, jnp.float32, low, high)),
                    cfg["time_step_floor"])
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


def recurrence(x, dt, decay, b, c):
    """``y [S, H, P]`` of ``S_t = decay_t S_{t-1} + dt_t x_t (x) B_t``, ``y_t
    = S_t C_t``, one position after another from a zero state: ``x [S, H,
    P]``, ``dt`` and ``decay [S, H]``, ``b`` and ``c [S, G, N]``."""
    seq, heads, dim = x.shape
    rep = heads // b.shape[1]
    block = math.gcd(seq, _STATE_BLOCK)

    def step(state, at):
        xt, dtt, decayed, bt, ct = at
        bt, ct = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        state = decayed[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return state, (state * ct[:, None, :]).sum(-1)

    @jax.checkpoint
    def some(state, positions):
        return lax.scan(step, state, positions)

    blocks = jax.tree_util.tree_map(
        lambda t: t.reshape((seq // block, block) + t.shape[1:]),
        (x, dt, decay, b, c))
    _, y = lax.scan(some, jnp.zeros((heads, dim, b.shape[2]), x.dtype),
                    blocks)
    return y.reshape(seq, heads, dim)


def _mamba(h, w, p, cfg, precision):
    """``y [S, d]`` of one Mamba-2 layer."""
    heads, dim, state, groups, inner, bc = _mamba_sizes(cfg)
    seq, taps = h.shape[0], cfg["conv_kernel"]
    z, xbc, dt = jnp.split(_mm("sd,de->se", h, w[p + "in_proj"], precision),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w[p + "conv_bias"] + sum(
        w[p + "conv"][j] * padded[taps - 1 - j:taps - 1 - j + seq]
        for j in range(taps)))
    x = xbc[:, :inner].reshape(seq, heads, dim)
    b = xbc[:, inner:inner + bc].reshape(seq, groups, state)
    c = xbc[:, inner + bc:].reshape(seq, groups, state)
    dt = jax.nn.softplus(dt + w[p + "dt_bias"])
    y = recurrence(x, dt, jnp.exp(dt * -jnp.exp(w[p + "A_log"])), b, c)
    y = (y + w[p + "D"][:, None] * x).reshape(seq, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(seq, groups, inner // groups), 1.0,
             cfg["layer_norm_epsilon"]).reshape(seq, inner)
    return _mm("se,ed->sd", y * w[p + "gate_norm"], w[p + "out_proj"],
               precision)


def _attention(h, w, p, cfg, precision):
    """``y [S, d]`` of one attention layer: every causal key, no positions."""
    heads, kv, dim = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    seq = h.shape[0]
    q = _mm("sd,de->se", h, w[p + "wq"], precision).reshape(seq, heads, dim)
    k = _mm("sd,de->se", h, w[p + "wk"], precision).reshape(seq, kv, dim)
    v = _mm("sd,de->se", h, w[p + "wv"], precision).reshape(seq, kv, dim)
    block = min(seq, _QUERY_BLOCK)
    qh, kh, vh = (x.transpose(1, 0, 2) for x in (q, k, v))    # [H, S, D]
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


def _relu2(h, w1, w2, precision):
    return _mm("sf,fd->sd", jnp.square(jax.nn.relu(
        _mm("sd,df->sf", h, w1, precision))), w2, precision)


def _experts(h, w, p, cfg, precision, shared=True):
    """Every held expert on every token, times a mask of the selection, plus
    (``shared``) the shared expert; the tokens in blocks, so that the
    experts' running sum is a block's."""
    first, held = cfg["held_experts"]
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, w[p + "router"], precision))
    _, sel = lax.top_k(scores + lax.stop_gradient(w[p + "expert_bias"]),
                       cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    weight = weight * cfg["routed_scaling_factor"]
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
            w1, w2, g = expert
            return y + g[:, None] * _relu2(hb, w1, w2, precision), None

        y = lax.scan(one, jnp.zeros_like(hb),
                     (w[p + "ew1"], w[p + "ew2"], take(gate).T))[0]
        if shared:
            y = y + _relu2(hb, w[p + "sw1"], w[p + "sw2"], precision)
        return y

    return lax.map(some, jnp.arange(0, seq, block)).reshape(h.shape)


_PARTS = {"M": _mamba, "*": _attention, "E": _experts}


def hidden(weights, tokens, cfg, precision="float32"):
    """``out [S, d]`` after the final norm."""
    x = weights["embed"][tokens]
    eps = cfg["layer_norm_epsilon"]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = "L%d." % i
        mine = {k: v for k, v in weights.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(x, w, p=p, kind=kind):
            return x + _PARTS[kind](_rms(x, w[p + "norm"], eps), w, p, cfg,
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
    # Adam's moments wait on the host between updates and pass through the
    # device a leaf at a time: beside the weights the chip then holds the
    # gradient program alone (at 667 M parameters the two moments are 5.3 GB
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
