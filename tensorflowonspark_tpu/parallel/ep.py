"""Expert parallelism: MoE FFN over the ``expert`` mesh axis, and the two
routers of :mod:`~tensorflowonspark_tpu.models.transformer`.

**Two routers.**

- :func:`_route`: grouped **top-1** Switch routing by softmax with a
  **capacity** (``capacity_factor * S / E`` slots an expert and batch row);
  tokens over capacity are dropped and ride the residual; dispatch and
  combine are dense one-hot tensors ``[G, S, E, C]`` (einsums on the MXU, no
  gathers).  ``MoEMlp`` and :func:`moe_ffn` use it.
- :func:`route_topk` with :func:`sort_pairs` and :func:`experts_ffn`:
  **top-k of E by sigmoid scores with a selection bias**, weights
  renormalised over the k chosen, or **by softmax scores without either**
  (the router's description says which), **no capacity and no token dropped
  at any imbalance**.  Dispatch is a sort of the (token, slot) pairs by expert, the
  experts' products (a SwiGLU's three, or the two of ``relu(.)**2``) are
  grouped matrix products
  (:mod:`~tensorflowonspark_tpu.ops.grouped_matmul`) over the stacked weights
  of the experts **held here** (a contiguous range of the E the router
  knows), and the pairs whose expert lives elsewhere contribute nothing: the layer returns its own
  experts' part of the sum, which is what one chip of an expert-parallel
  deployment computes before the exchange.  Buffers have the static
  worst-case size (every pair routed here); the work of the grouped
  products, of the row-wise passes between them
  (:mod:`~tensorflowonspark_tpu.ops.expert_gate`) and of the row movement
  around them (:mod:`~tensorflowonspark_tpu.ops.routed_rows`) follows the
  pairs that are.  ``TopKExperts`` uses it.  Nothing here stands
  in for the absent chips: on one chip there is no exchange.

**Two ways over the mesh** for the capacity router's layer (the last of the
classic parallelism modes to get an explicit implementation, SURVEY §2.4;
the reference ships none of them — like :mod:`.tp`/:mod:`.pp` this is
capability beyond parity), numerically identical:

1. **GSPMD** (:func:`ep_param_shardings`): shard the expert-stacked
   ``[E, ...]`` weights of :class:`~tensorflowonspark_tpu.models.transformer.MoEMlp`
   (and ``TopKExperts``' ``w1``/``w3``/``w2``) over ``expert`` and let XLA
   partition the dense dispatch/combine einsums — the all-to-alls fall out
   of the partitioner.  Zero model changes.

2. **shard_map** (:func:`moe_ffn`): the DeepSpeed-MoE/GShard schedule written
   explicitly — tokens (groups) sharded over ``expert``, expert weights
   sharded over ``expert``, and two ``lax.all_to_all`` hops:

       dispatch (local)                 [G_loc, E, C, D]
       all_to_all  split E, concat G -> [G,     E_loc, C, D]   # tokens->owners
       expert FFN  (local weights)      [G,     E_loc, C, D]
       all_to_all  split G, concat E -> [G_loc, E, C, D]       # results->home
       combine (local)

   Per-device FFN compute is ``1/ep`` of the dense layer and the only
   cross-device traffic is the two all-to-alls riding ICI — the layout the
   "How to Scale Your Model" MoE chapter prescribes.  Routing stays local
   (each group routes its own tokens), so there is no global shuffle.

The module-level contract mirrors :mod:`.tp`: pure functions over params +
mesh, no hidden state, everything traced once under jit.
"""

import logging
import re

logger = logging.getLogger(__name__)

# Expert-stacked parameter leaves of models.transformer.MoEMlp (w1, w2 and
# their biases) and TopKExperts (w1, w3, w2): the leading dim is the expert
# dim for all of them.
MOE_PARAM_RE = re.compile(r"(^|/)moe/(w1|w2|w3|b1|b2)$")


def ep_param_shardings(params, mesh, axis="expert", pattern=MOE_PARAM_RE):
    """NamedSharding tree: expert-stacked leaves (leading ``E`` dim) shard
    over ``axis``; everything else replicates on it.

    Thin, intentionally: the generic rule engine is
    :func:`~tensorflowonspark_tpu.parallel.tp.tp_param_shardings`; this
    wrapper just fixes the axis + rule set for the MoE layout so call sites
    read as expert parallelism."""
    from tensorflowonspark_tpu.parallel import tp as tp_mod

    pat = pattern.pattern if hasattr(pattern, "pattern") else pattern
    return tp_mod.tp_param_shardings(
        params, mesh, axis=axis, rules=[(pat, 0), ("", None)])


def _route(x, router_kernel, router_bias, num_experts, capacity):
    """Grouped top-1 routing (identical math to ``MoEMlp.__call__``):
    returns ``(dispatch [G,S,E,C], combine_prob [G,S], aux_stats)``.

    fp32 router regardless of compute dtype — routing decisions must not
    flip with bf16 rounding."""
    import jax
    import jax.numpy as jnp

    logits = x.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    logits = logits + router_bias.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # [G, S, E]
    expert_idx = jnp.argmax(probs, axis=-1)                  # [G, S]
    expert_prob = jnp.max(probs, axis=-1)
    expert_onehot = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(expert_onehot, axis=1) * expert_onehot
    pos = pos.sum(axis=-1) - 1                               # [G, S]
    keep = (pos < capacity).astype(x.dtype)
    pos_onehot = jax.nn.one_hot(pos, capacity, dtype=x.dtype)
    dispatch = (expert_onehot.astype(x.dtype) * keep[..., None])[..., None] \
        * pos_onehot[:, :, None, :]                          # [G, S, E, C]
    # Switch load-balance ingredients (summed/averaged by the caller so the
    # shard_map path can psum them into the global value)
    fraction = expert_onehot.astype(jnp.float32).mean(axis=(0, 1))
    mean_prob = probs.mean(axis=(0, 1))
    return dispatch, expert_prob, (fraction, mean_prob)


def route_topk(x, router_kernel, expert_bias, experts_per_token,
               norm_topk=True, scaling=1.0, score="sigmoid"):
    """Top-k routing by the router's scores, with or without a selection
    bias.

    ``x [T, D]``, ``router_kernel [D, E]``, ``expert_bias [E]`` or None ->
    ``(sel [T, k] int32, weights [T, k] float32)``.  The scores ``s`` of
    ``x W_r`` are taken in float32 at full precision (a selection must not
    flip with the compute dtype or the TPU's default one-pass products):
    ``score="sigmoid"`` scores each expert by itself (``s = sigmoid(x
    W_r)``), ``"softmax"`` all ``E`` against each other (``s = softmax(x
    W_r)`` over the experts, held here or not).  ``sel = top_k(s + b)``:
    the bias, where there is one, enters the selection only and gets no
    gradient.  ``weights = s[sel]``, divided by their sum (+1e-6) where
    ``norm_topk``, times ``scaling``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    logits = jnp.matmul(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)                     # [T, E]
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError("unknown router score {!r}".format(score))
    chosen_by = scores
    if expert_bias is not None:
        chosen_by = scores + lax.stop_gradient(
            expert_bias.astype(jnp.float32))
    _, sel = lax.top_k(chosen_by, experts_per_token)
    weights = jnp.take_along_axis(scores, sel, axis=-1)
    if norm_topk:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return sel.astype(jnp.int32), weights * scaling


def sort_pairs(sel, first, count):
    """The (token, slot) pairs of ``sel [T, k]`` in the order the grouped
    products want: pairs whose expert is one of the ``count`` held from
    ``first`` on, expert by expert, then every other pair.

    Returns ``(order [P], inverse [P], group_sizes [count], n_local)`` with
    ``P = T * k``: ``order[i]`` is the pair (``token * k + slot``) at sorted
    position ``i``, ``inverse`` its inverse permutation, ``group_sizes`` the
    pairs of each held expert, ``n_local`` their sum."""
    import jax.numpy as jnp
    from jax import lax

    flat = sel.reshape(-1)
    here = (flat >= first) & (flat < first + count)
    key = jnp.where(here, flat - first, count)
    iota = lax.iota(jnp.int32, flat.shape[0])
    _, order = lax.sort((key, iota), num_keys=1)             # stable
    _, inverse = lax.sort((order, iota), num_keys=1)
    group_sizes = (key[:, None] == jnp.arange(count, dtype=jnp.int32)).sum(
        axis=0, dtype=jnp.int32)
    return order, inverse, group_sizes, group_sizes.sum()


def _dispatch(x, src, idx, n_local):
    """Tokens into expert order: ``xs[i] = x[src[i]]`` for ``i < n_local``
    (``src [P]`` the token of each sorted position, ``idx [T, k]`` the sorted
    position of each (token, slot) pair); rows from ``n_local`` on are
    unspecified.  The backward pass is the gather-and-sum ``d_x[t] = sum_j
    [idx[t, j] < n_local] g[idx[t, j]]``, not the scatter-add that
    differentiating a take would give."""
    import jax

    from tensorflowonspark_tpu.ops.routed_rows import (gather_rows,
                                                        gather_sum_rows)

    @jax.custom_vjp
    def dispatch(x, src, idx, n_local):
        return gather_rows(x, src, n_local)

    def fwd(x, src, idx, n_local):
        return dispatch(x, src, idx, n_local), (idx, n_local)

    def bwd(residual, g):
        idx, n_local = residual
        return gather_sum_rows(g, idx, n_local), None, None, None

    dispatch.defvjp(fwd, bwd)
    return dispatch(x, src, idx, n_local)


def _combine(ys, weights, order, idx, n_local):
    """Experts' rows back to token order: ``y[t] = sum_j [idx[t, j] <
    n_local] weights[t, j] * ys[idx[t, j]]`` in float32, written once; rows
    of ``ys`` from ``n_local`` on are not read.  The backward pass gathers
    ``dy`` into expert order once, scaled by the pair's weight for ``d_ys``
    (unspecified from ``n_local`` on) and dotted with ``ys`` for the weights'
    gradient (0 for a pair routed elsewhere)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tensorflowonspark_tpu.ops.routed_rows import (gather_rows,
                                                        gather_sum_rows)

    @jax.custom_vjp
    def combine(ys, weights, order, idx, n_local):
        return gather_sum_rows(ys, idx, n_local, weights=weights)

    def fwd(ys, weights, order, idx, n_local):
        return (combine(ys, weights, order, idx, n_local),
                (ys, weights, order, idx, n_local))

    def bwd(residual, dy):
        ys, weights, order, idx, n_local = residual
        # one float a pair moves into expert order, and back, as the payload
        # of a sort by the pair's position there (0.2 ms for 131,072 pairs
        # on a v5e; XLA's gather of as many scalars takes 1.2)
        _, scale = lax.sort((idx.reshape(-1), weights.reshape(-1)),
                            num_keys=1)
        d_ys, dots = gather_rows(dy, order // idx.shape[1], n_local,
                                 scale=scale, dot_with=ys)
        _, dots = lax.sort((order, dots), num_keys=1)
        d_weights = jnp.where(idx < n_local, dots.reshape(idx.shape), 0.0)
        return d_ys, d_weights.astype(weights.dtype), None, None, None

    combine.defvjp(fwd, bwd)
    return combine(ys, weights, order, idx, n_local)


def _up(xs, w1, w3, group_sizes, n_local, act):
    """The "up" half of the held experts on the sorted rows: ``silu(xs W_1)
    * (xs W_3)``, or ``relu(xs W_1) ** 2`` (``w3`` None), rows from
    ``n_local`` on unspecified.  Both directions are written here so that
    every pass over a sorted buffer stops at ``n_local``
    (:mod:`~tensorflowonspark_tpu.ops.expert_gate`): differentiating the
    plain form leaves the gate, its backward and the sum of the two products'
    input gradients to fusions that pass over the whole buffer.  Kept for
    the backward are ``xs`` and the two products, as differentiating
    keeps them."""
    import jax

    from tensorflowonspark_tpu.ops.expert_gate import (add_rows, gate,
                                                        gate_grad)
    from tensorflowonspark_tpu.ops.grouped_matmul import (
        grouped_matmul, grouped_matmul_grads)

    def fwd(xs, w1, w3, group_sizes, n_local):
        h1 = grouped_matmul(xs, w1, group_sizes)
        h3 = None if w3 is None else grouped_matmul(xs, w3, group_sizes)
        return (gate(h1, h3, n_local, act),
                (xs, w1, w3, h1, h3, group_sizes, n_local))

    @jax.custom_vjp
    def up(xs, w1, w3, group_sizes, n_local):
        return fwd(xs, w1, w3, group_sizes, n_local)[0]

    def bwd(residual, d_h):
        xs, w1, w3, h1, h3, group_sizes, n_local = residual
        d_h1, d_h3 = gate_grad(h1, h3, d_h, n_local, act)
        d_xs, d_w1 = grouped_matmul_grads(xs, w1, group_sizes, d_h1)
        d_w3 = None
        if w3 is not None:
            d_xs3, d_w3 = grouped_matmul_grads(xs, w3, group_sizes, d_h3)
            d_xs = add_rows(d_xs, d_xs3, n_local)
        return d_xs, d_w1, d_w3, None, None

    up.defvjp(fwd, bwd)
    return up(xs, w1, w3, group_sizes, n_local)


def experts_ffn(x, sel, weights, w1, w3, w2, first, dtype=None,
                act="swiglu"):
    """The held experts' part of a top-k expert layer.

    ``x [T, D]`` tokens, ``sel``/``weights [T, k]`` from :func:`route_topk`,
    ``w1``/``w3 [H, D, F]`` and ``w2 [H, F, D]`` the stacked weights of the
    ``H`` experts held here, experts ``first .. first + H - 1`` of the
    router's.  ``act="swiglu"``: an expert is ``(silu(x W_1) * (x W_3))
    W_2``; ``"relu2"``: two matrices, ``relu(x W_1) ** 2 W_2``, and ``w3``
    is None (two grouped products for three; the row movement, the sort and
    the products themselves are the same).  Returns ``(y [T, D], load)``:
    ``y = sum over the token's slots whose expert is held of weight *
    expert_e(x)``, and ``load``, the
    token-slot counts of this call (int32 scalars ``slots_total``,
    ``slots_local``, ``expert_load_max``; float32 ``expert_load_mean``)
    with the row tiles of a sorted buffer that the gate's passes visit of
    those there are (int32 ``gate_tiles_live``, ``gate_tiles_total``).

    No pair is dropped: the sorted buffers hold all ``T * k`` pairs (the
    worst case, every pair routed here), the ``n_local`` pairs of held
    experts first.  **Nothing reads a sorted buffer behind ``n_local``, in
    either direction, so the rows there may hold anything** (the kernels
    never write them, and nothing masks them): the row movement
    (:mod:`~tensorflowonspark_tpu.ops.routed_rows`: pallas kernels on a TPU
    that fetch a row only where its sorted position lies in front of
    ``n_local``, XLA's take with a mask elsewhere) stops there by
    construction, the three grouped products
    (:func:`~tensorflowonspark_tpu.ops.grouped_matmul.grouped_matmul`: pallas
    kernels on a TPU, ``jax.lax.ragged_dot`` elsewhere) by their tile map,
    and the row-wise passes between them (the gate, its backward and the sum
    of the two "up" products' input gradients,
    :mod:`~tensorflowonspark_tpu.ops.expert_gate`: pallas kernels on a TPU,
    the plain ``jax.numpy`` form elsewhere) by theirs.  ``slots_local /
    slots_total`` is therefore the share of the rows that is fetched, and 1
    minus it the share that is skipped; ``gate_tiles_live /
    gate_tiles_total`` is that share rounded up to a row tile."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.ops.expert_gate import row_tile
    from tensorflowonspark_tpu.ops.grouped_matmul import grouped_matmul

    dtype = dtype or x.dtype
    tokens, k = sel.shape
    held = w1.shape[0]
    with jax.named_scope("route"):
        order, inverse, group_sizes, n_local = sort_pairs(sel, first, held)
    idx = inverse.reshape(tokens, k)
    with jax.named_scope("dispatch"):
        xs = _dispatch(x.astype(dtype), order // k, idx, n_local)
    if act not in ("swiglu", "relu2"):
        raise ValueError("unknown expert form {!r}".format(act))
    with jax.named_scope("experts"):
        h = _up(xs, w1.astype(dtype),
                None if act == "relu2" else w3.astype(dtype), group_sizes,
                n_local, act)
        ys = grouped_matmul(h, w2.astype(dtype), group_sizes)
    with jax.named_scope("combine"):
        y = _combine(ys, weights, order, idx, n_local)
    tile = row_tile(tokens * k, w1.shape[2], dtype)
    load = {"slots_total": jnp.asarray(tokens * k, jnp.int32),
            "slots_local": n_local,
            "expert_load_max": group_sizes.max(),
            "expert_load_mean": n_local.astype(jnp.float32) / held,
            "gate_tiles_live": (n_local + tile - 1) // tile,
            "gate_tiles_total": jnp.asarray(tokens * k // tile, jnp.int32)}
    return y, load


def moe_ffn(x, params, mesh, num_experts, capacity_factor=1.25,
            axis="expert", dtype=None, batch_axes=None):
    """Grouped top-1 MoE FFN with explicit expert parallelism.

    Args:
      x: ``[G, S, D]`` activations; the leading group dim is sharded over
        ``batch_axes`` inside the kernel (``G`` divisible by their product).
        The sequence dim is whole inside the kernel (routing's capacity
        cumsum is over the full sequence); a seq-sharded input is gathered
        at the kernel boundary and re-scattered after.
      params: dict with ``router/kernel [D,E]``, ``router/bias [E]``,
        ``w1 [E,D,H]``, ``b1 [E,H]``, ``w2 [E,H,D]``, ``b2 [E,D]`` —
        exactly ``MoEMlp``'s layout (pass
        ``flax_params["moe"]`` + ``flax_params["router"]`` leaves).
      mesh: the device mesh; ``axis`` must be one of its axes.
      num_experts: E (must be divisible by ``mesh.shape[axis]``).
      batch_axes: mesh axes the group dim is sharded over — pass the SAME
        axes the caller's batch sharding uses (e.g. ``("data", "fsdp",
        "expert")``) so the kernel keeps data parallelism instead of
        all-gathering the batch onto every expert shard and redoing the
        FFN per data shard.  Default ``(axis,)`` (pure EP).  ``axis`` is
        appended automatically when absent — the two ``all_to_all`` hops
        ride it, so the group dim must be partitioned over it.

    Returns:
      ``(y [G,S,D], aux_loss scalar)`` — numerically identical to the dense
      GSPMD path (equality-tested on a CPU mesh, ``tests/test_parallel.py``).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    if batch_axes is None:
        batch_axes = (axis,)
    elif isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    else:
        batch_axes = tuple(batch_axes)
    if axis not in batch_axes:
        # the two all_to_alls ride ``axis``, so the group dim must be
        # partitioned over it inside the kernel; appending it is a no-op
        # for the caller (shard_map re-lays out the input to in_specs)
        batch_axes = batch_axes + (axis,)
    ep = mesh.shape[axis]
    group_shards = 1
    for a in batch_axes:
        group_shards *= mesh.shape[a]
    assert num_experts % ep == 0, (
        "num_experts {} not divisible by expert axis size {}".format(
            num_experts, ep))
    assert x.shape[0] % group_shards == 0, (
        "group dim {} not divisible by the {} shards of batch_axes {} (the "
        "leading dim must shard over them)".format(
            x.shape[0], group_shards, batch_axes))
    dtype = dtype or x.dtype
    seq = x.shape[1]
    capacity = max(int(capacity_factor * seq / num_experts), 1)

    def local(xs, rk, rb, w1, b1, w2, b2):
        # xs: [G_loc, S, D]; w1/b1/w2/b2 carry E_loc on dim 0
        dispatch, expert_prob, (fraction, mean_prob) = _route(
            xs, rk, rb, num_experts, capacity)
        expert_in = jnp.einsum("gsec,gsd->gecd", dispatch, xs)
        # tokens -> expert owners: split the E dim over the axis, gather all
        # groups (tiled: concat, not stack)
        expert_in = lax.all_to_all(expert_in, axis, split_axis=1,
                                   concat_axis=0, tiled=True)
        h = jnp.einsum("gecd,edh->gech", expert_in, w1.astype(dtype))
        h = jax.nn.gelu(h + b1.astype(dtype)[:, None])
        out = jnp.einsum("gech,ehd->gecd", h, w2.astype(dtype))
        out = out + b2.astype(dtype)[:, None]
        # results -> home shard of each group
        out = lax.all_to_all(out, axis, split_axis=0, concat_axis=1,
                             tiled=True)
        combine = dispatch * expert_prob.astype(dtype)[..., None, None]
        y = jnp.einsum("gsec,gecd->gsd", combine, out)
        # global Switch aux: every shard routed its own (equal-size) slice
        # of the groups, so the global fraction/mean_prob are the means
        # across every axis the group dim is sharded over
        fraction = lax.pmean(fraction, batch_axes)
        mean_prob = lax.pmean(mean_prob, batch_axes)
        aux = num_experts * jnp.sum(fraction * mean_prob)
        return y, aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes), P(), P(), P(axis), P(axis), P(axis),
                  P(axis)),
        out_specs=(P(batch_axes), P()))
    return fn(x, params["router"]["kernel"], params["router"]["bias"],
              params["w1"], params["b1"], params["w2"], params["b2"])


def merge_ep_shardings(base_shardings, params, mesh, axis="expert",
                       pattern=MOE_PARAM_RE):
    """Overlay expert parallelism on an existing sharding layout.

    ``base_shardings`` (e.g. replicated, or :func:`..fsdp.tree_shardings`)
    keeps every leaf EXCEPT the expert-stacked MoE weights, which take the
    ``axis``-on-dim-0 spec from :func:`ep_param_shardings` — the merged
    tree is the canonical fsdp-everything + expert-for-experts layout
    (used by ``__graft_entry__``'s moe/fsdp/ep dryrun phase and the
    transformer example's ``--expert`` mode)."""
    import jax

    from tensorflowonspark_tpu.parallel import tp as tp_mod

    ep_tree = ep_param_shardings(params, mesh, axis=axis, pattern=pattern)
    pat = pattern if hasattr(pattern, "search") else re.compile(pattern)

    def pick(path, base, ep_leaf):
        return ep_leaf if pat.search(tp_mod._param_path(path)) else base

    return jax.tree_util.tree_map_with_path(pick, base_shardings, ep_tree)
