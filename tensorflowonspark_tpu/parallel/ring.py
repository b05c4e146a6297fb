"""Sequence/context parallelism: ring attention and all-to-all (Ulysses) attention.

The reference has no attention or sequence-length concept at all (SURVEY
§5.7) — its workloads are CNNs and "scaling" means more data-parallel
workers.  For a TPU-native framework long context is first-class: sequences
are sharded over a ``"seq"`` mesh axis and attention runs either as

- :func:`ring_attention` — blockwise attention with online (running-max)
  softmax; key/value blocks rotate around the ring of devices via
  ``ppermute`` so each device only ever materializes its local
  ``S/P x S/P`` score block.  Memory per device is O(S/P), enabling
  sequences P times longer than a single device could hold.  The ppermute
  rides ICI neighbor links — the topology ring attention was designed for.
- :func:`ulysses_attention` — ``all_to_all`` re-shards from sequence-sharded
  to head-sharded, runs ordinary full attention locally, and switches back.
  Cheaper at moderate S (two all_to_alls instead of P ppermutes) but caps the
  parallelism degree at the head count.

Both are exact (not approximations) and match full attention to numerical
tolerance; see ``tests/test_ring.py``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free


def _block_attention(q, k, v, o, m, l, q_offset, kv_offset, causal, scale):
    """One blockwise-attention accumulation step with online softmax.

    Shapes: q [B,Sq,H,D], k/v [B,Sk,H,D]; running state o [B,Sq,H,D],
    m/l [B,Sq,H].  Offsets are the global sequence positions of the local
    q block and the currently-held kv block (for causal masking).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B,H,Sq,Sk]
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        kv_pos = kv_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= kv_pos[None, :]
        s = jnp.where(mask[None, None, :, :], s, NEG_INF)
    m_blk = jnp.moveaxis(s.max(axis=-1), 1, -1)       # [B,Sq,H]
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(s - jnp.moveaxis(m_new, -1, 1)[..., None])  # [B,H,Sq,Sk]
    if causal:
        # fully-masked rows: keep their contribution exactly zero
        p = jnp.where(mask[None, None, :, :], p, 0.0)
    alpha = jnp.exp(m - m_new)                        # [B,Sq,H]
    l_new = l * alpha + jnp.moveaxis(p.sum(axis=-1), 1, -1)
    o_new = o * alpha[..., None] + jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o_new, m_new, l_new


def _ring_shard_fn(q, k, v, axis_name, causal, scale, vary_axes):
    """Per-device body: rotate kv blocks around the ring, accumulating
    blockwise attention with online softmax."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    batch, sq, heads, dim = q.shape
    sk = k.shape[1]
    o = jnp.zeros((batch, sq, heads, dim), dtype=jnp.float32)
    m = jnp.full((batch, sq, heads), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((batch, sq, heads), dtype=jnp.float32)
    # The loop carry must be device-varying-typed from the start (shard_map
    # vma typing): the accumulators are per-shard state.
    o, m, l = (jax.lax.pcast(x, vary_axes, to="varying") for x in (o, m, l))
    q32 = q.astype(jnp.float32)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def body(i, carry):
        o, m, l, k_blk, v_blk = carry
        kv_idx = (my_idx - i) % axis_size  # ring rotation: who made this block
        o, m, l = _block_attention(
            q32, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32),
            o, m, l,
            q_offset=my_idx * sq, kv_offset=kv_idx * sk,
            causal=causal, scale=scale)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, m, l, k_blk, v_blk

    o, m, l, _, _ = jax.lax.fori_loop(0, axis_size, body, (o, m, l, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _no_window(window, what):
    """The sequence-parallel contractions know the causal triangle alone."""
    if window is not None:
        raise ValueError(
            "{} has no window (window={}): run a layer that has one under "
            "attention=\"flash\" or \"full\"".format(what, window))


def ring_attention(q, k, v, mesh, seq_axis="seq", batch_axis="data",
                   causal=False, scale=None, window=None):
    """Exact multi-head attention over sequence-sharded q/k/v.

    Args:
      q, k, v: [batch, seq, heads, head_dim] arrays (may be bf16), logically
        global; sharded (or shardable) as [batch_axis, seq_axis, None, None].
      mesh: the device mesh; must contain ``seq_axis``.
      causal: apply causal masking using *global* sequence positions.
      scale: score scale (default 1/sqrt(head_dim)).
      window: refused (``ValueError``) unless None: a layer whose queries
        read their last ``window`` keys does not run sequence-parallel.

    Returns an array shaped/sharded like ``q``.
    """
    _no_window(window, "ring attention")
    assert seq_axis in mesh.axis_names, (
        "mesh {} has no {!r} axis".format(dict(mesh.shape), seq_axis))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    batch = batch_axis if batch_axis in mesh.axis_names else None
    spec = P(batch, seq_axis, None, None)
    vary_axes = tuple(a for a in (batch, seq_axis) if a is not None)
    fn = jax.shard_map(
        functools.partial(_ring_shard_fn, axis_name=seq_axis,
                          causal=causal, scale=scale, vary_axes=vary_axes),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _ulysses_shard_fn(q, k, v, axis_name, causal, scale, impl="einsum"):
    """Per-device body: all_to_all seq->heads, local full attention, back.

    ``impl="flash"`` runs the local attention through the pallas
    FlashAttention kernels (memory-linear in S — the einsum path
    materializes a per-device [B, H/P, S, S] score tensor); unlike ring
    attention the local softmax is complete, so no cross-device statistics
    are needed and the kernel composes directly.
    """

    def seq_to_heads(x):  # [B, S/P, H, D] -> [B, S, H/P, D]
        x = jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                               tiled=True)
        return x

    def heads_to_seq(x):  # [B, S, H/P, D] -> [B, S/P, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if impl == "flash":
        from tensorflowonspark_tpu.ops import flash_attention

        og = flash_attention(qg, kg, vg, causal=causal, scale=scale)
        return heads_to_seq(og)
    s = jnp.einsum("bqhd,bkhd->bhqk", qg.astype(jnp.float32),
                   kg.astype(jnp.float32)) * scale
    if causal:
        seq = qg.shape[1]
        mask = jnp.tril(jnp.ones((seq, seq), dtype=bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    og = jnp.einsum("bhqk,bkhd->bqhd", p, vg.astype(jnp.float32))
    return heads_to_seq(og.astype(q.dtype))


def ulysses_attention(q, k, v, mesh, seq_axis="seq", batch_axis="data",
                      causal=False, scale=None, impl="einsum", window=None):
    """All-to-all ("Ulysses"-style) sequence-parallel attention.

    Requires ``heads % mesh.shape[seq_axis] == 0``; each device attends over
    the full sequence for its slice of heads, with two all_to_alls doing the
    re-sharding.  Same signature/semantics as :func:`ring_attention`.
    """
    _no_window(window, "ulysses attention")
    assert q.shape[2] % mesh.shape[seq_axis] == 0, (
        "heads {} not divisible by seq-parallel degree {}".format(
            q.shape[2], mesh.shape[seq_axis]))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    batch = batch_axis if batch_axis in mesh.axis_names else None
    spec = P(batch, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(_ulysses_shard_fn, axis_name=seq_axis,
                          causal=causal, scale=scale, impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # flash: pallas_call emits ShapeDtypeStructs without vma annotations
        check_vma=(impl != "flash"))
    return fn(q, k, v)


def reference_attention(q, k, v, causal=False, scale=None, window=None):
    """Plain full attention (for tests and single-device fallback);
    ``window`` (with ``causal``): query ``t`` reads the keys ``t - window <
    s <= t``, the band as a mask."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        seq_q, seq_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((seq_q, seq_k), dtype=bool))
        if window is not None:
            mask = jnp.logical_and(mask, jnp.logical_not(jnp.tril(
                jnp.ones((seq_q, seq_k), dtype=bool), -window)))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
