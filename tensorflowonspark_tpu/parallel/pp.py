"""Pipeline parallelism over the ``pipe`` mesh axis (GPipe schedule).

The last of the classic parallelism modes (SURVEY §2.4; the reference ships
none — this is capability beyond parity), built the TPU way: no
send/recv rank programs, just a single SPMD program under ``shard_map``
where every device holds ONE stage's weights (stacked params sharded over
``pipe``) and activations hop stage-to-stage with ``lax.ppermute`` each
tick.  Because the whole schedule is pure traced jax, ``jax.grad``
differentiates straight through the permutes — backward pipelining comes
for free, and XLA overlaps the per-tick compute with the ICI hop.

Schedule: GPipe with ``n_micro`` microbatches over ``S`` stages; the loop
runs ``n_micro + S - 1`` ticks, stage 0 injecting microbatch ``t`` at tick
``t`` and the last stage emitting microbatch ``t - (S-1)`` at tick ``t``.
Bubble fraction is ``(S-1)/(n_micro+S-1)`` — pick ``n_micro >= 4*S`` for
>80% pipeline utilization.

Contract: homogeneous stages — ``stage_fn(stage_params, x) -> y`` with
``y.shape == x.shape`` (the transformer-block shape-preserving case).
Heterogeneous first/last layers (embed/unembed) run outside the pipeline.
"""

import functools
import logging

logger = logging.getLogger(__name__)


def stack_stage_params(params_list):
    """Stack per-stage parameter pytrees into one tree with a leading stage
    dim (what :func:`gpipe` consumes; shard that dim over ``pipe``)."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *params_list)


def stage_shardings(stacked_params, mesh, axis="pipe"):
    """NamedSharding tree placing the leading stage dim on ``axis`` —
    device ``i`` of the pipe axis holds exactly stage ``i``'s weights."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def one(x):
        return NamedSharding(
            mesh, PartitionSpec(axis, *([None] * (x.ndim - 1))))

    return jax.tree_util.tree_map(one, stacked_params)


def gpipe(stage_fn, stacked_params, microbatches, mesh, axis="pipe"):
    """Run ``stage_fn`` as an ``S``-stage GPipe pipeline over the mesh.

    Args:
      stage_fn: ``fn(stage_params, x) -> y`` with ``y.shape == x.shape``;
        traced once, executed by every pipe device on its own stage.
      stacked_params: pytree with leading dim ``S == mesh.shape[axis]``
        (see :func:`stack_stage_params`); shard with
        :func:`stage_shardings` (or let GSPMD move it).
      microbatches: ``[n_micro, micro_batch, ...]`` array — split your
        global batch with :func:`split_microbatches`.
      mesh: mesh containing ``axis``.

    Returns ``[n_micro, micro_batch, ...]`` outputs (replicated over
    ``axis``), differentiable end-to-end.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    if n_stages == 1:
        # degenerate pipe: plain sequential microbatching
        squeezed = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
        return jax.vmap(lambda x: stage_fn(squeezed, x))(microbatches)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)
    def run(params, inputs):
        # params: this stage's slice, leading dim 1 -> the stage's weights
        stage_params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(inputs[0])
        # shift activations one stage forward; the last stage's output wraps
        # to stage 0 where it is ignored (stage 0 always injects)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            prev_out, outputs = carry
            inject = jax.lax.cond(
                t < n_micro,
                lambda: jax.lax.dynamic_index_in_dim(
                    inputs, jnp.minimum(t, n_micro - 1), keepdims=False),
                lambda: zero)
            x = jnp.where(stage == 0, inject, prev_out)
            y = stage_fn(stage_params, x)
            # the last stage emits microbatch t-(S-1) at tick t
            emit_idx = t - (n_stages - 1)
            outputs = jax.lax.cond(
                jnp.logical_and(stage == n_stages - 1, emit_idx >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(emit_idx, 0), axis=0),
                lambda o: o,
                outputs)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outputs), None

        outputs0 = jnp.zeros_like(inputs)
        (final, outputs), _ = jax.lax.scan(
            tick, (zero, outputs0), jnp.arange(ticks))
        # only the last stage wrote real outputs; everyone else holds zeros
        # (out_specs=P() then hands back the psum'ed buffer, identical on
        # every device — inputs were replicated over any other axes)
        return jax.lax.psum(outputs, axis)

    return run(stacked_params, microbatches)


def split_microbatches(batch, n_micro):
    """``[global_batch, ...] -> [n_micro, global_batch/n_micro, ...]``."""
    import jax

    def one(x):
        assert x.shape[0] % n_micro == 0, (
            "batch {} not divisible into {} microbatches".format(
                x.shape[0], n_micro))
        return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

    return jax.tree_util.tree_map(one, batch)
