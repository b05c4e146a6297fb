"""Device-mesh construction: the TPU-native replacement for ``TF_CONFIG``.

Where the reference assembled a ``{"cluster": {"worker": [...]}}`` spec for
``tf.distribute`` strategies (reference ``TFSparkNode.py:264-286``), the TPU
framework arranges all devices of the jax world into a named
``jax.sharding.Mesh``.  Standard axis names:

- ``"data"``    — batch (data parallel; allreduce of grads rides ICI)
- ``"fsdp"``    — parameter sharding combined with data parallel
- ``"tensor"``  — tensor/model parallelism within a layer
- ``"seq"``     — sequence/context parallelism (ring attention)
- ``"expert"``  — expert parallelism (MoE)

Sync data parallelism — the reference's ``MultiWorkerMirroredStrategy`` path
(SURVEY §2.4) — is simply a ``("data",)`` mesh with batch-sharded inputs.
"""

import dataclasses
import logging
import math

logger = logging.getLogger(__name__)

AXIS_ORDER = ("pipe", "data", "fsdp", "seq", "expert", "tensor")


@dataclasses.dataclass
class MeshSpec:
    """Logical mesh shape; -1 for at most one axis means "fill with the
    remaining devices" (like a reshape wildcard).

    The default (``data=-1``) is pure sync data parallelism — capability
    parity with the reference's only first-class strategy (SURVEY §2.4).
    """

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1

    def resolve(self, num_devices):
        sizes = {axis: getattr(self, axis) for axis in AXIS_ORDER}
        wild = [a for a, s in sizes.items() if s == -1]
        assert len(wild) <= 1, "at most one mesh axis may be -1, got {}".format(wild)
        known = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            assert num_devices % known == 0, (
                "cannot fill axis {!r}: {} devices not divisible by {}".format(
                    wild[0], num_devices, known))
            sizes[wild[0]] = num_devices // known
        total = math.prod(sizes.values())
        assert total == num_devices, (
            "mesh {} uses {} devices but {} are available".format(
                sizes, total, num_devices))
        return sizes


def build_mesh(spec=None, devices=None, keep_trivial_axes=False):
    """Build a ``jax.sharding.Mesh`` over all devices of the jax world.

    Args:
      spec: a :class:`MeshSpec`, a ``{axis: size}`` dict, or None (pure DP).
      devices: device list override (defaults to ``jax.devices()`` — the
        global roster across all processes after ``jax.distributed``).
      keep_trivial_axes: keep size-1 axes in the mesh (useful when sharding
        specs name them); otherwise they are dropped for readability.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = MeshSpec()
    elif isinstance(spec, dict):
        spec = MeshSpec(**spec)
    sizes = spec.resolve(len(devices))
    axes = [a for a in AXIS_ORDER if keep_trivial_axes or sizes[a] > 1]
    if not axes:
        axes = ["data"]
    import numpy as np

    shape = [sizes[a] for a in axes]
    mesh = Mesh(np.asarray(devices).reshape(shape), tuple(axes))
    logger.info("built mesh %s over %d %s devices",
                dict(zip(axes, shape)), len(devices), devices[0].platform)
    return mesh


def batch_sharding(mesh, extra_dims=0):
    """NamedSharding that shards the leading (batch) dim over every
    data-like mesh axis present (``data`` and ``fsdp``), replicating the rest.

    ``extra_dims`` appends unsharded trailing dims to the spec explicitly.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    spec = PartitionSpec(batch_axes if batch_axes else None,
                         *([None] * extra_dims))
    return NamedSharding(mesh, spec)


def scan_batch_sharding(mesh):
    """NamedSharding for a **stacked group** of batches with shape
    ``(k, batch, ...)``: the leading scan dim is unsharded (every device
    steps through all k microbatches in lock-step via ``lax.scan``), the
    second dim is batch-sharded like :func:`batch_sharding`.

    Used by the K-steps-per-dispatch path
    (:meth:`~tensorflowonspark_tpu.train.Trainer.multi_step`), which
    amortizes per-step host dispatch and transfer overhead."""
    from jax.sharding import NamedSharding, PartitionSpec

    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    return NamedSharding(
        mesh, PartitionSpec(None, batch_axes if batch_axes else None))


def replicated(mesh):
    """Fully-replicated NamedSharding on this mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def local_batch_size(mesh, global_batch_size):
    """This process's share of a globally-sharded batch dimension."""
    import jax

    total = 1
    for a in ("data", "fsdp"):
        if a in mesh.axis_names:
            total *= mesh.shape[a]
    assert global_batch_size % total == 0, (
        "global batch {} not divisible by data-parallel degree {}".format(
            global_batch_size, total))
    # Every process hosts an equal slice of the mesh devices.
    procs = jax.process_count()
    assert global_batch_size % procs == 0, (
        "global batch {} not divisible by process count {}; each host "
        "contributes an equal local shard".format(global_batch_size, procs))
    return global_batch_size // procs
