"""On-device image augmentation: per-row crop + horizontal flip inside jit.

The TPU-first half of the pre-decoded ImageNet path
(``examples/resnet/imagenet_input.predecode_shards``): the host ships the
stored ``store_px`` uint8 rows untouched (its only per-pixel work is one
contiguous memcpy into the batch) plus three tiny int vectors, and the crop
window + flip happen HERE, fused into the training step where they are
effectively free (a dynamic-slice and a reverse on data XLA already has
in registers on its way into the conv).

Host-side counterpart (same sampling, same semantics):
``imagenet_input.predecoded_reader(device_crop=False)``; equality is
tested in ``tests/test_imagenet_input.py``
(``TestPredecoded::test_device_crop_matches_host_crop``).
"""


def crop_and_flip(images, xs, ys, flips, size):
    """Per-row ``size``-crop + optional horizontal flip, vmapped.

    Args:
      images: ``[B, H, W, C]`` (any dtype; uint8 stays uint8 — cast/scale
        belongs to the model's normalize step).
      xs, ys: ``[B]`` int32 top-left corners (``0 <= x <= W - size``).
      flips: ``[B]`` int32/bool; nonzero rows flip left-right.
      size: static crop size.

    Returns ``[B, size, size, C]``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    def one(img, x, y, f):
        crop = lax.dynamic_slice(
            img, (y, x, 0), (size, size, img.shape[-1]))
        return lax.cond(f != 0, lambda c: c[:, ::-1, :], lambda c: c, crop)

    return jax.vmap(one)(images, jnp.asarray(xs, jnp.int32),
                         jnp.asarray(ys, jnp.int32),
                         jnp.asarray(flips, jnp.int32))
