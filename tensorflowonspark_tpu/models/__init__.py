"""Model zoo for the example workloads (flax).

Covers the reference's example model families (SURVEY §2.6) rebuilt
TPU-first, plus a transformer LM (the long-context extension the TPU design
makes natural):

- :mod:`~tensorflowonspark_tpu.models.mnist`       — MNIST CNN
  (reference ``examples/mnist/keras/mnist_spark.py:14-20``)
- :mod:`~tensorflowonspark_tpu.models.resnet`      — ResNet56/CIFAR and
  ResNet50-v1.5/ImageNet (reference ``examples/resnet/resnet_model.py``,
  ``resnet_cifar_model.py``)
- :mod:`~tensorflowonspark_tpu.models.unet`        — U-Net segmentation
  (reference ``examples/segmentation/segmentation_spark.py:70-122``)
- :mod:`~tensorflowonspark_tpu.models.transformer` — decoder-only LM with
  full/ring/ulysses attention (sequence parallelism over the mesh), built
  from a description; :mod:`~tensorflowonspark_tpu.models.families` holds
  one module a published decoder family, each a description's author

The registry maps exported model names (checkpoint descriptors,
``checkpoint.export_model``) back to constructors so pipeline-transform
executors can rebuild a model from its name + config alone — the role
SavedModel's self-description played for the reference
(``pipeline.py:474-481``).
"""

_REGISTRY = {}


def register_model(name):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_model(name, **config):
    """Instantiate a registered model by name (used by pipeline transform)."""
    if name not in _REGISTRY:
        raise KeyError("unknown model {!r}; registered: {}".format(
            name, sorted(_REGISTRY)))
    return _REGISTRY[name](**config)


# Import for registration side effects.
from tensorflowonspark_tpu.models import (  # noqa: E402,F401
    linear, mnist, resnet, transformer, twotower, unet)
from tensorflowonspark_tpu.models import families  # noqa: E402,F401
