"""Plain reference for ``resnet50_v15``: forward, loss, gradients and SGD with
momentum in straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``.  No kernels, no flax, nothing of the program.

Follows He et al. 2015 with the v1.5 stride placement (stride 2 in the 3x3 of
the first bottleneck of stages 2-4), as torchvision's ``resnet50`` and the
MLPerf training reference do.  Departures, each because the configuration as
run says so (``benchmark/configs/resnet50_v15.json``):

- ``stem == "s2d"``: the 7x7/2 stem is computed as a 4x4/1 convolution over
  the space-to-depth input (the weights are drawn as a 7x7 kernel and folded;
  the fold is exact, but the 15 taps it adds start at zero and *are trained*,
  so a run of steps is that of the 4x4x12 kernel);
- convolutions and the 3x3/2 max-pool pad ``SAME`` as XLA defines it (the
  odd pixel goes to the bottom/right), not by a fixed 1;
- 1001 classes (class 0 is background), label smoothing 0.1, L2 1e-4 on
  kernels of rank > 1 inside the loss (the repo example's loss);
- BatchNorm momentum 0.9, epsilon 1e-5, biased batch variance in the running
  average too.

``precision`` selects the arithmetic: ``"float32"`` is the reference;
``"fp8"`` is the **control** (both operands of every convolution and matrix
product rounded to float8_e4m3 under a per-tensor scale, straight-through in
the backward pass): the nearest precision below the bfloat16 the
configuration states, and the step a later change would be tempted by.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references._common import key as _key, memo as _memo, \
    operand as _operand

CHANNEL_MEANS = (123.68, 116.779, 103.939)
_HI = lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# Weights from the seed, in this file's own naming
# ---------------------------------------------------------------------------

def block_plan(cfg):
    """[(name, cin, filters, stride, has_proj)] for every bottleneck."""
    plan, cin = [], cfg["num_filters"]
    n = 0
    for i, count in enumerate(cfg["stage_sizes"]):
        f = cfg["num_filters"] * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            plan.append(("block%d" % n, cin, f, stride, cin != 4 * f or
                         stride != 1))
            cin = 4 * f
            n += 1
    return plan


def _fold_s2d(kernel7):
    """(7, 7, C, F) stride-2 SAME kernel -> the equivalent (4, 4, 4C, F)
    stride-1 kernel over 2x2 space-to-depth input padded ((1, 2), (1, 2))."""
    kh, kw, c, f = kernel7.shape
    k = jnp.pad(kernel7, ((0, 1), (0, 1), (0, 0), (0, 0)))
    k = k.reshape(4, 2, 4, 2, c, f).transpose(0, 2, 1, 3, 4, 5)
    return k.reshape(4, 4, 4 * c, f)


def init_weights(cfg, seed):
    """(params, stats): dicts name -> float32 array, made on the device in
    one jitted call.  He-normal kernels, BatchNorm scale 1 (0.25 on the last
    of each block, so no branch is dead), running mean/variance drawn so
    that inference through them is not the identity."""
    plan = block_plan(cfg)

    def make(key):
        params, stats = {}, {}
        counter = [0]

        def nxt():
            counter[0] += 1
            return jax.random.fold_in(key, counter[0])

        def conv(name, kh, kw, cin, cout):
            std = np.sqrt(2.0 / (kh * kw * cin))
            params[name] = std * jax.random.normal(
                nxt(), (kh, kw, cin, cout), jnp.float32)

        def bn(name, c, scale=1.0):
            params[name + "/scale"] = jnp.full((c,), scale, jnp.float32)
            params[name + "/bias"] = 0.1 * jax.random.normal(
                nxt(), (c,), jnp.float32)
            stats[name + "/mean"] = 0.1 * jax.random.normal(
                nxt(), (c,), jnp.float32)
            stats[name + "/var"] = jax.random.uniform(
                nxt(), (c,), jnp.float32, 0.5, 1.5)

        conv("stem/conv", 7, 7, 3, cfg["num_filters"])
        if cfg["stem"] == "s2d":
            params["stem/conv"] = _fold_s2d(params["stem/conv"])
        bn("stem/bn", cfg["num_filters"])
        for name, cin, f, _, proj in plan:
            conv(name + "/conv0", 1, 1, cin, f)
            bn(name + "/bn0", f)
            conv(name + "/conv1", 3, 3, f, f)
            bn(name + "/bn1", f)
            conv(name + "/conv2", 1, 1, f, 4 * f)
            bn(name + "/bn2", 4 * f, scale=0.25)
            if proj:
                conv(name + "/proj", 1, 1, cin, 4 * f)
                bn(name + "/projbn", 4 * f)
        width = 4 * cfg["num_filters"] * 2 ** (len(cfg["stage_sizes"]) - 1)
        params["fc/kernel"] = jax.random.normal(
            nxt(), (width, cfg["num_classes"]), jnp.float32) / np.sqrt(width)
        params["fc/bias"] = jnp.zeros((cfg["num_classes"],), jnp.float32)
        return params, stats

    return _memo(cfg, "init")(lambda: make)(_key(seed))


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _conv(x, w, stride, padding, precision):
    return lax.conv_general_dilated(
        _operand(x, precision), _operand(w, precision), (stride, stride),
        padding, dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HI)


def _bn(x, params, stats, name, train, cfg, new_stats):
    if train:
        mean = x.mean((0, 1, 2))
        var = jnp.square(x - mean).mean((0, 1, 2))
        m = cfg["bn_momentum"]
        new_stats[name + "/mean"] = m * stats[name + "/mean"] + (1 - m) * mean
        new_stats[name + "/var"] = m * stats[name + "/var"] + (1 - m) * var
    else:
        mean, var = stats[name + "/mean"], stats[name + "/var"]
    y = (x - mean) * lax.rsqrt(var + cfg["bn_epsilon"])
    return y * params[name + "/scale"] + params[name + "/bias"]


def _space_to_depth(x):
    b, h, w, c = x.shape
    x = x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // 2, w // 2, 4 * c)


def forward(params, stats, images, cfg, train, precision="float32"):
    """images: float32 [N, H, W, 3], mean-subtracted.  Returns (logits,
    new running statistics)."""
    new_stats = dict(stats)
    bn = functools.partial(_bn, train=train, cfg=cfg, new_stats=new_stats)
    x = images
    if cfg["stem"] == "s2d":
        x = _conv(_space_to_depth(x), params["stem/conv"], 1,
                  ((1, 2), (1, 2)), precision)
    else:
        x = _conv(x, params["stem/conv"], 2, "SAME", precision)
    x = jax.nn.relu(bn(x, params, stats, "stem/bn"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")

    def block(x, p, s, name, stride, proj):
        out = {}
        inner = functools.partial(_bn, train=train, cfg=cfg, new_stats=out)
        y = _conv(x, p[name + "/conv0"], 1, "SAME", precision)
        y = jax.nn.relu(inner(y, p, s, name + "/bn0"))
        y = _conv(y, p[name + "/conv1"], stride, "SAME", precision)
        y = jax.nn.relu(inner(y, p, s, name + "/bn1"))
        y = _conv(y, p[name + "/conv2"], 1, "SAME", precision)
        y = inner(y, p, s, name + "/bn2")
        r = x
        if proj:
            r = _conv(x, p[name + "/proj"], stride, "SAME", precision)
            r = inner(r, p, s, name + "/projbn")
        return jax.nn.relu(r + y), out

    for name, _, _, stride, proj in block_plan(cfg):
        p = {k: v for k, v in params.items() if k.startswith(name + "/")}
        s = {k: v for k, v in stats.items() if k.startswith(name + "/")}
        fn = functools.partial(block, name=name, stride=stride, proj=proj)
        if train:
            # recompute each block in the backward pass: float32 activations
            # of 256 images do not fit beside each other otherwise
            fn = jax.checkpoint(fn)
        x, out = fn(x, p, s)
        new_stats.update(out)
    x = x.mean((1, 2))
    logits = jnp.dot(_operand(x, precision),
                     _operand(params["fc/kernel"], precision),
                     precision=_HI) + params["fc/bias"]
    return logits, new_stats


def preprocess(batch, cfg):
    """uint8 stored rows -> float32 crops, flipped and mean-subtracted, as
    the configuration's input pipeline defines them."""
    size = cfg["image_size"]

    def one(img, x, y, f):
        crop = lax.dynamic_slice(img, (y, x, 0), (size, size, 3))
        return jnp.where(f != 0, crop[:, ::-1, :], crop)

    imgs = jax.vmap(one)(batch["image"], batch["cropx"], batch["cropy"],
                         batch["flip"])
    return imgs.astype(jnp.float32) - jnp.asarray(CHANNEL_MEANS, jnp.float32)


def loss_fn(params, stats, batch, cfg, precision="float32"):
    logits, new_stats = forward(params, stats, preprocess(batch, cfg), cfg,
                                True, precision)
    k = logits.shape[-1]
    a = cfg["label_smoothing"]
    target = jax.nn.one_hot(batch["label"], k) * (1 - a) + a / k
    ce = -(target * jax.nn.log_softmax(logits)).sum(-1).mean()
    l2 = sum(jnp.sum(p ** 2) for p in params.values() if p.ndim > 1)
    return ce + cfg["weight_decay"] * l2, new_stats


def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def train_steps(cfg, seed, batches, precision="float32"):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights.  Returns host values: ``losses`` (one a step),
    ``first_gradient`` (per leaf, the first gradient as the optimizer gets
    it), ``extra_delta`` (per leaf, the change of the running statistics in
    the first step) and ``delta_norms`` (per leaf, the norm of the parameters' change
    over the steps)."""
    params, stats = init_weights(cfg, seed)
    opt = cfg["optimizer"]

    def step(params, stats, trace, batch):
        (loss, new_stats), grads = jax.value_and_grad(
            lambda p: loss_fn(p, stats, batch, cfg, precision),
            has_aux=True)(params)
        trace = {k: opt["momentum"] * trace[k] + grads[k] for k in grads}
        new = {k: params[k] - opt["learning_rate"] * trace[k] for k in params}
        return new, new_stats, trace, loss, grads

    step = _memo(cfg, "step", precision)(lambda: step)
    start = params
    trace = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, first, extra = [], None, None
    for batch in batches:
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        before = stats
        params, stats, trace, loss, grads = step(params, stats, trace, batch)
        losses.append(float(loss))
        if first is None:
            first = {k: np.asarray(v) for k, v in grads.items()}
            extra = {k: np.asarray(stats[k]) - np.asarray(before[k])
                     for k in stats}
        del grads, before
    delta = _memo(cfg, "delta")(lambda: lambda a, b: _leaf_norms(
        {k: a[k] - b[k] for k in a}))(params, start)
    return {"losses": losses, "first_gradient": first, "extra_delta": extra,
            "delta_norms": {k: float(v) for k, v in delta.items()}}


def predict(cfg, seed, images, precision="float32", block=16):
    """Inference logits for float32 ``images`` [N, H, W, 3] (as a client
    sends them), through the running statistics, in blocks of rows."""
    params, stats = init_weights(cfg, seed)
    fn = _memo(cfg, "predict", precision)(
        lambda: lambda p, s, x: forward(p, s, x, cfg, False, precision)[0])
    out = []
    for lo in range(0, len(images), block):
        chunk = np.asarray(images[lo:lo + block], np.float32)
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.zeros((pad,) + chunk.shape[1:],
                                                    np.float32)])
        out.append(np.asarray(fn(params, stats, chunk))[:block - pad])
    return np.concatenate(out)
