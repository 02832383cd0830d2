"""Plain layer equations shared by the benchmark's references.

Straightforward jax.numpy, written from Caffe's published layer
definitions (caffe/src/caffe/layers/*.cpp), at the precision the
configurations state: float32 storage and JAX's default precision for
the products (one bfloat16 pass on the TPU, plain float32 on the CPU).
A reference pinned to "highest" was tried first and could not tell
float32 activations from bfloat16 ones: against it both read the
rounding of the products themselves (PERF.md section 2).  Nothing here
imports the program.

Two things are not numerics but seeded draws, and a comparison needs
them equal on both sides, so they follow the derivation the program
documents (net.py `Net.init`, ops/layers.py `LayerContext.take_rng`):

    blob i of layer L   <- fill(fold_in(fold_in(key(seed), crc32(L)), i))
    dropout mask, it t  <- bernoulli(fold_in(fold_in(key(seed + rank), t),
                                             crc32(L)), keep, shape)

A PR that changes either derivation changes the seeded stream, not the
mathematics; it then needs a `benchmark` PR to follow it here.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def crc(name: str) -> int:
    return zlib.crc32(name.encode("utf-8"))


# ------------------------------------------------------------ seeded draws

def fill(key, filler, shape):
    """Caffe filler.hpp: constant / gaussian / msra / xavier (FAN_IN)."""
    kind = filler[0]
    shape = tuple(int(s) for s in shape)
    fan_in = math.prod(shape) / shape[0] if shape else 1.0
    if kind == "constant":
        return jnp.full(shape, filler[1], F32)
    if kind == "gaussian":
        return filler[1] * jax.random.normal(key, shape)
    if kind == "msra":
        std = math.sqrt(2.0 / fan_in)
        return std * jax.random.normal(key, shape)
    if kind == "xavier":
        scale = math.sqrt(3.0 / fan_in)
        return jax.random.uniform(key, shape, F32, -scale, scale)
    raise ValueError(f"filler {kind!r}")


def init_params(layers, seed: int):
    """layers: [(layer name, [(shape, filler, lr_mult, decay_mult), ...])]
    -> {layer: [blob, ...]} in Caffe's blob order."""
    root = jax.random.key(int(seed))
    out = {}
    for lname, blobs in layers:
        lkey = jax.random.fold_in(root, crc(lname))
        out[lname] = [fill(jax.random.fold_in(lkey, i), f, shape)
                      for i, (shape, f, _, _) in enumerate(blobs)]
    return out


def dropout_mask(seed: int, it: int, lname: str, shape, keep: float):
    step_key = jax.random.fold_in(jax.random.key(int(seed)), it)
    return jax.random.bernoulli(jax.random.fold_in(step_key, crc(lname)),
                                keep, shape)


# ---------------------------------------------------------------- layers

_FLOPS = None           # a list while forward_flops() traces


def forward_flops(model, cfg, crop: int, rows: int) -> int:
    """Multiply-accumulate work of one forward pass over `rows` images,
    from the shapes alone: 2 * outputs * (weights per output) for every
    convolution and inner product (the MXU's share; elementwise layers
    are not counted).  Training is taken as 3x this: dL/dx and dL/dW are
    each one more pass of the same products."""
    global _FLOPS
    layers = model.layers(cfg, crop)
    shapes = {n: [jax.ShapeDtypeStruct(sh, F32) for sh, _, _, _ in bl]
              for n, bl in layers}
    masks = jax.eval_shape(lambda: model.masks(cfg, 0, 0, rows))
    _FLOPS = []
    try:
        jax.eval_shape(model.loss_sum, shapes,
                       jax.ShapeDtypeStruct((rows, 3, crop, crop), F32),
                       jax.ShapeDtypeStruct((rows,), F32), masks)
        return int(sum(_FLOPS))
    finally:
        _FLOPS = None


def conv(x, w, b=None, *, stride=1, pad=0, groups=1):
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups)
    if _FLOPS is not None:
        _FLOPS.append(2 * math.prod(y.shape) * math.prod(w.shape[1:]))
    if b is not None:
        y = y + b[None, :, None, None]
    return y


def relu(x):
    return jnp.maximum(x, 0)


def _pool_pad(size, k, s):
    """Caffe pooling_layer.cpp: out = ceil((size - k) / s) + 1, the last
    window clipped at the edge."""
    out = int(math.ceil((size - k) / s)) + 1
    return out, max(0, (out - 1) * s + k - size)


def max_pool(x, k, s):
    _, ph = _pool_pad(x.shape[2], k, s)
    _, pw = _pool_pad(x.shape[3], k, s)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, k, k),
                             (1, 1, s, s),
                             [(0, 0), (0, 0), (0, ph), (0, pw)])


def global_ave_pool(x):
    return jnp.mean(x, axis=(2, 3), keepdims=True)


def lrn(x, n=5, alpha=1e-4, beta=0.75, k=1.0):
    """lrn_layer.cpp ACROSS_CHANNELS:
    y_c = x_c / (k + alpha/n * sum_{c' in window(c)} x_c'^2)^beta."""
    sq = jnp.pad(x * x, [(0, 0), (n // 2, n // 2), (0, 0), (0, 0)])
    c = x.shape[1]
    ssum = sum(sq[:, i:i + c] for i in range(n))
    scale = k + (alpha / n) * ssum
    return x * jnp.power(scale, -beta)


def fc(x, w, b):
    x = x.reshape(x.shape[0], -1)
    if _FLOPS is not None:
        _FLOPS.append(2 * x.shape[0] * math.prod(w.shape))
    return jnp.matmul(x, w.T) + b


def batch_norm_train(x, eps=1e-5):
    """batch_norm_layer.cpp, use_global_stats false: normalise with the
    batch mean and the (biased) batch variance; returns the statistics
    the layer accumulates as well."""
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - mean[None, :, None, None]), axis=(0, 2, 3))
    y = (x - mean[None, :, None, None]) / jnp.sqrt(
        var[None, :, None, None] + eps)
    return y, mean, var


def softmax_loss_sum(logits, labels):
    """Sum over rows of -log softmax(logits)[label]."""
    lse = jax.nn.logsumexp(logits, axis=1)
    picked = jnp.take_along_axis(
        logits, labels.astype(jnp.int32)[:, None], axis=1)[:, 0]
    return jnp.sum(lse - picked)


# ------------------------------------------------------------------- SGD

def sgd_update(params, hist, grads, layers, *, lr, momentum, weight_decay):
    """sgd_solver.cpp: Regularize (L2, decay_mult), ComputeUpdateValue
    (V = momentum V + lr lr_mult g), Update (w -= V)."""
    new_p, new_h = {}, {}
    for lname, blobs in layers:
        new_p[lname], new_h[lname] = [], []
        for i, (_, _, lr_mult, decay_mult) in enumerate(blobs):
            w, v, g = params[lname][i], hist[lname][i], grads[lname][i]
            if lr_mult == 0:            # BatchNorm statistics: no optimizer
                new_p[lname].append(w)
                new_h[lname].append(v)
                continue
            g = g + (weight_decay * decay_mult) * w
            v = momentum * v + (lr * lr_mult) * g
            new_p[lname].append(w - v)
            new_h[lname].append(v)
    return new_p, new_h


def train_steps(model, cfg, seed, batches, devices=None):
    """Follow the first len(batches) solver iterations from the seed.

    model: a reference module (layers, loss_sum, ROW_BLOCK); cfg: the
    configuration's JSON; batches: [(data, labels)] as the data layer
    delivers them (float32 NCHW after crop/mirror/mean, float labels).
    devices: the chips of the cell.  Given more than one, the rows of a
    block lie over them and the parameters on each: the same equations
    over the whole batch (BatchNorm's statistics are over all its rows),
    in a quarter of the memory a chip; a batch of four chips does not
    fit one.  Returns each step's loss and, on the host in float32 under
    "layer/i", the parameters at the start, after step 1 and after the
    last step, and the momentum after step 1."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    sv = cfg["solver"]
    lr, mom, wd = sv["base_lr"], sv["momentum"], sv["weight_decay"]
    crop = batches[0][0].shape[-1]
    layers = model.layers(cfg, crop)
    block = model.ROW_BLOCK

    def host(tree):
        return {f"{ln}/{i}": np.asarray(a)
                for ln, bl in tree.items() for i, a in enumerate(bl)}

    @jax.jit
    def block_grads(p, data, labels, masks):
        (total, stats), g = jax.value_and_grad(
            model.loss_sum, has_aux=True)(p, data, labels, masks)
        return total, stats, g

    if devices is not None and len(devices) > 1:
        mesh = Mesh(np.asarray(devices), ("rows",))
        by_rows = NamedSharding(mesh, PartitionSpec("rows"))
        on_each = NamedSharding(mesh, PartitionSpec())
    else:
        by_rows = on_each = (devices or jax.local_devices())[0]

    params = jax.device_put(init_params(layers, seed), on_each)
    out = {"p0": host(params), "losses": []}
    hist = jax.tree.map(jnp.zeros_like, params)
    for it, (data, labels) in enumerate(batches):
        n = data.shape[0]
        step = block or n
        masks_all = model.masks(cfg, seed, it, n)
        total, gsum, stats = 0.0, None, {}
        for lo in range(0, n, step):
            m, d, lab = jax.device_put(
                ({k: v[lo:lo + step] for k, v in masks_all.items()},
                 data[lo:lo + step], labels[lo:lo + step]), by_rows)
            t, stats, g = block_grads(params, d, lab, m)
            total += float(t)
            gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
        grads = jax.tree.map(lambda a: a / n, gsum)
        out["losses"].append(total / n)
        params, hist = sgd_update(params, hist, grads, layers, lr=lr,
                                  momentum=mom, weight_decay=wd)
        for ln, blobs in stats.items():     # forward-updated statistics
            params[ln] = list(blobs)
        if it == 0:
            out["v1"], out["p1"] = host(hist), host(params)
    out["p_last"] = host(params)
    return out
