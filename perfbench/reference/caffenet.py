"""CaffeNet (BVLC bvlc_reference_caffenet/train_val.prototxt), plain.

conv1 11x11/4 -> relu -> pool 3/2 -> LRN -> conv2 5x5 pad 2 group 2 ->
relu -> pool -> LRN -> conv3 -> relu -> conv4 (group 2) -> relu -> conv5
(group 2) -> relu -> pool -> fc6 -> relu -> dropout -> fc7 -> relu ->
dropout -> fc8 -> softmax loss.  Fillers and multipliers as published:
gaussian 0.01 (0.005 for fc6/fc7), bias 0 or 1, lr_mult 1/2, decay 1/0.
"""

import jax
import jax.numpy as jnp

from . import common as c

ROW_BLOCK = 256          # no layer couples rows: gradients add over blocks

#        name    out  k  stride pad groups std  bias
CONVS = [("conv1", 96, 11, 4, 0, 1, 0.01, 0.0),
         ("conv2", 256, 5, 1, 2, 2, 0.01, 1.0),
         ("conv3", 384, 3, 1, 1, 1, 0.01, 0.0),
         ("conv4", 384, 3, 1, 1, 2, 0.01, 1.0),
         ("conv5", 256, 3, 1, 1, 2, 0.01, 1.0)]
FCS = [("fc6", 4096, 0.005, 1.0), ("fc7", 4096, 0.005, 1.0),
       ("fc8", None, 0.01, 0.0)]
DROPOUT = {"drop6": 0.5, "drop7": 0.5}


def _features(p, x):
    for name, _, _, stride, pad, groups, _, _ in CONVS:
        x = c.relu(c.conv(x, p[name][0], p[name][1], stride=stride, pad=pad,
                          groups=groups))
        if name in ("conv1", "conv2"):
            x = c.lrn(c.max_pool(x, 3, 2))
        elif name == "conv5":
            x = c.max_pool(x, 3, 2)
    return x


def layers(cfg, crop):
    out, cin = [], 3
    for name, n, k, _, _, groups, std, bias in CONVS:
        out.append((name, [((n, cin // groups, k, k), ("gaussian", std), 1, 1),
                           ((n,), ("constant", bias), 2, 0)]))
        cin = n
    shapes = {n: [jax.ShapeDtypeStruct(s, c.F32) for s, _, _, _ in bl]
              for n, bl in out}
    feat = jax.eval_shape(_features, shapes,
                          jax.ShapeDtypeStruct((1, 3, crop, crop), c.F32))
    fan = feat.shape[1] * feat.shape[2] * feat.shape[3]
    for name, n, std, bias in FCS:
        n = n or cfg["num_classes"]
        out.append((name, [((n, fan), ("gaussian", std), 1, 1),
                           ((n,), ("constant", bias), 2, 0)]))
        fan = n
    return out


def masks(cfg, seed, it, n):
    return {name: c.dropout_mask(seed, it, name, (n, 4096), 1.0 - ratio)
            for name, ratio in DROPOUT.items()}


def forward(p, x, masks=None):
    """fc8 logits; `masks` None is the TEST phase (dropout passes)."""
    x = _features(p, x)
    for fcn, drop in (("fc6", "drop6"), ("fc7", "drop7")):
        x = c.relu(c.fc(x, p[fcn][0], p[fcn][1]))
        if masks is not None:
            keep = 1.0 - DROPOUT[drop]
            x = jnp.where(masks[drop], x / keep, 0).astype(x.dtype)
    return c.fc(x, p["fc8"][0], p["fc8"][1])


def loss_sum(p, x, labels, masks):
    return c.softmax_loss_sum(forward(p, x, masks), labels), {}
