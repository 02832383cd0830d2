"""ResNet-50 (He et al. 2015; KaimingHe/deep-residual-networks
ResNet-50-deploy.prototxt), plain, in the TRAIN phase.

conv1 7x7/2 pad 3 -> BN -> Scale -> relu -> max pool 3/2 -> bottleneck
stages (64-256 x3, 128-512 x4, 256-1024 x6, 512-2048 x3; 1x1 -> 3x3 pad 1
-> 1x1, stride on the first 1x1 and on the projection of each stage's
first block) -> global average pool -> fc1000 -> softmax loss.
Convolutions carry no bias.  BatchNorm normalises with batch statistics
and accumulates them (moving_average_fraction 0.999); Scale holds gamma
and beta.  The deploy file has no fillers: msra for convolutions, xavier
for fc1000 are assumed (the configuration's `assumed`).
"""

from . import common as c

ROW_BLOCK = None         # BatchNorm couples the rows of a batch
MAF = 0.999
STAGES = [("res2", 64, 256, 3, 1), ("res3", 128, 512, 4, 2),
          ("res4", 256, 1024, 6, 2), ("res5", 512, 2048, 3, 2)]


def _units():
    """[(conv name, cin, cout, k, stride, pad)] in the prototxt's order,
    with the block structure beside it."""
    units = [("conv1", 3, 64, 7, 2, 3)]
    blocks = []
    cin = 64
    for stage, mid, out, n, stride in STAGES:
        for b in range(n):
            name = f"{stage}{chr(ord('a') + b)}"
            s = stride if b == 0 else 1
            convs = [(f"{name}_branch2a", cin, mid, 1, s, 0),
                     (f"{name}_branch2b", mid, mid, 3, 1, 1),
                     (f"{name}_branch2c", mid, out, 1, 1, 0)]
            proj = (f"{name}_branch1", cin, out, 1, s, 0) if b == 0 else None
            units += convs + ([proj] if proj else [])
            blocks.append((name, convs, proj))
            cin = out
    return units, blocks


def layers(cfg, crop):
    units, _ = _units()
    out = []
    for name, cin, cout, k, _, _ in units:
        out.append((name, [((cout, cin, k, k), ("msra",), 1, 1)]))
        out.append((f"bn_{name}", [((cout,), ("constant", 0.0), 0, 0),
                                   ((cout,), ("constant", 0.0), 0, 0),
                                   ((1,), ("constant", 0.0), 0, 0)]))
        out.append((f"scale_{name}", [((cout,), ("constant", 1.0), 1, 1),
                                      ((cout,), ("constant", 0.0), 1, 1)]))
    n = cfg["num_classes"]
    out.append(("fc1000", [((n, 2048), ("xavier",), 1, 1),
                           ((n,), ("constant", 0.0), 2, 0)]))
    return out


def masks(cfg, seed, it, n):
    return {}


def _conv_bn(p, stats, x, unit):
    name, _, _, _, stride, pad = unit
    x = c.conv(x, p[name][0], stride=stride, pad=pad)
    y, mean, var = c.batch_norm_train(x)
    m = x.shape[0] * x.shape[2] * x.shape[3]
    mean_b, var_b, count = p[f"bn_{name}"]
    stats[f"bn_{name}"] = [mean_b * MAF + mean,
                           var_b * MAF + var * (m / (m - 1.0)),
                           count * MAF + 1.0]
    g, b = p[f"scale_{name}"]
    return y * g[None, :, None, None] + b[None, :, None, None]


def loss_sum(p, x, labels, masks):
    units, blocks = _units()
    stats = {}
    x = c.max_pool(c.relu(_conv_bn(p, stats, x, units[0])), 3, 2)
    for _, convs, proj in blocks:
        y = x
        for i, unit in enumerate(convs):
            y = _conv_bn(p, stats, y, unit)
            if i < 2:
                y = c.relu(y)
        short = _conv_bn(p, stats, x, proj) if proj else x
        x = c.relu(short + y)
    logits = c.fc(c.global_ave_pool(x), p["fc1000"][0], p["fc1000"][1])
    stats = {k: [c.lax.stop_gradient(a) for a in v] for k, v in stats.items()}
    return c.softmax_loss_sum(logits, labels), stats
