"""Phi-4-mini-flash-reasoning (`model_type: phi4flash`, the SambaY
decoder-hybrid-decoder of arXiv:2507.06607), plain: a pipeline stage.

Written from the family's equations; nothing here imports the program.
Straightforward jax.numpy in float32, no kernels, one sequence at a time
(no term of the model couples two sequences, so losses and gradients add
over sequences).  Products run at the ambient precision: the benchmark
calls this at the precision the configuration states (JAX's default: one
bfloat16 pass on the TPU), the repository's CPU tests under
`jax.default_matmul_precision("highest")`.  The scan's own arithmetic
(exp, the products with the state, the sums over it) is elementwise
float32 either way.

Every layer, pre-norm, N = `published.num_hidden_layers` deciding the
kind of published layer i (`kinds`):

    x <- x + Mixer_i(LN(x));  x <- x + MLP(LN(x))
    LN:   LayerNorm with scale and bias, eps `layer_norm_eps`
    MLP:  (silu(h W_gate) * (h W_up)) W_down, no bias
    Mamba (i even, i <= N/2):  [a, z] = h W_in;
          u = silu(taps over time of a + conv_bias), causal, d_conv taps;
          [r, B, C] = u W_x;  dt = softplus(r W_dt + dt_bias);
          A = -exp(A_log);  per channel c and state n
          s_t = exp(dt_t[c] A[c, n]) s_(t-1) + dt_t[c] u_t[c] B_t[n],
          s_(-1) = 0;  y_t[c] = sum_n s_t[c, n] C_t[n] + D[c] u_t[c];
          output (y * silu(z)) W_out.  Layer N/2 also hands on m = y.
    Differential attention (i odd, i <= N/2 + 1):  H query heads and
          Hkv key/value heads of hd, g = H / Hkv.  Key pair j is key
          heads 2j (k1) and 2j + 1 (k2), its value [v_2j, v_2j+1], 2 hd
          wide; it serves the g query pairs p = g j + r, whose first
          query is query head 2 g j + r and whose second is query head
          2 g j + g + r (W_q's own order);
          o_p = softmax(q1 k1^T / sqrt(hd) + mask) v
                - lambda_i softmax(q2 k2^T / sqrt(hd) + mask) v,
          lambda_i = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init_i,
          lambda_init_i = 0.8 - 0.6 exp(-0.3 i), i the PUBLISHED index;
          RMSNorm over o_p's 2 hd (one scale, eps `layer_norm_eps`)
          times (1 - lambda_init_i); the pairs side by side; W_o.
          Mask: causal, and for i < N/2 row t sees the columns s with
          t - W < s <= t (W = `sliding_window` keys, its own among
          them).  Layer N/2 + 1 sees its whole past and also hands on
          its k and v.
    Gated Memory Unit (i even, i >= N/2 + 2):  (m * silu(h W_in)) W_out
    Cross-attention (i odd, i >= N/2 + 3):  W_q and W_o alone; the
          differential attention above with layer N/2 + 1's k and v,
          causal over the whole past.
    head: LN, logits = x E^T with E the embedding (tied), mean
          cross-entropy over the vocabulary slice.  No layer carries a
          position.

Departures from the published description, each under `assumed` in the
configuration: which columns of W_q / W_k / W_v pair (a fixed
permutation of seeded random columns against the family's interleaving,
which q k^T and the pairwise sum do not see); the window read as W keys
with the row's own; no mask and no reset of the state at a document's
start.

The layers run are the published layers [first_layer, first_layer +
num_hidden_layers), named L0, L1, ... in that order; the vocabulary is
its first `vocab_size` rows.

`SCAN_BLOCK` steps of the scan go under one `jax.checkpoint` (0: the
whole sequence is one `lax.scan` and nothing is computed twice);
attention goes one head pair at a time over whole score matrices, each
pair recomputed in the backward pass when `SCAN_BLOCK` is set: what a
row of 8,192 tokens needs to fit beside the parameters on one chip.

Seeded draws follow the derivation the program documents (net.py
`Net.init`): blob i of layer L <- fill(fold_in(fold_in(key(seed),
crc32(L)), i)).
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
SCAN_BLOCK = 256        # steps under one checkpoint; 0 = one plain scan


# ------------------------------------------------------------------ shapes

class _Dims(dict):
    """The sizes, hashable so that jit and checkpoint take them as a
    static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def kinds(total: int):
    """Published layer i's kind in a model of `total` layers."""
    half = total // 2
    return tuple(
        ("mamba" if i < half else "mamba_memory" if i == half else "gmu")
        if i % 2 == 0 else
        ("window" if i < half else "full_kv" if i == half + 1 else "cross")
        for i in range(total))


def dims(cfg: dict) -> dict:
    first = int(cfg.get("first_layer", 0))
    n = int(cfg["num_hidden_layers"])
    total = int(cfg.get("published", {}).get("num_hidden_layers", n))
    a = cfg["assumed"]
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return _Dims(
        d=d, h=h, hkv=int(cfg["num_key_value_heads"]), hd=d // h,
        ff=int(cfg["intermediate_size"]),
        di=int(a["mamba_expand"]) * d, n=int(a["mamba_d_state"]),
        taps=int(a["mamba_d_conv"]), rank=int(a["mamba_dt_rank"]),
        window=int(cfg["sliding_window"]), vocab=int(cfg["vocab_size"]),
        n_layers=n, first=first,
        kinds=kinds(total)[first:first + n],
        eps=float(cfg["layer_norm_eps"]), std=float(a["init_std"]),
        lambda_std=float(a["lambda_std"]),
        conv_bound=float(a["conv_bound"]),
        dt_min=float(a["dt_min"]), dt_max=float(a["dt_max"]),
        tied=bool(cfg.get("tie_word_embeddings", True)))


def lambda_init(published: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * published)


def layers(cfg: dict):
    """[(layer, [(blob, shape, filler, lr_mult)])] in the program's blob
    order (the index i of the key derivation)."""
    m = dims(cfg)
    g = ("gaussian", m["std"])
    lam = ("gaussian", m["lambda_std"])
    conv = ("uniform", -m["conv_bound"], m["conv_bound"])
    one, zero = ("constant", 1.0), ("constant", 0.0)
    d, hd, di, n = m["d"], m["hd"], m["di"], m["n"]
    norm = [("scale", (d,), one, 1), ("bias", (d,), zero, 1)]
    lambdas = [(f"lambda_{x}", (hd,), lam, 1)
               for x in ("q1", "k1", "q2", "k2")] \
        + [("sub_norm", (2 * hd,), one, 1)]
    out = [("embed", [("weight", (m["vocab"], d), g, 1)])]
    for i, kind in enumerate(m["kinds"]):
        p = f"L{i}"
        out.append((f"{p}.norm1", norm))
        if kind in ("mamba", "mamba_memory"):
            out.append((f"{p}.mamba", [
                ("W_in", (2 * di, d), g, 1), ("taps", (di, m["taps"]),
                                              conv, 1),
                ("conv_bias", (di,), conv, 1),
                ("W_x", (m["rank"] + 2 * n, di), g, 1),
                ("W_dt", (di, m["rank"]), g, 1),
                ("dt_bias", (di,), ("inv_softplus_log_uniform",
                                    m["dt_min"], m["dt_max"]), 1),
                ("A_log", (di, n), ("log_arange",), 1),
                ("D", (di,), one, 1), ("W_out", (d, di), g, 1)]))
        elif kind in ("window", "full_kv"):
            out.append((f"{p}.attn", [
                ("W_q", (m["h"] * hd, d), g, 1),
                ("W_k", (m["hkv"] * hd, d), g, 1),
                ("W_v", (m["hkv"] * hd, d), g, 1),
                ("W_o", (d, m["h"] * hd), g, 1)] + lambdas))
        elif kind == "gmu":
            out.append((f"{p}.gmu", [("W_in", (di, d), g, 1),
                                     ("W_out", (d, di), g, 1)]))
        else:
            out.append((f"{p}.attn", [
                ("W_q", (m["h"] * hd, d), g, 1),
                ("W_o", (d, m["h"] * hd), g, 1)] + lambdas))
        out.append((f"{p}.norm2", norm))
        out.append((f"{p}.gate", [("weight", (m["ff"], d), g, 1)]))
        out.append((f"{p}.up", [("weight", (m["ff"], d), g, 1)]))
        out.append((f"{p}.down", [("weight", (d, m["ff"]), g, 1)]))
    out.append(("head.norm", norm))
    if not m["tied"]:
        out.append(("head.logits", [("weight", (m["vocab"], d), g, 1)]))
    return out


def num_params(cfg: dict) -> int:
    return sum(math.prod(s) for _, bl in layers(cfg) for _, s, _, _ in bl)


def fill(key, filler, shape):
    kind = filler[0]
    if kind == "constant":
        return jnp.full(shape, filler[1], F32)
    if kind == "gaussian":
        return (filler[1] * jax.random.normal(key, shape)).astype(F32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, F32, filler[1], filler[2])
    if kind == "log_arange":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=F32)), shape)
    if kind == "inv_softplus_log_uniform":
        dt = jnp.exp(jax.random.uniform(key, shape, F32,
                                        math.log(filler[1]),
                                        math.log(filler[2])))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def init_params(cfg: dict, seed: int) -> dict:
    """{"layer/blob": array} from the seed."""
    root = jax.random.key(int(seed))
    out = {}
    for lname, blobs in layers(cfg):
        lkey = jax.random.fold_in(root, zlib.crc32(lname.encode("utf-8")))
        for i, (bname, shape, filler, _) in enumerate(blobs):
            out[f"{lname}/{bname}"] = fill(jax.random.fold_in(lkey, i),
                                           filler, shape)
    return out


def lr_mults(cfg: dict) -> dict:
    return {f"{ln}/{bn}": lm for ln, bl in layers(cfg)
            for bn, _, _, lm in bl}


# ---------------------------------------------------------------- the model

def layer_norm(x, p, pre, eps):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc * lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps) \
        * p[pre + "/scale"] + p[pre + "/bias"]


def causal_taps(a, taps):
    """a (T, C), taps (C, L): tap j multiplies the input at t - (L - 1)
    + j, zero before t = 0."""
    n, t = taps.shape[1], a.shape[0]
    ap = jnp.pad(a, ((n - 1, 0), (0, 0)))
    return sum(ap[j:j + t] * taps[:, j] for j in range(n))


def scan_steps(state, x, a):
    """The recurrence over the steps of x = (u, dt, B, C) from `state`
    (C, N) -> (the state after them, y (steps, C))."""
    def step(s, x):
        u, dt, b, c = x
        s = jnp.exp(dt[:, None] * a) * s + (dt * u)[:, None] * b[None, :]
        return s, jnp.sum(s * c[None, :], axis=-1)
    return lax.scan(step, state, x)


def selective_scan(u, dt, a, b, c):
    """u, dt (T, C), a (C, N), b, c (T, N) -> y (T, C)."""
    t = u.shape[0]
    state = jnp.zeros(a.shape, F32)
    if not SCAN_BLOCK or t % SCAN_BLOCK:
        return scan_steps(state, (u, dt, b, c), a)[1]
    blocks = tuple(x.reshape(t // SCAN_BLOCK, SCAN_BLOCK, -1)
                   for x in (u, dt, b, c))
    _, y = lax.scan(
        jax.checkpoint(lambda s, x: scan_steps(s, x, a)), state, blocks)
    return y.reshape(t, -1)


def mamba(p, pre, x, m):
    """-> (the mixer's output (T, d), the scan's output y (T, di))."""
    di, n, rank = m["di"], m["n"], m["rank"]
    az = x @ p[pre + "/W_in"].T
    u = jax.nn.silu(causal_taps(az[:, :di], p[pre + "/taps"])
                    + p[pre + "/conv_bias"])
    rbc = u @ p[pre + "/W_x"].T
    dt = jax.nn.softplus(rbc[:, :rank] @ p[pre + "/W_dt"].T
                         + p[pre + "/dt_bias"])
    y = selective_scan(u, dt, -jnp.exp(p[pre + "/A_log"]),
                       rbc[:, rank:rank + n], rbc[:, rank + n:])
    y = y + p[pre + "/D"] * u
    return (y * jax.nn.silu(az[:, di:])) @ p[pre + "/W_out"].T, y


def _pair(q1, q2, k1, k2, v, lam, window: int):
    """One head pair over whole score matrices: q, k (T, hd), v (T,
    2 hd) -> o1 - lam o2 (T, 2 hd)."""
    t = q1.shape[0]
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]     # t - s
    seen = ahead >= 0
    if window:
        seen &= ahead < window

    def one(q, k):
        s = (q @ k.T) / math.sqrt(q.shape[-1])
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

    return one(q1, k1) - lam * one(q2, k2)


def differential(p, pre, q, k, v, m, published: int, window: int):
    """q (T, H, hd), k, v (T, Hkv, hd) -> the pairs' normed outputs side
    by side (T, H hd)."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    lam0 = lambda_init(published)
    lam = (jnp.exp(jnp.sum(p[pre + "/lambda_q1"] * p[pre + "/lambda_k1"]))
           - jnp.exp(jnp.sum(p[pre + "/lambda_q2"] * p[pre + "/lambda_k2"]))
           + lam0)
    pair = jax.checkpoint(_pair, static_argnums=(6,)) if SCAN_BLOCK \
        else _pair
    # query pair (j, r) -> its two queries, its key pair, the pair's value
    q = q.reshape(t, h // (2 * g), 2, g, hd)
    k = k.reshape(t, h // (2 * g), 2, hd)
    v = v.reshape(t, h // (2 * g), 2 * hd)
    rows = lambda a: jnp.moveaxis(a, 0, -2)     # noqa: E731  time last but one
    q1, q2 = rows(q[:, :, 0]), rows(q[:, :, 1])             # (J, g, T, hd)
    k1, k2 = rows(k[:, :, 0]), rows(k[:, :, 1])             # (J, T, hd)
    o = lax.map(
        lambda a: lax.map(
            lambda b: pair(b[0], b[1], a[2], a[3], a[4], lam, window),
            (a[0], a[1])),
        (q1, q2, k1, k2, rows(v)))                          # (J, g, T, 2hd)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + m["eps"]) \
        * p[pre + "/sub_norm"] * (1.0 - lam0)
    return jnp.moveaxis(o.reshape(h // 2, t, 2 * hd), 0, 1).reshape(
        t, h * hd)


def attention(p, pre, x, m, published: int, window: int, kv=None):
    """-> (the mixer's output, (k, v)); `kv` given: another layer's."""
    t = x.shape[0]
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    q = (x @ p[pre + "/W_q"].T).reshape(t, h, hd)
    if kv is None:
        kv = ((x @ p[pre + "/W_k"].T).reshape(t, hkv, hd),
              (x @ p[pre + "/W_v"].T).reshape(t, hkv, hd))
    o = differential(p, pre, q, kv[0], kv[1], m, published, window)
    return o @ p[pre + "/W_o"].T, kv


def gmu(p, pre, x, memory):
    return (memory * jax.nn.silu(x @ p[pre + "/W_in"].T)) \
        @ p[pre + "/W_out"].T


def mlp(p, pre, x):
    return (jax.nn.silu(x @ p[pre + ".gate/weight"].T)
            * (x @ p[pre + ".up/weight"].T)) @ p[pre + ".down/weight"].T


def mixer(p, i, x, shared, m):
    """Layer i's mixer half: x + Mixer(LN(x)) -> (x, what it hands on:
    {"memory": y} or {"kv": (k, v)} or {})."""
    pre = f"L{i}"
    kind = m["kinds"][i]
    published = m["first"] + i
    n1 = layer_norm(x, p, pre + ".norm1", m["eps"])
    hands = {}
    if kind in ("mamba", "mamba_memory"):
        a, y = mamba(p, pre + ".mamba", n1, m)
        if kind == "mamba_memory":
            hands["memory"] = y
    elif kind in ("window", "full_kv"):
        a, kv = attention(p, pre + ".attn", n1, m, published,
                          m["window"] if kind == "window" else 0)
        if kind == "full_kv":
            hands["kv"] = kv
    elif kind == "gmu":
        a = gmu(p, pre + ".gmu", n1, shared["memory"])
    else:
        a, _ = attention(p, pre + ".attn", n1, m, published, 0,
                         kv=shared["kv"])
    return x + a, hands


def feed_forward(p, i, x, m):
    pre = f"L{i}"
    return x + mlp(p, pre, layer_norm(x, p, pre + ".norm2", m["eps"]))


def run_layers(p, x, m, shared=None):
    """x (T, d) through the layers run -> x after the last."""
    shared = dict(shared or {})
    remat = (lambda f, **kw: jax.checkpoint(f, **kw)) if SCAN_BLOCK \
        else (lambda f, **kw: f)
    for i in range(m["n_layers"]):
        x, hands = remat(mixer, static_argnums=(1, 4))(p, i, x, shared, m)
        shared.update(hands)
        x = remat(feed_forward, static_argnums=(1, 3))(p, i, x, m)
    return x


def logits_of(p, x, m):
    x = layer_norm(x, p, "head.norm", m["eps"])
    return x @ (p["embed/weight"] if m["tied"]
                else p["head.logits/weight"]).T


def forward(p, ids, m):
    """ids (T,) int -> logits (T, vocab)."""
    return logits_of(p, run_layers(p, p["embed/weight"][ids], m), m)


def loss_sum(p, ids, targets, m):
    """Sum over the sequence's tokens of -log softmax(logits)[target]."""
    logits = forward(p, ids, m)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.sum(lse - picked)


# ----------------------------------------------------------------- training

def adam_leaf(w, g, m1, m2, *, lr, b1, b2, delta, t, scale):
    """adam_solver.cpp with Caffe's clip-then-update order: g already
    scaled by the clip factor `scale`."""
    g = g * scale
    m1 = b1 * m1 + (1 - b1) * g
    m2 = b2 * m2 + (1 - b2) * g * g
    corr = jnp.sqrt(1.0 - jnp.power(b2, t)) / (1.0 - jnp.power(b1, t))
    return w - lr * corr * m1 / (jnp.sqrt(m2) + delta), m1, m2


def grads_of_batch(p, ids, targets, m):
    """ids, targets (B, T) -> mean loss, mean-loss gradients; one
    sequence at a time."""
    fn = jax.jit(jax.value_and_grad(loss_sum), static_argnums=(3,))
    total, gsum = 0.0, None
    for b in range(ids.shape[0]):
        lsum, g = fn(p, jnp.asarray(ids[b]), jnp.asarray(targets[b]), m)
        total += float(lsum)
        gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
        del g
    n = ids.shape[0] * ids.shape[1]
    scale = jax.jit(lambda a: a / n, donate_argnums=0)
    return total / n, {k: scale(v) for k, v in gsum.items()}


def train_steps(cfg: dict, seed: int, batches, reduce):
    """Follow len(batches) solver iterations from the seed.  batches:
    [(ids (B, T), targets (B, T))] int arrays.  `reduce(name, tree)` is
    handed each compared state as {"layer/blob": host float32 array}
    (p0, then m1, v1, p1 after step 1, p_last after the last) and
    returns what the caller keeps of it; Adam's moments live on the
    host between steps so that the device holds parameters and two
    gradient trees at most.  -> {"losses", "counts" (no expert layer:
    empty), name: reduce()}"""
    m = dims(cfg)
    sv = cfg["solver"]
    lr, b1, b2 = float(sv["base_lr"]), float(sv["momentum"]), \
        float(sv["momentum2"])
    delta, clip = float(sv["delta"]), float(sv.get("clip_gradients", -1))
    mults = lr_mults(cfg)
    host = lambda tree: {k: np.asarray(v) for k, v in tree.items()}  # noqa: E731
    params = init_params(cfg, seed)
    out = {"p0": reduce("p0", host(params)), "losses": [], "counts": []}
    mom1 = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
    mom2 = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
    upd = jax.jit(adam_leaf, static_argnames=("lr", "b1", "b2", "delta"),
                  donate_argnums=(0, 2, 3))
    sq = jax.jit(lambda a: jnp.sum(a * a))
    for it, (ids, targets) in enumerate(batches):
        loss, grads = grads_of_batch(params, ids, targets, m)
        out["losses"].append(loss)
        out["counts"].append(np.zeros((0,)))
        scale = 1.0
        if clip > 0:        # SGDSolver::ClipGradients over every blob
            gnorm = math.sqrt(sum(float(sq(g)) for g in grads.values()))
            scale = clip / gnorm if gnorm > clip else 1.0
        for k in list(params):
            w, m1, m2 = upd(params[k], grads.pop(k),
                            jnp.asarray(mom1[k]), jnp.asarray(mom2[k]),
                            lr=lr * mults[k], b1=b1, b2=b2, delta=delta,
                            t=jnp.float32(it + 1), scale=jnp.float32(scale))
            params[k] = w
            mom1[k], mom2[k] = np.asarray(m1), np.asarray(m2)
        if it == 0:
            out["m1"] = reduce("m1", mom1)
            out["v1"] = reduce("v1", mom2)
            out["p1"] = reduce("p1", host(params))
    out["p_last"] = reduce("p_last", host(params))
    return out


# ------------------------------------------------------------- operations

def visible_pairs(seq: int, window: int) -> int:
    """(row, column) pairs a head scores over `seq` rows: row r sees
    min(r + 1, window) columns under a window, and its causal past
    counted as seq / 2 columns a row without one (the count the other
    configurations' `forward_flops` take)."""
    if 0 < window < seq:
        return window * (window + 1) // 2 + (seq - window) * window
    return seq * seq // 2


SCAN_OPS = 9    # a token, channel and state: dt A, its exponential, the
#                 decay's product, dt u B (2), the sum, the read's
#                 product and its sum over states, counted as 9


def forward_flops(cfg: dict, seq: int, seqs: int) -> int:
    """Work of one forward pass over `seqs` sequences of `seq` tokens,
    from the shapes: per token 2 x the matmul parameters it touches (the
    tied head's product among them; the embedding is a gather);
    attention over the scores a row can see and no others, per visible
    pair and query head the score (2 hd) and the weighted value, which
    is two heads wide (2 x 2 hd); the scan's elementwise work as
    `SCAN_OPS` operations a token, channel and state (operations, not
    MXU work).  Norms, taps, gates and softmax are not counted."""
    m = dims(cfg)
    d, hd, di, n = m["d"], m["hd"], m["di"], m["n"]
    per_token, pairs, scans = m["vocab"] * d, 0, 0
    for kind in m["kinds"]:
        per_token += 3 * d * m["ff"]
        if kind in ("mamba", "mamba_memory"):
            per_token += 3 * di * d + (m["rank"] + 2 * n) * di \
                + di * m["rank"]
            scans += 1
        elif kind == "gmu":
            per_token += 2 * di * d
        else:
            per_token += 2 * m["h"] * hd * d
            if kind != "cross":
                per_token += 2 * m["hkv"] * hd * d
            pairs += visible_pairs(seq, m["window"] if kind == "window"
                                   else 0)
    return int(seqs * (seq * 2 * per_token + 6 * hd * m["h"] * pairs
                       + SCAN_OPS * seq * di * n * scans))
