"""LFM2-24B-A2B (`model_type: lfm2_moe`), plain: one chip's share.

Written from the lfm2_moe equations; nothing here imports the program.
Straightforward jax.numpy in float32, no kernels, a dense pass over
every expert held, one sequence at a time (no term of the model couples
two sequences, and the router couples no two tokens, so losses and
gradients add over sequences).  Products run at the ambient precision:
the benchmark calls this at the precision the configuration states
(JAX's default: one bfloat16 pass on the TPU), the repository's CPU
tests under `jax.default_matmul_precision("highest")`.  Only the
router's product is pinned to HIGHEST, as the program's is.

    h = x + Op(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Op, `conv`: [b, c, u] = split3(x W_in);  z = b * u;
          v[t] = k_0 z[t-2] + k_1 z[t-1] + k_2 z[t] per channel (taps
          conv_L_cache = 3, zero before t = 0);  (c * v) W_out
    Op, `full_attention`: q = x W_q -> H x 64;  k = x W_k, v = x W_v ->
          H/g x 64;  RMSNorm over each q and k head (one 64-wide scale
          each);  RoPE on adjacent pairs of the whole head, angle
          t theta^(-2i/64);  query head h reads key/value head h // g;
          causal softmax(q k^T / 8) v -> W_o
    FFN, published layers below num_dense_layers:
          W_down (silu(x W_gate) * x W_up), width intermediate_size
    FFN, the others: s = sigmoid(x W_g) over ALL experts; the top_k of
          s + b; w_i = s_i / (sum(s chosen) + 1e-6) x
          routed_scaling_factor;  y = sum_{i chosen and held here}
          w_i E_i(x);  no shared expert
    head: RMSNorm, W_out over the vocabulary slice, mean cross-entropy

The layers run are the published layers [first_layer, first_layer +
num_hidden_layers), named L0, L1, ... in that order; a layer's operator
is `layer_types[published index]`.  The share: the experts
[first_expert, first_expert + experts_held) of each expert layer and
`vocab_size` rows of the vocabulary; what the absent experts would add
is left out, here as in the program.

At T = 8,192 the scores of all 32 heads would be 8.6 GB: attention goes
over the heads `HEAD_CHUNK` at a time (each chunk recomputed in the
backward pass), so they are never whole.

Seeded draws follow the derivation the program documents (net.py
`Net.init`): blob i of layer L <- fill(fold_in(fold_in(key(seed),
crc32(L)), i)), gaussian(std) = std * normal(key, shape).
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HEAD_CHUNK = 2          # heads whose (T, T) scores are alive together


# ------------------------------------------------------------------ shapes

class _Dims(dict):
    """The sizes, hashable so that jit and checkpoint take them as a
    static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def dims(cfg: dict) -> dict:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    first = int(cfg.get("first_layer", 0))
    n = int(cfg["num_hidden_layers"])
    return _Dims(
        d=d, h=h, hkv=int(cfg["num_key_value_heads"]), hd=d // h,
        dense=int(cfg["intermediate_size"]),
        ew=int(cfg["moe_intermediate_size"]),
        e=int(cfg["num_experts"]), k=int(cfg["num_experts_per_tok"]),
        held=int(cfg.get("experts_held", cfg["num_experts"])),
        first=int(cfg.get("first_expert", 0)),
        vocab=int(cfg["vocab_size"]), n_layers=n,
        # per layer run: (operator, dense feed-forward?)
        kinds=tuple((str(cfg["layer_types"][first + i]),
                     first + i < int(cfg["num_dense_layers"]))
                    for i in range(n)),
        taps=int(cfg["conv_L_cache"]),
        factor=float(cfg["routed_scaling_factor"]),
        route_eps=float(cfg["assumed"]["route_norm_epsilon"]),
        eps=float(cfg["norm_eps"]),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        std=float(cfg["assumed"]["init_std"]))


def layers(cfg: dict):
    """[(layer, [(blob, shape, filler, lr_mult)])] in the program's blob
    order (the index i of the key derivation)."""
    m = dims(cfg)
    g = ("gaussian", m["std"])
    one, zero = ("constant", 1.0), ("constant", 0.0)
    d, hd = m["d"], m["hd"]
    out = [("embed", [("weight", (m["vocab"], d), g, 1)])]
    for i, (kind, dense) in enumerate(m["kinds"]):
        p = f"L{i}"
        out.append((f"{p}.norm1", [("scale", (d,), one, 1)]))
        if kind == "conv":
            out.append((f"{p}.conv", [
                ("W_in", (3 * d, d), g, 1), ("taps", (d, m["taps"]), g, 1),
                ("W_out", (d, d), g, 1)]))
        elif kind == "full_attention":
            out.append((f"{p}.attn", [
                ("W_q", (m["h"] * hd, d), g, 1),
                ("W_k", (m["hkv"] * hd, d), g, 1),
                ("W_v", (m["hkv"] * hd, d), g, 1),
                ("W_o", (d, m["h"] * hd), g, 1),
                ("q_norm", (hd,), one, 1), ("k_norm", (hd,), one, 1)]))
        else:
            raise ValueError(f"layer type {kind!r}")
        out.append((f"{p}.norm2", [("scale", (d,), one, 1)]))
        if dense:
            out.append((f"{p}.gate", [("weight", (m["dense"], d), g, 1)]))
            out.append((f"{p}.up", [("weight", (m["dense"], d), g, 1)]))
            out.append((f"{p}.down", [("weight", (d, m["dense"]), g, 1)]))
        else:
            out.append((f"{p}.moe", [
                ("router", (d, m["e"]), g, 1),
                ("bias", (m["e"],), zero, 0),       # moves only the choice
                ("W_gate", (m["held"], d, m["ew"]), g, 1),
                ("W_up", (m["held"], d, m["ew"]), g, 1),
                ("W_down", (m["held"], m["ew"], d), g, 1)]))
    out.append(("head.norm", [("scale", (d,), one, 1)]))
    out.append(("head.logits", [("weight", (m["vocab"], d), g, 1)]))
    return out


def num_params(cfg: dict) -> int:
    return sum(math.prod(s) for _, bl in layers(cfg) for _, s, _, _ in bl)


def init_params(cfg: dict, seed: int) -> dict:
    """{"layer/blob": array} from the seed."""
    root = jax.random.key(int(seed))
    out = {}
    for lname, blobs in layers(cfg):
        lkey = jax.random.fold_in(root, zlib.crc32(lname.encode("utf-8")))
        for i, (bname, shape, (kind, v), _) in enumerate(blobs):
            key = jax.random.fold_in(lkey, i)
            out[f"{lname}/{bname}"] = (
                jnp.full(shape, v, F32) if kind == "constant"
                else (v * jax.random.normal(key, shape)).astype(F32))
    return out


def lr_mults(cfg: dict) -> dict:
    return {f"{ln}/{bn}": lm for ln, bl in layers(cfg)
            for bn, _, _, lm in bl}


# ---------------------------------------------------------------- the model

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x (T, ..., w): adjacent pairs (2i, 2i+1) turn by t theta^(-2i/w)."""
    t, w = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, w, 2, dtype=F32) / w))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (w // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xo * cos + xe * sin],
                     axis=-1).reshape(x.shape)


def _heads_attention(q, k, v):
    """q, k, v (h, T, hd), one key/value head a query head: causal
    softmax attention -> (h, T, hd)."""
    t = q.shape[1]
    s = jnp.einsum("htd,hsd->hts", q, k) / math.sqrt(q.shape[-1])
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,hsd->htd", p, v)


def grouped_attention(q, k, v):
    """q (T, H, hd), k, v (T, H/g, hd): query head h reads key/value
    head h // g -> (T, H, hd).  The heads go through `_heads_attention`
    `HEAD_CHUNK` at a time, each chunk recomputed in the backward
    pass."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    heads = lambda a: jnp.transpose(a, (1, 0, 2))            # noqa: E731
    q = heads(q)
    k, v = (jnp.repeat(heads(a), g, axis=0) for a in (k, v))  # head h // g
    c = math.gcd(HEAD_CHUNK, h)
    o = lax.map(lambda a: jax.checkpoint(_heads_attention)(*a),
                tuple(a.reshape(h // c, c, t, hd) for a in (q, k, v)))
    return jnp.transpose(o.reshape(h, t, hd), (1, 0, 2))


def attention(p, pre, x, m):
    t = x.shape[0]
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    q = (x @ p[pre + "/W_q"].T).reshape(t, h, hd)
    k = (x @ p[pre + "/W_k"].T).reshape(t, hkv, hd)
    v = (x @ p[pre + "/W_v"].T).reshape(t, hkv, hd)
    q = rope(rms_norm(q, p[pre + "/q_norm"], m["eps"]), m["theta"])
    k = rope(rms_norm(k, p[pre + "/k_norm"], m["eps"]), m["theta"])
    o = grouped_attention(q, k, v)
    return o.reshape(t, h * hd) @ p[pre + "/W_o"].T


def short_conv(p, pre, x, m):
    """The gated short convolution."""
    t, d = x.shape
    bcu = x @ p[pre + "/W_in"].T
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    z = b * u
    taps = p[pre + "/taps"]                                  # (d, L)
    n = taps.shape[1]
    zp = jnp.concatenate([jnp.zeros((n - 1, d), z.dtype), z], axis=0)
    v = sum(zp[j:j + t] * taps[:, j][None, :] for j in range(n))
    return (c * v) @ p[pre + "/W_out"].T


def swiglu(x, w_gate, w_up, w_down):
    """(in, width), (in, width), (width, in) weights."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(p, pre, x, m):
    """-> chosen experts (T, k), their weights (T, k)."""
    s = jax.nn.sigmoid(jnp.matmul(x, p[pre + "/router"],
                                  precision=lax.Precision.HIGHEST))
    _, topi = lax.top_k(s + lax.stop_gradient(p[pre + "/bias"])[None, :],
                        m["k"])
    topv = jnp.take_along_axis(s, topi, axis=1)
    total = jnp.sum(topv, axis=-1, keepdims=True) + m["route_eps"]
    return topi, topv / total * m["factor"]


def moe(p, pre, x, m):
    """This share's part of the expert layer -> (y, rows per held
    expert): every held expert over every token, weighted by what the
    router gave it (0 where it was not chosen)."""
    topi, w = route(p, pre, x, m)

    def one(y, held):
        j, w_gate, w_up, w_down = held
        hit = topi == (m["first"] + j)                        # (T, k)
        wj = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)         # (T,)
        return (y + wj[:, None] * swiglu(x, w_gate, w_up, w_down),
                jnp.sum(hit))

    return lax.scan(one, jnp.zeros_like(x),
                    (jnp.arange(m["held"]), p[pre + "/W_gate"],
                     p[pre + "/W_up"], p[pre + "/W_down"]))


def block(p, i, x, m):
    pre = f"L{i}"
    kind, dense = m["kinds"][i]
    n1 = rms_norm(x, p[pre + ".norm1/scale"], m["eps"])
    if kind == "conv":
        h = x + short_conv(p, pre + ".conv", n1, m)
    else:
        h = x + attention(p, pre + ".attn", n1, m)
    n2 = rms_norm(h, p[pre + ".norm2/scale"], m["eps"])
    if dense:
        f = swiglu(n2, p[pre + ".gate/weight"].T, p[pre + ".up/weight"].T,
                   p[pre + ".down/weight"].T)
        counts = jnp.zeros((m["held"],), jnp.int32)
    else:
        f, counts = moe(p, pre + ".moe", n2, m)
    return h + f, counts


def forward(p, ids, m):
    """ids (T,) int -> logits (T, vocab), rows per held expert of every
    layer run (n_layers, held)."""
    x = p["embed/weight"][ids]
    counts = []
    for i in range(m["n_layers"]):
        x, c = jax.checkpoint(block, static_argnums=(1, 3))(p, i, x, m)
        counts.append(c)
    x = rms_norm(x, p["head.norm/scale"], m["eps"])
    return x @ p["head.logits/weight"].T, jnp.stack(counts)


def loss_sum(p, ids, targets, m):
    """Sum over the sequence's tokens of -log softmax(logits)[target]."""
    logits, counts = forward(p, ids, m)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.sum(lse - picked), counts


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
    """ids, targets (B, T) -> mean loss, mean-loss gradients, rows per
    held expert summed over the sequences; one sequence at a time."""
    fn = jax.jit(jax.value_and_grad(loss_sum, has_aux=True),
                 static_argnums=(3,))
    total, gsum, csum = 0.0, None, 0
    for b in range(ids.shape[0]):
        (lsum, counts), g = fn(p, jnp.asarray(ids[b]),
                               jnp.asarray(targets[b]), m)
        total += float(lsum)
        csum = csum + np.asarray(counts)
        gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
        del g
    n = ids.shape[0] * ids.shape[1]
    scale = jax.jit(lambda a: a / n, donate_argnums=0)
    return total / n, {k: scale(v) for k, v in gsum.items()}, csum


def train_steps(cfg: dict, seed: int, batches, reduce):
    """Follow len(batches) solver iterations from the seed.  batches:
    [(ids (B, T), targets (B, T))] int arrays.  `reduce(name, tree)` is
    handed each compared state as {"layer/blob": host float32 array}
    (p0, then m1, v1, p1 after step 1, p_last after the last) and
    returns what the caller keeps of it; Adam's moments live on the
    host between steps so that the device holds parameters and two
    gradient trees at most.  -> {"losses", "counts", name: reduce()}"""
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
        loss, grads, counts = grads_of_batch(params, ids, targets, m)
        out["losses"].append(loss)
        out["counts"].append(counts)
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

def forward_flops(cfg: dict, seq: int, seqs: int) -> int:
    """Multiply-accumulate work of one forward pass over `seqs`
    sequences of `seq` tokens, from the shapes: per token 2 x the matmul
    parameters it touches (the routed experts as the k x held / experts
    of them this share runs for an even router), plus causal attention,
    2 x 2 x head width x heads x seq / 2 a token an attention layer.
    The embedding is a gather; norms, rotary turns, the convolution's
    taps and gates, the router's sigmoid and the softmaxes are not
    counted."""
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    per_token, scores = 0.0, 0.0
    for kind, dense in m["kinds"]:
        if kind == "conv":
            per_token += 3 * d * d + d * d
        else:
            per_token += 2 * m["h"] * hd * d + 2 * m["hkv"] * hd * d
            scores += 2 * 2 * hd * m["h"] * seq / 2
        if dense:
            per_token += 3 * d * m["dense"]
        else:
            per_token += (d * m["e"]
                          + m["k"] * m["held"] / m["e"] * 3 * d * m["ew"])
    per_token += m["vocab"] * d
    return int(seqs * seq * (2 * per_token + scores))
