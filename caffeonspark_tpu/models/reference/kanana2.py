"""kanana-2-30b-a3b (`model_type: deepseek_v3`), plain: one chip's share.

Written from the deepseek_v3 equations; nothing here imports the
program.  Straightforward jax.numpy in float32, no kernels, a dense
loop over the experts held, one sequence at a time (no term of the
model couples two sequences, and the router couples no two tokens, so
losses and gradients add over sequences).  Products run at the ambient
precision: the benchmark calls this at the precision the configuration
states (JAX's default: one bfloat16 pass on the TPU), the repository's
CPU tests under `jax.default_matmul_precision("highest")`.  Only the
router's product is pinned to HIGHEST, as the model states ("in
float32").

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Attn: q = x W_q -> H x (nope + rope);  x W_kva -> c_kv (kv_lora_rank)
          and ONE k_rope shared by the heads;  RMSNorm(c_kv) W_kvb ->
          H x (k_nope + v);  RoPE on adjacent pairs of the rope parts,
          angle t theta^(-2i/rope);  causal softmax(q k^T / sqrt(nope +
          rope)) v -> W_o
    FFN, layer 0: W_down (silu(x W_gate) * x W_up), width 6144
    FFN, layers 1..: s = sigmoid(x W_g) over ALL experts; the top_k of
          s + b; w_i = s_i / sum(s chosen) * routed_scaling_factor;
          y = sum_{i chosen and held here} w_i E_i(x) + S(x)
    head: RMSNorm, W_out over the vocabulary slice, mean cross-entropy

The share: the experts [first_expert, first_expert + experts_held) of
each expert layer and `vocab_size` rows of the vocabulary; what the
absent experts would add is left out, here as in the program.

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
HEAD_CHUNK = 8          # heads whose (T, T) scores are alive together


# ------------------------------------------------------------------ shapes

class _Dims(dict):
    """The sizes, hashable so that jit and checkpoint take them as a
    static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def dims(cfg: dict) -> dict:
    return _Dims(
        d=int(cfg["hidden_size"]), h=int(cfg["num_attention_heads"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        vd=int(cfg["v_head_dim"]), r=int(cfg["kv_lora_rank"]),
        dense=int(cfg["intermediate_size"]),
        ew=int(cfg["moe_intermediate_size"]),
        e=int(cfg["n_routed_experts"]), k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]),
        held=int(cfg.get("experts_held", cfg["n_routed_experts"])),
        first=int(cfg.get("first_expert", 0)),
        vocab=int(cfg["vocab_size"]), n_layers=int(cfg["num_hidden_layers"]),
        n_dense=int(cfg["first_k_dense_replace"]),
        factor=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        std=float(cfg["assumed"]["init_std"]))


def layers(cfg: dict):
    """[(layer, [(blob, shape, filler, lr_mult)])] in the program's blob
    order (the index i of the key derivation)."""
    m = dims(cfg)
    g = ("gaussian", m["std"])
    one, zero = ("constant", 1.0), ("constant", 0.0)
    d = m["d"]
    out = [("embed", [("weight", (m["vocab"], d), g, 1)])]
    for i in range(m["n_layers"]):
        p = f"L{i}"
        out.append((f"{p}.norm1", [("scale", (d,), one, 1)]))
        out.append((f"{p}.attn", [
            ("W_q", (m["h"] * (m["nope"] + m["rope"]), d), g, 1),
            ("W_kva", (m["r"] + m["rope"], d), g, 1),
            ("kv_norm", (m["r"],), one, 1),
            ("W_kvb", (m["h"] * (m["nope"] + m["vd"]), m["r"]), g, 1),
            ("W_o", (d, m["h"] * m["vd"]), g, 1)]))
        out.append((f"{p}.norm2", [("scale", (d,), one, 1)]))
        if i < m["n_dense"]:
            out.append((f"{p}.gate", [("weight", (m["dense"], d), g, 1)]))
            out.append((f"{p}.up", [("weight", (m["dense"], d), g, 1)]))
            out.append((f"{p}.down", [("weight", (d, m["dense"]), g, 1)]))
        else:
            hs = m["shared"] * m["ew"]
            out.append((f"{p}.moe", [
                ("router", (d, m["e"]), g, 1),
                ("bias", (m["e"],), zero, 0),       # moves only the choice
                ("W_gate", (m["held"], d, m["ew"]), g, 1),
                ("W_up", (m["held"], d, m["ew"]), g, 1),
                ("W_down", (m["held"], m["ew"], d), g, 1),
                ("S_gate", (d, hs), g, 1), ("S_up", (d, hs), g, 1),
                ("S_down", (hs, d), g, 1)]))
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
    """q, k (T, h, dq), v (T, h, dv): causal softmax attention."""
    t = q.shape[0]
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v)


def attention(p, pre, x, m):
    t = x.shape[0]
    h, nope, rp, vd, r = m["h"], m["nope"], m["rope"], m["vd"], m["r"]
    q = (x @ p[pre + "/W_q"].T).reshape(t, h, nope + rp)
    kva = x @ p[pre + "/W_kva"].T
    c_kv = rms_norm(kva[:, :r], p[pre + "/kv_norm"], m["eps"])
    k_rope = rope(kva[:, r:], m["theta"])                    # (T, rope)
    kvb = (c_kv @ p[pre + "/W_kvb"].T).reshape(t, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], m["theta"])],
                        axis=-1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (t, h, rp))],
        axis=-1)
    v = kvb[..., nope:]
    chunk = min(HEAD_CHUNK, h)
    o = jnp.concatenate(
        [jax.checkpoint(_heads_attention)(
            q[:, a:a + chunk], k[:, a:a + chunk], v[:, a:a + chunk])
         for a in range(0, h, chunk)], axis=1)
    return o.reshape(t, h * vd) @ p[pre + "/W_o"].T


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
    return topi, topv / jnp.sum(topv, axis=-1, keepdims=True) * m["factor"]


def moe(p, pre, x, m, *, routed=True, shared=True):
    """This share's part of the expert layer -> (y, rows per held
    expert).  `routed` / `shared` False leave that part out (the share
    test, and the rehearsal that a missing part reads `correct`
    false)."""
    topi, w = route(p, pre, x, m)
    y = jnp.zeros_like(x)
    counts = []
    for j in range(m["held"]):
        hit = topi == (m["first"] + j)                        # (T, k)
        counts.append(jnp.sum(hit))
        if routed:
            wj = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)     # (T,)
            y = y + wj[:, None] * swiglu(
                x, p[pre + "/W_gate"][j], p[pre + "/W_up"][j],
                p[pre + "/W_down"][j])
    if shared:
        y = y + swiglu(x, p[pre + "/S_gate"], p[pre + "/S_up"],
                       p[pre + "/S_down"])
    return y, jnp.stack(counts)


def block(p, i, x, m):
    pre = f"L{i}"
    h = x + attention(p, pre + ".attn",
                      rms_norm(x, p[pre + ".norm1/scale"], m["eps"]), m)
    n2 = rms_norm(h, p[pre + ".norm2/scale"], m["eps"])
    if i < m["n_dense"]:
        f = swiglu(n2, p[pre + ".gate/weight"].T, p[pre + ".up/weight"].T,
                   p[pre + ".down/weight"].T)
        counts = jnp.zeros((m["held"],), jnp.int32)
    else:
        f, counts = moe(p, pre + ".moe", n2, m)
    return h + f, counts


def forward(p, ids, m):
    """ids (T,) int -> logits (T, vocab), rows per held expert of every
    layer (n_layers, held)."""
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
    2 x (qk width + v width) x heads x seq / 2 a token a layer.  The
    embedding is a gather; norms, rotary turns, the router's sigmoid and
    the softmaxes are not counted."""
    m = dims(cfg)
    d = m["d"]
    attn = (m["h"] * (m["nope"] + m["rope"]) * d + (m["r"] + m["rope"]) * d
            + m["h"] * (m["nope"] + m["vd"]) * m["r"] + d * m["h"] * m["vd"])
    expert = 3 * d * m["ew"]
    per_token = 0.0
    for i in range(m["n_layers"]):
        per_token += attn
        if i < m["n_dense"]:
            per_token += 3 * d * m["dense"]
        else:
            per_token += (d * m["e"] + m["shared"] * expert
                          + m["k"] * m["held"] / m["e"] * expert)
    per_token += m["vocab"] * d
    scores = (m["n_layers"] * 2 * (m["nope"] + m["rope"] + m["vd"])
              * m["h"] * seq / 2)
    return int(seqs * seq * (2 * per_token + scores))
