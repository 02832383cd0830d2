"""Qwen3-Next-80B-A3B-Instruct (`model_type: qwen3_next`), plain: one
chip's share.

Written from the qwen3_next equations; nothing here imports the program.
Straightforward jax.numpy in float32, no kernels, the gated delta rule
token by token as written, a dense pass over every expert held, one
sequence at a time (no term of the model couples two sequences, and the
router couples no two tokens, so losses and gradients add over
sequences).  Products run at the ambient precision: the benchmark calls
this at the precision the configuration states (JAX's default: one
bfloat16 pass on the TPU), the repository's CPU tests under
`jax.default_matmul_precision("highest")`.  The router's product is
pinned to HIGHEST, as the program's is, and the recurrence multiplies
and adds elementwise in float32 (the program's chunked products of the
state are at HIGHEST).

    h = x + Op(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    Op of published layer i: full attention where (i + 1) %
    full_attention_interval == 0, Gated DeltaNet otherwise.
    Gated DeltaNet: [q, k, v, z] = x W_qkvz (whole blocks, in this
          order);  [b, a] = x W_ba;  [q, k, v] <- silu(conv(concat(q, k,
          v))), depthwise, causal, linear_conv_kernel_dim taps, zero
          before t = 0, no bias;  beta = sigmoid(b);  g = -exp(A_log)
          softplus(a + dt_bias);  key head h // R serves value head h;
          q <- q / |q| / sqrt(dk), k <- k / |k| (x rsqrt(sum x^2 +
          1e-6));  per value head, S (dk, dv) from 0:
              S' = e^(g_t) S;  S = S' + k_t (beta_t (v_t - S'^T k_t))^T;
              o_t = S^T q_t
          o <- RMSNorm(o) w silu(z), one dv-wide w;  y = o W_out
    Full attention: [q, gate] = x W_q, a head's 2 x head_dim side by
          side;  k = x W_k, v = x W_v -> H/g x head_dim;  RMSNorm over
          each q and k head (one head_dim-wide scale each);  RoPE on
          adjacent pairs of the FIRST rotary_dim = head_dim x
          partial_rotary_factor dims, angle t theta^(-2i/rotary_dim),
          the rest left as they are;  query head h reads key/value head
          h // g;  causal softmax(q k^T / sqrt(head_dim)) v;
          y = (o sigmoid(gate)) W_o
    MoE: p = softmax(x W_g) over ALL experts;  the num_experts_per_tok
          largest;  w_i = p_i / sum(chosen);  sum_{i chosen and held
          here} w_i E_i(x), E a SiLU-gated feed-forward;  plus
          sigmoid(x w_sg) Shared(x)
    head: RMSNorm, W_out over the vocabulary slice, mean cross-entropy

Every RMSNorm of the family is y = x / rms(x) (1 + w) with w filled 0;
here one scale filled 1: the same function, and without weight decay the
same Adam trajectory.

The layers run are the published layers [first_layer, first_layer +
num_hidden_layers), named L0, L1, ... in that order.  The share: the
experts [first_expert, first_expert + experts_held) of each layer and
`vocab_size` rows of the vocabulary; what the absent experts would add
is left out, here as in the program.

At T = 8,192 the scores of all 16 heads would be 4.3 GB and the states
of every token 17 GB: attention goes over the heads `HEAD_CHUNK` at a
time and the recurrence over `SEGMENT` tokens at a time (each chunk and
each segment recomputed in the backward pass), so neither is ever whole.

Seeded draws follow the derivation the program documents (net.py
`Net.init`): blob i of layer L <- fill(fold_in(fold_in(key(seed),
crc32(L)), i)), gaussian(std) = std * normal(key, shape), log_uniform
(lo, hi) = log(uniform(key, shape, lo, hi)).
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
SEGMENT = 64            # tokens whose states are alive together


# ------------------------------------------------------------------ shapes

class _Dims(dict):
    """The sizes, hashable so that jit and checkpoint take them as a
    static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def dims(cfg: dict) -> dict:
    first = int(cfg.get("first_layer", 0))
    n = int(cfg["num_hidden_layers"])
    hd = int(cfg["head_dim"])
    every = int(cfg["full_attention_interval"])
    return _Dims(
        d=int(cfg["hidden_size"]), h=int(cfg["num_attention_heads"]),
        hkv=int(cfg["num_key_value_heads"]), hd=hd,
        rd=int(round(hd * float(cfg["partial_rotary_factor"]))),
        lk=int(cfg["linear_num_key_heads"]),
        lv=int(cfg["linear_num_value_heads"]),
        dk=int(cfg["linear_key_head_dim"]),
        dv=int(cfg["linear_value_head_dim"]),
        taps=int(cfg["linear_conv_kernel_dim"]),
        ew=int(cfg["moe_intermediate_size"]),
        sw=int(cfg["shared_expert_intermediate_size"]),
        e=int(cfg["num_experts"]), k=int(cfg["num_experts_per_tok"]),
        held=int(cfg.get("experts_held", cfg["num_experts"])),
        first=int(cfg.get("first_expert", 0)),
        vocab=int(cfg["vocab_size"]), n_layers=n,
        # per layer run: its operator
        kinds=tuple("full_attention" if (first + i + 1) % every == 0
                    else "linear_attention" for i in range(n)),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        a_lo=float(cfg["assumed"]["A_log_uniform"][0]),
        a_hi=float(cfg["assumed"]["A_log_uniform"][1]),
        dt_bias=float(cfg["assumed"]["dt_bias"]),
        std=float(cfg["assumed"]["init_std"]))


def layers(cfg: dict):
    """[(layer, [(blob, shape, filler, lr_mult)])] in the program's blob
    order (the index i of the key derivation)."""
    m = dims(cfg)
    g = ("gaussian", m["std"])
    one = ("constant", 1.0)
    d, hd = m["d"], m["hd"]
    kw, vw = m["lk"] * m["dk"], m["lv"] * m["dv"]
    out = [("embed", [("weight", (m["vocab"], d), g, 1)])]
    for i, kind in enumerate(m["kinds"]):
        p = f"L{i}"
        out.append((f"{p}.norm1", [("scale", (d,), one, 1)]))
        if kind == "linear_attention":
            out.append((f"{p}.gdn", [
                ("W_qkvz", (2 * kw + 2 * vw, d), g, 1),
                ("W_ba", (2 * m["lv"], d), g, 1),
                ("taps", (2 * kw + vw, m["taps"]), g, 1),
                ("A_log", (m["lv"],), ("log_uniform", (m["a_lo"],
                                                       m["a_hi"])), 1),
                ("dt_bias", (m["lv"],), ("constant", m["dt_bias"]), 1),
                ("norm", (m["dv"],), one, 1),
                ("W_out", (d, vw), g, 1)]))
        else:
            out.append((f"{p}.attn", [
                ("W_q", (m["h"] * 2 * hd, d), g, 1),
                ("W_k", (m["hkv"] * hd, d), g, 1),
                ("W_v", (m["hkv"] * hd, d), g, 1),
                ("W_o", (d, m["h"] * hd), g, 1),
                ("q_norm", (hd,), one, 1), ("k_norm", (hd,), one, 1)]))
        out.append((f"{p}.norm2", [("scale", (d,), one, 1)]))
        out.append((f"{p}.moe", [
            ("router", (d, m["e"]), g, 1),
            ("W_gate", (m["held"], d, m["ew"]), g, 1),
            ("W_up", (m["held"], d, m["ew"]), g, 1),
            ("W_down", (m["held"], m["ew"], d), g, 1),
            ("S_gate", (d, m["sw"]), g, 1), ("S_up", (d, m["sw"]), g, 1),
            ("S_down", (m["sw"], d), g, 1), ("S_sgate", (d, 1), g, 1)]))
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
            if kind == "constant":
                a = jnp.full(shape, v, F32)
            elif kind == "log_uniform":
                a = jnp.log(jax.random.uniform(key, shape, F32, *v))
            else:
                a = (v * jax.random.normal(key, shape)).astype(F32)
            out[f"{lname}/{bname}"] = a
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


def partial_rope(x, theta, rd):
    """The first rd dims of the last axis turn, the rest stay."""
    return jnp.concatenate([rope(x[..., :rd], theta), x[..., rd:]], axis=-1)


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
    """Gated full attention."""
    t = x.shape[0]
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    qg = (x @ p[pre + "/W_q"].T).reshape(t, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(t, h * hd)
    k = (x @ p[pre + "/W_k"].T).reshape(t, hkv, hd)
    v = (x @ p[pre + "/W_v"].T).reshape(t, hkv, hd)
    q = partial_rope(rms_norm(q, p[pre + "/q_norm"], m["eps"]),
                     m["theta"], m["rd"])
    k = partial_rope(rms_norm(k, p[pre + "/k_norm"], m["eps"]),
                     m["theta"], m["rd"])
    o = grouped_attention(q, k, v).reshape(t, h * hd)
    return (o * jax.nn.sigmoid(gate)) @ p[pre + "/W_o"].T


def _tokens(state, xs):
    """The gated delta rule over a run of tokens, one at a time, as
    written.  state (H, dk, dv); q, k (n, H, dk), v (n, H, dv), g, beta
    (n, H) -> state after, o (n, H, dv).  Multiplies and adds in
    float32, no matrix product."""
    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]                  # S'
        read = jnp.sum(s * k_t[:, :, None], axis=1)          # S'^T k
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
        return s, jnp.sum(s * q_t[:, :, None], axis=1)       # S^T q
    return lax.scan(token, state, xs)


def delta_rule(q, k, v, g, beta):
    """q, k (T, H, dk), v (T, H, dv), g, beta (T, H) -> o (T, H, dv),
    the state from 0.  `SEGMENT` tokens at a time, each segment
    recomputed in the backward pass, which so keeps the states at the
    segments' edges only.  A last partial segment is filled with tokens
    that neither write (beta 0) nor decay (g 0)."""
    t, h, dk = q.shape
    seg = min(SEGMENT, t)
    n = -(-t // seg)
    xs = tuple(jnp.pad(a, ((0, n * seg - t),) + ((0, 0),) * (a.ndim - 1)
                       ).reshape((n, seg) + a.shape[1:])
               for a in (q, k, v, g, beta))
    _, o = lax.scan(jax.checkpoint(_tokens),
                    jnp.zeros((h, dk, v.shape[-1]), F32), xs)
    return o.reshape((n * seg,) + o.shape[2:])[:t]


def l2_norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def gated_delta_net(p, pre, x, m):
    t = x.shape[0]
    lk, lv, dk, dv = m["lk"], m["lv"], m["dk"], m["dv"]
    kw, vw, r = lk * dk, lv * dv, lv // lk
    qkvz = x @ p[pre + "/W_qkvz"].T
    ba = x @ p[pre + "/W_ba"].T
    taps = p[pre + "/taps"]                                  # (C, L)
    n = taps.shape[1]
    c = 2 * kw + vw
    zp = jnp.concatenate([jnp.zeros((n - 1, c), F32), qkvz[:, :c]], axis=0)
    qkv = jax.nn.silu(sum(zp[j:j + t] * taps[:, j][None, :]
                          for j in range(n)))
    z = qkvz[:, c:].reshape(t, lv, dv)
    beta = jax.nn.sigmoid(ba[:, :lv])
    g = -jnp.exp(p[pre + "/A_log"]) * jax.nn.softplus(
        ba[:, lv:] + p[pre + "/dt_bias"])
    q = l2_norm(qkv[:, :kw].reshape(t, lk, dk)) / math.sqrt(dk)
    k = l2_norm(qkv[:, kw:2 * kw].reshape(t, lk, dk))
    # value head h reads key head h // r
    q, k = (jnp.repeat(a, r, axis=1) for a in (q, k))
    o = delta_rule(q, k, qkv[:, 2 * kw:].reshape(t, lv, dv), g, beta)
    o = rms_norm(o, p[pre + "/norm"], m["eps"]) * jax.nn.silu(z)
    return o.reshape(t, vw) @ p[pre + "/W_out"].T


def swiglu(x, w_gate, w_up, w_down):
    """(in, width), (in, width), (width, in) weights."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(p, pre, x, m):
    """-> chosen experts (T, k), their weights (T, k)."""
    s = jax.nn.softmax(jnp.matmul(x, p[pre + "/router"],
                                  precision=lax.Precision.HIGHEST), axis=-1)
    topv, topi = lax.top_k(s, m["k"])
    return topi, topv / jnp.sum(topv, axis=-1, keepdims=True)


def moe(p, pre, x, m):
    """This share's part of the expert layer -> (y, rows per held
    expert): every held expert over every token, weighted by what the
    router gave it (0 where it was not chosen), plus the gated shared
    expert."""
    topi, w = route(p, pre, x, m)

    def one(y, held):
        j, w_gate, w_up, w_down = held
        hit = topi == (m["first"] + j)                        # (T, k)
        wj = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)         # (T,)
        return (y + wj[:, None] * swiglu(x, w_gate, w_up, w_down),
                jnp.sum(hit))

    y, counts = lax.scan(one, jnp.zeros_like(x),
                         (jnp.arange(m["held"]), p[pre + "/W_gate"],
                          p[pre + "/W_up"], p[pre + "/W_down"]))
    shared = swiglu(x, p[pre + "/S_gate"], p[pre + "/S_up"],
                    p[pre + "/S_down"])
    return y + jax.nn.sigmoid(x @ p[pre + "/S_sgate"]) * shared, counts


def block(p, i, x, m):
    pre = f"L{i}"
    n1 = rms_norm(x, p[pre + ".norm1/scale"], m["eps"])
    if m["kinds"][i] == "linear_attention":
        h = x + gated_delta_net(p, pre + ".gdn", n1, m)
    else:
        h = x + attention(p, pre + ".attn", n1, m)
    n2 = rms_norm(h, p[pre + ".norm2/scale"], m["eps"])
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

def scan_flops(cfg: dict, seq: int, seqs: int) -> int:
    """Operations of the recurrence AS WRITTEN, one forward pass of all
    the Gated DeltaNet layers run: per token and value head the read
    S'^T k, the rank-one write and the read S^T q, 2 x dk x dv each
    (the state's decay, an elementwise pass over it, is not counted)."""
    m = dims(cfg)
    n = sum(kind == "linear_attention" for kind in m["kinds"])
    return n * seqs * seq * m["lv"] * 3 * 2 * m["dk"] * m["dv"]


def scan_bytes(cfg: dict, seq: int, seqs: int) -> int:
    """Bytes one such pass has to move: q and k (a key head each), v,
    g and beta read and o written once, float32 as stored; the state
    never leaves the chip's fast memory in this count."""
    m = dims(cfg)
    n = sum(kind == "linear_attention" for kind in m["kinds"])
    per_token = (2 * m["lk"] * m["dk"] + 2 * m["lv"] * m["dv"]
                 + 2 * m["lv"])
    return n * seqs * seq * per_token * 4


def forward_flops(cfg: dict, seq: int, seqs: int) -> int:
    """Multiply-accumulate work of one forward pass over `seqs`
    sequences of `seq` tokens, from the shapes: per token 2 x the matmul
    parameters it touches (the routed experts as the k x held / experts
    of them this share runs for an even router), plus causal attention,
    2 x 2 x head width x heads x seq / 2 a token an attention layer,
    plus the recurrence as written (`scan_flops`).  The embedding is a
    gather; norms, rotary turns, taps, gates, decays and the softmaxes
    are not counted."""
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    kw, vw = m["lk"] * m["dk"], m["lv"] * m["dv"]
    per_token, scores = 0.0, 0.0
    for kind in m["kinds"]:
        if kind == "linear_attention":
            per_token += (2 * kw + 2 * vw) * d + 2 * m["lv"] * d + vw * d
        else:
            per_token += (3 * m["h"] * hd * d + 2 * m["hkv"] * hd * d)
            scores += 2 * 2 * hd * m["h"] * seq / 2
        per_token += (d * m["e"]
                      + m["k"] * m["held"] / m["e"] * 3 * d * m["ew"]
                      + 3 * d * m["sw"] + d)
    per_token += m["vocab"] * d
    return int(seqs * seq * (2 * per_token + scores)) \
        + scan_flops(cfg, seq, seqs)
