"""SmallThinker-21BA3B-Instruct (`model_name: smallthinker_21b_instruct`),
plain: one chip's share.

Written from the family's equations; nothing here imports the program.
Straightforward jax.numpy in float32, no kernels, a dense pass over
every expert held, one sequence at a time (no term of the model couples
two sequences, and the router couples no two tokens, so losses and
gradients add over sequences).  Products run at the ambient precision:
the benchmark calls this at the precision the configuration states
(JAX's default: one bfloat16 pass on the TPU), the repository's CPU
tests under `jax.default_matmul_precision("highest")`.  Only the
router's product is pinned to HIGHEST, as the program's is.

    n1 = RMSNorm(x);  h = x + Attn_i(n1);  n2 = RMSNorm(h)
    y = h + MoE(router reads n1, experts read n2)
    Attn_i: q = n1 W_q -> H x 128;  k = n1 W_k, v = n1 W_v -> H/g x 128;
          no norm on q or k, no bias;  query head h reads key/value
          head h // g;  o = softmax(q k^T / sqrt(128) + mask) v -> W_o.
          Where sliding_window_layout[i] is 1, row t sees the columns s
          with t - W < s <= t (W = sliding_window_size keys, its own
          among them); where it is 0, every s <= t.  Where
          rope_layout[i] is 1, q and k turn by position on adjacent
          pairs of the whole head, angle t theta^(-2j/128); where it is
          0 nothing carries a position: the mask alone orders the
          tokens.
    MoE:  l = n1 W_g over ALL experts (float32, HIGHEST);  the top_k of
          l;  w = softmax over those top_k logits;
          y = sum_{i chosen and held here} w_i E_i(n2),
          E(u) = (relu(u W_gate) * (u W_up)) W_down;  none shared
    head: RMSNorm, W_out over the vocabulary slice, mean cross-entropy

`assumed.router_reads` "n2" is the other reading of "router placed
before attention" that the tests hold against this one: a router fed
what the experts are fed.

The layers run are the published layers [first_layer, first_layer +
num_hidden_layers), named L0, L1, ... in that order.  The share: the
experts [first_expert, first_expert + experts_held) of each layer and
`vocab_size` rows of the vocabulary; what the absent experts would add
is left out, here as in the program.

At T = 16,384 the scores of one head are 1 GB: attention goes over the
heads `HEAD_CHUNK` at a time and over the query rows `Q_BLOCK` at a
time, each block against the key columns one of its rows can see and no
others (the mask is built from t - s inside the block), each piece
recomputed in the backward pass: no (T, T) matrix is ever whole.

Seeded draws follow the derivation the program documents (net.py
`Net.init`): blob i of layer L <- fill(fold_in(fold_in(key(seed),
crc32(L)), i)), gaussian(std) = std * normal(key, shape).  Every matrix
takes `assumed.init_std` but the embedding, which takes
`assumed.embed_std` where the configuration gives one.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HEAD_CHUNK = 4          # heads whose score blocks are alive together
Q_BLOCK = 2048          # query rows of a score block


# ------------------------------------------------------------------ shapes

class _Dims(dict):
    """The sizes, hashable so that jit and checkpoint take them as a
    static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def dims(cfg: dict) -> dict:
    first = int(cfg.get("first_layer", 0))
    n = int(cfg["num_hidden_layers"])
    window = int(cfg["sliding_window_size"])
    return _Dims(
        d=int(cfg["hidden_size"]), h=int(cfg["num_attention_heads"]),
        hkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        ew=int(cfg["moe_ffn_hidden_size"]),
        e=int(cfg["moe_num_primary_experts"]),
        k=int(cfg["moe_num_active_primary_experts"]),
        held=int(cfg.get("experts_held", cfg["moe_num_primary_experts"])),
        first=int(cfg.get("first_expert", 0)),
        vocab=int(cfg["vocab_size"]), n_layers=n,
        # per layer run: (window in keys, 0 = the whole past; rotary?)
        kinds=tuple((window * int(cfg["sliding_window_layout"][first + i]),
                     bool(cfg["rope_layout"][first + i]))
                    for i in range(n)),
        router_reads=str(cfg["assumed"].get("router_reads", "n1")),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        std=float(cfg["assumed"]["init_std"]),
        embed_std=float(cfg["assumed"].get("embed_std",
                                           cfg["assumed"]["init_std"])))


def layers(cfg: dict):
    """[(layer, [(blob, shape, filler, lr_mult)])] in the program's blob
    order (the index i of the key derivation)."""
    m = dims(cfg)
    g = ("gaussian", m["std"])
    one = ("constant", 1.0)
    d, hd = m["d"], m["hd"]
    out = [("embed", [("weight", (m["vocab"], d),
                       ("gaussian", m["embed_std"]), 1)])]
    for i in range(m["n_layers"]):
        p = f"L{i}"
        out.append((f"{p}.norm1", [("scale", (d,), one, 1)]))
        out.append((f"{p}.attn", [
            ("W_q", (m["h"] * hd, d), g, 1),
            ("W_k", (m["hkv"] * hd, d), g, 1),
            ("W_v", (m["hkv"] * hd, d), g, 1),
            ("W_o", (d, m["h"] * hd), g, 1)]))
        out.append((f"{p}.norm2", [("scale", (d,), one, 1)]))
        out.append((f"{p}.moe", [
            ("router", (d, m["e"]), g, 1),
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


def _block_attention(q, k, v, first_row: int, first_col: int, window: int):
    """q (h, R, hd) rows first_row..., k, v (h, C, hd) columns
    first_col...: softmax attention of the rows over the columns they
    see, the mask from t - s -> (h, R, hd).  Every row sees a column
    (its own)."""
    s = jnp.einsum("htd,hsd->hts", q, k) / math.sqrt(q.shape[-1])
    ahead = (first_row + jnp.arange(q.shape[1])[:, None]
             - first_col - jnp.arange(k.shape[1])[None, :])     # t - s
    seen = ahead >= 0
    if window:
        seen &= ahead < window
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,hsd->htd", p, v)


def _heads_attention(q, k, v, window: int):
    """q, k, v (h, T, hd), one key/value head a query head -> (h, T,
    hd): the query rows a block at a time, each over the columns from
    the first one its first row sees to its last row's own."""
    t = q.shape[1]
    rows = Q_BLOCK if t % Q_BLOCK == 0 else t
    out = []
    for lo in range(0, t, rows):
        c0 = max(0, lo - window + 1) if window else 0
        out.append(jax.checkpoint(
            _block_attention, static_argnums=(3, 4, 5))(
                q[:, lo:lo + rows], k[:, c0:lo + rows], v[:, c0:lo + rows],
                lo, c0, window))
    return jnp.concatenate(out, axis=1)


def grouped_attention(q, k, v, window: int = 0):
    """q (T, H, hd), k, v (T, H/g, hd): query head h reads key/value
    head h // g -> (T, H, hd); `window` keys a row, 0 = its whole past.
    The heads go through `_heads_attention` `HEAD_CHUNK` at a time."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    heads = lambda a: jnp.transpose(a, (1, 0, 2))            # noqa: E731
    q = heads(q)
    k, v = (jnp.repeat(heads(a), g, axis=0) for a in (k, v))  # head h // g
    c = math.gcd(HEAD_CHUNK, h)
    o = lax.map(lambda a: _heads_attention(*a, window),
                tuple(a.reshape(h // c, c, t, hd) for a in (q, k, v)))
    return jnp.transpose(o.reshape(h, t, hd), (1, 0, 2))


def attention(p, pre, x, m, window: int, rotary: bool):
    t = x.shape[0]
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    q = (x @ p[pre + "/W_q"].T).reshape(t, h, hd)
    k = (x @ p[pre + "/W_k"].T).reshape(t, hkv, hd)
    v = (x @ p[pre + "/W_v"].T).reshape(t, hkv, hd)
    if rotary:
        q, k = rope(q, m["theta"]), rope(k, m["theta"])
    o = grouped_attention(q, k, v, window)
    return o.reshape(t, h * hd) @ p[pre + "/W_o"].T


def reglu(x, w_gate, w_up, w_down):
    """(in, width), (in, width), (width, in) weights."""
    return (jax.nn.relu(x @ w_gate) * (x @ w_up)) @ w_down


def route(p, pre, x, m):
    """x = what the router reads -> chosen experts (T, k), their weights
    (T, k): softmax over the chosen logits."""
    logits = jnp.matmul(x, p[pre + "/router"],
                        precision=lax.Precision.HIGHEST)
    topl, topi = lax.top_k(logits, m["k"])
    return topi, jax.nn.softmax(topl, axis=-1)


def moe(p, pre, routed_from, x, m):
    """This share's part of the expert layer -> (y, rows per held
    expert): every held expert over every token of x, weighted by what
    the router (reading `routed_from`) gave it, 0 where it was not
    chosen."""
    topi, w = route(p, pre, routed_from, m)

    def one(y, held):
        j, w_gate, w_up, w_down = held
        hit = topi == (m["first"] + j)                        # (T, k)
        wj = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)         # (T,)
        return (y + wj[:, None] * reglu(x, w_gate, w_up, w_down),
                jnp.sum(hit))

    return lax.scan(one, jnp.zeros_like(x),
                    (jnp.arange(m["held"]), p[pre + "/W_gate"],
                     p[pre + "/W_up"], p[pre + "/W_down"]))


def block(p, i, x, m):
    pre = f"L{i}"
    window, rotary = m["kinds"][i]
    n1 = rms_norm(x, p[pre + ".norm1/scale"], m["eps"])
    h = x + attention(p, pre + ".attn", n1, m, window, rotary)
    n2 = rms_norm(h, p[pre + ".norm2/scale"], m["eps"])
    f, counts = moe(p, pre + ".moe",
                    n1 if m["router_reads"] == "n1" else n2, n2, m)
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

def visible_pairs(seq: int, window: int) -> int:
    """(row, column) pairs a head scores over `seq` rows: row r sees
    min(r + 1, window) columns under a window, and its causal past
    counted as seq / 2 columns a row without one (the count the other
    configurations' `forward_flops` take)."""
    if 0 < window < seq:
        return window * (window + 1) // 2 + (seq - window) * window
    return seq * seq // 2


def window_attn_flops(cfg: dict, seq: int, seqs: int) -> int:
    """Operations of one forward pass of the windowed layers' attention
    as written: per visible pair and query head the score and the
    weighted value, 2 x 2 x head width."""
    m = dims(cfg)
    return seqs * sum(4 * visible_pairs(seq, w) * m["hd"] * m["h"]
                      for w, _ in m["kinds"] if w)


def window_attn_bytes(cfg: dict, seq: int, seqs: int,
                      operand_bytes: int = 2) -> int:
    """Bytes of the same pass: q read and o written once a query head,
    k and v read once a key/value head, at the operands' width (one
    bfloat16 pass at the stated precision)."""
    m = dims(cfg)
    n = sum(1 for w, _ in m["kinds"] if w)
    return seqs * n * seq * m["hd"] * operand_bytes * (
        2 * m["h"] + 2 * m["hkv"])


def forward_flops(cfg: dict, seq: int, seqs: int) -> int:
    """Multiply-accumulate work of one forward pass over `seqs`
    sequences of `seq` tokens, from the shapes: per token 2 x the matmul
    parameters it touches (the routed experts as the k x held / experts
    of them this share runs for an even router), plus attention over
    the scores a row can see AND NO OTHERS: 2 x 2 x head width x heads
    a visible pair (`visible_pairs`).  The embedding is a gather; norms,
    rotary turns, the router's softmax and the attention's are not
    counted."""
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    per_token = m["n_layers"] * (
        2 * m["h"] * hd * d + 2 * m["hkv"] * hd * d + d * m["e"]
        + m["k"] * m["held"] / m["e"] * 3 * d * m["ew"])
    per_token += m["vocab"] * d
    pairs = sum(visible_pairs(seq, w) for w, _ in m["kinds"])
    return int(seqs * (seq * 2 * per_token + 4 * hd * m["h"] * pairs))
