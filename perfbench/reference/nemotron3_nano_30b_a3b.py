"""NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type: nemotron_h`), plain: one
chip's share of an expert-parallel pipeline stage.

Written from the family's equations; nothing here imports the program.
Straightforward jax.numpy in float32, no kernels, one sequence at a time
(no term of the model couples two sequences, so losses and gradients add
over sequences).  Products run at the ambient precision: the benchmark
calls this at the precision the configuration states (JAX's default: one
bfloat16 pass on the TPU), the repository's CPU tests under
`jax.default_matmul_precision("highest")`.  The Mamba-2 recurrence is a
scan over time, one token a step, elementwise float32 either way (the
products with the state and the sums over it among them): what the
program's chunked products at HIGHEST are held to.

Every block i of the layers run is ONE operator, pre-norm:

    x <- x + Op_i(RMSNorm(x))       eps `layer_norm_epsilon`, one scale
    Op_i by `hybrid_override_pattern[first_layer + i]`:

    M, the Mamba-2 mixer (H = `mamba_num_heads` heads of P =
       `mamba_head_dim`, G = `n_groups`, N = `ssm_state_size`):
          [z | xBC | dt] = h W_in            H P, H P + 2 G N, H wide
          xBC = silu(taps over time of xBC + conv_bias), causal,
                `conv_kernel` taps a channel
          [u | B | C] = xBC                  head j reads group j // (H / G)
          dt = softplus(dt + dt_bias);  A = -exp(A_log), a scalar a head
          S_t[j] = exp(dt_t[j] A[j]) S_(t-1)[j] + dt_t[j] u_t[j] B_t[g]^T
          y_t[j] = S_t[j] C_t[g] + D[j] u_t[j],    S_(-1) = 0
          y = RMSNorm(y * silu(z)) over each of the G groups of H P / G
              channels (eps `layer_norm_epsilon`), times an H P-wide scale
          output y W_out
    E, the expert layer: s = sigmoid(h W_r) over all `n_routed_experts`
          at HIGHEST precision; the `num_experts_per_tok` largest of
          s + b are chosen (b the selection bias: 0 and frozen);
          weights `routed_scaling_factor` s_i / (sum of the chosen s +
          1e-20);  sum_i w_i W2_i relu(W1_i h)^2 over the chosen
          experts THIS SHARE HOLDS (`experts_held` from `first_expert`
          on: what the absent ones would add is left out), plus the
          shared expert W2_s relu(W1_s h)^2, ungated like them
    *, attention: `num_attention_heads` query heads over
          `num_key_value_heads` key/value heads of `head_dim`, no bias,
          NO position of any kind, causal, 1 / sqrt(head_dim)
    head: RMSNorm, logits over the vocabulary slice (untied), mean
          cross-entropy.

The layers run are the published blocks [first_layer, first_layer +
num_hidden_layers), named L0, L1, ... in that order; the vocabulary is
its first `vocab_size` rows.

`SCAN_BLOCK` steps of the recurrence go under one `jax.checkpoint` (0:
the whole sequence is one `lax.scan` and nothing is computed twice);
attention goes `HEAD_CHUNK` heads and `Q_BLOCK` query rows at a time,
every block a `jax.checkpoint`: what a row of 8,192 tokens needs to fit
beside the parameters on one chip.

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
    a = cfg["assumed"]
    e = int(cfg["n_routed_experts"])
    return _Dims(
        d=int(cfg["hidden_size"]), h=int(cfg["num_attention_heads"]),
        hkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        mh=int(cfg["mamba_num_heads"]), mp=int(cfg["mamba_head_dim"]),
        g=int(cfg["n_groups"]), n=int(cfg["ssm_state_size"]),
        taps=int(cfg["conv_kernel"]),
        ew=int(cfg["moe_intermediate_size"]),
        sw=int(cfg["moe_shared_expert_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        e=e, k=int(cfg["num_experts_per_tok"]),
        factor=float(cfg["routed_scaling_factor"]),
        held=int(cfg.get("experts_held", e)),
        first_expert=int(cfg.get("first_expert", 0)),
        vocab=int(cfg["vocab_size"]), n_layers=n, first=first,
        kinds=cfg["hybrid_override_pattern"][first:first + n],
        eps=float(cfg["layer_norm_epsilon"]), std=float(a["init_std"]),
        conv_bound=float(a["conv_bound"]),
        dt_min=float(cfg["time_step_min"]),
        dt_max=float(cfg["time_step_max"]))


def layers(cfg: dict):
    """[(layer, [(blob, shape, filler, lr_mult)])] in the program's blob
    order (the index i of the key derivation)."""
    m = dims(cfg)
    g = ("gaussian", m["std"])
    conv = ("uniform", -m["conv_bound"], m["conv_bound"])
    one, zero = ("constant", 1.0), ("constant", 0.0)
    d, hd = m["d"], m["hd"]
    di = m["mh"] * m["mp"]
    cw = di + 2 * m["g"] * m["n"]
    out = [("embed", [("weight", (m["vocab"], d), g, 1)])]
    for i, kind in enumerate(m["kinds"]):
        p = f"L{i}"
        if kind == "M":
            out.append((f"{p}.norm1", [("scale", (d,), one, 1)]))
            out.append((f"{p}.mamba2", [
                ("W_in", (di + cw + m["mh"], d), g, 1),
                ("taps", (cw, m["taps"]), conv, 1),
                ("conv_bias", (cw,), conv, 1),
                ("dt_bias", (m["mh"],), ("inv_softplus_log_uniform",
                                         m["dt_min"], m["dt_max"]), 1),
                ("A_log", (m["mh"],), ("log_arange",), 1),
                ("D", (m["mh"],), one, 1), ("norm", (di,), one, 1),
                ("W_out", (d, di), g, 1)]))
        elif kind == "*":
            out.append((f"{p}.norm1", [("scale", (d,), one, 1)]))
            out.append((f"{p}.attn", [
                ("W_q", (m["h"] * hd, d), g, 1),
                ("W_k", (m["hkv"] * hd, d), g, 1),
                ("W_v", (m["hkv"] * hd, d), g, 1),
                ("W_o", (d, m["h"] * hd), g, 1)]))
        elif kind == "E":
            out.append((f"{p}.norm2", [("scale", (d,), one, 1)]))
            out.append((f"{p}.moe", [
                ("router", (d, m["e"]), g, 1), ("bias", (m["e"],), zero, 0),
                ("W1", (m["held"], d, m["ew"]), g, 1),
                ("W2", (m["held"], m["ew"], d), g, 1),
                ("S_up", (d, m["sw"]), g, 1),
                ("S_down", (m["sw"], d), g, 1)]))
        else:
            raise ValueError(f"pattern letter {kind!r}")
    out.append(("head.norm", [("scale", (d,), one, 1)]))
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

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def causal_taps(a, taps):
    """a (T, C), taps (C, L): tap j multiplies the input at t - (L - 1)
    + j, zero before t = 0."""
    n, t = taps.shape[1], a.shape[0]
    ap = jnp.pad(a, ((n - 1, 0), (0, 0)))
    return sum(ap[j:j + t] * taps[:, j] for j in range(n))


def scan_steps(state, x, a):
    """The recurrence over the steps of x = (u (., H, P), dt (., H), B,
    C (., H, N)) from `state` (H, P, N) -> (the state after them,
    y (steps, H, P))."""
    def step(s, x):
        u, dt, b, c = x
        s = jnp.exp(dt * a)[:, None, None] * s \
            + (dt[:, None] * u)[:, :, None] * b[:, None, :]
        return s, jnp.sum(s * c[:, None, :], axis=-1)
    return lax.scan(step, state, x)


def ssd_recurrence(u, dt, a, b, c):
    """u (T, H, P), dt (T, H), a (H,), b, c (T, G, N) -> y (T, H, P):
    one token a step; head j reads group j // (H / G)."""
    t, h, p = u.shape
    r = h // b.shape[1]
    b, c = (jnp.repeat(v, r, axis=1) for v in (b, c))
    state = jnp.zeros((h, p, b.shape[-1]), F32)
    if not SCAN_BLOCK or t % SCAN_BLOCK:
        return scan_steps(state, (u, dt, b, c), a)[1]
    blocks = tuple(x.reshape((t // SCAN_BLOCK, SCAN_BLOCK) + x.shape[1:])
                   for x in (u, dt, b, c))
    _, y = lax.scan(
        jax.checkpoint(lambda s, x: scan_steps(s, x, a)), state, blocks)
    return y.reshape(t, h, p)


def mamba2(p, pre, x, m):
    t = x.shape[0]
    h, hp, g, n = m["mh"], m["mp"], m["g"], m["n"]
    di, bc = h * hp, g * n
    zxd = x @ p[pre + "/W_in"].T
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * bc], \
        zxd[:, 2 * di + 2 * bc:]
    xbc = jax.nn.silu(causal_taps(xbc, p[pre + "/taps"])
                      + p[pre + "/conv_bias"])
    u = xbc[:, :di].reshape(t, h, hp)
    dt = jax.nn.softplus(dt + p[pre + "/dt_bias"])
    y = ssd_recurrence(u, dt, -jnp.exp(p[pre + "/A_log"]),
                       xbc[:, di:di + bc].reshape(t, g, n),
                       xbc[:, di + bc:].reshape(t, g, n))
    y = y + p[pre + "/D"][:, None] * u
    y = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, g, di // g)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + m["eps"])
    return (y.reshape(t, di) * p[pre + "/norm"]) @ p[pre + "/W_out"].T


def _block_attention(q, k, v, first_row: int):
    """q (h, R, hd) rows first_row..., k, v (h, C, hd) columns 0...:
    causal softmax attention of the rows over the columns -> (h, R,
    hd)."""
    s = jnp.einsum("htd,hsd->hts", q, k) / math.sqrt(q.shape[-1])
    seen = (first_row + jnp.arange(q.shape[1])[:, None]
            >= jnp.arange(k.shape[1])[None, :])
    w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,hsd->htd", w, v)


def _heads_attention(q, k, v):
    """q, k, v (h, T, hd) -> (h, T, hd): the query rows a block at a
    time, each over the columns up to its last row's own."""
    t = q.shape[1]
    rows = Q_BLOCK if t % Q_BLOCK == 0 else t
    return jnp.concatenate([
        jax.checkpoint(_block_attention, static_argnums=(3,))(
            q[:, lo:lo + rows], k[:, :lo + rows], v[:, :lo + rows], lo)
        for lo in range(0, t, rows)], axis=1)


def attention(p, pre, x, m):
    """Query head j reads key/value head j // g; no position."""
    t = x.shape[0]
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    heads = lambda a: jnp.transpose(a, (1, 0, 2))            # noqa: E731
    q = heads((x @ p[pre + "/W_q"].T).reshape(t, h, hd))
    k, v = (jnp.repeat(heads((x @ p[pre + w].T).reshape(t, hkv, hd)),
                       h // hkv, axis=0) for w in ("/W_k", "/W_v"))
    c = math.gcd(HEAD_CHUNK, h)
    o = lax.map(lambda a: _heads_attention(*a),
                tuple(a.reshape(h // c, c, t, hd) for a in (q, k, v)))
    return jnp.transpose(o.reshape(h, t, hd), (1, 0, 2)).reshape(
        t, h * hd) @ p[pre + "/W_o"].T


def relu2(x, w_in, w_out):
    """(in, width), (width, in) weights: the ungated squared-ReLU
    feed-forward."""
    return jnp.square(jax.nn.relu(x @ w_in)) @ w_out


def route(p, pre, x, m):
    """-> chosen experts (T, k), their weights (T, k)."""
    s = jax.nn.sigmoid(jnp.matmul(x, p[pre + "/router"],
                                  precision=lax.Precision.HIGHEST))
    _, topi = lax.top_k(s + lax.stop_gradient(p[pre + "/bias"])[None, :],
                        m["k"])
    topv = jnp.take_along_axis(s, topi, axis=1)
    return topi, topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20) \
        * m["factor"]


def moe(p, pre, x, m, *, routed=True, shared=True):
    """This share's part of the expert layer -> (y, rows per held
    expert).  `routed` / `shared` False leave that part out (the share
    test)."""
    topi, w = route(p, pre, x, m)
    y = jnp.zeros_like(x)
    counts = []
    for j in range(m["held"]):
        hit = topi == (m["first_expert"] + j)                 # (T, k)
        counts.append(jnp.sum(hit))
        if routed:
            wj = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)     # (T,)
            y = y + wj[:, None] * relu2(x, p[pre + "/W1"][j],
                                        p[pre + "/W2"][j])
    if shared:
        y = y + relu2(x, p[pre + "/S_up"], p[pre + "/S_down"])
    return y, jnp.stack(counts)


def block(p, i, x, m):
    """Block i -> (x after it, rows per held expert: zeros for a block
    that is no expert layer)."""
    pre = f"L{i}"
    kind = m["kinds"][i]
    counts = jnp.zeros((m["held"],), jnp.int32)
    if kind == "E":
        f, counts = moe(p, pre + ".moe",
                        rms_norm(x, p[pre + ".norm2/scale"], m["eps"]), m)
        return x + f, counts
    n1 = rms_norm(x, p[pre + ".norm1/scale"], m["eps"])
    if kind == "M":
        return x + mamba2(p, pre + ".mamba2", n1, m), counts
    return x + attention(p, pre + ".attn", n1, m), counts


def forward(p, ids, m):
    """ids (T,) int -> logits (T, vocab), rows per held expert of every
    block (n_layers, held)."""
    x = p["embed/weight"][ids]
    remat = jax.checkpoint(block, static_argnums=(1, 3)) if SCAN_BLOCK \
        else block
    counts = []
    for i in range(m["n_layers"]):
        x, c = remat(p, i, x, m)
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
    of them this share runs for an even router; the embedding is a
    gather); causal attention, 2 x 2 hd x heads x seq / 2 a token a
    layer; the Mamba-2 recurrence AS WRITTEN: per token and head the
    state's decay, the rank-one write and the read, P N multiply-adds
    each (not the chunked form's products).  Norms, taps, gates,
    softplus, the router's sigmoid and the softmaxes are not counted."""
    m = dims(cfg)
    d = m["d"]
    di = m["mh"] * m["mp"]
    per_token = m["vocab"] * d
    scan = scores = 0
    for kind in m["kinds"]:
        if kind == "M":
            per_token += (2 * di + 2 * m["g"] * m["n"] + m["mh"]) * d \
                + d * di
            scan += m["mh"] * 3 * 2 * m["mp"] * m["n"]
        elif kind == "*":
            per_token += 2 * (m["h"] + m["hkv"]) * m["hd"] * d
            scores += 2 * 2 * m["hd"] * m["h"] * seq / 2
        else:
            per_token += d * m["e"] + 2 * d * m["sw"] \
                + m["k"] * m["held"] / m["e"] * 2 * d * m["ew"]
    return int(seqs * seq * (2 * per_token + scan + scores))
