"""The benchmark's own plain reference: straightforward ``jax.numpy`` in a
stated dtype (float32 for the reference, bfloat16 for its control), with
every matmul at HIGHEST precision.

It imports nothing of the program. What it computes follows the paper
(arXiv:2205.13705) and the family definitions the configuration states:

  * the client families' forwards (MLP, ResNet-1D, a one-layer causal
    transformer and a one-layer Mamba-2 SSD mixer over patch tokens), the
    client objective Eq. 3/5/6, SGD with momentum and Adam;
  * the server: Eq. 1 grades, Eq. 2 divergences, the Def. 3 top-Q pool,
    the Def. 4/5 top-K neighbour selection and the Eq. 5 targets.

The SSD mixer is the plain sequential recurrence, not the chunked form.
Weights are made here too, from the seed, in one jitted call; the drivers
hand the same values to the program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import assignment

HI = jax.lax.Precision.HIGHEST
EPS_SIM = 1e-8          # Def. 4 floor of the divergence before 1/d


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _ein(spec, *ops):
    return jnp.einsum(spec, *ops, precision=HI)


# --------------------------------------------------------------------------
# weights, made from the seed
# --------------------------------------------------------------------------

def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(max(fan_in,
                                                                      1))


def _n_patch(in_dim: int, seq: int) -> int:
    return -(-in_dim // seq)


def _init_one(fam: dict, in_dim: int, n_classes: int, key) -> dict:
    kind = fam["kind"]
    if kind == "mlp":
        dims = (in_dim, *fam["hidden"], n_classes)
        layers = []
        for a, b in zip(dims[:-1], dims[1:]):
            key, sub = jax.random.split(key)
            layers.append({"w": _normal(sub, (a, b), a),
                           "b": jnp.zeros((b,), jnp.float32)})
        return {"layers": layers}
    if kind == "resnet1d":
        w = fam["width"]
        ks = list(jax.random.split(key, 2 + sum(fam["blocks"])))
        p = {"stem": _normal(ks[0], (3, 1, w), 3),
             "stem_s": jnp.ones((w,)), "stem_b": jnp.zeros((w,)),
             "stages": []}
        c_in, ki = w, 1
        for stage, n_blocks in enumerate(fam["blocks"]):
            c_out = w * 2 ** stage
            blocks = []
            for _ in range(n_blocks):
                kb = jax.random.split(ks[ki], 4)
                blk = {"w1": _normal(kb[0], (3, c_in, c_out), 3 * c_in),
                       "w2": _normal(kb[1], (3, c_out, c_out), 3 * c_out),
                       "s1": jnp.ones((c_out,)), "b1": jnp.zeros((c_out,)),
                       "s2": jnp.ones((c_out,)), "b2": jnp.zeros((c_out,))}
                if c_in != c_out:
                    blk["w_skip"] = _normal(kb[3], (1, c_in, c_out), c_in)
                blocks.append(blk)
                ki += 1
                c_in = c_out
            p["stages"].append(blocks)
        p["head_w"] = _normal(ks[-1], (c_in, n_classes), c_in)
        p["head_b"] = jnp.zeros((n_classes,))
        return p
    # sequence families: patch embedding, one mixer, mean pool, head
    d = fam["d_model"]
    patch = _n_patch(in_dim, fam["seq_len"])
    k_emb, k_mix, k_head = jax.random.split(key, 3)
    if kind == "transformer":
        h, kv = fam["n_heads"], fam["n_kv_heads"]
        hd = d // h
        km = jax.random.split(k_mix, 4)
        mixer = {"wq": _normal(km[0], (d, h, hd), d),
                 "wk": _normal(km[1], (d, kv, hd), d),
                 "wv": _normal(km[2], (d, kv, hd), d),
                 "wo": _normal(km[3], (h, hd, d), h * hd)}
    elif kind == "ssd":
        di = fam["ssm_expand"] * d
        n, h = fam["ssm_state"], fam["ssm_heads"]
        conv_ch = di + 2 * n
        km = jax.random.split(k_mix, 4)
        mixer = {"w_in": _normal(km[0], (d, 2 * di + 2 * n + h), d),
                 "conv_w": _normal(km[1], (fam["conv_width"], conv_ch),
                                   fam["conv_width"]),
                 "conv_b": jnp.zeros((conv_ch,)),
                 "a_log": jnp.zeros((h,)), "dt_bias": jnp.zeros((h,)),
                 "d_skip": jnp.ones((h,)), "norm_scale": jnp.ones((di,)),
                 "w_out": _normal(km[3], (di, d), di)}
    else:
        raise ValueError(f"unknown family kind {kind!r}")
    return {"embed_w": _normal(k_emb, (patch, d), patch),
            "embed_b": jnp.zeros((d,)), "mixer": mixer,
            "head_w": _normal(k_head, (d, n_classes), d),
            "head_b": jnp.zeros((n_classes,))}


def init_weights(families: Dict[str, dict], counts: Dict[str, int],
                 in_dim: int, n_classes: int, seed_key) -> Dict[str, dict]:
    """Every family's stacked (n_clients, ...) float32 weights, in one
    jitted call."""
    names = tuple(families)

    def make(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            keys = jax.random.split(k, counts[name])
            out[name] = jax.vmap(lambda kk, f=families[name]: _init_one(
                f, in_dim, n_classes, kk))(keys)
        return out

    return jax.jit(make)(seed_key)


# --------------------------------------------------------------------------
# forwards
# --------------------------------------------------------------------------

def _conv1d(x, w, stride: int):
    """x (B,L,Cin), w (K,Cin,Cout), SAME padding as TensorFlow/XLA."""
    k, length = w.shape[0], x.shape[1]
    out = -(-length // stride)
    pad = max((out - 1) * stride + k - length, 0)
    xp = jnp.pad(x, ((0, 0), (pad // 2, pad - pad // 2), (0, 0)))
    span = (out - 1) * stride + 1
    return sum(_ein("blc,cd->bld", xp[:, i:i + span:stride], w[i])
               for i in range(k))


def _group_norm(s, b, x, eps):
    m = jnp.mean(x, axis=(1, 2), keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=(1, 2), keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * s + b


def _resnet(fam, p, x):
    eps = fam["norm_eps"]
    h = x[..., None]
    h = jax.nn.relu(_group_norm(p["stem_s"], p["stem_b"],
                                _conv1d(h, p["stem"], 1), eps))
    for stage, blocks in enumerate(p["stages"]):
        for b, bp in enumerate(blocks):
            stride = fam["pool_stride"] if (b == 0 and stage > 0) else 1
            if "w_skip" in bp:
                skip = _conv1d(h, bp["w_skip"], stride)
            else:
                skip = h[:, ::stride]
            y = jax.nn.relu(_group_norm(bp["s1"], bp["b1"],
                                        _conv1d(h, bp["w1"], stride), eps))
            y = _group_norm(bp["s2"], bp["b2"], _conv1d(y, bp["w2"], 1), eps)
            h = jax.nn.relu(y + skip)
    return _mm(jnp.mean(h, axis=1), p["head_w"]) + p["head_b"]


def _rope(x, theta):
    """x (B,S,H,hd): rotate the two halves by position."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(fam, p, h):
    q = _rope(_ein("bsd,dhk->bshk", h, p["wq"]), fam["rope_theta"])
    k = _rope(_ein("bsd,dhk->bshk", h, p["wk"]), fam["rope_theta"])
    v = _ein("bsd,dhk->bshk", h, p["wv"])
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = h.shape[1]
    scores = _ein("bshk,bthk->bhst", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    o = _ein("bhst,bthk->bshk", jax.nn.softmax(scores, axis=-1), v)
    return _ein("bshk,hkd->bsd", o, p["wo"])


def _ssd(fam, p, h):
    d = h.shape[-1]
    di = fam["ssm_expand"] * d
    n, nh = fam["ssm_state"], fam["ssm_heads"]
    hp = di // nh
    proj = _ein("bsd,de->bse", h, p["w_in"])
    z = proj[..., :di]
    u = proj[..., di:2 * di + 2 * n]
    dt_raw = proj[..., 2 * di + 2 * n:]
    width = p["conv_w"].shape[0]
    up = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    s = u.shape[1]
    conv = sum(up[:, i:i + s] * p["conv_w"][i] for i in range(width))
    u = jax.nn.silu(conv + p["conv_b"])
    xin, bm, cm = u[..., :di], u[..., di:di + n], u[..., di + n:]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])               # (B,S,H)
    a = -jnp.exp(p["a_log"])                                   # (H,)
    xh = xin.reshape(*xin.shape[:2], nh, hp)                   # (B,S,H,P)
    state = jnp.zeros((h.shape[0], nh, hp, n), h.dtype)
    ys = []
    for t in range(s):                                         # recurrence
        decay = jnp.exp(dt[:, t] * a)                          # (B,H)
        inp = (dt[:, t, :, None] * xh[:, t])[..., None] * bm[:, t, None,
                                                             None, :]
        state = state * decay[..., None, None] + inp
        ys.append(jnp.sum(state * cm[:, t, None, None, :], axis=-1))
    y = jnp.stack(ys, axis=1) + p["d_skip"][:, None] * xh
    y = y.reshape(*y.shape[:2], di) * jax.nn.silu(z)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                     + fam["norm_eps"]) * p["norm_scale"]
    return _ein("bse,ed->bsd", y, p["w_out"])


def forward(fam: dict, p, x):
    """Logits (B, C) of one client with weights ``p`` on series ``x``
    (B, L), computed in the dtype of ``p``."""
    x = x.astype(jax.tree.leaves(p)[0].dtype)
    kind = fam["kind"]
    if kind == "mlp":
        h = x.reshape(x.shape[0], -1)
        for i, layer in enumerate(p["layers"]):
            h = _mm(h, layer["w"]) + layer["b"]
            if i < len(p["layers"]) - 1:
                h = jax.nn.relu(h)
        return h
    if kind == "resnet1d":
        return _resnet(fam, p, x)
    seq = fam["seq_len"]
    patch = p["embed_w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (0, seq * patch - x.shape[1])))
    h = _ein("bsp,pd->bsd", xp.reshape(x.shape[0], seq, patch),
             p["embed_w"]) + p["embed_b"]
    mixer = _attention if kind == "transformer" else _ssd
    h = h + mixer(fam, p["mixer"], h)
    return _mm(jnp.mean(h, axis=1), p["head_w"]) + p["head_b"]


# --------------------------------------------------------------------------
# the client: Eq. 3/5/6 and its optimizer
# --------------------------------------------------------------------------

def client_loss(fam, p, x, y, ref_x, target, rho, use_ref):
    """Eq. 6: (1 - rho) Eq. 3 + rho Eq. 5 where ``use_ref`` is 1, Eq. 3
    alone where it is 0 (exactly: the Eq. 5 term is weighed by 0)."""
    logp = jax.nn.log_softmax(forward(fam, p, x), axis=-1)
    loc = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
    probs = jax.nn.softmax(forward(fam, p, ref_x), axis=-1)
    ref = jnp.mean(jnp.sum(jnp.square(probs - target), axis=-1))
    w = rho * use_ref
    return (1.0 - w) * loc + w * ref


def opt_init(opt: dict, params):
    z = jax.tree.map(jnp.zeros_like, params)
    if opt["name"] == "sgd":
        return {"m": z}
    return {"mu": z, "nu": jax.tree.map(jnp.zeros_like, params)}


def opt_step(opt: dict, params, state, grads, step):
    """One optimizer step (``step`` counts from 0, and may be traced)."""
    lr = opt["lr"]
    if opt["name"] == "sgd":
        m = jax.tree.map(lambda a, g: opt["momentum"] * a + g, state["m"],
                         grads)
        return jax.tree.map(lambda p, a: p - lr * a, params, m), {"m": m}
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, state["nu"],
                      grads)
    t = jnp.asarray(step + 1, jnp.float32)
    bc1, bc2 = 1 - jnp.power(b1, t), 1 - jnp.power(b2, t)
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1.astype(p.dtype))
        / (jnp.sqrt(v / bc2.astype(p.dtype)) + eps), params, mu, nu)
    return new, {"mu": mu, "nu": nu}


@functools.partial(jax.jit, static_argnames=("fam_key", "opt_key", "rho",
                                             "batch", "used"))
def _cohort_round(fam_key, opt_key, params, state, key, data_x, data_y,
                  ref_x, targets, rho, use_ref, step, batch, used):
    """One local step of every client of a family, then its messengers.
    Each client's batch is ``batch`` uniform indices drawn from ``key``,
    of which the first ``used`` are trained on."""
    fam, opt = dict(fam_key), dict(opt_key)
    idx = jax.random.randint(key, (data_y.shape[0], batch), 0,
                             data_y.shape[1])[:, :used]
    bx = jnp.take_along_axis(data_x, idx[..., None], axis=1)
    by = jnp.take_along_axis(data_y, idx, axis=1)

    def one(p, x, y, t):
        return jax.value_and_grad(
            lambda q: client_loss(fam, q, x, y, ref_x, t, rho, use_ref))(p)

    loss, grads = jax.vmap(one)(params, bx, by, targets)
    new, state = jax.vmap(lambda p, s, g: opt_step(opt, p, s, g, step))(
        params, state, grads)
    msgs = jax.vmap(lambda p: jax.nn.log_softmax(forward(fam, p, ref_x),
                                                 axis=-1))(new)
    return new, state, loss, grads, msgs


def _freeze(d: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items() if not isinstance(v, dict)))


# --------------------------------------------------------------------------
# the server: Eq. 1, Eq. 2, Defs. 3-5, Eq. 5
# --------------------------------------------------------------------------

@jax.jit
def grades(logp, labels):
    """Eq. 1: summed cross-entropy of each messenger (N,R,C) -> (N,)."""
    lse = jax.nn.logsumexp(logp, axis=-1)
    pick = jnp.take_along_axis(logp, labels[None, :, None], axis=-1)[..., 0]
    return jnp.sum(lse - pick, axis=-1)


@jax.jit
def divergence(la, lb):
    """Eq. 2 strip: mean over the R samples of KL(a || b), from log-probs
    la (U,R,C) and lb (M,R,C) -> (U,M)."""
    r = la.shape[1]
    fa = la.reshape(la.shape[0], -1)
    fb = lb.reshape(lb.shape[0], -1)
    pa = jnp.exp(fa)
    row = jnp.sum(pa * fa, axis=-1)
    return (row[:, None] - _mm(pa, fb.T)) / r


def pool(g, active, q: int):
    """Def. 3: the q lowest-graded active clients (ties: lower index)."""
    order = np.lexsort((np.arange(len(g)), np.where(active, g, np.inf)))
    mask = np.zeros(len(g), bool)
    mask[order[:q]] = True
    return mask & active


def select(div, cand, k: int):
    """Defs. 4-5: each client's K most similar pool members other than
    itself; div (N,N) host array -> (N,K) ids, -1 where fewer exist."""
    n = div.shape[0]
    cols = np.nonzero(cand)[0]
    sim = 1.0 / np.maximum(div[:, cols], EPS_SIM)
    sim = np.where(cols[None, :] == np.arange(n)[:, None], -np.inf, sim)
    order = np.argsort(-sim, axis=1, kind="stable")[:, :k]
    nbrs = np.where(np.isfinite(np.take_along_axis(sim, order, 1)),
                    cols[order], -1)
    if nbrs.shape[1] < k:
        nbrs = np.pad(nbrs, ((0, 0), (0, k - nbrs.shape[1])),
                      constant_values=-1)
    return nbrs


@jax.jit
def neighbor_mean(probs, nbrs):
    """Eq. 5: the mean of each client's neighbours' probabilities;
    probs (N,R,C), nbrs (N,K) with -1 for no neighbour."""
    valid = (nbrs >= 0).astype(probs.dtype)
    gathered = probs[jnp.maximum(nbrs, 0)]                     # (N,K,R,C)
    total = jnp.sum(gathered * valid[..., None, None], axis=1)
    return total / jnp.maximum(jnp.sum(valid, axis=1), 1.0)[:, None, None]


# --------------------------------------------------------------------------
# the federation: rounds of the sync engine
# --------------------------------------------------------------------------

def federation_rounds(cfg: dict, data, weights: Dict[str, dict], rng_key,
                      rounds: int, dtype=jnp.float32,
                      batch_fraction: float = 1.0) -> dict:
    """``rounds`` rounds of Algorithm 1 on every client, from ``weights``.

    Local batches are drawn as the configuration states: per family and
    round one split of the key, then B uniform indices per client.
    ``batch_fraction`` < 1 trains on only that share of each drawn batch
    (a fault the check must catch). Returns per round the clients' losses
    (family -> (n_c,)) and weights, the starting weights and the first
    round's gradients, all as float32 host arrays."""
    fams = cfg["families"]
    names = list(fams)
    n, c = cfg["n_clients"], cfg["n_classes"]
    who = assignment(names, n)
    rows = {f: np.array([i for i in range(n) if who[i] == f]) for f in names}
    params = {f: jax.tree.map(lambda a: a.astype(dtype), weights[f])
              for f in names}
    states = {f: opt_init(fams[f]["optimizer"], params[f]) for f in names}
    xs, ys = {}, {}
    for f in names:
        m = min(len(data.clients[i].train_y) for i in rows[f])
        xs[f] = jnp.asarray(np.stack([data.clients[i].train_x[:m]
                                      for i in rows[f]]), dtype)
        ys[f] = jnp.asarray(np.stack([data.clients[i].train_y[:m]
                                      for i in rows[f]]))
    ref_x = jnp.asarray(data.ref_x, dtype)
    labels = jnp.asarray(data.ref_y)
    batch = cfg["batch_size"]
    used = max(1, int(round(batch * batch_fraction)))
    proto = cfg["protocol"]
    targets = jnp.full((n, cfg["ref_size"], c), 1.0 / c, dtype)
    to_host = lambda t: jax.tree.map(  # noqa: E731
        lambda a: np.asarray(a, np.float32), t)
    out = {"loss": [], "grads": None, "weights": [],
           "weights0": {f: to_host(params[f]) for f in names}}
    key = rng_key
    with jax.default_matmul_precision("highest"):
        for rnd in range(rounds):
            repo = jnp.zeros((n, cfg["ref_size"], c), dtype)
            losses, grads = {}, {}
            for f in names:
                key, sub = jax.random.split(key)
                params[f], states[f], loss, g, msgs = _cohort_round(
                    _freeze(fams[f]), _freeze(fams[f]["optimizer"]),
                    params[f], states[f], sub, xs[f], ys[f], ref_x,
                    targets[rows[f]], proto["rho"],
                    jnp.asarray(rnd > 0, dtype), jnp.int32(rnd), batch, used)
                losses[f] = np.asarray(loss, np.float32)
                grads[f] = to_host(g)
                repo = repo.at[rows[f]].set(msgs)
            out["loss"].append(losses)
            if rnd == 0:
                out["grads"] = grads
            out["weights"].append({f: to_host(params[f]) for f in names})
            g_all = np.asarray(grades(repo, labels), np.float64)
            cand = pool(g_all, np.ones(n, bool), proto["q"])
            div = np.asarray(divergence(repo, repo), np.float64)
            nbrs = select(div, cand, min(proto["k"], n - 1))
            targets = neighbor_mean(jnp.exp(repo), jnp.asarray(nbrs))
    return out
