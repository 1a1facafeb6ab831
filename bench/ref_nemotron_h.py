"""The benchmark's own plain reference for the ``sc-long`` cell: the
``sc-nemotron3-nano`` federation, whose client 0 trains Nemotron-3-Nano's
hybrid block. Straightforward ``jax.numpy`` in a stated dtype (float32
for the reference, bfloat16 for its control) with every matmul at
HIGHEST precision; it imports nothing of the program.

The hybrid client follows Nemotron-H (arXiv:2504.03624) and the
configuration's published numbers: layers ``x + op(RMSNorm(x))`` in the
order of ``hybrid_override_pattern``'s first ``num_hidden_layers``
letters, where ``M`` is the Mamba-2 mixer (the SSD as the sequential
recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t, y_t = C_t · h_t +
D x_t, B and C read by head group, and the gated RMSNorm over groups of
d_inner / n_groups channels), ``E`` the expert layer (sigmoid router over
``router_experts``, the top ``num_experts_per_tok`` of scores plus the
correction bias, normalised weights times ``routed_scaling_factor``,
relu² experts and a relu² shared expert) and ``*`` softmax attention
over an explicit causal mask. Its departures are the configuration's
``assumed`` and ``reduced``: a patch front end, a mean-pool class head,
the held experts 0..``n_routed_experts``-1 alone, a frozen correction
bias, no positional encoding.

To fit one chip beside its Adam state, the client's gradient is summed
over blocks of ``BLOCK`` sequences and each layer is rematerialised: the
same sums as one pass, in another order. The other four families and the server are
``bench/ref.py``'s. Weights are made here from the seed, in the program's
parameter tree, in one jitted call.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import ref
from bench.data import assignment

BLOCK = 8             # sequences a gradient block
FAMILY = "nemotron-h"


def who(cfg: dict):
    """Each client's family: the first ``nemotron_clients`` run the
    hybrid, the rest the other families round-robin."""
    k = cfg["nemotron_clients"]
    others = [f for f in cfg["families"] if f != FAMILY]
    return [FAMILY] * k + assignment(others, cfg["n_clients"] - k)


def _pattern(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def _seq(cfg: dict) -> int:
    return -(-cfg["series_length"] // cfg["patch"])


# --------------------------------------------------------------------------
# weights, made from the seed
# --------------------------------------------------------------------------

def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _init_layer(cfg: dict, kind: str, key) -> dict:
    d = cfg["hidden_size"]
    ks = jax.random.split(key, 6)
    if kind == "M":
        h, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        di, gn = h * hp, cfg["n_groups"] * cfg["ssm_state_size"]
        w = cfg["conv_kernel"]
        a = jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(
            ks[3], (h,), jnp.float32, math.log(cfg["time_step_min"]),
            math.log(cfg["time_step_max"])))
        dt = jnp.maximum(dt, cfg["time_step_floor"])
        mixer = {"w_in": _normal(ks[0], (d, 2 * di + 2 * gn + h), d),
                 "conv_w": _normal(ks[1], (w, di + 2 * gn), w),
                 "conv_b": jnp.zeros((di + 2 * gn,)),
                 "a_log": jnp.log(a),
                 "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                 "d_skip": jnp.ones((h,)), "norm_scale": jnp.ones((di,)),
                 "w_out": _normal(ks[4], (di, d), di)}
    elif kind == "E":
        f, fs = cfg["moe_intermediate_size"], \
            cfg["moe_shared_expert_intermediate_size"]
        held = cfg["n_routed_experts"]
        mixer = {"router": _normal(ks[0], (d, cfg["router_experts"]), d),
                 "router_bias": jnp.zeros((cfg["router_experts"],)),
                 "w_up": _normal(ks[1], (held, d, f), d),
                 "w_down": _normal(ks[2], (held, f, d), f),
                 "shared_up": _normal(ks[3], (d, fs), d),
                 "shared_down": _normal(ks[4], (fs, d), fs)}
    else:
        h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        mixer = {"wq": _normal(ks[0], (d, h, hd), d),
                 "wk": _normal(ks[1], (d, kv, hd), d),
                 "wv": _normal(ks[2], (d, kv, hd), d),
                 "wo": _normal(ks[3], (h, hd, d), h * hd)}
    return {"norm1": {"scale": jnp.ones((d,))}, "mixer": mixer}


def _init_client(cfg: dict, key) -> dict:
    """One client's weights, in the program's tree: the stack's layers
    under ``groups`` with a leading axis of one group."""
    d, c, patch = cfg["hidden_size"], cfg["n_classes"], cfg["patch"]
    k_emb, k_head, *ks = jax.random.split(key, 2 + len(_pattern(cfg)))
    groups = {f"pos{i}": jax.tree.map(lambda a: a[None],
                                      _init_layer(cfg, kind, ks[i]))
              for i, kind in enumerate(_pattern(cfg))}
    return {"embed_w": _normal(k_emb, (patch, d), patch),
            "embed_b": jnp.zeros((d,)),
            "stack": {"groups": groups, "rem": []},
            "final_norm": {"scale": jnp.ones((d,))},
            "head_w": _normal(k_head, (d, c), d), "head_b": jnp.zeros((c,))}


def init_weights(cfg: dict, seed_key) -> Dict[str, dict]:
    """Every family's stacked (n_clients, ...) float32 weights: the
    hybrid's here, the others' from ``bench/ref.py``."""
    names = who(cfg)
    counts = {f: names.count(f) for f in cfg["families"]}
    k_hyb, k_rest = jax.random.split(seed_key)
    rest = {f: fam for f, fam in cfg["families"].items() if f != FAMILY}
    out = ref.init_weights(rest, counts, cfg["series_length"],
                           cfg["n_classes"], k_rest)
    out[FAMILY] = jax.jit(lambda k: jax.vmap(
        lambda kk: _init_client(cfg, kk))(jax.random.split(
            k, counts[FAMILY])))(k_hyb)
    return dict((f, out[f]) for f in cfg["families"])


# --------------------------------------------------------------------------
# the hybrid forward
# --------------------------------------------------------------------------

def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _mamba(cfg, p, x):
    b, s, _ = x.shape
    h, hp, g, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                   cfg["n_groups"], cfg["ssm_state_size"])
    di = h * hp
    proj = ref._ein("bsd,de->bse", x, p["w_in"])
    z, xbc = proj[..., :di], proj[..., di:2 * di + 2 * g * n]
    dt = jax.nn.softplus(proj[..., 2 * di + 2 * g * n:] + p["dt_bias"])
    width = p["conv_w"].shape[0]
    past = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(past[:, i:i + s] * p["conv_w"][i]
                          for i in range(width)) + p["conv_b"])
    xh = xbc[..., :di].reshape(b, s, h, hp)
    grp = jnp.arange(h) // (h // g)
    bm = xbc[..., di:di + g * n].reshape(b, s, g, n)[:, :, grp]
    cm = xbc[..., di + g * n:].reshape(b, s, g, n)[:, :, grp]
    a = -jnp.exp(p["a_log"]).astype(x.dtype)

    def step(state, t):                                  # the recurrence
        dt_t, x_t, b_t, c_t = t
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    _, ys = jax.lax.scan(step, jnp.zeros((b, h, hp, n), x.dtype),
                         tuple(jnp.moveaxis(v, 1, 0) for v in (dt, xh, bm,
                                                               cm)))
    y = jnp.moveaxis(ys, 0, 1) + p["d_skip"][:, None] * xh
    y = (y.reshape(b, s, di) * jax.nn.silu(z)).reshape(b, s, g, di // g)
    y = _rms(1.0, y, cfg["layer_norm_epsilon"]).reshape(b, s, di)
    return ref._ein("bse,ed->bsd", y * p["norm_scale"], p["w_out"])


def _experts(cfg, p, x):
    """The held experts' part and the shared expert, per token."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(ref._mm(xf, p["router"]))
    _, ids = jax.lax.top_k(scores + p["router_bias"],
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    held = p["w_up"].shape[0]
    gate = jnp.sum(jnp.where(ids[:, :, None] == jnp.arange(held),
                             w[:, :, None], 0.0), axis=1)      # (T, held)
    y = sum(gate[:, i:i + 1] * ref._mm(_relu2(ref._mm(xf, p["w_up"][i])),
                                       p["w_down"][i])
            for i in range(held))
    y = y + ref._mm(_relu2(ref._mm(xf, p["shared_up"])), p["shared_down"])
    return y.reshape(b, s, d)


def _attention(cfg, p, x):
    q = ref._ein("bsd,dhk->bshk", x, p["wq"])
    k = ref._ein("bsd,dhk->bshk", x, p["wk"])
    v = ref._ein("bsd,dhk->bshk", x, p["wv"])
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = x.shape[1]
    scores = ref._ein("bshk,bthk->bhst", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return ref._ein("bshk,hkd->bsd", ref._ein("bhst,bthk->bshk", probs, v),
                    p["wo"])


_OPS = {"M": _mamba, "E": _experts, "*": _attention}


def forward(cfg: dict, p, x):
    """Logits (B, C) of one hybrid client with weights ``p`` on series x
    (B, L), in the dtype of ``p``; each layer rematerialised."""
    x = x.astype(p["embed_w"].dtype)
    seq, patch = _seq(cfg), cfg["patch"]
    xp = jnp.pad(x, ((0, 0), (0, seq * patch - x.shape[1])))
    h = ref._ein("bsp,pd->bsd", xp.reshape(x.shape[0], seq, patch),
                 p["embed_w"]) + p["embed_b"]
    eps = cfg["layer_norm_epsilon"]
    for i, kind in enumerate(_pattern(cfg)):
        lp = jax.tree.map(lambda a: a[0], p["stack"]["groups"][f"pos{i}"])

        @jax.checkpoint
        def layer(lp, h, kind=kind):
            return h + _OPS[kind](cfg, lp["mixer"],
                                  _rms(lp["norm1"]["scale"], h, eps))

        h = layer(lp, h)
    h = jnp.mean(_rms(p["final_norm"]["scale"], h, eps), axis=1)
    return ref._mm(h, p["head_w"]) + p["head_b"]


def _blocks(a, block: int):
    """(n, ...) -> (n / nb, nb rows, ...) in the fewest equal blocks of at
    most ``block`` rows."""
    n = a.shape[0]
    nb = next(k for k in range(-(-n // block), n + 1) if n % k == 0)
    return a.reshape(nb, n // nb, *a.shape[1:])


@functools.partial(jax.jit, static_argnames=("cfg_key", "batch"),
                   donate_argnames=("params", "state"))
def _hybrid_round(cfg_key, params, state, key, data_x, data_y, ref_x,
                  targets, use_ref, step, batch, used):
    """One local step of every hybrid client, then its messengers. The
    batches are drawn as ``bench/ref.py`` draws them, of which the first
    ``used`` are trained on; the Eq. 5 term is weighed by ``use_ref``
    (exactly nought where it is 0). The gradient is summed over blocks of
    ``BLOCK`` sequences, local ones first, each weighing its samples'
    terms of Eq. 6."""
    cfg = _thaw(cfg_key)
    rho = cfg["protocol"]["rho"]
    opt = cfg["families"][FAMILY]["optimizer"]
    idx = jax.random.randint(key, (data_y.shape[0], batch), 0,
                             data_y.shape[1])
    bx = jnp.take_along_axis(data_x, idx[..., None], axis=1)
    by = jnp.take_along_axis(data_y, idx, axis=1)
    r, c = targets.shape[1:]
    w = rho * use_ref
    # each sample's weight on its cross-entropy and on its Eq. 5 term
    w_ce = jnp.concatenate([(1.0 - w) * (jnp.arange(batch) < used) / used,
                            jnp.zeros((r,))]).astype(bx.dtype)
    w_sq = jnp.concatenate([jnp.zeros((batch,)),
                            jnp.full((r,), w / r)]).astype(bx.dtype)

    def block_loss(q, x, y, t, wc, ws):
        logits = forward(cfg, q, x)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        sq = jnp.sum(jnp.square(jnp.exp(logp) - t), axis=-1)
        return jnp.sum(wc * ce + ws * sq)

    def one(p, s, x, y, t):
        blocks = tuple(_blocks(a, BLOCK) for a in (
            jnp.concatenate([x, ref_x]),
            jnp.concatenate([y, jnp.zeros((r,), y.dtype)]),
            jnp.concatenate([jnp.zeros((batch, c), t.dtype), t]),
            w_ce, w_sq))

        def add(acc, blk):
            v, g = jax.value_and_grad(block_loss)(p, *blk)
            return jax.tree.map(jnp.add, acc, (v.astype(jnp.float32), g)), \
                None

        (loss, grads), _ = jax.lax.scan(
            add, (jnp.zeros((), jnp.float32),
                  jax.tree.map(jnp.zeros_like, p)), blocks)
        new, s = ref.opt_step(opt, p, s, grads, step)
        logp = jax.lax.map(
            lambda xb: jax.nn.log_softmax(forward(cfg, new, xb), axis=-1),
            _blocks(ref_x, 5 * BLOCK))
        return new, s, loss, grads, logp.reshape(r, c)

    return jax.vmap(one)(params, state, bx, by, targets)


def _freeze(cfg: dict):
    """A hashable copy of the configuration's numbers and family
    settings."""
    keep = {k: v for k, v in cfg.items() if isinstance(v, (int, float, str))}
    keep["protocol"] = tuple(sorted(cfg["protocol"].items()))
    keep["families"] = ((FAMILY, (("optimizer", tuple(sorted(
        cfg["families"][FAMILY]["optimizer"].items()))),)),)
    return tuple(sorted(keep.items()))


def _thaw(cfg_key):
    cfg = dict(cfg_key)
    cfg["protocol"] = dict(cfg["protocol"])
    cfg["families"] = {f: {k: dict(v) for k, v in fam}
                       for f, fam in cfg["families"]}
    return cfg


# --------------------------------------------------------------------------
# the federation: rounds of the sync engine
# --------------------------------------------------------------------------

def federation_rounds(cfg: dict, data, weights: Dict[str, dict], rng_key,
                      rounds: int, dtype=jnp.float32,
                      batch_fraction: float = 1.0) -> dict:
    """``rounds`` rounds of Algorithm 1 on every client, from ``weights``,
    as ``bench/ref.py``'s ``federation_rounds`` with client 0 the hybrid.

    Returns per round the clients' losses (family -> (n_c,)), the first
    round's gradients, the starting weights and those after the last
    round (float32 host arrays), and per round the hybrid clients'
    messengers (n_hybrid, R, C) log-probabilities."""
    fams = cfg["families"]
    names = list(fams)
    n, c = cfg["n_clients"], cfg["n_classes"]
    owner = who(cfg)
    rows = {f: np.array([i for i in range(n) if owner[i] == f])
            for f in names}
    params = {f: jax.tree.map(lambda a: a.astype(dtype), weights[f])
              for f in names}
    states = {f: ref.opt_init(fams[f]["optimizer"], params[f])
              for f in names}
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
    out = {"loss": [], "grads": None, "msg": [],
           "weights0": {f: to_host(params[f]) for f in names}}
    key = rng_key
    cfg_key = _freeze(cfg)
    with jax.default_matmul_precision("highest"):
        for rnd in range(rounds):
            repo = jnp.zeros((n, cfg["ref_size"], c), dtype)
            losses, grads = {}, {}
            for f in names:
                key, sub = jax.random.split(key)
                use_ref = jnp.asarray(rnd > 0, dtype)
                if f == FAMILY:
                    params[f], states[f], loss, g, msgs = _hybrid_round(
                        cfg_key, params[f], states[f], sub, xs[f], ys[f],
                        ref_x, targets[rows[f]], use_ref, jnp.int32(rnd),
                        batch, jnp.asarray(used, dtype))
                    out["msg"].append(np.asarray(msgs, np.float32))
                else:
                    params[f], states[f], loss, g, msgs = ref._cohort_round(
                        ref._freeze(fams[f]), ref._freeze(fams[f]
                                                          ["optimizer"]),
                        params[f], states[f], sub, xs[f], ys[f], ref_x,
                        targets[rows[f]], proto["rho"], use_ref,
                        jnp.int32(rnd), batch, used)
                losses[f] = np.asarray(loss, np.float32)
                if rnd == 0:
                    grads[f] = to_host(g)
                del g
                repo = repo.at[rows[f]].set(msgs)
            out["loss"].append(losses)
            if rnd == 0:
                out["grads"] = grads
            g_all = np.asarray(ref.grades(repo, labels), np.float64)
            cand = ref.pool(g_all, np.ones(n, bool), proto["q"])
            div = np.asarray(ref.divergence(repo, repo), np.float64)
            nbrs = ref.select(div, cand, min(proto["k"], n - 1))
            targets = ref.neighbor_mean(jnp.exp(repo), jnp.asarray(nbrs))
    out["weights"] = {f: to_host(params[f]) for f in names}
    return out
