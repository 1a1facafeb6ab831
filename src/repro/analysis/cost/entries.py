"""Parameterized traces of the audited entry points for the cost model.

PR 6's ``fixtures`` traces each entry once at fixed probe dims; the cost
model needs the SAME entry points re-traceable at several sizes so the
scaling fits can recover leading exponents. Every builder here returns a
``(fn, args)`` pair where ``args`` are ``jax.ShapeDtypeStruct``s —
``jax.make_jaxpr`` accepts them directly, so tracing at N=4096 costs
milliseconds and zero array memory.

One deliberate divergence from the PR 6 fixtures: the graph entries
(``sqmd.build_graph`` / ``sqmd.build_graph_delta``) stage the candidate
POOL concretely, exactly as the runtime does. ``graph.select_neighbors``
needs concrete candidates to take its (N,Q) pool path and falls back to
the dense O(N²) top-k under a tracer — tracing the policy hook naively
would mis-attribute a Θ(N²) selection to the delta path and the
``superlinear-memory`` rule could never pin it at Θ(u·N). The builders
therefore precompute the pool with numpy (probe quality profile, fixed
q/k) and trace the same jitted kernels the server actually dispatches.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# reference dims the budgets are pinned at; every structural dim distinct
# (fixtures idiom) so shapes in reports name their dimension
DEFAULT_DIMS: Dict[str, int] = {
    "n": 64,        # clients
    "r": 8,         # reference-set rows
    "c": 10,        # classes
    "batch": 3,     # local batch
    "feat": 7,      # input features
    "hidden": 16,   # MLP hidden width
    "u": 2,         # uploads per delta round
    "q": 8,         # quality pool size
    "k": 4,         # neighbors
    "b": 8,         # serve batch
}

# the axis each entry's scaling fit sweeps, and the sweep values.
# Geometric spacing conditions the log-log fit; the N²-class entries
# sweep up to 2048 (the largest monolithic rebuild before ops.CHUNK_ROWS
# strip-chunking changes the traced structure) so the quadratic term
# actually dominates the Θ(N) low-order terms inside the fit window —
# tracing is ShapeDtypeStruct-only, so large N costs no memory
SCALE_AXES: Dict[str, Tuple[str, Tuple[int, ...]]] = {
    "cohort_step": ("n", (32, 64, 128, 256)),
    "cohort_messenger_upload": ("n", (32, 64, 128, 256)),
    "cohort_messenger_upload[int8]": ("n", (32, 64, 128, 256)),
    "sqmd.grade": ("n", (64, 128, 256, 512)),
    "sqmd.build_graph": ("n", (256, 512, 1024, 2048)),
    "sqmd.build_graph_delta": ("n", (256, 512, 1024, 2048)),
    "divergence_matrix": ("n", (256, 512, 1024, 2048)),
    "int8_dequant_kl": ("n", (256, 512, 1024, 2048)),
    # the IVF entries sweep wider: their whole point is the sub-quadratic
    # tail (ncent ~ sqrt(n), candidates ~ n^{3/4}) and the low-order
    # terms only recede at larger n
    "centroid_assign": ("n", (256, 1024, 4096, 16384)),
    "ivf_search": ("n", (256, 1024, 4096, 16384)),
    "serve_step": ("b", (8, 16, 32, 64)),
}


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _family(d: Dict[str, int]):
    from repro.models.mlp import MLPConfig, mlp_family
    return mlp_family(MLPConfig("cost-probe", d["feat"],
                                (d["hidden"],), d["c"]))


def _cohort_param_shapes(d: Dict[str, int]):
    """ShapeDtypeStruct pytrees for a stacked (n,) cohort's params and
    adam state — via eval_shape, so no arrays materialize at any n."""
    from repro.optim import adam
    init_fn, apply_fn = _family(d)
    optimizer = adam(1e-3)
    n = d["n"]

    def build():
        keys = jax.random.split(jax.random.key(0), n)
        params = jax.vmap(init_fn)(keys)
        opt_state = jax.vmap(optimizer.init)(params)
        return params, opt_state

    params_s, opt_s = jax.eval_shape(build)
    return apply_fn, optimizer, params_s, opt_s


# --------------------------------------------------------------------------
# builders: name -> (traceable fn, ShapeDtypeStruct args)
# --------------------------------------------------------------------------

def _cohort_step(d):
    from repro.core import client
    apply_fn, optimizer, params_s, opt_s = _cohort_param_shapes(d)
    n, b, f = d["n"], d["batch"], d["feat"]

    def fn(params, opt_state, bx, by, ref_x, targets, trainable):
        return client._cohort_step(apply_fn, optimizer, params, opt_state,
                                   bx, by, ref_x, targets, trainable,
                                   0.5, True)

    args = (params_s, opt_s, _f32(n, b, f), _i32(n, b), _f32(d["r"], f),
            _f32(n, d["r"], d["c"]),
            jax.ShapeDtypeStruct((n,), jnp.bool_))
    return fn, args


def _messenger_upload(codec_spec):
    def build(d):
        from repro.core import wire
        from repro.core.client import _cohort_messenger_upload
        apply_fn, _, params_s, _ = _cohort_param_shapes(d)
        codec = wire.as_codec(codec_spec) if codec_spec else None

        def fn(params, ref_x):
            return _cohort_messenger_upload(apply_fn, params, ref_x,
                                            codec=codec)

        return fn, (params_s, _f32(d["r"], d["feat"]))
    return build


def _grade(d):
    from repro.kernels import ops

    def fn(repo_logp, labels):
        return ops.soft_ce(repo_logp, labels)

    return fn, (_f32(d["n"], d["r"], d["c"]), _i32(d["r"]))


def _concrete_pool(d):
    """The runtime's concrete candidate staging: a fixed probe quality
    profile through the REAL mask + pow2 pool bucketing."""
    from repro.core import graph as graph_mod
    from repro.core.quality import candidate_mask
    n = d["n"]
    quality = jnp.asarray(np.linspace(0.1, 3.0, n, dtype=np.float32))
    active = jnp.ones((n,), bool)
    cand = np.asarray(candidate_mask(quality, active, d["q"]))
    bucket = graph_mod._pool_bucket(cand, d["k"])
    if bucket is None:         # q=0 probe — cannot happen with DEFAULT_DIMS
        raise ValueError("probe candidate pool is empty")
    return tuple(jnp.asarray(a) for a in bucket)


def _build_graph(d):
    from repro.core import graph as graph_mod
    from repro.core import similarity
    pool, pool_valid = _concrete_pool(d)
    k = d["k"]

    def fn(repo_logp):
        div = similarity.divergence_matrix(repo_logp)
        return graph_mod._select_pool_div(div, pool, pool_valid, k)

    return fn, (_f32(d["n"], d["r"], d["c"]),)


def _build_graph_delta(d):
    from repro.core import graph as graph_mod
    from repro.core import similarity
    pool, pool_valid = _concrete_pool(d)
    n, k = d["n"], d["k"]
    up = np.zeros(n, bool)
    up[:d["u"]] = True

    def fn(div_cache, repo_logp):
        div = similarity.update_divergence_cache(div_cache, repo_logp, up)
        return graph_mod._select_pool_div(div, pool, pool_valid, k)

    return fn, (_f32(n, n), _f32(n, d["r"], d["c"]))


def _divergence_matrix(d):
    from repro.core import similarity

    def fn(repo_logp):
        return similarity.divergence_matrix(repo_logp)

    return fn, (_f32(d["n"], d["r"], d["c"]),)


def _int8_dequant_kl(d):
    from repro.kernels import ops
    n, r, c = d["n"], d["r"], d["c"]

    def fn(q, scale, zp):
        return ops.int8_pairwise_kl(q, scale, zp)

    return fn, (jax.ShapeDtypeStruct((n, r, c), jnp.uint8),
                _f32(n, r), _f32(n, r))


def _ivf_dims(d):
    """Derived IVF population shapes, mirroring NeighborIndex defaults:
    ncent = isqrt(n) coarse clusters, n_probe = isqrt(ncent) probed, so
    the candidate strip width is n_probe · ceil(n/ncent) ~ n^{3/4} —
    the sub-quadratic structure the exponent ceiling pins."""
    import math
    n = d["n"]
    ncent = max(1, math.isqrt(n))
    probe = max(1, math.isqrt(ncent))
    cand = min(n, probe * -(-n // ncent))
    return ncent, cand


def _centroid_assign(d):
    from repro.kernels import ops
    u, r, c = d["u"], d["r"], d["c"]
    ncent, _ = _ivf_dims(d)

    def fn(q, scale, lse, centroids):
        # wire-form reconstruction (logp = q·scale − lse) + the exact
        # upload-vs-centroid KL strip — NeighborIndex._centroid_div
        recon = (q.astype(jnp.float32) * scale[..., None]
                 - lse[..., None])
        return ops.pairwise_kl_pair(recon, centroids)

    return fn, (jax.ShapeDtypeStruct((u, r, c), jnp.uint8),
                _f32(u, r), _f32(u, r), _f32(ncent, r, c))


def _ivf_search(d):
    from repro.kernels import ops
    u, r, c = d["u"], d["r"], d["c"]
    ncent, cand = _ivf_dims(d)

    def fn(qu, su, lu, centroids, qc, sc, zc):
        # assignment strip + the forward/reverse candidate strips off the
        # int8 wire form — one NeighborIndex.update search round
        recon = (qu.astype(jnp.float32) * su[..., None] - lu[..., None])
        d_cent = ops.pairwise_kl_pair(recon, centroids)
        zu = jnp.zeros_like(su)
        fwd = ops.int8_pairwise_kl_pair(qu, su, zu, qc, sc, zc)
        rev = ops.int8_pairwise_kl_pair(qc, sc, zc, qu, su, zu)
        return d_cent, fwd, rev

    return fn, (jax.ShapeDtypeStruct((u, r, c), jnp.uint8),
                _f32(u, r), _f32(u, r), _f32(ncent, r, c),
                jax.ShapeDtypeStruct((cand, r, c), jnp.uint8),
                _f32(cand, r), _f32(cand, r))


def _serve_step(d):
    from repro.serve import engine
    apply_fn, _, params_s, _ = _cohort_param_shapes(d)
    b = d["b"]

    def fn(params, rows, xs):
        return engine._serve_forward(apply_fn, params, rows, xs)

    return fn, (params_s, _i32(b), _f32(b, d["feat"]))


def _zoo_cohort_step(family: str):
    """cohort_step traced through a REGISTERED zoo family (its real
    builder + its real per-family default optimizer), so every
    architecture's training step carries its own budget — a regression
    in, say, the transformer adapter cannot hide inside the MLP probe."""
    def build(d):
        from repro.core import client
        from repro.models.zoo import get_family
        spec = get_family(family)
        init_fn, apply_fn = spec.builder(d["feat"], d["c"])
        optimizer = spec.make_optimizer()
        n, b, f = d["n"], d["batch"], d["feat"]

        def shapes():
            keys = jax.random.split(jax.random.key(0), n)
            params = jax.vmap(init_fn)(keys)
            opt_state = jax.vmap(optimizer.init)(params)
            return params, opt_state

        params_s, opt_s = jax.eval_shape(shapes)

        def fn(params, opt_state, bx, by, ref_x, targets, trainable):
            return client._cohort_step(apply_fn, optimizer, params,
                                       opt_state, bx, by, ref_x, targets,
                                       trainable, 0.5, True)

        args = (params_s, opt_s, _f32(n, b, f), _i32(n, b), _f32(d["r"], f),
                _f32(n, d["r"], d["c"]),
                jax.ShapeDtypeStruct((n,), jnp.bool_))
        return fn, args
    return build


ENTRY_BUILDERS: Dict[str, Callable] = {
    "cohort_step": _cohort_step,
    "cohort_messenger_upload": _messenger_upload(None),
    "cohort_messenger_upload[int8]": _messenger_upload("int8"),
    "sqmd.grade": _grade,
    "sqmd.build_graph": _build_graph,
    "sqmd.build_graph_delta": _build_graph_delta,
    "divergence_matrix": _divergence_matrix,
    "int8_dequant_kl": _int8_dequant_kl,
    "centroid_assign": _centroid_assign,
    "ivf_search": _ivf_search,
    "serve_step": _serve_step,
}


def _register_zoo_entries() -> None:
    """One ``cohort_step[<family>]`` entry per registered zoo family —
    registry-driven so a newly registered architecture gets a budget (and
    a Θ(n) scaling sweep) without touching this file."""
    from repro.models.zoo import registered_families
    for fam in registered_families():
        name = f"cohort_step[{fam}]"
        ENTRY_BUILDERS[name] = _zoo_cohort_step(fam)
        SCALE_AXES[name] = ("n", (32, 64, 128, 256))


_register_zoo_entries()


def trace_entry(name: str, **overrides):
    """Trace entry ``name`` at DEFAULT_DIMS overridden by ``overrides``;
    returns the ClosedJaxpr."""
    builder = ENTRY_BUILDERS.get(name)
    if builder is None:
        raise KeyError(f"unknown cost entry {name!r}; known: "
                       f"{sorted(ENTRY_BUILDERS)}")
    dims = dict(DEFAULT_DIMS)
    bad = set(overrides) - set(dims)
    if bad:
        raise KeyError(f"unknown dims {sorted(bad)}; known: {sorted(dims)}")
    dims.update(overrides)
    fn, args = builder(dims)
    return jax.make_jaxpr(fn)(*args)


def entry_names() -> Tuple[str, ...]:
    return tuple(sorted(ENTRY_BUILDERS))
