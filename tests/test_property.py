"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip cleanly if absent
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (candidate_mask, decode, divergence_matrix, encode,
                        init_server, select_neighbors, similarity_matrix,
                        update_divergence_cache, upload_messengers)
from repro.core.distill import ref_loss
from repro.kernels import ref

_dims = st.tuples(st.integers(2, 12), st.integers(1, 20), st.integers(2, 8))


@settings(max_examples=25, deadline=None)
@given(_dims, st.integers(0, 2**31 - 1))
def test_pairwise_kl_nonneg_zero_diag(dims, seed):
    n, r, c = dims
    z = jax.random.normal(jax.random.key(seed), (n, r, c)) * 3
    logp = jax.nn.log_softmax(z, -1)
    d = np.asarray(ref.pairwise_kl_ref(logp))
    assert (d >= -1e-4).all()
    assert np.allclose(np.diag(d), 0.0, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(_dims, st.integers(0, 2**31 - 1), st.integers(1, 5))
def test_div_cache_scatter_matches_full_rebuild(dims, seed, steps):
    """Delta-path invariant: scatter-updating the cached divergence matrix
    over ANY upload sequence (empty/partial/full masks, rows that never
    upload and keep their uniform init) equals a from-scratch rebuild."""
    n, r, c = dims
    rng = np.random.default_rng(seed)
    state = init_server(n, r, c)
    cache = state.div_cache
    for i in range(steps):
        mask = rng.random(n) < rng.uniform(0.0, 1.0)
        z = jax.random.normal(jax.random.key((seed + i) % 2**31),
                              (n, r, c)) * 3
        state = upload_messengers(state, jax.nn.log_softmax(z, -1),
                                  jnp.asarray(mask))
        cache = update_divergence_cache(cache, state.repo_logp, mask)
    full = np.asarray(divergence_matrix(state.repo_logp))
    np.testing.assert_allclose(np.asarray(cache), full, atol=1e-4,
                               rtol=1e-4)
    # rows nobody uploaded keep the exact zero-KL uniform block
    never = ~np.asarray(state.active)
    if never.any():
        assert np.allclose(np.asarray(cache)[np.ix_(never, never)], 0.0,
                           atol=1e-6)


# per-codec decode∘encode error budget: max mean round-trip KL
# (nats/ref-sample). dense32 is asserted bitwise below, not via KL.
_CODEC_KL_BOUND = {"dense16": 2e-2, "int8": 5e-2, "topk": 1.5,
                   "topk:2": 2.5}


@settings(max_examples=20, deadline=None)
@given(_dims, st.integers(0, 2**31 - 1))
def test_wire_dense32_roundtrip_is_bitwise_identity(dims, seed):
    n, r, c = dims
    z = jax.random.normal(jax.random.key(seed), (n, r, c)) * 4
    logp = jax.nn.log_softmax(z, -1)
    out = decode(encode("dense32", logp))
    assert out.dtype == logp.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(logp))


@settings(max_examples=15, deadline=None)
@given(_dims, st.integers(0, 2**31 - 1),
       st.sampled_from(sorted(_CODEC_KL_BOUND)))
def test_wire_lossy_roundtrip_kl_bounded(dims, seed, codec):
    """decode∘encode stays within each codec's KL budget and always
    returns a normalized distribution — over arbitrary shapes, including
    near-one-hot rows (logits scaled x4)."""
    n, r, c = dims
    z = jax.random.normal(jax.random.key(seed), (n, r, c)) * 4
    logp = jax.nn.log_softmax(z, -1)
    dec = decode(encode(codec, logp))
    np.testing.assert_allclose(np.asarray(jax.nn.logsumexp(dec, -1)), 0.0,
                               atol=1e-4)
    # mean KL(orig || decoded) per reference sample, via the Eq.2 strip
    kl = np.diag(np.asarray(ref.pairwise_kl_pair_ref(logp, dec)))
    assert (kl > -1e-5).all()
    assert kl.mean() <= _CODEC_KL_BOUND[codec]


@settings(max_examples=15, deadline=None)
@given(_dims, st.integers(0, 2**31 - 1),
       st.sampled_from(["dense16", "int8", "topk"]))
def test_wire_prob_domain_roundtrip_stays_on_simplex(dims, seed, codec):
    n, r, c = dims
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.key(seed), (n, r, c)) * 3, -1)
    dec = np.asarray(decode(encode(codec, probs, domain="prob")))
    np.testing.assert_allclose(dec.sum(-1), 1.0, atol=1e-4)
    assert (dec >= 0).all()
    # L1 error bounded (worst over rows); topk's tail respread dominates
    l1 = np.abs(dec - np.asarray(probs)).sum(-1).max()
    assert l1 <= (0.05 if codec == "dense16" else 1.0)


@settings(max_examples=25, deadline=None)
@given(_dims, st.integers(0, 2**31 - 1))
def test_neighbor_mean_is_convex_combination(dims, seed):
    """Targets stay inside the probability simplex (rows sum to 1, bounds
    within min/max of inputs)."""
    n, r, c = dims
    k1, k2 = jax.random.split(jax.random.key(seed))
    probs = jax.nn.softmax(jax.random.normal(k1, (n, r, c)) * 2, -1)
    w = jax.random.uniform(k2, (n, n)) + 1e-3
    w = w / w.sum(1, keepdims=True)
    t = np.asarray(ref.neighbor_mean_ref(w, probs))
    np.testing.assert_allclose(t.sum(-1), 1.0, atol=1e-4)
    assert (t >= np.asarray(probs).min(0) - 1e-5).all()
    assert (t <= np.asarray(probs).max(0) + 1e-5).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 16), st.integers(1, 16), st.integers(0, 2**31 - 1))
def test_candidate_mask_cardinality(n, q, seed):
    k1, k2 = jax.random.split(jax.random.key(seed))
    quality = jax.random.uniform(k1, (n,)) * 10
    active = jax.random.bernoulli(k2, 0.7, (n,))
    m = np.asarray(candidate_mask(quality, active, q))
    n_active = int(np.asarray(active).sum())
    assert m.sum() == min(q, n_active)
    assert not (m & ~np.asarray(active)).any()


@settings(max_examples=20, deadline=None)
@given(st.integers(3, 10), st.integers(1, 4), st.integers(0, 2**31 - 1))
def test_topk_neighbors_are_most_similar(n, k, seed):
    k = min(k, n - 1)
    z = jax.random.normal(jax.random.key(seed), (n, 10, 4)) * 2
    logp = jax.nn.log_softmax(z, -1)
    div = divergence_matrix(logp)
    g = select_neighbors(div, jnp.ones((n,), bool), k)
    s = np.asarray(similarity_matrix(div))
    for i in range(n):
        chosen = set(np.asarray(g.neighbors[i]).tolist())
        others = [j for j in range(n) if j != i and j not in chosen]
        if others:
            worst_chosen = min(s[i, j] for j in chosen)
            best_other = max(s[i, j] for j in others)
            assert worst_chosen >= best_other - 1e-5


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_ref_loss_zero_iff_targets_match(seed):
    """Eq.5 is exactly 0 when targets equal own soft decisions, > 0 else."""
    from repro.models.mlp import MLPConfig, init_mlp, apply_mlp
    cfg = MLPConfig("t", 6, (8,), 3)
    p = init_mlp(jax.random.key(seed), cfg)
    ref_x = jax.random.normal(jax.random.key(seed + 1), (5, 6))
    own = jax.nn.softmax(apply_mlp(cfg, p, ref_x), -1)
    fn = lambda pp, x: apply_mlp(cfg, pp, x)
    assert float(ref_loss(fn, p, ref_x, own)) < 1e-10
    other = jnp.roll(own, 1, axis=-1)
    assert float(ref_loss(fn, p, ref_x, other)) > 1e-6


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 6), st.integers(0, 2**31 - 1))
def test_optimizer_descends_quadratic(dim, seed):
    from repro.optim import adam, sgd, apply_updates
    target = jax.random.normal(jax.random.key(seed), (dim,))
    params = {"w": jnp.zeros((dim,))}
    for opt in (sgd(0.1), adam(0.1)):
        p = params
        s = opt.init(p)
        loss = lambda q: jnp.sum((q["w"] - target) ** 2)
        l0 = float(loss(p))
        for _ in range(50):
            g = jax.grad(loss)(p)
            u, s = opt.update(g, s, p)
            p = apply_updates(p, u)
        assert float(loss(p)) < l0 * 0.5
